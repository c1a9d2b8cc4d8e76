"""eval_ms: device time per round of the modules dispatched under the
program's span ``fl.eval``: the eval of f over every train row, each round
or after the window's last round only, as the mix asks
(``program_trace.Program.readings``).  Under the latter it reads the eval
over the traced rounds, which may be fewer than the timed window's:
``fedavg-gplus.cohort10`` traces one round (the configuration's
``traced_rounds``), so its reading, like its ``device_idle`` and
``round_mfu``, comes from a round that includes the eval of f, which the
timed window's four rounds share."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.readings(ctx["window"]["rounds"])["eval_ms"] or None
