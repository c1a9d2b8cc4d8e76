"""full_grad_ms: device time per round of the modules dispatched under
the program's span ``fl.prelude``: FSVRG's eager full gradient
(``program_trace.Program.readings``)."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.readings(ctx["window"]["rounds"])["full_grad_ms"] or None
