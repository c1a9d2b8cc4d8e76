"""gather_ms: device time per round of the round's operations under the
program's scopes ``fl.sample`` (the participation draw) and ``fl.gather``
(the cohort gather of rows, weights, keys and state, and the scatter
back), with the compiler's unscoped instructions that inherit them, as
``program_trace.Program.readings`` gives it.  Only a cohort round has
them."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.readings(ctx["window"]["rounds"])["gather_ms"] or None
