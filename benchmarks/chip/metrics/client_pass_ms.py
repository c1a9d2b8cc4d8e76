"""client_pass_ms: device time per round of the round's operations under
the program's scope ``fl.client_pass`` (each bucket's or chunk's client
pass), with the compiler's unscoped instructions that inherit it (the
scatter fusions of the pass), as ``program_trace.Program.readings``
gives it."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.readings(ctx["window"]["rounds"])["client_pass_ms"] or None
