"""aggregate_ms: device time per round of the round's operations under
the program's scope ``fl.aggregate`` (the deltas' weighted sum, the
fused_aggregate kernel's entries, the server update), with the compiler's
unscoped instructions that inherit it, as
``program_trace.Program.readings`` gives it."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    return prog.readings(ctx["window"]["rounds"])["aggregate_ms"] or None
