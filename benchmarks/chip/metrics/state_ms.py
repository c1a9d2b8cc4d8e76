"""state_ms: device time per round of the round's operations under the
program's scope ``fl.state`` (the per-client state's own movement: its
chunking into the streamed slices, the write-back in client order, the
freeze of non-participants' state), with the compiler's unscoped
instructions that inherit it (``program_trace.inherited``).  Only a round
that carries per-client state has them."""


def read(ctx):
    prog = ctx.get("program")
    if prog is None:
        return None
    s = prog.scope_s("fl.state", inherit=True)
    return 1e3 * s / ctx["window"]["rounds"] if s > 0 else None
