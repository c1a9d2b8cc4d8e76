"""round_program_ms: device time per round inside the round's own XLA
module, whose name set-up reads from solver.lower_round(...)."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s = t.module_s(ctx["round_module"])
    return 1e3 * s / ctx["window"]["rounds"] if s > 0 else None
