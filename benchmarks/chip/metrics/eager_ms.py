"""eager_ms: device time per round in every module other than the round's
own: FSVRG's eager full gradient, the eval of f, the finiteness check."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s = t.other_modules_s(ctx["round_module"])
    return 1e3 * s / ctx["window"]["rounds"] if s > 0 else None
