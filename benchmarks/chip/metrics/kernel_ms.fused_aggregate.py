"""kernel_ms.fused_aggregate: device time per round of the aggregation
kernel's events (kernels/scaled_aggregate.py: fused_aggregate and its
fused_accumulate / fused_epilogue entries, one Pallas kernel)."""

#: the custom call's name: the jitted function that calls pallas_call
KERNEL = "fused_aggregate"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s = t.kernel_s(KERNEL)
    return 1e3 * s / ctx["window"]["rounds"] if s > 0 else None
