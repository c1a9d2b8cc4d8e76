"""roofline.fused_aggregate: the least time of the aggregation kernel's
required work in the traced rounds (work/<solver>.fused_aggregate.py) at
the chip's published peaks, over the kernel's device time, in %."""
import peaks

KERNEL = "fused_aggregate"
WORK = "fused_aggregate"


def read(ctx):
    t = ctx["trace"]
    work = ctx["cell"].work(WORK) if t is not None else None
    if work is None:
        return None
    s = t.kernel_s(KERNEL)
    if s <= 0:
        return None
    params = ctx["cell"].solver_kwargs()
    least = sum(peaks.least_time_s(work.kernel_work(ctx["shapes"], params, r),
                                   ctx["peaks"])[0]
                for r in ctx["traced_rounds"])
    return 100.0 * least / s
