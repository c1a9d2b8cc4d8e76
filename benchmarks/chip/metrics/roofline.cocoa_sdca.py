"""roofline.cocoa_sdca: the least time of the SDCA scalar-solve kernel's
required work in the traced rounds (work/<solver>.cocoa_sdca.py) at the
chip's published peaks, over the kernel's device time, in %."""
import peaks

KERNEL = "cocoa_sdca_update"
WORK = "cocoa_sdca"


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
