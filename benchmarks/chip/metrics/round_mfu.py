"""round_mfu: the least time the traced rounds' required work
(work/<solver>.py) could take at the chip's published peaks, over the
traced window's wall time, in %.  A round's least time is the larger of
FLOPs / peak FLOP/s and bytes / peak HBM bytes/s."""
import peaks


def read(ctx):
    if ctx["trace"] is None:
        return None
    work = ctx["cell"].work()
    params = ctx["cell"].solver_kwargs()
    least = sum(peaks.least_time_s(work.round_work(ctx["shapes"], params, r),
                                   ctx["peaks"])[0]
                for r in ctx["traced_rounds"])
    return 100.0 * least / ctx["window"]["seconds"]
