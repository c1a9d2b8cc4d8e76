"""kernel_ms.cocoa_sdca: device time per round of the SDCA scalar-solve
kernel's events (kernels/cocoa_sdca.py: one Pallas call per lockstep SDCA
step of each client batch).  The pad and reshape copies that feed it are
not its events."""

#: the custom call's name: the jitted function that calls pallas_call
KERNEL = "cocoa_sdca_update"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s = t.kernel_s(KERNEL)
    return 1e3 * s / ctx["window"]["rounds"] if s > 0 else None
