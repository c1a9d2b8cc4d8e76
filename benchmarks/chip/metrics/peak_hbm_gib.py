"""peak_hbm_gib: the device allocator's peak_bytes_in_use after the window,
in GiB."""


def read(ctx):
    peak = ctx["memory"].get("peak_bytes_in_use")
    return None if not peak else peak / 2 ** 30
