"""compile_s: the program's ``compile_s`` counter (``repro.utils.obs``) at
the window's start: the seconds set-up spent tracing, lowering and
building executables, a load from the persistent cache included."""


def read(ctx):
    counters = ctx.get("counters")
    if not counters or not counters["start"].get("compile_s"):
        return None
    return counters["start"]["compile_s"]
