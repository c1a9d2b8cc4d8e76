"""setup_s: process start until the first timed round (host clock):
generation, build_problem, loading or compiling the round program, and the
checked warm rounds."""


def read(ctx):
    return ctx["setup"]["setup_s"]
