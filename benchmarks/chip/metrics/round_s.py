"""round_s: wall time of the window over the rounds completed in it (host
clock), each round as fit runs it: the round, the finiteness sync, and the
eval of f where the mix asks for it."""


def read(ctx):
    w = ctx["window"]
    return w["seconds"] / w["rounds"]
