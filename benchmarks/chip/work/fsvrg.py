"""Required work of one FSVRG round (arXiv:1610.02527 Alg. 4), counted
from the problem's shapes and the clients that took part — the same
whatever implements it.

A row holds ``entries`` (index, value) pairs and a label: 8·entries + 4
bytes.  The round reads every train row once for the full gradient, each
participant's rows once in its client pass, and each participant's dense
delta (d floats) once in the server's reduction; it reads the iterate, the
gradient and A, and writes the iterate.  An eval of f reads every row
once more.  FLOPs: a margin is 2 per entry, a gradient scatter 2 per
entry, an SVRG step takes two margins and a scatter; the reduction is 2
per delta coordinate.
"""
from __future__ import annotations

import numpy as np


def round_work(shapes: dict, params: dict, rnd: dict) -> dict:
    n, d, e = shapes["n"], shapes["d"], shapes["entries"]
    row = 8 * e + 4
    sizes = np.concatenate([np.asarray(s) for s in rnd["participant_sizes"]])
    clients, rows = len(sizes), int(sizes.sum())
    bytes_ = n * row + 2 * 4 * d                        # full gradient
    flops = n * (4 * e + 8)
    bytes_ += rows * row + clients * 4 * d              # passes, reduction
    flops += rows * (6 * e + 16) + 2 * clients * d
    bytes_ += 4 * 4 * d                                 # server update
    flops += 3 * d
    if rnd["evaluates"]:
        bytes_ += n * row + 4 * d
        flops += n * (2 * e + 8)
    return {"flops": float(flops), "bytes": float(bytes_)}
