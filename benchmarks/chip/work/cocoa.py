"""Required work of one CoCoA+ round (arXiv:1502.03508, γ = 1, one local
SDCA pass), counted from the problem's shapes and the clients that took
part — the same whatever implements it.

A row holds ``entries`` (index, value) pairs and a label: 8·entries + 4
bytes.  Each participant reads its rows once, and each row's dual
coordinate α_i is read and written (8 bytes); its dense delta (d floats)
is read once in the server's reduction; the server reads and writes the
iterate.  An eval of f reads every row once more.  FLOPs per row step:
the two margins x·w and x·r and ‖x‖², 2 per entry each, the scatter of
the step into r, 2 per entry, the scalar solve (``SOLVE_FLOPS``) and 10
for the coefficients and the step; the reduction is 2 per delta
coordinate.
"""
from __future__ import annotations

import numpy as np

#: one scalar solve: clip(sigmoid(−m)) (6) and 12 clipped Newton steps of
#: 16 (log and reciprocal counted as one each)
SOLVE_FLOPS = 6 + 12 * 16


def round_work(shapes: dict, params: dict, rnd: dict) -> dict:
    n, d, e = shapes["n"], shapes["d"], shapes["entries"]
    row = 8 * e + 4
    sizes = np.concatenate([np.asarray(s) for s in rnd["participant_sizes"]])
    clients, rows = len(sizes), int(sizes.sum())
    bytes_ = rows * (row + 8) + clients * 4 * d + 3 * 4 * d
    flops = rows * (8 * e + SOLVE_FLOPS + 10) + 2 * clients * d + 2 * d
    if rnd["evaluates"]:
        bytes_ += n * row + 4 * d
        flops += n * (2 * e + 8)
    return {"flops": float(flops), "bytes": float(bytes_)}
