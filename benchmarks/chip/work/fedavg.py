"""Required work of one FedAvg round (arXiv:1602.05629, B = ∞), counted
from the problem's shapes and the clients that took part — the same
whatever implements it.

A row holds ``entries`` (index, value) pairs and a label: 8·entries + 4
bytes.  Each participant reads its rows once per local epoch, and its
dense delta (d floats) is read once in the server's reduction; the server
reads and writes the iterate.  An eval of f reads every row once more.
FLOPs: an SGD step is a margin and a gradient scatter, 2 per entry each;
the reduction is 2 per delta coordinate.
"""
from __future__ import annotations

import numpy as np


def round_work(shapes: dict, params: dict, rnd: dict) -> dict:
    n, d, e = shapes["n"], shapes["d"], shapes["entries"]
    epochs = int(params["local_epochs"])
    row = 8 * e + 4
    sizes = np.concatenate([np.asarray(s) for s in rnd["participant_sizes"]])
    clients, rows = len(sizes), int(sizes.sum())
    bytes_ = epochs * rows * row + clients * 4 * d + 3 * 4 * d
    flops = epochs * rows * (4 * e + 12) + 2 * clients * d + 2 * d
    if rnd["evaluates"]:
        bytes_ += n * row + 4 * d
        flops += n * (2 * e + 8)
    return {"flops": float(flops), "bytes": float(bytes_)}
