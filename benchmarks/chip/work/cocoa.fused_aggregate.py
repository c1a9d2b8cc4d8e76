"""Required work of the aggregation kernel in one CoCoA+ round: each
participant's delta (d floats) and weight (1: the deltas are summed) read
once, the iterate and A read, the iterate written; 2 FLOPs per delta
coordinate and 3 per coordinate of the update."""
from __future__ import annotations


def kernel_work(shapes: dict, params: dict, rnd: dict) -> dict:
    d = shapes["d"]
    clients = sum(len(s) for s in rnd["participant_sizes"])
    return {"flops": float(2 * clients * d + 3 * d),
            "bytes": float(4 * clients * d + 4 * clients + 3 * 4 * d)}
