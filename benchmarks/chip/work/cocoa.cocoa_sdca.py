"""Required work of the SDCA scalar-solve kernel in one CoCoA+ round: for
each row step of each participant, β₀, m and c read and β written (16
bytes), and the solve's FLOPs (``work/cocoa.py``'s ``SOLVE_FLOPS``).
Padding — the row slots past a client's n_k, the lanes that fill a
kernel tile, the copies that feed the kernel — is not required work."""
from __future__ import annotations

import pathlib

import numpy as np

import catalog

SOLVE_FLOPS = catalog.load_module(
    pathlib.Path(__file__).with_name("cocoa.py"), "work").SOLVE_FLOPS


def kernel_work(shapes: dict, params: dict, rnd: dict) -> dict:
    rows = sum(int(np.asarray(s).sum()) for s in rnd["participant_sizes"])
    return {"flops": float(SOLVE_FLOPS * rows), "bytes": float(16 * rows)}
