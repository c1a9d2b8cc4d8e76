"""The numbers that decide ``correct``: the program's first rounds against
the plain reference's, from the same rows and keys.

The round is a training step of a convex problem, so both sides follow one
trajectory up to rounding; every number is a share of the reference's own
size, and smaller is closer:

* ``loss_gap``: the largest |f_prog − f_ref| / f_ref over the checked
  rounds (f, the §4 objective, after each round);
* ``update_norm_gap``: | ‖u_prog‖ − ‖u_ref‖ | / ‖u_ref‖ for the first
  round's server update u = w_1 − w_0 (w_0 = 0);
* ``change_norm_gap``: the same for the change over all checked rounds;
* ``iterate_gap``: the largest ‖w_prog − w_ref‖_∞ / ‖w_ref − w_0‖_∞ over
  the checked rounds — every coordinate, every round.
"""
from __future__ import annotations

import math

import numpy as np


def _share(gap: float, size: float):
    """``gap`` as a share of ``size``; None where it is not a finite number
    (a NaN iterate, or a gap against nothing)."""
    if gap == 0.0:
        return 0.0
    share = gap / size if size else math.inf
    return float(share) if math.isfinite(share) else None


def numbers(prog_w, prog_f, ref_w, ref_f) -> dict:
    norm = lambda v: float(np.linalg.norm(np.asarray(v, np.float64)))
    top = lambda v: float(np.abs(np.asarray(v, np.float64)).max())
    shares = lambda pairs: [_share(g, s) for g, s in pairs]
    worst = lambda xs: None if None in xs else max(xs)
    return {
        "loss_gap": worst(shares(
            (abs(p - r), abs(r)) for p, r in zip(prog_f, ref_f))),
        "update_norm_gap": _share(abs(norm(prog_w[0]) - norm(ref_w[0])),
                                  norm(ref_w[0])),
        "change_norm_gap": _share(abs(norm(prog_w[-1]) - norm(ref_w[-1])),
                                  norm(ref_w[-1])),
        "iterate_gap": worst(shares(
            (top(np.asarray(p, np.float64) - r), top(r))
            for p, r in zip(prog_w, ref_w))),
    }


def holds(checks: dict) -> bool:
    """Every number is finite and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
