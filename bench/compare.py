"""The comparison that decides ``correct``.

Numbers compared, each between the timed path's readings and the plain
reference's over the same seed, batches and draws (rounds 0, 1, 2):

    loss_gap   largest |loss_p - loss_r| / |loss_r| over the three rounds
    gnorm_gap  the same for the norm of the clean (noise-free, unfaded)
               client-mean gradient that each round reports
    grad1_gap  worst leaf of the first g_t as the optimizer got it:
               |norm_p - norm_r| / max(norm_r, median leaf norm_r)
    dw_gap     worst leaf of the weights' change after three rounds, by
               the same measure, leaving out leaves whose clean reference
               gradient is under a thousandth of the median leaf's (they
               move by round-off alone)

Each number has a limit in ``bench/limits/<workload>.json``; a number
without one is printed and not held to anything. ``correct`` is true when
every limited number is finite and within its limit.
"""

from __future__ import annotations

import math

import numpy as np

QUIET_LEAF = 1e-3     # of the median leaf's clean reference gradient


def _rel(p, r) -> float:
    return max(abs(a - b) / abs(b) for a, b in zip(p, r))


def worst_leaf(p: np.ndarray, r: np.ndarray, keep=None) -> float:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    floor = np.median(r)
    gap = np.abs(p - r) / np.maximum(r, floor)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def numbers(prog: dict, ref: dict) -> dict:
    clean = np.asarray(ref["clean1_leaf"])
    keep = clean >= QUIET_LEAF * np.median(clean)
    return {"loss_gap": _rel(prog["loss"], ref["loss"]),
            "gnorm_gap": _rel(prog["clean_norm"], ref["clean_norm"]),
            "grad1_gap": worst_leaf(prog["g1_leaf"], ref["g1_leaf"]),
            "dw_gap": worst_leaf(prog["dw_leaf"], ref["dw_leaf"], keep)}


def judge(nums: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit or None)]) in a fixed order."""
    rows, ok = [], True
    for name in sorted(nums):
        lim = limits.get(name)
        v = nums[name]
        if lim is not None:
            ok = ok and math.isfinite(v) and v <= lim
        rows.append((name, v, lim))
    return ok, rows
