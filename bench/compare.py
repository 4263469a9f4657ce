"""The numbers that decide ``correct``, and their limits.

Each cell's limits live in ``bench/limits/<cell>.json``: ``{number:
limit}``.  A number passes when it is a finite value at or below its limit.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

LIMITS = Path(__file__).resolve().parent / "limits"


def limits(cell: str) -> dict:
    return json.loads((LIMITS / f"{cell}.json").read_text())["limits"]


def judge(cell: str, numbers: list) -> tuple[bool, list]:
    """(correct, [(name, value, limit)]) for every number that has a limit;
    a reading without one is printed by the driver and not compared."""
    lim = limits(cell)
    got = dict(numbers)
    if not set(lim) <= set(got):
        raise ValueError(f"{cell}: numbers {sorted(got)} but limits "
                         f"{sorted(lim)}")
    rows = [(n, float(got[n]), float(lim[n])) for n in lim]
    ok = all(math.isfinite(v) and v <= l for _, v, l in rows)
    return ok, rows


def widest_gap(want: np.ndarray, chosen: np.ndarray) -> float:
    """The widest gap by which a chosen token's reference logit lies below
    the reference's best, over rows of ``want`` (rows, vocab)."""
    want = np.asarray(want, np.float64)
    chosen = np.asarray(chosen, np.int64)
    picked = np.take_along_axis(want, chosen[:, None], axis=1)[:, 0]
    return float(np.max(want.max(axis=1) - picked))


def rel_l2(got, want) -> float:
    """||got - want|| / ||want||, per row, the worst row."""
    got = np.atleast_2d(np.asarray(got, np.float64))
    want = np.atleast_2d(np.asarray(want, np.float64))
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    return float(np.max(np.linalg.norm(got - want, axis=1)
                        / np.linalg.norm(want, axis=1)))
