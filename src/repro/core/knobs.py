"""KnobSpace — the discrete runtime execution-config space ADSALA tunes over.

The paper's knob is the thread count ``nt ∈ {1..cores×HT}``.  On TPU the
runtime-variable knob of a BLAS L3 kernel is its Pallas block configuration
``(bm, bk, bn)`` (DESIGN.md §2).  Both are *finite discrete sets whose choice
changes runtime but not semantics* — the ADSALA mechanism (predict the runtime
of every candidate, run the argmin) only needs:

  * an enumeration of candidates,
  * a scalar ``parallelism(candidate, dims)`` measure that plays the role of
    ``nt`` in the paper's Table-III features.

Block shapes are MXU/VMEM-aligned multiples of 128 on the minor dims by
construction.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Sequence

import numpy as np

__all__ = ["Knob", "KnobSpace", "block_knob_space", "grouped_knob_space",
           "thread_knob_space"]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One candidate execution config (an arbitrary mapping of named fields)."""
    values: tuple[tuple[str, Any], ...]

    @property
    def dict(self) -> dict:
        return dict(self.values)

    def __getitem__(self, k: str) -> Any:
        return self.dict[k]

    def __repr__(self) -> str:  # compact, stable — used as cache/registry keys
        return "Knob(" + ",".join(f"{k}={v}" for k, v in self.values) + ")"


class KnobSpace:
    """A named, enumerable space of execution configs."""

    def __init__(self, name: str, candidates: Sequence[dict],
                 parallelism_fn=None) -> None:
        self.name = name
        self.candidates: list[Knob] = [
            Knob(tuple(sorted(c.items()))) for c in candidates
        ]
        if not self.candidates:
            raise ValueError("empty knob space")
        self._parallelism_fn = parallelism_fn

    def __len__(self) -> int:
        return len(self.candidates)

    def __iter__(self):
        return iter(self.candidates)

    def parallelism(self, knob: Knob, dims: tuple[int, ...]) -> float:
        """The ``nt``-analogue feature for this knob at these dims."""
        if self._parallelism_fn is not None:
            return float(self._parallelism_fn(knob, dims))
        if "nt" in knob.dict:
            return float(knob["nt"])
        raise ValueError("knob space has no parallelism definition")

    def parallelism_vec(self, dims: tuple[int, ...]) -> np.ndarray:
        return np.array([self.parallelism(c, dims) for c in self.candidates])

    def index(self, knob: Knob) -> int:
        return self.candidates.index(knob)

    # -- persistence ------------------------------------------------------
    def get_state(self) -> dict:
        return {"name": self.name,
                "candidates": [c.dict for c in self.candidates]}


def thread_knob_space(max_threads: int, *,
                      powers_of_two: bool = False) -> KnobSpace:
    """The paper's literal knob: nt ∈ {1..max_threads} (or powers of two)."""
    if powers_of_two:
        nts = [2 ** i for i in range(int(math.log2(max_threads)) + 1)]
    else:
        nts = list(range(1, max_threads + 1))
    return KnobSpace("threads", [{"nt": t} for t in nts],
                     parallelism_fn=lambda k, dims: k["nt"])


def _grid_parallelism(knob: Knob, dims: tuple[int, ...]) -> float:
    """Parallel Pallas grid cells = ceil(m/bm)*ceil(n/bn) — the nt analogue.

    The ``tri_packed`` variant launches only the lower-triangle blocks, so
    its cell count carries the packed fraction: (cm+1)/2 live row blocks
    per column on average instead of cm.  This is what makes the variant
    *learnable* — it is the only knob-dependent feature channel, and
    without the adjustment full/tri_packed candidates would produce
    byte-identical Table-III rows the model provably cannot separate.
    ('full' and 'tri' launch the same grid — tri's dead cells still occupy
    slots — so those two deliberately share a feature row and tie.)
    Legacy persisted spaces contain no tri_packed candidates, so their
    features are bit-for-bit unchanged.
    """
    d = knob.dict
    if len(dims) == 3:
        m, _, n = dims
    else:
        m, n = dims
    cm = math.ceil(m / d["bm"])
    cn = math.ceil(n / d["bn"])
    if d.get("variant") == "tri_packed":
        return (cm + 1) * cn / 2.0
    return cm * cn


def block_knob_space(
    *,
    bms: Sequence[int] = (128, 256, 512),
    bks: Sequence[int] = (128, 256, 512),
    bns: Sequence[int] = (128, 256, 512),
    vmem_limit_bytes: int = 96 * 1024 * 1024,
    dtype_bytes: int = 4,
    variants: Sequence[str] = ("full",),
) -> KnobSpace:
    """TPU BLAS knob space: Pallas block shapes (bm, bk, bn) (+ kernel variant).

    Candidates whose VMEM working set (A, B, C + accumulator tiles) exceeds
    ``vmem_limit_bytes`` are excluded — they could never be launched.
    ``variants`` optionally adds the triangle-aware kernel variants
    (DESIGN.md §7.4) to the search space.
    """
    cands = []
    for bm, bk, bn, var in itertools.product(bms, bks, bns, variants):
        vmem = dtype_bytes * (bm * bk + bk * bn + 2 * bm * bn)
        if vmem <= vmem_limit_bytes:
            cands.append({"bm": bm, "bk": bk, "bn": bn, "variant": var})
    return KnobSpace("blocks", cands, parallelism_fn=_grid_parallelism)


def _grouped_parallelism(knob: Knob, dims: tuple[int, ...]) -> float:
    """Grid cells of the grouped gemm (``kernels/grouped_gemm.py``) at dims
    ``(m, k, n, g)``: row-tile visits times column tiles, with the visits at
    their bound ``ceil(m/bm) + g - 1`` (each group boundary inside a tile
    adds one).  The group sizes are data, not part of the key, so the bound
    is what a decision can see."""
    d = knob.dict
    m, _, n, g = dims
    return (math.ceil(m / d["bm"]) + g - 1) * math.ceil(n / d["bn"])


def grouped_knob_space(sizes: Sequence[int] = (128, 256, 512)) -> KnobSpace:
    """The grouped gemm's (bm, bk, bn) tiles: the gemm's candidates under
    the name ``grouped_blocks``, whose parallelism is
    :func:`_grouped_parallelism` (restored by that name on load)."""
    space = block_knob_space(bms=sizes, bks=sizes, bns=sizes)
    return KnobSpace("grouped_blocks", [k.dict for k in space],
                     parallelism_fn=_grouped_parallelism)
