"""Jaxpr introspection for the zero-copy execution contract.

The masked kernels promise two structural properties that numerics alone
cannot witness:

  * **zero-copy** — no operand pad / result slice-back materializes outside
    the pallas_call (the old path allocated padded copies of every operand
    on non-block-multiple shapes);
  * **packed grids** — the ``tri_packed`` variant launches exactly the
    n(n+1)/2 live lower-triangle blocks (plus the write-only mirror step for
    the rank-k updates) instead of a full n² grid.

Both are facts about the *traced program*, so this module walks jaxprs:
``pallas_grids`` extracts every pallas_call grid and ``copy_op_counts``
counts the data-movement primitives at every level outside kernel bodies.
Used by ``tests/test_zero_copy_kernels.py``, which asserts these
deterministic structural facts exactly.
"""

from __future__ import annotations

import math

import jax

__all__ = ["pallas_grids", "copy_op_counts", "grid_slots",
           "packed_grid_for", "full_grid_for"]

#: the data-movement primitives the zero-copy contract forbids on the
#: dispatch path (pad = operand padding, slice = result slice-back,
#: gather covers jnp-advanced-indexing forms of the same copy)
COPY_PRIMITIVES = ("pad", "slice", "dynamic_slice", "gather")


def _walk(jaxpr, grids, counts):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            gm = eqn.params.get("grid_mapping")
            if gm is not None:
                grids.append(tuple(int(g) for g in gm.grid))
            # kernel bodies are never descended into — they are allowed
            # any masking ops they like; the contract is host-side copies
            continue
        if name in COPY_PRIMITIVES:
            counts[name] = counts.get(name, 0) + 1
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                _walk(sub, grids, counts)


def _trace(fn, *args, **kwargs):
    return jax.make_jaxpr(lambda *xs: fn(*xs, **kwargs))(*args)


def pallas_grids(fn, *args, **kwargs) -> list[tuple[int, ...]]:
    """Grids of every pallas_call reached when tracing ``fn(*args)``."""
    grids: list = []
    _walk(_trace(fn, *args, **kwargs).jaxpr, grids, {})
    return grids


def copy_op_counts(fn, *args, **kwargs) -> dict[str, int]:
    """Counts of :data:`COPY_PRIMITIVES` outside pallas kernel bodies."""
    counts: dict = {}
    _walk(_trace(fn, *args, **kwargs).jaxpr, [], counts)
    return counts


def grid_slots(grid: tuple[int, ...]) -> int:
    return math.prod(grid)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def full_grid_for(op: str, dims: tuple[int, ...], bm: int, bk: int,
                  bn: int | None = None) -> tuple[int, ...]:
    """The rectangular grid the 'full'/'tri' variants launch."""
    if op == "gemm":
        m, k, n = dims
        return (_cdiv(m, bm), _cdiv(n, bn), _cdiv(k, bk))
    if op == "symm":
        m, n = dims
        return (_cdiv(m, bm), _cdiv(n, bn), _cdiv(m, bm))
    if op in ("syrk", "syr2k"):
        n, k = dims
        return (_cdiv(n, bm), _cdiv(n, bm), _cdiv(k, bk))
    if op == "trmm":
        m, n = dims
        return (_cdiv(m, bm), _cdiv(n, bn), _cdiv(m, bm))
    raise ValueError(op)


def packed_grid_for(op: str, dims: tuple[int, ...], bm: int, bk: int,
                    bn: int | None = None) -> tuple[int, ...]:
    """The packed grid the 'tri_packed' variant launches: T = nb(nb+1)/2
    live blocks — times (k-steps + the write-only mirror step) for the
    rank-k updates, times the n-blocks for trmm."""
    if op in ("syrk", "syr2k"):
        n, k = dims
        nb = _cdiv(n, bm)
        return (nb * (nb + 1) // 2, _cdiv(k, bk) + 1)
    if op == "trmm":
        m, n = dims
        nb = _cdiv(m, bm)
        return (_cdiv(n, bn), nb * (nb + 1) // 2)
    raise ValueError(op)
