"""Grouped (ragged-M) GEMM Pallas TPU kernel: ``out[r] = x[r] @ w[g(r)]``
for rows ``x (M, K)`` sorted by group, one weight ``w[g] (K, N)`` per group
and ``group_sizes (G,)`` summing to M — the dropless MoE expert gemm.

Rows are not padded per group.  The row axis is cut into ``bm``-row tiles;
a tile that straddles two groups is visited once per group, and each visit
stores only the rows of its own group (a row mask from the group's offsets).
So the grid's row axis has at most ``⌈M/bm⌉ + G - 1`` visits, static, and M
stays the static ``tokens · top_k``.  The visit → (group, row tile) tables
are computed on the device from ``group_sizes`` and read by the index maps
as scalar prefetch (the precedent is ``megablox/gmm.py``'s group metadata).
A group of size 0 gets no visit, so none of its weight is read; visits past
the last one repeat its block indices (no new copy) and skip the MXU work.

Conventions follow ``gemm.py``: f32 accumulation in a VMEM scratch, the
ragged contraction tail masked on both operands, out-of-bounds rows and
columns dropped by Pallas on the store, ``pallas_call(name=...)``.  Grid
``(⌈N/bn⌉, visits, ⌈K/bk⌉)``: visits of one row tile are consecutive, so its
output block stays in VMEM across the groups that share it.

With a ``layer`` index, ``w`` is a stack ``(L, G, K, N)`` (an MoE segment's
stacked expert weights) and the kernel reads layer ``layer``'s experts in
place: the index is one more scalar prefetch, read by ``w``'s index map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gemm import compiler_params, mask_cols, mask_rows, mxu_dot

__all__ = ["grouped_gemm_pallas", "group_metadata"]


def group_metadata(group_sizes, m: int, bm: int):
    """``(offsets (G+1,), group_ids (V,), tile_ids (V,), visits ())`` for
    ``V = ⌈m/bm⌉ + G - 1`` grid visits: visit ``i < visits`` computes row
    tile ``tile_ids[i]`` for group ``group_ids[i]``; later visits repeat the
    last one's indices."""
    sizes = group_sizes.astype(jnp.int32)
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // bm
    tiles = jnp.where(sizes > 0, (ends - 1) // bm - first + 1, 0)
    visits = jnp.sum(tiles)
    V = pl.cdiv(m, bm) + G - 1
    group_ids = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                           total_repeat_length=V)
    before = jnp.cumsum(tiles) - tiles             # visits of earlier groups
    i = jnp.minimum(jnp.arange(V, dtype=jnp.int32), visits - 1)
    group_ids = group_ids[i]
    tile_ids = first[group_ids] + i - before[group_ids]
    return offsets, group_ids, tile_ids.astype(jnp.int32), visits


def _grouped_kernel(offsets, group_ids, tile_ids, visits, *refs, k, bm, bk,
                    layered):
    """``refs`` = ([layer,] x, w, o, acc): the layer index, when given, is
    read only by ``w``'s index map."""
    x_ref, w_ref, o_ref, acc_ref = refs[1:] if layered else refs
    v, l = pl.program_id(1), pl.program_id(2)

    @pl.when(v < visits[0])
    def _visit():
        @pl.when(l == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a, b = x_ref[...], w_ref[0]
        if k % bk:
            a = mask_cols(a, bk, l, k)
            b = mask_rows(b, bk, l, k)
        acc_ref[...] += mxu_dot(a, b)

        @pl.when(l == pl.num_programs(2) - 1)
        def _store():
            g = group_ids[v]
            rows = tile_ids[v] * bm + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
            o_ref[...] = jnp.where(mine, acc_ref[...],
                                   o_ref[...].astype(jnp.float32)
                                   ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def grouped_gemm_pallas(x, w, group_sizes, *, layer=None, bm: int = 128,
                        bk: int = 128, bn: int = 128,
                        interpret: bool = False):
    """``x (M, K)`` rows sorted by group, ``w (G, K, N)``, ``group_sizes
    (G,)`` int32 summing to M → ``(M, N)`` in ``x``'s dtype.  ``layer`` (an
    int32 scalar) makes ``w`` a stack ``(L, G, K, N)`` of which the kernel
    reads layer ``layer`` in place."""
    m, k = x.shape
    G, k2, n = w.shape[-3:]
    layered = layer is not None
    assert w.ndim == 3 + layered, (w.shape, layered)
    assert k == k2 and group_sizes.shape == (G,), (x.shape, w.shape,
                                                    group_sizes.shape)
    offsets, group_ids, tile_ids, visits = group_metadata(group_sizes, m, bm)
    V = group_ids.shape[0]
    prefetch = [offsets, group_ids, tile_ids, visits.reshape(1)]

    def x_map(j, v, l, offsets, group_ids, tile_ids, visits, *_):
        return tile_ids[v], l

    def o_map(j, v, l, offsets, group_ids, tile_ids, visits, *_):
        return tile_ids[v], j

    if layered:
        prefetch.append(jnp.reshape(layer, (1,)).astype(jnp.int32))

        def w_map(j, v, l, offsets, group_ids, tile_ids, visits, layer):
            return layer[0], group_ids[v], l, j

        w_block = (None, 1, bk, bn)
    else:
        def w_map(j, v, l, offsets, group_ids, tile_ids, visits):
            return group_ids[v], l, j

        w_block = (1, bk, bn)

    return pl.pallas_call(
        functools.partial(_grouped_kernel, k=k, bm=bm, bk=bk,
                          layered=layered),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(pl.cdiv(n, bn), V, pl.cdiv(k, bk)),
            in_specs=[pl.BlockSpec((bm, bk), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_gemm",
    )(*prefetch, x, w)
