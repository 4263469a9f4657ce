"""TRMM Pallas TPU kernel: B := alpha * tril(A) @ B (left, lower, non-unit).

The contraction over l only references A's lower triangle, so block rows
truncate at the diagonal:

    l < i : dense block A[i,l]
    l = i : diagonal block, masked to its lower triangle in-kernel
    l > i : structurally zero

Three variants, selectable by the ADSALA knob:

  'full'       — rectangular (i, j, l) grid; l > i steps multiply by an
                 explicitly zeroed tile (uniform pipeline, no branch
                 divergence).
  'tri'        — same grid, l > i MXU work skipped with ``pl.when``
                 (≈½ FLOPs, same output); the dead cells still pay
                 grid/DMA overhead.
  'tri_packed' — only the live (i, l<=i) contraction pairs are launched:
                 grid (⌈n/bn⌉, T) with T = nb(nb+1)/2, the packed pair
                 index de-triangularized to (i, l) inside the index maps
                 (j outermost so each output block's k-steps stay
                 consecutive).  No dead grid cells at all.

Which wins depends on the (m, n) shape — the ADSALA model's job to learn.

Zero-copy: ⌈·⌉-sized grids over the unpadded operands, ragged contraction
tail masked in-kernel, leading batch axis as a leading grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._batching import with_batch_axis
from .gemm import compiler_params, mask_cols, mask_rows, mxu_dot
from .syrk import detri, tri_count

__all__ = ["trmm_pallas"]


def _tril_block(a, i, l, m, bm):
    """A[i,l] truncated at the diagonal (tril on the diag block, zeros
    above it) with the ragged contraction tail masked."""
    a = jnp.where(l < i, a, jnp.where(l == i, jnp.tril(a),
                                      jnp.zeros_like(a)))
    if m % bm:
        a = mask_cols(a, bm, l, m)
    return a


def _trmm_kernel(a_ref, b_ref, o_ref, acc_ref, *, alpha, m, bm, tri, off):
    i = pl.program_id(off + 0)
    l = pl.program_id(off + 2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    compute = (l <= i) if tri else (l == l)

    @pl.when(compute)
    def _acc():
        a = a_ref[0] if off else a_ref[...]
        b = b_ref[0] if off else b_ref[...]
        a = _tril_block(a, i, l, m, bm)
        if m % bm:
            b = mask_rows(b, bm, l, m)
        acc_ref[...] += mxu_dot(a, b)

    @pl.when(l == pl.num_programs(off + 2) - 1)
    def _flush():
        res = (alpha * acc_ref[...]).astype(o_ref.dtype)
        if off:
            o_ref[0] = res
        else:
            o_ref[...] = res


def _trmm_packed_kernel(a_ref, b_ref, o_ref, acc_ref, *, alpha, m, bm, off):
    """Packed (j, t) grid: t enumerates the live (i, l<=i) contraction
    pairs, l innermost within each i, so every output block's accumulation
    steps are consecutive."""
    t = pl.program_id(off + 1)
    i, l = detri(t)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0] if off else a_ref[...]
    b = b_ref[0] if off else b_ref[...]
    a = _tril_block(a, i, l, m, bm)
    if m % bm:
        b = mask_rows(b, bm, l, m)
    acc_ref[...] += mxu_dot(a, b)

    @pl.when(l == i)
    def _flush():
        res = (alpha * acc_ref[...]).astype(o_ref.dtype)
        if off:
            o_ref[0] = res
        else:
            o_ref[...] = res


@functools.partial(jax.jit, static_argnames=("bm", "bn", "alpha", "variant",
                                             "interpret"))
def trmm_pallas(a, b, *, bm: int = 128, bn: int = 128, alpha: float = 1.0,
                variant: str = "full", interpret: bool = False):
    *lead, m, m2 = a.shape
    mb, n = b.shape[-2:]
    assert m == m2 == mb
    assert len(lead) <= 1 and b.shape[:-2] == tuple(lead)
    batch = lead[0] if lead else None
    off = 1 if batch is not None else 0
    nbm = pl.cdiv(m, bm)

    if variant == "tri_packed":
        grid2 = (pl.cdiv(n, bn), tri_count(nbm))
        in_maps = [lambda j, t: detri(t),               # A[i, l]
                   lambda j, t: (detri(t)[1], j)]       # B[l, j]
        out_map = lambda j, t: (detri(t)[0], j)         # noqa: E731
        kernel = functools.partial(_trmm_packed_kernel, alpha=alpha, m=m,
                                   bm=bm, off=off)
        semantics = ("parallel", "arbitrary")
    else:
        grid2 = (nbm, pl.cdiv(n, bn), nbm)
        in_maps = [lambda i, j, l: (i, l), lambda i, j, l: (l, j)]
        out_map = lambda i, j, l: (i, j)                # noqa: E731
        kernel = functools.partial(_trmm_kernel, alpha=alpha, m=m, bm=bm,
                                   tri=(variant == "tri"), off=off)
        semantics = ("parallel", "parallel", "arbitrary")

    grid, in_maps, in_blocks, out_map, out_block, semantics, out_shape = \
        with_batch_axis(batch, grid2, in_maps, [(bm, bm), (bm, bn)],
                        out_map, (bm, bn), semantics, (m, n))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(blk, f)
                  for blk, f in zip(in_blocks, in_maps)],
        out_specs=pl.BlockSpec(out_block, out_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(semantics),
        interpret=interpret,
        name=f"trmm_{variant}",
    )(a, b)
