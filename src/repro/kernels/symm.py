"""SYMM Pallas TPU kernel: C := alpha*sym(A)@B + beta*C (left side, lower
storage).

A is stored in its lower triangle only.  The kernel receives **two views of
the same array** with mirrored index maps — block (i,l) and block (l,i) — and
reconstructs the symmetric block on the fly:

    i > l : A[i,l] is in the stored lower triangle           → use view 1
    i < l : sym(A)[i,l] = A[l,i]^T, A[l,i] stored            → use view 2^T
    i = l : diagonal block, mirror its own lower triangle

A-blocks are square (bm × bm) so the mirrored view has the same block shape.
Loading two views costs ≤2× A-tile traffic; the ADSALA tuner sees that cost
in its measured/For-oracle timings and sizes blocks accordingly.

Zero-copy: the grid is ⌈·⌉-sized over the unpadded operands; the ragged
contraction tail masks both dot operands in-kernel (see ``gemm.mask_cols``),
OOB output rows/cols are dropped on store, and the C operand only exists
when ``beta != 0``.  A leading batch axis becomes a leading grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._batching import with_batch_axis
from .gemm import compiler_params, mask_cols, mask_rows, mxu_dot

__all__ = ["symm_pallas"]


def _symm_kernel(*refs, alpha, beta, m, bm, has_c, off):
    if has_c:
        a_il_ref, a_li_ref, b_ref, c_ref, o_ref, acc_ref = refs
    else:
        a_il_ref, a_li_ref, b_ref, o_ref, acc_ref = refs
    i = pl.program_id(off + 0)
    l = pl.program_id(off + 2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_il = a_il_ref[0] if off else a_il_ref[...]
    a_li = a_li_ref[0] if off else a_li_ref[...]
    b = b_ref[0] if off else b_ref[...]
    diag = jnp.tril(a_il) + jnp.tril(a_il, -1).T
    a = jnp.where(i > l, a_il, jnp.where(i < l, a_li.T, diag))
    if m % bm:
        # ragged contraction tail (the contraction dim of symm is m itself)
        a = mask_cols(a, bm, l, m)
        b = mask_rows(b, bm, l, m)
    acc_ref[...] += mxu_dot(a, b)

    @pl.when(l == pl.num_programs(off + 2) - 1)
    def _flush():
        out = alpha * acc_ref[...]
        if has_c:
            c = c_ref[0] if off else c_ref[...]
            out = out + beta * c.astype(jnp.float32)
        if off:
            o_ref[0] = out.astype(o_ref.dtype)
        else:
            o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "alpha", "beta",
                                             "interpret"))
def symm_pallas(a, b, c=None, *, bm: int = 128, bn: int = 128,
                alpha: float = 1.0, beta: float = 0.0,
                interpret: bool = False):
    *lead, m, m2 = a.shape
    mb, n = b.shape[-2:]
    assert m == m2 == mb
    assert len(lead) <= 1 and b.shape[:-2] == tuple(lead)
    batch = lead[0] if lead else None
    has_c = c is not None and beta != 0.0
    off = 1 if batch is not None else 0

    grid, in_maps, in_blocks, out_map, out_block, semantics, out_shape = \
        with_batch_axis(
            batch, (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(m, bm)),
            [lambda i, j, l: (i, l), lambda i, j, l: (l, i),
             lambda i, j, l: (l, j), lambda i, j, l: (i, j)],
            [(bm, bm), (bm, bm), (bm, bn), (bm, bn)],
            lambda i, j, l: (i, j), (bm, bn),
            ("parallel", "parallel", "arbitrary"), (m, n))

    operands = [a, a, b] + ([c] if has_c else [])
    in_specs = [pl.BlockSpec(blk, f)
                for blk, f in zip(in_blocks, in_maps)][: len(operands)]
    return pl.pallas_call(
        functools.partial(_symm_kernel, alpha=alpha, beta=beta, m=m, bm=bm,
                          has_c=has_c, off=off),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, out_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(semantics),
        interpret=interpret,
        name="symm",
    )(*operands)
