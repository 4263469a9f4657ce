"""SYRK / SYR2K Pallas TPU kernels (lower-triangle rank-k updates).

  syrk : C := alpha*A@A^T + beta*C          A(n,k), C(n,n)
  syr2k: C := alpha*(A@B^T + B@A^T) + beta*C

Three kernel variants, selectable by the ADSALA knob (DESIGN.md §7.4):

  'full'       — every (i, j) output block is computed (both triangles):
                 simple, maximally parallel grid, 2× the minimal FLOPs.
  'tri'        — a full n² grid where blocks strictly above the diagonal
                 skip the MXU work (``pl.when(j <= i)``) and emit zeros;
                 the caller mirrors the lower triangle afterwards as an XLA
                 pass.  ~half the FLOPs, but the skipped cells still pay
                 grid/DMA overhead.
  'tri_packed' — only the n(n+1)/2 lower-triangle blocks are launched: a
                 flattened grid index t de-triangularizes to (i, j) inside
                 the BlockSpec index maps, and the mirror is done in-kernel
                 — after the k loop flushes block (i, j), one extra grid
                 step per tile stores the transposed tile to (j, i) from
                 VMEM scratch (no tril + trilᵀ XLA pass, no dead grid
                 cells).  Grid = (T, nk+1) with T = nb(nb+1)/2: exactly the
                 packed tile count times the k steps, plus the write-only
                 mirror step.

Which variant wins is shape- and hardware-dependent — exactly the trade-off
the ML model learns.

Zero-copy: all grids are ⌈·⌉-sized over the unpadded operands with in-kernel
ragged-tail masking (see gemm.py); C is only an input when ``beta != 0``; a
leading batch axis becomes a leading grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._batching import with_batch_axis
from .gemm import compiler_params, mask_cols, mxu_dot

__all__ = ["syrk_pallas", "syr2k_pallas", "detri", "tri_count"]


def tri_count(i):
    """Lower-triangle block count up to row ``i`` (exclusive): i(i+1)/2."""
    return (i * (i + 1)) // 2


def detri(t):
    """Flattened packed index -> (i, j) with j <= i (row-major over the
    lower triangle).  float32 sqrt seed + exact integer correction, so it
    is exact for any block count a real grid could reach."""
    i = ((jnp.sqrt(8.0 * t.astype(jnp.float32) + 1.0) - 1.0) / 2.0) \
        .astype(jnp.int32)
    i = jnp.where(tri_count(i) > t, i - 1, i)
    i = jnp.where(tri_count(i + 1) <= t, i + 1, i)
    return i, t - tri_count(i)


def _sym_lower(x):
    return jnp.tril(x) + jnp.tril(x, -1).T


# ---------------------------------------------------------------------------
# full / tri kernels: rectangular (i, j, l) grid
# ---------------------------------------------------------------------------

def _rank_k_kernel(*refs, alpha, beta, k, bk, tri, two, has_c, off):
    """Shared syrk/syr2k body.  ``two`` adds the B@Aᵀ term (syr2k); refs =
    (a_i, a_j[, b_i, b_j][, c], o, acc)."""
    pos = 2 + (2 if two else 0)
    a_i_ref, a_j_ref = refs[0], refs[1]
    b_i_ref, b_j_ref = (refs[2], refs[3]) if two else (None, None)
    c_ref = refs[pos] if has_c else None
    o_ref, acc_ref = refs[-2], refs[-1]
    i = pl.program_id(off + 0)
    j = pl.program_id(off + 1)
    l = pl.program_id(off + 2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    compute = (j <= i) if tri else (j == j)  # tri: skip upper blocks

    @pl.when(compute)
    def _acc():
        a_i = a_i_ref[0] if off else a_i_ref[...]
        a_j = a_j_ref[0] if off else a_j_ref[...]
        if k % bk:
            a_i = mask_cols(a_i, bk, l, k)
            a_j = mask_cols(a_j, bk, l, k)
        if two:
            b_i = b_i_ref[0] if off else b_i_ref[...]
            b_j = b_j_ref[0] if off else b_j_ref[...]
            if k % bk:
                b_i = mask_cols(b_i, bk, l, k)
                b_j = mask_cols(b_j, bk, l, k)
            acc_ref[...] += mxu_dot(a_i, b_j.T)
            acc_ref[...] += mxu_dot(b_i, a_j.T)
        else:
            acc_ref[...] += mxu_dot(a_i, a_j.T)

    @pl.when(l == pl.num_programs(off + 2) - 1)
    def _flush():
        out = alpha * acc_ref[...]
        if has_c:
            c = c_ref[0] if off else c_ref[...]
            if tri:
                # tri treats C as lower-stored symmetric: zero its strict
                # upper in-kernel (the old path ran a jnp.tril pre-pass)
                c = jnp.where(j < i, c, jnp.tril(c))
            out = out + beta * c.astype(jnp.float32)
        if off:
            o_ref[0] = out.astype(o_ref.dtype)
        else:
            o_ref[...] = out.astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# tri_packed kernel: (T, nk+1) packed grid with in-kernel mirror
# ---------------------------------------------------------------------------

def _rank_k_packed_kernel(*refs, alpha, beta, k, bk, nk, two, has_c, off):
    """Packed lower-triangle grid.  Steps l < nk accumulate block (i, j)
    with j <= i; step l == nk-1 flushes it (diag blocks symmetrized
    in-kernel) and parks the tile in ``mir_ref``; the extra step l == nk
    stores the transposed tile to block (j, i) — the mirror without any
    XLA post-pass."""
    pos = 2 + (2 if two else 0)
    a_i_ref, a_j_ref = refs[0], refs[1]
    b_i_ref, b_j_ref = (refs[2], refs[3]) if two else (None, None)
    c_ref = refs[pos] if has_c else None
    o_ref, acc_ref, mir_ref = refs[-3], refs[-2], refs[-1]
    t = pl.program_id(off + 0)
    l = pl.program_id(off + 1)
    i, j = detri(t)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(l < nk)
    def _acc():
        a_i = a_i_ref[0] if off else a_i_ref[...]
        a_j = a_j_ref[0] if off else a_j_ref[...]
        if k % bk:
            a_i = mask_cols(a_i, bk, l, k)
            a_j = mask_cols(a_j, bk, l, k)
        if two:
            b_i = b_i_ref[0] if off else b_i_ref[...]
            b_j = b_j_ref[0] if off else b_j_ref[...]
            if k % bk:
                b_i = mask_cols(b_i, bk, l, k)
                b_j = mask_cols(b_j, bk, l, k)
            acc_ref[...] += mxu_dot(a_i, b_j.T)
            acc_ref[...] += mxu_dot(b_i, a_j.T)
        else:
            acc_ref[...] += mxu_dot(a_i, a_j.T)

    @pl.when(l == nk - 1)
    def _flush():
        out = alpha * acc_ref[...]
        if has_c:
            c = c_ref[0] if off else c_ref[...]
            c = jnp.where(j < i, c, jnp.tril(c))   # lower-stored C
            out = out + beta * c.astype(jnp.float32)
        # diagonal blocks: keep the lower triangle and mirror it, exactly
        # like the tri variant's tril + trilᵀ post-pass restricted to the
        # block — off-diagonal lower blocks pass through
        out = jnp.where(j < i, out, _sym_lower(out))
        mir_ref[...] = out
        res = out.astype(o_ref.dtype)
        if off:
            o_ref[0] = res
        else:
            o_ref[...] = res

    @pl.when(l == nk)
    def _mirror():
        res = mir_ref[...].T.astype(o_ref.dtype)
        if off:
            o_ref[0] = res
        else:
            o_ref[...] = res


def _rank_k_call(a, b, c, *, bm, bk, alpha, beta, variant, interpret, two):
    *lead, n, k = a.shape
    assert b is None or b.shape == a.shape
    assert len(lead) <= 1
    batch = lead[0] if lead else None
    has_c = c is not None and beta != 0.0
    off = 1 if batch is not None else 0
    nb, nk = pl.cdiv(n, bm), pl.cdiv(k, bk)

    # operand order: A twice (row-i / row-j views), then B twice for syr2k,
    # then the optional C
    ops_ = [a, a] + ([b, b] if two else []) + ([c] if has_c else [])
    ab_blocks = [(bm, bk)] * (4 if two else 2) + [(bm, bm)] * int(has_c)

    if variant == "tri_packed":
        grid2 = (tri_count(nb), nk + 1)

        def row_i(t, l):
            return (detri(t)[0], jnp.minimum(l, nk - 1))

        def row_j(t, l):
            return (detri(t)[1], jnp.minimum(l, nk - 1))

        def c_map(t, l):
            return detri(t)

        def out_map2(t, l):
            i, j = detri(t)
            mirror = l == nk
            return (jnp.where(mirror, j, i), jnp.where(mirror, i, j))

        in_maps = ([row_i, row_j] * (2 if two else 1) +
                   ([c_map] if has_c else []))
        kernel = functools.partial(_rank_k_packed_kernel, alpha=alpha,
                                   beta=beta, k=k, bk=bk, nk=nk, two=two,
                                   has_c=has_c, off=off)
        semantics = ("arbitrary", "arbitrary")
        scratch = [pltpu.VMEM((bm, bm), jnp.float32),
                   pltpu.VMEM((bm, bm), jnp.float32)]
        out_map = out_map2
    else:
        grid2 = (nb, nb, nk)

        def mk(sel):
            return lambda i, j, l: (sel(i, j), l)

        in_maps = ([mk(lambda i, j: i), mk(lambda i, j: j)] *
                   (2 if two else 1) +
                   ([lambda i, j, l: (i, j)] if has_c else []))
        kernel = functools.partial(_rank_k_kernel, alpha=alpha, beta=beta,
                                   k=k, bk=bk, tri=(variant == "tri"),
                                   two=two, has_c=has_c, off=off)
        semantics = ("parallel", "parallel", "arbitrary")
        scratch = [pltpu.VMEM((bm, bm), jnp.float32)]
        out_map = lambda i, j, l: (i, j)              # noqa: E731

    grid, in_maps, ab_blocks, out_map, out_block, semantics, out_shape = \
        with_batch_axis(batch, grid2, in_maps, ab_blocks, out_map,
                        (bm, bm), semantics, (n, n))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(blk, f)
                  for blk, f in zip(ab_blocks, in_maps)],
        out_specs=pl.BlockSpec(out_block, out_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, a.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params(semantics),
        interpret=interpret,
        name=f"{'syr2k' if two else 'syrk'}_{variant}",
    )(*ops_)
    if variant == "tri":
        out = jnp.tril(out) + jnp.tril(out, -1).swapaxes(-1, -2)
    return out


@functools.partial(jax.jit, static_argnames=("bm", "bk", "alpha", "beta",
                                             "variant", "interpret"))
def syrk_pallas(a, c=None, *, bm: int = 128, bk: int = 128,
                alpha: float = 1.0, beta: float = 0.0,
                variant: str = "full", interpret: bool = False):
    return _rank_k_call(a, None, c, bm=bm, bk=bk, alpha=alpha, beta=beta,
                        variant=variant, interpret=interpret, two=False)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "alpha", "beta",
                                             "variant", "interpret"))
def syr2k_pallas(a, b, c=None, *, bm: int = 128, bk: int = 128,
                 alpha: float = 1.0, beta: float = 0.0,
                 variant: str = "full", interpret: bool = False):
    return _rank_k_call(a, b, c, bm=bm, bk=bk, alpha=alpha, beta=beta,
                        variant=variant, interpret=interpret, two=True)
