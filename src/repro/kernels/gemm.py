"""GEMM Pallas TPU kernel: C := alpha*A@B + beta*C with (bm, bk, bn) VMEM
tiling — the op whose block config ADSALA tunes at runtime.

Zero-copy execution: the grid is (⌈m/bm⌉, ⌈n/bn⌉, ⌈k/bk⌉) over the *unpadded*
operands.  Ragged edge tiles are handled in-kernel — out-of-bounds reads of
the last contraction tile return undefined values (NaN in interpret mode),
so both dot operands mask their ragged k-columns/rows to zero with an iota
bound check; out-of-bounds output rows/cols are dropped by Pallas on the
store.  When every dim divides its block the masks vanish at trace time, so
the aligned path compiles to exactly the pre-masking kernel.  The masked
zeros occupy the same lanes as the old zero-padded operands, so masked and
padded execution are bit-identical.

The contraction dim is innermost and marked ``arbitrary`` (sequential
revisits of the same output block); the two output dims are ``parallel``.  A
float32 VMEM scratch accumulator holds the partial C tile across k steps so
low-precision inputs (bf16) accumulate at full precision in the MXU.

A leading batch axis on the operands (``(B, m, k)``) becomes a leading
``parallel`` grid dimension — one pallas_call executes the whole stack (the
serving layer's bucket primitive), replacing the old ``jax.vmap`` lift.
The C operand is only an input when ``beta != 0`` and a C was given; the
old path materialised a ``jnp.zeros`` C (and DMA'd it) even for the
``beta == 0`` common case.

With a ``layer`` index, B is a stack of weights ``(L, k, n)`` (a model
segment's stacked layer weights) and the kernel reads layer ``layer``'s
tiles straight from it: the index arrives as scalar prefetch and B's index
map selects the layer, so no per-layer copy of the weight is made before
the call.  Without one the kernel is the plain gemm above.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._batching import with_batch_axis

__all__ = ["gemm_pallas", "mxu_dot", "compiler_params"]

#: scoped VMEM a kernel may use.  The compiler's default (16 MiB) is too
#: little for the full-f32 contraction of 512-blocks (syr2k's two
#: accumulating products need 16.2 MiB); a v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def compiler_params(semantics) -> pltpu.CompilerParams:
    """Mosaic parameters shared by every kernel of this package."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def mxu_dot(a, b):
    """``a @ b`` accumulated in float32 on the MXU.  Two float32 operands
    are contracted at full float32 precision: Mosaic's default contracts
    them in one bf16 pass, a relative error near 2e-3 at k=2048 on a TPU
    v5e, which is not an SGEMM.  Other operands keep the default (Mosaic
    refuses full precision for a mixed f32 x bf16 product)."""
    precision = (jax.lax.Precision.HIGHEST
                 if a.dtype == b.dtype == jnp.float32 else None)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision)


def _where_below(ids, dim: int, x):
    """``x`` where ``ids < dim``, else 0.  A packed (sub-32-bit) tile is
    selected in float32: Mosaic cannot select a bf16 tile of fewer rows
    than a packed sublane group ("Not implemented: Sublane broadcast", a
    decode step's few-row gemms with k not a multiple of bk)."""
    if x.dtype.itemsize < 4:
        return jnp.where(ids < dim, x.astype(jnp.float32),
                         0.0).astype(x.dtype)
    return jnp.where(ids < dim, x, jnp.zeros_like(x))


def mask_cols(x, block: int, step, dim: int):
    """Zero the columns of tile ``x`` whose global index (``step``-th block
    of width ``block``) falls at or beyond ``dim`` — the ragged tail mask."""
    ids = block * step + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return _where_below(ids, dim, x)


def mask_rows(x, block: int, step, dim: int):
    """Row-axis twin of :func:`mask_cols`."""
    ids = block * step + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return _where_below(ids, dim, x)


def _gemm_kernel(*refs, alpha, beta, k, bk, has_c, off, shared_b, layered):
    """``refs`` = ([layer,] a, b[, c], o, acc); ``off`` = 1 when a leading
    batch grid dim is present (refs then carry a leading length-1 block
    axis).  ``shared_b`` — B is a single weight shared across the stack (its
    ref never gained the batch block axis).  ``layered`` — the first ref is
    the scalar-prefetched layer index, which only B's index map reads."""
    if layered:
        refs = refs[1:]
    if has_c:
        a_ref, b_ref, c_ref, o_ref, acc_ref = refs
    else:
        a_ref, b_ref, o_ref, acc_ref = refs
    l = pl.program_id(off + 2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0] if off else a_ref[...]
    b = b_ref[0] if (off and not shared_b) else b_ref[...]
    if k % bk:
        # ragged contraction tail: both operands masked (OOB reads are
        # undefined, and 0 * garbage is still garbage when garbage is NaN)
        a = mask_cols(a, bk, l, k)
        b = mask_rows(b, bk, l, k)
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


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "alpha",
                                             "beta", "interpret", "name"))
def gemm_pallas(a, b, c=None, *, layer=None, bm: int = 128, bk: int = 128,
                bn: int = 128, alpha: float = 1.0, beta: float = 0.0,
                interpret: bool = False, name: str = "gemm"):
    """alpha*A@B + beta*C for arbitrary (ragged) shapes; a leading batch
    axis executes as one batched grid.  A 2-D B against a batched A is
    treated as a weight shared across the stack: each stack item re-reads
    it (a model's routed linear folds its batch into M instead).  ``layer``
    (an int32 scalar) makes B a stack ``(L, k, n)`` of which the kernel
    reads layer ``layer`` in place, as the 2-D weight it stands for.
    ``name`` is the kernel's name in the compiled program, which a profiler
    trace shows: callers that use the gemm as a step of another op name
    that step."""
    *lead, m, k = a.shape
    k2, n = b.shape[-2:]
    layered = layer is not None
    b_lead = b.shape[1:-2] if layered else b.shape[:-2]
    assert k == k2, (a.shape, b.shape)
    assert len(lead) <= 1 and b_lead in (tuple(lead), ()), (a.shape, b.shape)
    assert not (layered and b_lead), (a.shape, b.shape)
    batch = lead[0] if lead else None
    shared_b = batch is not None and not b_lead
    has_c = c is not None and beta != 0.0
    off = 1 if batch is not None else 0

    # index maps take the scalar-prefetch refs (the layer) as trailing
    # arguments; only B's reads them
    if layered:
        b_map, b_block = (lambda i, j, l, s: (s[0], l, j)), (None, bk, bn)
    else:
        b_map, b_block = (lambda i, j, l: (l, j)), (bk, bn)
    grid, in_maps, in_blocks, out_map, out_block, semantics, out_shape = \
        with_batch_axis(
            batch, (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk)),
            [lambda i, j, l, *_: (i, l), b_map,
             lambda i, j, l, *_: (i, j)],
            [(bm, bk), b_block, (bm, bn)],
            lambda i, j, l, *_: (i, j), (bm, bn),
            ("parallel", "parallel", "arbitrary"), (m, n),
            broadcast=(False, shared_b, False))

    operands = [a, b] + ([c] if has_c else [])
    specs = dict(
        grid=grid,
        in_specs=[pl.BlockSpec(blk, f)
                  for blk, f in zip(in_blocks, in_maps)][: len(operands)],
        out_specs=pl.BlockSpec(out_block, out_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)])
    if layered:
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **specs))
        operands = [jnp.reshape(layer, (1,)).astype(jnp.int32)] + operands
    return pl.pallas_call(
        functools.partial(_gemm_kernel, alpha=alpha, beta=beta, k=k, bk=bk,
                          has_c=has_c, off=off, shared_b=shared_b,
                          layered=layered),
        **specs,
        out_shape=jax.ShapeDtypeStruct(out_shape, a.dtype),
        compiler_params=compiler_params(semantics),
        interpret=interpret,
        name=name,
    )(*operands)
