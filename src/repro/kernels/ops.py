"""Public BLAS L3 API with ADSALA runtime block selection.

Each op:
  1. asks the :class:`~repro.core.runtime.AdsalaRuntime` (if provided or
     globally installed) for the argmin-predicted block config at the call's
     dims — at *trace* time, so the decision costs nothing per executed step
     and is memoized across identical shapes (paper Fig. 1b);
  2. dispatches to the Pallas kernel *zero-copy*: grids are ⌈dim/block⌉ over
     the unpadded operands and ragged edge tiles are masked in-kernel, so no
     operand copy, pad, or result slice-back ever materializes (the old
     pad-to-block-multiple path is gone).  Operands carrying a leading batch
     axis execute as one batched grid — one pallas_call per stack.  A
     ``layer`` index makes the weight operand a stack of layer weights, of
     which the kernel reads that layer in place (a model segment's scan).

Each call writes profiler spans (``jax.profiler.TraceAnnotation``, inert
without a profiler session) on the device trace's clock: ``blas.run_op``
around the whole call, ``adsala.select`` around the knob decision and
``blas.launch`` around the enqueue of the jitted kernel program.

The knob spaces used by install-time calibration live here too, so the tuner
and the executor can never disagree about the candidate set.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.knobs import (Knob, KnobSpace, block_knob_space,
                              grouped_knob_space)
from repro.core.runtime import AdsalaRuntime, global_runtime

from .gemm import gemm_pallas
from .grouped_gemm import grouped_gemm_pallas
from .symm import symm_pallas
from .syrk import syr2k_pallas, syrk_pallas
from .trmm import trmm_pallas
from .trsm import trsm_pallas

__all__ = [
    "gemm", "symm", "syrk", "syr2k", "trmm", "trsm", "grouped_gemm",
    "knob_space_for", "default_knob", "dims_of", "run_op", "DTYPE_BYTES",
    "PALLAS_OPS",
]


@functools.lru_cache(maxsize=None)
def DTYPE_BYTES(dtype) -> int:
    return int(jnp.dtype(dtype).itemsize)


#: lazily bound repro.backends.resolve_backend (the backends package imports
#: this module's knob spaces, so a top-level import would be circular; the
#: per-call `from ... import` was measurable constant overhead on the
#: cache-hit path)
_resolve_backend = None


def _backend_resolver():
    global _resolve_backend
    if _resolve_backend is None:
        from repro.backends import resolve_backend
        _resolve_backend = resolve_backend
    return _resolve_backend


# ---------------------------------------------------------------------------
# knob spaces (shared between calibration and execution)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def knob_space_for(op: str, *, small: bool = False,
                   sizes: tuple[int, ...] | None = None) -> KnobSpace:
    """Candidate block configs per subroutine.

    GEMM tunes (bm, bk, bn); the 2-dim subroutines tune (bm, bn) with the
    A-dimension block tied to bm (square A tiles), plus the kernel variant
    for the triangular/symmetric-output ops: 'full' (every block computed),
    'tri' (dead blocks skip MXU work but still occupy grid cells), and
    'tri_packed' (only the n(n+1)/2 live blocks are launched, mirror done
    in-kernel) — three genuinely different execution strategies for the
    model to discriminate between.

    ``sizes`` overrides the block-edge candidates: TPU targets default to
    MXU-aligned (128, 256, 512); CPU-host calibration passes cache-scale
    edges (e.g. 64, 128, 256).
    """
    if sizes is None:
        sizes = (128, 256) if small else (128, 256, 512)
    if op == "gemm":
        return block_knob_space(bms=sizes, bks=sizes, bns=sizes)
    if op == "grouped_gemm":
        return grouped_knob_space(sizes)
    variants = ("full", "tri", "tri_packed") \
        if op in ("syrk", "syr2k", "trmm") else ("full",)
    space = block_knob_space(bms=sizes, bks=(128,), bns=sizes,
                             variants=variants)
    # collapse bk (unused for 2-dim ops) out of the candidate identity
    seen, cands = set(), []
    for k in space:
        d = k.dict
        key = (d["bm"], d["bn"], d["variant"])
        if key not in seen:
            seen.add(key)
            cands.append({"bm": d["bm"], "bk": d["bm"], "bn": d["bn"],
                          "variant": d["variant"]})
    from repro.core.knobs import _grid_parallelism
    return KnobSpace("blocks", cands, parallelism_fn=_grid_parallelism)


@functools.lru_cache(maxsize=None)
def default_knob(op: str) -> Knob:
    """Baseline config (paper: max threads) = maximum grid parallelism =
    smallest blocks.  Cached: the parallelism argmax over the whole knob
    space used to recompute on every call — including every cache-hit
    call, where it dominated the remaining decision latency."""
    space = knob_space_for(op)
    dims = {"gemm": (4096, 4096, 4096),
            "grouped_gemm": (4096, 4096, 4096, 64)}.get(op, (4096, 4096))
    return space.candidates[int(np.argmax(
        [space.parallelism(c, dims) for c in space.candidates]))]


def dims_of(op: str, shapes: tuple[tuple[int, ...], ...]) -> tuple[int, ...]:
    """The subroutine's free dims (paper Table I) from operand shapes.

    Leading batch axes are ignored: a stacked ``(B, m, k)`` operand yields
    the same dims as its per-item ``(m, k)`` slice, so stacked and unstacked
    calls share one decision-cache key.  So is a weight's leading layer
    axis (a call with ``layer``): it keys as the layer's own weight.
    """
    if op == "gemm":
        (m, k), (_, n) = shapes[0][-2:], shapes[1][-2:]
        return (m, k, n)
    if op == "grouped_gemm":          # x (m, k), w (g, k, n); sizes are data
        (m, k), (g, _, n) = shapes[0], shapes[1][-3:]
        return (m, k, n, g)
    if op == "symm":
        (m, _), (_, n) = shapes[0][-2:], shapes[1][-2:]
        return (m, n)
    if op in ("syrk", "syr2k"):
        (n, k) = shapes[0][-2:]
        return (n, k)
    (m, _), (_, n) = shapes[0][-2:], shapes[1][-2:]   # trmm/trsm
    return (m, n)


def _select(op: str, dims: tuple[int, ...], dtype,
            knob: Optional[Knob], runtime: Optional[AdsalaRuntime]) -> Knob:
    if knob is not None:
        return knob
    rt = runtime if runtime is not None else global_runtime()
    with TraceAnnotation("adsala.select"):
        return rt.select_or_default(op, dims, DTYPE_BYTES(dtype),
                                    default_knob(op), backend="pallas")


def _launch(kernel, *args, **kw):
    """Enqueue the jitted kernel program ``kernel(*args, **kw)`` (inside a
    trace: stage it) under the ``blas.launch`` span."""
    with TraceAnnotation("blas.launch"):
        return kernel(*args, **kw)


def _rup(v: int, b: int) -> int:
    return ((v + b - 1) // b) * b


# ---------------------------------------------------------------------------
# public ops (zero-copy: masked kernels take the unpadded operands directly;
# a leading batch axis on every operand executes as one batched grid)
# ---------------------------------------------------------------------------

def gemm(a, b, c=None, *, layer=None, alpha=1.0, beta=0.0, knob=None,
         runtime=None, interpret: bool = False):
    """``alpha·a@b + beta·c``; with ``layer``, ``b`` is a stack ``(L, k, n)``
    and the kernel reads ``b[layer]`` in place.  The decision key is
    ``(m, k, n)`` either way."""
    m, k = a.shape[-2:]
    n = b.shape[-1]
    kb = _select("gemm", (m, k, n), a.dtype, knob, runtime).dict
    bm, bk, bn = (min(kb["bm"], _rup(m, 128)), min(kb["bk"], _rup(k, 128)),
                  min(kb["bn"], _rup(n, 128)))
    return _launch(gemm_pallas, a, b, c, layer=layer, bm=bm, bk=bk, bn=bn,
                   alpha=alpha, beta=beta, interpret=interpret)


def symm(a, b, c=None, *, alpha=1.0, beta=0.0, knob=None, runtime=None,
         interpret: bool = False):
    m, n = a.shape[-2], b.shape[-1]
    kb = _select("symm", (m, n), a.dtype, knob, runtime).dict
    bm, bn = min(kb["bm"], _rup(m, 128)), min(kb["bn"], _rup(n, 128))
    return _launch(symm_pallas, a, b, c, bm=bm, bn=bn, alpha=alpha,
                   beta=beta, interpret=interpret)


def syrk(a, c=None, *, alpha=1.0, beta=0.0, knob=None, runtime=None,
         interpret: bool = False):
    n, k = a.shape[-2:]
    kb = _select("syrk", (n, k), a.dtype, knob, runtime).dict
    bm, bk = min(kb["bm"], _rup(n, 128)), min(kb["bn"], _rup(k, 128))
    return _launch(syrk_pallas, a, c, bm=bm, bk=bk, alpha=alpha, beta=beta,
                   variant=kb.get("variant", "full"), interpret=interpret)


def syr2k(a, b, c=None, *, alpha=1.0, beta=0.0, knob=None, runtime=None,
          interpret: bool = False):
    n, k = a.shape[-2:]
    kb = _select("syr2k", (n, k), a.dtype, knob, runtime).dict
    bm, bk = min(kb["bm"], _rup(n, 128)), min(kb["bn"], _rup(k, 128))
    return _launch(syr2k_pallas, a, b, c, bm=bm, bk=bk, alpha=alpha,
                   beta=beta, variant=kb.get("variant", "full"),
                   interpret=interpret)


def trmm(a, b, *, alpha=1.0, knob=None, runtime=None,
         interpret: bool = False):
    m, n = a.shape[-2], b.shape[-1]
    kb = _select("trmm", (m, n), a.dtype, knob, runtime).dict
    bm, bn = min(kb["bm"], _rup(m, 128)), min(kb["bn"], _rup(n, 128))
    return _launch(trmm_pallas, a, b, bm=bm, bn=bn, alpha=alpha,
                   variant=kb.get("variant", "full"), interpret=interpret)


def trsm(a, b, *, alpha=1.0, knob=None, runtime=None,
         interpret: bool = False):
    m, n = a.shape[-2], b.shape[-1]
    kb = _select("trsm", (m, n), a.dtype, knob, runtime).dict
    bm, bn = min(kb["bm"], _rup(m, 128)), min(kb["bn"], _rup(n, 128))
    return _launch(trsm_pallas, a, b, bm=bm, bn=bn, alpha=alpha,
                   interpret=interpret)


def grouped_gemm(x, w, group_sizes, *, layer=None, knob=None, runtime=None,
                 interpret: bool = False):
    """``out[r] = x[r] @ w[g(r)]``: rows of ``x (m, k)`` sorted by group,
    ``w (g, k, n)``, ``group_sizes (g,)`` summing to m; with ``layer``,
    ``w`` is a stack ``(L, g, k, n)`` read at ``layer`` in place.  The
    decision key is ``(m, k, n, g)``; the group sizes are data and never
    part of it."""
    m, k = x.shape
    g, _, n = w.shape[-3:]
    kb = _select("grouped_gemm", (m, k, n, g), x.dtype, knob, runtime).dict
    bm, bk, bn = (min(kb["bm"], _rup(m, 128)), min(kb["bk"], _rup(k, 128)),
                  min(kb["bn"], _rup(n, 128)))
    return _launch(grouped_gemm_pallas, x, w, group_sizes, layer=layer,
                   bm=bm, bk=bk, bn=bn, interpret=interpret)


#: the pallas-path executors (what the ``pallas`` backend dispatches to)
PALLAS_OPS = {"gemm": gemm, "symm": symm, "syrk": syrk, "syr2k": syr2k,
              "trmm": trmm, "trsm": trsm, "grouped_gemm": grouped_gemm}


def run_op(op: str, operands: tuple, *, backend: str = "pallas",
           knob: Optional[Knob] = None,
           runtime: Optional[AdsalaRuntime] = None,
           stacked: Optional[bool] = None, layer=None, **kw):
    """Execute ``op`` through the backend registry.

    Dispatch resolves the requested backend with a graceful fallback chain
    (requested → ref), so an unregistered or host-unavailable backend still
    yields a correct result.  When no ``knob`` is given the ADSALA runtime
    selects one under the *resolved* backend's key, falling back to that
    backend's default config if it has no tuned model.

    Operands carrying a leading batch axis (``(B, m, k)`` instead of
    ``(m, k)``) execute as one stacked call via ``Backend.execute_stacked``
    — all items share dims/dtype, so a single knob decision covers the whole
    stack.  Trailing operands of one-lower rank (a shared 2-D weight against
    batched activations) broadcast across the stack without a host reshape
    or copy.  ``stacked`` forces the interpretation when auto-detection by
    rank is ambiguous.

    ``layer`` (an int32 scalar, ``gemm`` and ``grouped_gemm`` only) says
    that ``operands[1]`` is a stack of layer weights and the call uses
    layer ``layer`` of it.  A backend that reads weight stacks (``pallas``)
    reads that layer in place; any other gets the layer sliced out and runs
    as without the stack.

    The whole call runs under the ``blas.run_op`` profiler span; while a
    profiler session is on, the span carries the op and its dims.
    """
    if TraceAnnotation.is_enabled():
        span = TraceAnnotation("blas.run_op", op=op, dims=dims_of(
            op, tuple(x.shape for x in operands)))
    else:
        span = TraceAnnotation("blas.run_op")
    with span:
        return _run_op(op, operands, backend=backend, knob=knob,
                       runtime=runtime, stacked=stacked, layer=layer, **kw)


def _run_op(op: str, operands: tuple, *, backend: str,
            knob: Optional[Knob], runtime: Optional[AdsalaRuntime],
            stacked: Optional[bool], layer, **kw):
    be = _backend_resolver()(backend)
    if layer is not None:
        if be.reads_weight_stacks:
            kw["layer"] = layer
        else:
            operands = (operands[0], jax.lax.dynamic_index_in_dim(
                operands[1], layer, keepdims=False)) + tuple(operands[2:])
    if stacked is None:
        stacked = getattr(operands[0], "ndim", 2) == 3
    # chaos seam: a fault plan on the runtime can crash the dispatch exactly
    # where a real kernel launch would fail (guarded so the default path
    # costs two attribute checks and nothing else)
    faults = getattr(runtime, "_faults", None) if runtime is not None else None
    if be.selects_own_knob:
        # the backend's executors resolve the knob themselves (pallas: at
        # jit trace time) — forward the runtime instead of pre-selecting
        if faults is not None:
            faults.fire("kernel_execute", backend=be.name, op=op,
                        stacked=bool(stacked), knob=knob)
        if stacked:
            return be.execute_stacked(op, operands, knob, runtime=runtime,
                                      **kw)
        return be.execute(op, operands, knob, runtime=runtime, **kw)
    if knob is None:
        rt = runtime if runtime is not None else global_runtime()
        dims = dims_of(op, tuple(x.shape for x in operands))
        with TraceAnnotation("adsala.select"):
            knob = rt.select_or_default(op, dims,
                                        DTYPE_BYTES(operands[0].dtype),
                                        be.default_knob(op), backend=be.name)
    if faults is not None:
        faults.fire("kernel_execute", backend=be.name, op=op,
                    stacked=bool(stacked), knob=knob)
    if stacked:
        return be.execute_stacked(op, operands, knob, **kw)
    return be.execute(op, operands, knob, **kw)
