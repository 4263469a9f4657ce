"""Cross-backend numeric conformance harness.

One reusable implementation of the check "does backend X compute op Y
correctly", shared by the pytest suite (``tests/test_backend_conformance.py``)
and the CLI gate (``scripts/check_backends.py``).

The oracle here is *pure numpy in float64* — deliberately independent of
every registered backend (including ``ref``, which is itself jnp-based and
therefore also under test).  Tolerances are per dtype: float32 absorbs
accumulation-order differences across blocked/stacked implementations;
float64 is held tight (backends that cannot execute f64 at full precision —
e.g. jax paths under the default no-x64 config — report it via
``Backend.supports_dtype`` and are skipped, not excused).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .registry import available_backends, get_backend

__all__ = ["DEFAULT_DIMS", "RAGGED_DIMS", "TOLERANCES", "ConformanceResult",
           "check_backend_op", "oracle", "rel_err", "run_conformance",
           "tolerance_for"]

#: tiny, deliberately non-block-aligned dims (exercise the padding paths)
DEFAULT_DIMS = {"gemm": (48, 32, 40), "symm": (48, 40), "syrk": (48, 32),
                "syr2k": (48, 32), "trmm": (48, 40), "trsm": (48, 40)}

#: ragged dims spanning a ragged *last* tile behind full tiles (129, 257),
#: a degenerate single-row problem (1, ...), and an off-multiple square
#: (300) — the edge-tile masks of the zero-copy kernels at their corners
#: (DEFAULT_DIMS never exceeds one block, so the last-tile masking with
#: full tiles before it was previously unexercised)
RAGGED_DIMS = {
    "gemm": ((129, 65, 257), (1, 300, 384), (300, 300, 300)),
    "symm": ((129, 257), (1, 384), (300, 300)),
    "syrk": ((129, 257), (1, 384), (300, 300)),
    "syr2k": ((129, 257), (1, 384), (300, 300)),
    "trmm": ((129, 257), (1, 384), (300, 300)),
    "trsm": ((129, 257), (1, 384), (300, 300)),
}

#: max relative error vs the f64 numpy oracle, keyed by operand dtype bytes
#: (bfloat16 results are rounded to 8 significant bits: 2**-8 ≈ 3.9e-3 per
#: element before any accumulation-order difference)
TOLERANCES = {2: 1e-2, 4: 5e-4, 8: 1e-10}


def tolerance_for(dtype) -> float:
    return TOLERANCES[int(np.dtype(dtype).itemsize)]


def rel_err(got, want) -> float:
    """max |got - want| / max |want|, at float64: the error the tolerances
    bound.  Shapes must agree (a wrong shape may still broadcast)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def _sym_lower(a: np.ndarray) -> np.ndarray:
    lo = np.tril(a)
    return lo + np.tril(a, -1).T


def oracle(op: str, operands: tuple) -> np.ndarray:
    """BLAS semantics (paper Table I) in plain numpy at float64."""
    xs = [np.asarray(x, np.float64) for x in operands]
    if op == "gemm":
        return xs[0] @ xs[1]
    if op == "grouped_gemm":          # rows sorted by group, one weight each
        ends = np.cumsum(np.asarray(operands[2], np.int64))
        out = np.zeros((xs[0].shape[0], xs[1].shape[2]))
        for g, (lo, hi) in enumerate(zip(ends - np.asarray(operands[2]),
                                         ends)):
            out[lo:hi] = xs[0][lo:hi] @ xs[1][g]
        return out
    if op == "symm":
        return _sym_lower(xs[0]) @ xs[1]
    if op == "syrk":
        return xs[0] @ xs[0].T
    if op == "syr2k":
        return xs[0] @ xs[1].T + xs[1] @ xs[0].T
    if op == "trmm":
        return np.tril(xs[0]) @ xs[1]
    if op == "trsm":
        return np.linalg.solve(np.tril(xs[0]), xs[1])
    raise ValueError(op)


@dataclasses.dataclass
class ConformanceResult:
    backend: str
    op: str
    dtype: str
    dims: tuple[int, ...]
    stacked: int            # 0 = single 2-D call, >0 = stack width
    rel_err: float = float("nan")
    ok: bool = False
    skipped: str | None = None      # reason, when not executed
    error: str | None = None        # exception repr, when execution raised

    def line(self) -> str:
        tag = f"{self.backend}:{self.op}:{self.dtype}" + \
            (f":x{self.stacked}" if self.stacked else "")
        if self.skipped:
            return f"{tag} SKIP ({self.skipped})"
        if self.error:
            return f"{tag} ERROR {self.error}"
        return (f"{tag} dims={self.dims} relerr={self.rel_err:.2e} "
                f"{'ok' if self.ok else 'MISMATCH'}")


def check_backend_op(backend: str, op: str, dtype=np.float32, *,
                     dims: tuple[int, ...] | None = None,
                     tol: float | None = None, stacked: int = 0,
                     seed: int = 0) -> ConformanceResult:
    """Run one (backend, op, dtype) instance against the numpy oracle.

    ``stacked > 0`` exercises ``Backend.execute_stacked`` with that stack
    width (each slice gets distinct operands) instead of a single 2-D call.
    """
    be = get_backend(backend)
    dims = tuple(dims) if dims is not None else DEFAULT_DIMS[op]
    dtype = np.dtype(dtype)
    res = ConformanceResult(backend=backend, op=op, dtype=dtype.name,
                            dims=dims, stacked=stacked)
    if not be.is_available():
        res.skipped = "backend unavailable on host"
        return res
    if not be.supports_dtype(dtype):
        res.skipped = f"{dtype.name} unsupported"
        return res
    tol = tol if tol is not None else tolerance_for(dtype)
    try:
        knob = be.default_knob(op)
        if stacked:
            items = [be.make_operands(op, dims, dtype, seed=seed + i)
                     for i in range(stacked)]
            operands = tuple(np.stack([it[i] for it in items])
                             for i in range(len(items[0])))
            got = np.asarray(be.execute_stacked(
                op, be.prepare(operands), knob))
            want = np.stack([oracle(op, it) for it in items])
        else:
            operands = be.make_operands(op, dims, dtype, seed=seed)
            got = np.asarray(be.execute(op, be.prepare(operands), knob))
            want = oracle(op, operands)
    except Exception as e:   # noqa: BLE001 — report, don't crash the sweep
        res.error = f"{type(e).__name__}: {e}"
        return res
    if got.shape != want.shape:
        res.error = f"shape {got.shape} != {want.shape}"
        return res
    res.rel_err = rel_err(got, want)
    res.ok = res.rel_err < tol
    return res


def run_conformance(backends=None, ops=None, dtypes=(np.float32, np.float64),
                    *, tol: float | None = None, stacked_width: int = 0,
                    ragged: bool = False) -> list[ConformanceResult]:
    """The full sweep: every backend × its ops × dtypes (+ optionally the
    stacked path at ``stacked_width``); ``ragged`` additionally sweeps every
    cell over :data:`RAGGED_DIMS` (non-block-multiple shapes, stacked and
    unstacked).  Returns one result per cell."""
    names = tuple(backends) if backends else available_backends()
    results = []
    for name in names:
        be = get_backend(name)
        for op in (tuple(ops) if ops else be.ops()):
            for dtype in dtypes:
                dims_sweep = [None]
                if ragged:
                    dims_sweep += list(RAGGED_DIMS[op])
                for dims in dims_sweep:
                    results.append(check_backend_op(name, op, dtype,
                                                    dims=dims, tol=tol))
                    if stacked_width:
                        results.append(check_backend_op(
                            name, op, dtype, dims=dims, tol=tol,
                            stacked=stacked_width))
    return results
