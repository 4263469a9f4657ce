"""Plain reference of the six Level-3 ops (the configuration's semantics) in
float64 numpy, and the control: the same mathematics with every product
taken as three bfloat16 passes (``Precision.HIGH``), the step below the
full float32 contraction that the configuration states."""

from __future__ import annotations

import numpy as np


def _sym_lower(a):
    return np.tril(a) + np.tril(a, -1).T


def oracle(op: str, operands) -> np.ndarray:
    xs = [np.asarray(x, np.float64) for x in operands]
    if op == "gemm":
        return xs[0] @ xs[1]
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


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _dot3(a, b):
    """``a @ b`` as three bfloat16 products accumulated in float32: the
    high parts against each other and against the other's low part."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (ah @ bh + ah @ bl + al @ bh).astype(np.float32)


def control(op: str, operands) -> np.ndarray:
    """The reference at the lower precision, in the program's place."""
    xs = [np.asarray(x, np.float32) for x in operands]
    if op == "gemm":
        return _dot3(xs[0], xs[1])
    if op == "symm":
        return _dot3(_sym_lower(xs[0]), xs[1])
    if op == "syrk":
        return _dot3(xs[0], xs[0].T)
    if op == "syr2k":
        return _dot3(xs[0], xs[1].T) + _dot3(xs[1], xs[0].T)
    if op == "trmm":
        return _dot3(np.tril(xs[0]), xs[1])
    if op == "trsm":
        # the exact inverse, applied at the lower precision
        inv = np.linalg.inv(np.tril(np.asarray(operands[0], np.float64)))
        return _dot3(inv.astype(np.float32), xs[1])
    raise ValueError(op)


def rel_err(got, want) -> float:
    """max |got - want| / max |want| at float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != {want.shape}")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))
