"""The FROZEN pre-PR-5 dispatch path, kept only as a verification oracle.

Before the zero-copy rework, ``ops.py`` zero-padded every operand to block
multiples (identity-padding the TRSM diagonal), ran the kernels on aligned
shapes, and sliced the result back.  The masked kernels promise to be
bit-identical to that path (the masked zeros occupy exactly the lanes the
padding filled), so the old behavior is preserved here verbatim — in ONE
place — and the unit contract (``tests/test_zero_copy_kernels.py``) asserts
against it.  Never used on any execution path.
"""

from __future__ import annotations

import jax.numpy as jnp

from .gemm import gemm_pallas
from .symm import symm_pallas
from .syrk import syr2k_pallas, syrk_pallas
from .trmm import trmm_pallas
from .trsm import trsm_pallas

__all__ = ["padded_run"]


def _rup(v: int, b: int) -> int:
    return ((v + b - 1) // b) * b


def _pad(x, r: int, c: int):
    m, n = x.shape
    return jnp.pad(x, ((0, r - m), (0, c - n)))


def padded_run(op: str, operands: tuple, *, variant: str = "full",
               block: int = 128, interpret: bool = True):
    """Run ``op`` the pre-PR-5 way: pad to ``block`` multiples, execute
    aligned (where the masks are no-ops), slice back."""
    B = block
    if op == "gemm":
        a, b = operands
        (m, k), n = a.shape, b.shape[1]
        M, K, N = _rup(m, B), _rup(k, B), _rup(n, B)
        return gemm_pallas(_pad(a, M, K), _pad(b, K, N),
                           bm=B, bk=B, bn=B, interpret=interpret)[:m, :n]
    if op == "symm":
        a, b = operands
        m, n = a.shape[0], b.shape[1]
        M, N = _rup(m, B), _rup(n, B)
        return symm_pallas(_pad(a, M, M), _pad(b, M, N),
                           bm=B, bn=B, interpret=interpret)[:m, :n]
    if op == "syrk":
        (a,) = operands
        n, k = a.shape
        return syrk_pallas(_pad(a, _rup(n, B), _rup(k, B)), bm=B, bk=B,
                           variant=variant, interpret=interpret)[:n, :n]
    if op == "syr2k":
        a, b = operands
        n, k = a.shape
        N, K = _rup(n, B), _rup(k, B)
        return syr2k_pallas(_pad(a, N, K), _pad(b, N, K), bm=B, bk=B,
                            variant=variant, interpret=interpret)[:n, :n]
    if op == "trmm":
        a, b = operands
        m, n = a.shape[0], b.shape[1]
        M, N = _rup(m, B), _rup(n, B)
        return trmm_pallas(_pad(a, M, M), _pad(b, M, N), bm=B, bn=B,
                           variant=variant, interpret=interpret)[:m, :n]
    if op == "trsm":
        a, b = operands
        m, n = a.shape[0], b.shape[1]
        M, N = _rup(m, B), _rup(n, B)
        ap = _pad(a, M, M)
        if M > m:  # identity-pad the diagonal (the old well-posedness trick)
            ap = ap + jnp.eye(M, dtype=a.dtype).at[:m, :m].set(0)
        return trsm_pallas(ap, _pad(b, M, N), bm=B, bn=B,
                           interpret=interpret)[:m, :n]
    raise ValueError(op)
