"""TRSM on TPU: solve tril(A) @ X = alpha * B (left, lower, non-unit).

A GPU TRSM serialises scalar forward substitution inside the kernel; the
TPU-native formulation (DESIGN.md §2, hardware adaptation) is *blocked
forward substitution driven by GEMM*:

    1. invert the diagonal blocks once:  Dᵢ⁻¹
       (small triangular solves against I — XLA's triangular_solve, runs on
       the MXU; O(m·bm²) total, negligible vs. the O(m²·n) updates)
    2. for each block row i (sequential, ⌈m/bm⌉ steps):
         Rᵢ = alpha·Bᵢ − A[i, :i] @ X[:i]      ← Pallas GEMM (the hot loop)
         Xᵢ = Dᵢ⁻¹ @ Rᵢ                        ← Pallas GEMM (bm × bm × n)

   The two GEMMs' kernels are named ``trsm_update`` and ``trsm_diag`` in the
   compiled program, so a profiler trace tells them from a plain gemm's.

This keeps >95% of the FLOPs inside the tuned Pallas GEMM; many production
BLAS (cuBLAS, oneMKL) use exactly this inversion-based scheme for large
TRSM.  The sequential loop over block rows is a Python loop at trace time —
the number of blocks is static, so every slice below is a *static* slice.

Zero-copy: the masked GEMM accepts ragged shapes directly, so no operand is
ever padded — the last (ragged) diagonal block is solved at its true
(r × r) size instead of the old identity-padded (bm × bm) solve, and a
leading batch axis flows through every step natively (batched
triangular_solve + batched GEMM grids), replacing the old ``jax.vmap``
lift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gemm import gemm_pallas

__all__ = ["trsm_pallas"]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "alpha", "variant",
                                             "interpret"))
def trsm_pallas(a, b, *, bm: int = 128, bn: int = 128, alpha: float = 1.0,
                variant: str = "full", interpret: bool = False):
    del variant  # blocked substitution already does minimal (tri) FLOPs
    *lead, m, m2 = a.shape
    mb, n = b.shape[-2:]
    assert m == m2 == mb
    assert len(lead) <= 1 and b.shape[:-2] == tuple(lead)
    nblk = -(-m // bm)

    x = jnp.zeros((*lead, m, n), a.dtype)
    for i in range(nblk):
        lo, hi = i * bm, min((i + 1) * bm, m)
        # diagonal block inverse at its true (possibly ragged) size
        d = jnp.tril(a[..., lo:hi, lo:hi])
        eye = jnp.eye(hi - lo, dtype=a.dtype)
        if lead:
            eye = jnp.broadcast_to(eye, d.shape)
        dinv = jax.lax.linalg.triangular_solve(d, eye, left_side=True,
                                               lower=True)
        r = alpha * b[..., lo:hi, :]
        if i > 0:
            upd = gemm_pallas(a[..., lo:hi, :lo], x[..., :lo, :],
                              bm=bm, bk=bm, bn=bn, interpret=interpret,
                              name="trsm_update")
            r = r - upd.astype(r.dtype)
        xi = gemm_pallas(dinv, r, bm=bm, bk=bm, bn=bn, interpret=interpret,
                         name="trsm_diag")
        x = jax.lax.dynamic_update_slice(
            x, xi.astype(x.dtype), (0,) * len(lead) + (lo, 0))
    return x
