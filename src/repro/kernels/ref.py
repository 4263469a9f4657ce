"""Pure-jnp reference oracles for all six BLAS L3 subroutines.

Semantics follow the BLAS standard (paper Table I), specialised to the
variants this library implements on TPU:

  gemm : C := alpha*A@B + beta*C                      A(m,k) B(k,n) C(m,n)
  symm : C := alpha*sym(A)@B + beta*C  (left, lower)  A(m,m) B(m,n) C(m,n)
  syrk : C := alpha*A@A^T + beta*C     (lower)        A(n,k) C(n,n)
  syr2k: C := alpha*(A@B^T + B@A^T) + beta*C (lower)  A,B(n,k) C(n,n)
  trmm : B := alpha*tril(A)@B          (left, lower, non-unit)  A(m,m) B(m,n)
  trsm : solve tril(A)@X = alpha*B     (left, lower, non-unit)
  grouped_gemm: out[r] = X[r]@W[g(r)], rows sorted by group  X(m,k) W(g,k,n)

Symmetric operands are *stored* in the lower triangle (the upper triangle of
the input array is ignored, as a real BLAS would).  Outputs of syrk/syr2k are
returned as full symmetric matrices (both triangles valid) — the kernels'
``tri`` variants compute only the lower triangle and mirror.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["gemm", "symm", "syrk", "syr2k", "trmm", "trsm", "grouped_gemm",
           "REFS"]


def _sym_lower(a):
    lo = jnp.tril(a)
    return lo + jnp.tril(a, -1).swapaxes(-1, -2)


def gemm(a, b, c=None, *, alpha=1.0, beta=0.0):
    out = alpha * (a @ b)
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out.astype(a.dtype)


def symm(a, b, c=None, *, alpha=1.0, beta=0.0):
    out = alpha * (_sym_lower(a) @ b)
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out.astype(a.dtype)


def syrk(a, c=None, *, alpha=1.0, beta=0.0):
    out = alpha * (a @ a.swapaxes(-1, -2))
    if c is not None and beta != 0.0:
        out = out + beta * _sym_lower(c)
    return out.astype(a.dtype)


def syr2k(a, b, c=None, *, alpha=1.0, beta=0.0):
    out = alpha * (a @ b.swapaxes(-1, -2) + b @ a.swapaxes(-1, -2))
    if c is not None and beta != 0.0:
        out = out + beta * _sym_lower(c)
    return out.astype(a.dtype)


def trmm(a, b, *, alpha=1.0):
    return (alpha * (jnp.tril(a) @ b)).astype(a.dtype)


def trsm(a, b, *, alpha=1.0):
    import jax
    x = jax.lax.linalg.triangular_solve(
        jnp.tril(a), alpha * b, left_side=True, lower=True)
    return x.astype(a.dtype)


def grouped_gemm(x, w, group_sizes):
    """XLA's ragged dot, accumulated in float32."""
    import jax
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


REFS = {"gemm": gemm, "symm": symm, "syrk": syrk, "syr2k": syr2k,
        "trmm": trmm, "trsm": trsm, "grouped_gemm": grouped_gemm}
