"""Parametrized cross-backend conformance suite: every registered backend ×
all six BLAS L3 ops × both dtypes, checked against the float64 numpy oracle
with a per-dtype tolerance (``scripts/check_backends.py`` is a thin CLI
wrapper over the same harness in ``repro.backends.conformance``)."""

import numpy as np
import pytest

from repro.backends import L3_OPS, available_backends, get_backend
from repro.backends.conformance import (DEFAULT_DIMS, RAGGED_DIMS,
                                        check_backend_op, oracle,
                                        tolerance_for)

BACKENDS = available_backends()
DTYPES = pytest.mark.parametrize(
    "dtype", (np.float32, np.float64), ids=("f32", "f64"))


def _gate(backend, op, dtype):
    be = get_backend(backend)
    if not be.is_available():
        pytest.skip(f"{backend} unavailable on host")
    if not be.supports_dtype(dtype):
        pytest.skip(f"{backend} does not execute {np.dtype(dtype).name} "
                    f"at full precision")
    return be


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", L3_OPS)
@DTYPES
def test_matches_oracle(backend, op, dtype):
    _gate(backend, op, dtype)
    res = check_backend_op(backend, op, dtype, seed=7)
    assert res.skipped is None, res.line()
    assert res.error is None, res.line()
    assert res.ok, res.line()


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", L3_OPS)
def test_stacked_matches_oracle(backend, op):
    """execute_stacked over a width-3 stack of distinct problems equals
    three independent oracle calls (the serving layer's batch primitive)."""
    _gate(backend, op, np.float32)
    res = check_backend_op(backend, op, np.float32, stacked=3, seed=11)
    assert res.skipped is None and res.error is None, res.line()
    assert res.ok, res.line()


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", L3_OPS)
@pytest.mark.parametrize("ragged_idx", (0, 1, 2),
                         ids=("ragged-tail", "one-row", "off-square"))
@DTYPES
def test_ragged_matches_oracle(backend, op, ragged_idx, dtype):
    """Non-block-multiple dims across every op × backend: a ragged last
    tile behind full tiles, a single-row problem, and an off-multiple
    square — the masked edge tiles of the zero-copy kernels at their
    corners."""
    _gate(backend, op, dtype)
    dims = RAGGED_DIMS[op][ragged_idx]
    res = check_backend_op(backend, op, dtype, dims=dims, seed=17)
    assert res.skipped is None and res.error is None, res.line()
    assert res.ok, res.line()


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("op", L3_OPS)
def test_ragged_stacked_matches_oracle(backend, op):
    """The stacked (leading-batch-grid) path at ragged dims — a width-2
    stack of distinct ragged problems equals two oracle calls."""
    _gate(backend, op, np.float32)
    res = check_backend_op(backend, op, np.float32,
                           dims=RAGGED_DIMS[op][0], stacked=2, seed=23)
    assert res.skipped is None and res.error is None, res.line()
    assert res.ok, res.line()


@pytest.mark.parametrize("op", L3_OPS)
def test_oracle_self_consistent(op):
    """The numpy oracle agrees with the repo's jnp reference kernels — the
    two independent statements of Table-I semantics cross-check each other."""
    from repro.kernels import ref
    be = get_backend("ref")
    operands = be.make_operands(op, DEFAULT_DIMS[op], np.float32, seed=3)
    want = oracle(op, operands)
    got = np.asarray(ref.REFS[op](*[np.asarray(x) for x in operands]),
                     np.float64)
    rel = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9)
    assert rel < tolerance_for(np.float32)


def test_tolerances_are_per_dtype():
    import jax.numpy as jnp
    assert tolerance_for(np.float64) < tolerance_for(np.float32) \
        < tolerance_for(jnp.bfloat16)


@pytest.mark.parametrize("op", L3_OPS)
@pytest.mark.parametrize("dtype", ("bfloat16", "float32", "float64"))
def test_operands_have_the_requested_dtype(op, dtype):
    """A mixed pair (trsm's diagonal shift once promoted a bf16 ``a`` to
    f32) would test a different kernel than the one asked for."""
    import jax.numpy as jnp
    want = jnp.dtype(dtype)
    operands = get_backend("ref").make_operands(op, DEFAULT_DIMS[op], want)
    assert [x.dtype for x in operands] == [want] * len(operands)
