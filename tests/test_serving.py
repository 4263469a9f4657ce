"""Tests for the shape-bucketed BLAS serving layer: correctness through the
async path, bucket grouping/flush policy, padding, error propagation,
per-bucket stats, and warm-start via the persisted decision cache."""

import numpy as np
import pytest

from repro.backends import get_backend
from repro.backends.conformance import oracle
from repro.core import AdsalaRuntime, ModelRegistry, install_backend
from repro.serving import BlasService, ServeConfig, bucket_key
from repro.serving.service import SERVABLE_OPS


def make(op, dims, seed=0, dtype=np.float32):
    return get_backend("ref").make_operands(op, dims, dtype, seed=seed)


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServeConfig(workers=0)
    with pytest.raises(ValueError):
        ServeConfig(linger_ms=-1)


def test_bucket_key_splits_on_shape_dtype_backend_and_scalars():
    a32 = [(48, 32), (32, 40)]
    f32x2 = [np.float32, np.float32]
    base = bucket_key("gemm", a32, f32x2, "ref")
    assert base == ("ref", "gemm", 4, (48, 32, 40),
                    ("float32", "float32"), ())
    assert bucket_key("gemm", a32, [np.float64] * 2, "ref") != base
    assert bucket_key("gemm", a32, f32x2, "pallas") != base
    assert bucket_key("gemm", [(48, 32), (32, 48)], f32x2, "ref") != base
    assert bucket_key("gemm", a32, f32x2, "ref", (("alpha", 2.0),)) != base
    # equal itemsize must NOT merge distinct dtypes (f32 vs i32 would
    # silently promote under np.stack) — in ANY operand position
    assert bucket_key("gemm", a32, [np.int32, np.int32], "ref") != base
    assert bucket_key("gemm", a32, [np.float32, np.float64], "ref") != base


def test_mixed_traffic_round_trip():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=8, linger_ms=2.0)
    cases = []
    with BlasService(runtime=rt, config=cfg) as svc:
        for i in range(30):
            op = SERVABLE_OPS[i % len(SERVABLE_OPS)]
            dims = {"gemm": (48, 32, 40), "symm": (48, 40),
                    "syrk": (48, 32), "syr2k": (48, 32),
                    "trmm": (48, 40), "trsm": (48, 40)}[op]
            operands = make(op, dims, seed=i)
            cases.append((op, operands, svc.submit(op, operands)))
        for op, operands, fut in cases:
            got = np.asarray(fut.result(timeout=30), np.float64)
            want = oracle(op, operands)
            rel = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9)
            assert rel < 5e-4, (op, rel)
    assert svc.stats.completed == 30 and svc.stats.failed == 0


def test_full_bucket_flushes_as_one_batch():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=8, linger_ms=60_000.0,
                      min_steal=8)     # no early steal: deterministic batch
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", make("gemm", (32, 32, 32), seed=i))
                for i in range(8)]
        for f in futs:
            f.result(timeout=30)     # resolves without any linger expiry
        assert svc.stats.batches == 1
        assert svc.stats.max_batch == 8
        key = ("ref", "gemm", 4, (32, 32, 32))
        b = svc.bucket_stats()[key]
        assert (b.batches, b.requests, b.max_batch) == (1, 8, 8)
    assert rt.stats.calls == 1       # ONE knob decision for all 8 requests


def test_linger_flushes_partial_bucket():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=1000, linger_ms=30.0)
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", make("gemm", (32, 32, 32), seed=i))
                for i in range(3)]
        for f in futs:
            f.result(timeout=30)
        assert svc.stats.batches == 1          # one linger-triggered flush
        assert svc.stats.completed == 3


def test_padding_to_canonical_width():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=16, linger_ms=10.0,
                      pad_batches=True)
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", make("gemm", (32, 32, 32), seed=i))
                for i in range(3)]
        outs = [np.asarray(f.result(timeout=30)) for f in futs]
    assert svc.stats.padded_items == 1         # 3 → width 4
    for i, out in enumerate(outs):             # padding never leaks out
        want = oracle("gemm", make("gemm", (32, 32, 32), seed=i))
        assert np.max(np.abs(out - want)) / np.max(np.abs(want)) < 5e-4


def test_loop_backends_are_not_padded():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="cpu_blocked", max_batch=16, linger_ms=10.0,
                      pad_batches=True)
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", make("gemm", (32, 32, 32), seed=i))
                for i in range(3)]
        for f in futs:
            f.result(timeout=30)
    # cpu_blocked executes stacks as a loop — padding would be wasted ops
    assert svc.stats.padded_items == 0


def test_scalar_kwargs_get_their_own_bucket():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=8, linger_ms=10.0)
    operands = make("gemm", (32, 32, 32), seed=1)
    with BlasService(runtime=rt, config=cfg) as svc:
        f1 = svc.submit("gemm", operands)
        f2 = svc.submit("gemm", operands, alpha=2.0)
        r1 = np.asarray(f1.result(timeout=30))
        r2 = np.asarray(f2.result(timeout=30))
    assert svc.stats.batches == 2              # alpha split the bucket
    np.testing.assert_allclose(2.0 * r1, r2, rtol=1e-5)


def test_execution_error_fails_the_whole_bucket():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=4, linger_ms=5.0)
    bad = (np.ones((8, 8), np.float32), np.ones((4, 4), np.float32))
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", bad) for _ in range(2)]
        for f in futs:
            with pytest.raises(Exception):
                f.result(timeout=30)
    assert svc.stats.failed == 2 and svc.stats.completed == 0


def test_submit_validation():
    with BlasService(runtime=AdsalaRuntime(),
                     config=ServeConfig(backend="ref")) as svc:
        with pytest.raises(ValueError, match="unknown op"):
            svc.submit("axpy", (np.ones((4, 4), np.float32),))
        with pytest.raises(ValueError, match="2-D"):
            svc.submit("gemm", (np.ones((2, 4, 4), np.float32),
                                np.ones((2, 4, 4), np.float32)))


def test_submit_after_close_raises():
    svc = BlasService(runtime=AdsalaRuntime(),
                      config=ServeConfig(backend="ref"))
    svc.close()
    svc.close()                                 # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit("gemm", make("gemm", (32, 32, 32)))


def test_backpressure_bound_still_completes():
    rt = AdsalaRuntime()
    cfg = ServeConfig(backend="ref", max_batch=4, linger_ms=1.0,
                      max_pending=8)
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", make("gemm", (32, 32, 32), seed=i))
                for i in range(40)]             # 5× the pending bound
        for f in futs:
            f.result(timeout=60)
    assert svc.stats.completed == 40


@pytest.mark.slow
def test_warm_start_skips_model_evals(tmp_path):
    """Cold server evaluates models once per shape; a restarted server
    warm-started from the persisted decision cache evaluates none."""
    registry = ModelRegistry(tmp_path)
    install_backend(get_backend("ref"), ops=("gemm",), n_samples=12,
                    dim_lo=32, dim_hi=128, max_footprint_bytes=1_000_000,
                    tune_trials=1, candidates=("LinearRegression",),
                    registry=registry, seed=0)
    shapes = [(32, 32, 32), (64, 32, 64), (96, 96, 96)]

    def serve(runtime):
        cfg = ServeConfig(backend="ref", max_batch=4, linger_ms=2.0)
        with BlasService(runtime=runtime, config=cfg,
                         registry=registry) as svc:
            warm = svc.warm_started
            futs = [svc.submit("gemm", make("gemm", dims, seed=i))
                    for i, dims in enumerate(shapes * 3)]
            for f in futs:
                f.result(timeout=30)
        return warm

    cold_rt = AdsalaRuntime()
    registry.load_into(cold_rt)
    assert serve(cold_rt) == 0
    assert cold_rt.stats.model_evals == len(shapes)
    assert registry.decision_cache_path.exists()

    warm_rt = AdsalaRuntime()
    registry.load_into(warm_rt)
    assert serve(warm_rt) == len(shapes)
    assert warm_rt.stats.model_evals == 0
    assert warm_rt.stats.cache_hits == warm_rt.stats.calls

