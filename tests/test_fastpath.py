"""Compiled decision fast path: argmin/bit parity against the reference
path (all six ops x both dtypes x every persisted model family), lock-free
hit-path concurrency (stats stay exact), and select_many equivalence with N
individual selects."""

import random
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import (SUBROUTINE_NDIMS, AdsalaRuntime, install_subroutine,
                        load_subroutine)
from repro.core.fastpath import CompiledPredictor, compile_predictor
from repro.core.knobs import Knob, thread_knob_space
from repro.kernels import ops


class StubSub:
    """Uncompilable TunedSubroutine stand-in (no pipeline/model): the
    runtime must fall back to its reference ``select``."""

    def __init__(self, backend: str, op: str = "gemm",
                 dtype_bytes: int = 4) -> None:
        self.backend = backend
        self.op = op
        self.dtype_bytes = dtype_bytes
        self.knob = Knob((("bm", 128), ("bn", 128)))
        self.evals = 0

    def select(self, dims):
        self.evals += 1
        return self.knob

#: model families present in the repo's persisted artifact store
PERSISTED_FAMILIES = ("LinearRegression", "DecisionTree")

OPS = ("gemm", "symm", "syrk", "syr2k", "trmm", "trsm")

#: the frozen chip installs the benchmark loads (read here, never written)
INSTALL_ROOT = Path(__file__).resolve().parents[1] / "bench" / "data" / \
    "install"
FROZEN_ARTIFACTS = sorted(INSTALL_ROOT.rglob("*.adsala"))


def _dims_sweep(op: str, n_random: int = 12, seed: int = 7):
    nd = SUBROUTINE_NDIMS[op]
    fixed = [(16,) * nd, (64,) * nd, (512,) * nd, (2048,) * nd,
             (33, 257, 1023, 7)[:nd], (1024, 48, 640, 64)[:nd]]
    rng = np.random.default_rng(seed)
    rand = [tuple(int(v) for v in rng.integers(8, 2048, size=nd))
            for _ in range(n_random)]
    return fixed + rand


def _timer(space):
    """Structured synthetic timer: dims- and knob-dependent (compute +
    per-grid-cell launch overhead + block cost), so fitted models produce a
    dims-dependent argmin structure — including exact prediction ties for
    knobs whose surviving features coincide."""
    def t(dims, knob):
        d = knob.dict
        par = space.parallelism(knob, dims)
        work = float(np.prod(np.asarray(dims, dtype=np.float64)))
        return 1e-9 * work / par + 3e-6 * par \
            + 1e-8 * (d.get("bm", 1) + d.get("bn", 1))
    return t


@pytest.fixture(scope="module")
def installed():
    """One tuned artifact per (op, dtype_bytes, model family)."""
    out = {}
    for op in OPS:
        space = ops.knob_space_for(op, sizes=(32, 64))
        for dtype_bytes in (4, 8):
            for family in PERSISTED_FAMILIES:
                out[(op, dtype_bytes, family)] = install_subroutine(
                    op, space, _timer(space), n_samples=10, dim_lo=16,
                    dim_hi=256, max_footprint_bytes=10_000_000,
                    dtype_bytes=dtype_bytes, candidates=(family,),
                    tune_trials=1, use_lof=False, backend="cpu_blocked")
    return out


# ---------------------------------------------------------------------------
# argmin / bit parity: fast path vs reference path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("dtype_bytes", [4, 8])
@pytest.mark.parametrize("family", PERSISTED_FAMILIES)
def test_parity_installed(installed, op, dtype_bytes, family):
    sub = installed[(op, dtype_bytes, family)]
    cp = compile_predictor(sub)
    assert cp is not None
    for dims in _dims_sweep(op):
        ref_t = sub.predict_times(dims)
        fast_t = cp.predict_times(dims)
        assert np.array_equal(ref_t, fast_t), (op, family, dims)
        assert cp.select(dims) == sub.select(dims), (op, family, dims)


@pytest.mark.parametrize(
    "path", FROZEN_ARTIFACTS,
    ids=[str(p.relative_to(INSTALL_ROOT)) for p in FROZEN_ARTIFACTS])
def test_parity_persisted_artifacts(path):
    """Zero argmin decision changes on every artifact the repo ships: the
    frozen chip installs, loaded as the benchmark loads them."""
    sub = load_subroutine(path)
    cp = compile_predictor(sub)
    assert cp is not None, (sub.backend, sub.op)
    for dims in _dims_sweep(sub.op, n_random=20, seed=11):
        assert np.array_equal(cp.predict_times(dims),
                              sub.predict_times(dims)), \
            (sub.backend, sub.op, dims)
        assert cp.select(dims) == sub.select(dims), \
            (sub.backend, sub.op, dims)


# ---------------------------------------------------------------------------
# v2 lowerings: predicated single trees, screened exact KNN
# ---------------------------------------------------------------------------

V2_FAMILIES = ("KNN", "DistilledTree")


@pytest.fixture(scope="module")
def installed_v2():
    """KNN artifacts for every op plus distilled trees for two ops (the
    lowerings PR 4 added), fit on the same structured synthetic timer."""
    out = {}
    for op in OPS:
        space = ops.knob_space_for(op, sizes=(32, 64))
        out[(op, "KNN")] = install_subroutine(
            op, space, _timer(space), n_samples=10, dim_lo=16, dim_hi=256,
            max_footprint_bytes=10_000_000, candidates=("KNN",),
            tune_trials=1, use_lof=False, backend="cpu_blocked")
    for op in ("gemm", "symm"):
        space = ops.knob_space_for(op, sizes=(32, 64))
        out[(op, "DistilledTree")] = install_subroutine(
            op, space, _timer(space), n_samples=10, dim_lo=16, dim_hi=256,
            max_footprint_bytes=10_000_000, candidates=("DistilledTree",),
            tune_trials=1, use_lof=False, backend="cpu_blocked")
    return out


@pytest.mark.parametrize("op", OPS)
def test_parity_knn(installed_v2, op):
    """The screened exact KNN lookup is bit-identical to the reference
    brute-force path on every op's feature space."""
    sub = installed_v2[(op, "KNN")]
    cp = compile_predictor(sub)
    assert cp is not None and cp.lowering == "screened-knn"
    for dims in _dims_sweep(op):
        assert np.array_equal(cp.predict_times(dims),
                              sub.predict_times(dims)), (op, dims)
        assert cp.select(dims) == sub.select(dims)


@pytest.mark.parametrize("op", ("gemm", "symm"))
def test_parity_distilled_tree(installed_v2, op):
    sub = installed_v2[(op, "DistilledTree")]
    cp = compile_predictor(sub)
    assert cp is not None and cp.lowering == "predicated-tree"
    for dims in _dims_sweep(op):
        assert np.array_equal(cp.predict_times(dims),
                              sub.predict_times(dims)), (op, dims)


def test_parity_knn_distance_weights(installed_v2):
    """Distance-weighted KNN: the weighted combine over canonical
    neighbours reproduces the reference bit for bit."""
    from repro.core.ml.knn import KNN
    sub = installed_v2[("gemm", "KNN")]
    m = sub.model
    import dataclasses
    sub2 = dataclasses.replace(
        sub, model=KNN(k=m.k, weights="distance").fit(m.X_, m.y_),
        dataset=None, reports=[])
    cp = compile_predictor(sub2)
    for dims in _dims_sweep("gemm", n_random=8):
        assert np.array_equal(cp.predict_times(dims),
                              sub2.predict_times(dims)), dims


def test_parity_batch_v2(installed_v2):
    """Batched prediction (with its duplicate-row fold) stays bit-identical
    to per-dims prediction for the new lowerings."""
    rng = np.random.default_rng(5)
    for key in ((("gemm", "KNN")), ("gemm", "DistilledTree")):
        sub = installed_v2[key]
        cp = compile_predictor(sub)
        dims_list = [tuple(int(v) for v in rng.integers(8, 2048, size=3))
                     for _ in range(7)]
        dims_list.append(dims_list[0])          # duplicate item
        t = cp.predict_times_batch(dims_list)
        for b, dims in enumerate(dims_list):
            assert np.array_equal(t[b], sub.predict_times(dims)), (key, dims)


def test_lowering_names(installed, installed_v2):
    assert compile_predictor(
        installed[("gemm", 4, "LinearRegression")]).lowering \
        == "reference-predict"
    assert compile_predictor(
        installed[("gemm", 4, "DecisionTree")]).lowering == "predicated-tree"
    assert compile_predictor(
        installed_v2[("gemm", "KNN")]).lowering == "screened-knn"
    assert compile_predictor(
        installed_v2[("gemm", "DistilledTree")]).lowering \
        == "predicated-tree"


def test_screened_knn_screen_path_parity():
    """The sgemm screen + certification + exact rescore, driven directly
    at n >> 4k so the brute-force early exit can NOT mask it: parity with
    the canonical reference on clustered data, duplicate training points
    (exact distance ties), and queries placed exactly on tie boundaries
    (exercising the union fallback)."""
    from repro.core.fastpath import _ScreenedKNN
    from repro.core.ml.knn import KNN
    rng = np.random.default_rng(17)
    n, C = 600, 7
    X = rng.normal(size=(n, C)) * rng.uniform(0.5, 3.0, size=C)
    X[100:140] = X[60:100]          # duplicate blocks: exact tie clusters
    X[500:530] = X[0]               # one point duplicated 30x > PAD
    y = rng.normal(size=n)
    for k, weights in ((5, "uniform"), (15, "distance"), (3, "distance")):
        m = KNN(k=k, weights=weights).fit(X, y)
        sk = _ScreenedKNN(m)
        Q = np.vstack([
            X[rng.integers(0, n, size=6)] + rng.normal(scale=1e-3,
                                                       size=(6, C)),
            X[[0, 60, 100, 500]],   # exactly ON the tie clusters
            rng.normal(size=(4, C)) * 5.0,        # far queries
        ])
        assert np.array_equal(sk.predict(Q), m.predict(Q)), (k, weights)


def test_screened_knn_workspace_reuse_is_bit_stable():
    """The per-thread screen workspace (PR 5: persistent Z32/d2a buffers
    keyed by query-row count) must return the same bits call after call —
    and per-thread buffers must not be shared across threads."""
    import threading

    from repro.core.fastpath import _ScreenedKNN
    from repro.core.ml.knn import KNN
    rng = np.random.default_rng(21)
    X = rng.normal(size=(500, 6))
    m = KNN(k=7).fit(X, rng.normal(size=500))
    sk = _ScreenedKNN(m)
    Q = rng.normal(size=(27, 6))
    first = sk.predict(Q)
    # buffer reuse: same Q-row count hits the same per-thread workspace
    b1 = sk._screen_buffers(27, 6)
    assert sk._screen_buffers(27, 6)[0] is b1[0]
    for _ in range(3):
        assert np.array_equal(sk.predict(Q), first)
    assert np.array_equal(first, m.predict(Q))
    # distinct row counts get distinct buffers; threads get their own
    assert sk._screen_buffers(9, 6)[1] is not b1[1]
    seen = {}

    def worker():
        seen[threading.get_ident()] = sk._screen_buffers(27, 6)[0]
        assert np.array_equal(sk.predict(Q), first)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    (tid, buf), = seen.items()
    assert tid != threading.get_ident() and buf is not b1[0]


def test_screened_knn_nonfinite_queries_fall_back():
    from repro.core.fastpath import _ScreenedKNN
    from repro.core.ml.knn import KNN
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 5))
    m = KNN(k=5).fit(X, rng.normal(size=300))
    sk = _ScreenedKNN(m)
    Q = rng.normal(size=(4, 5))
    Q[2, 3] = np.inf                # feature overflow: exact full rescore
    assert np.array_equal(sk.predict(Q), m.predict(Q))


def test_threshold_fold_saturating_lambda_at_inf(installed):
    """Negative-lambda YJ columns saturate at a finite limit as x -> inf;
    the folded thresholds must route an infinite raw feature exactly like
    the reference transform would."""
    from repro.core.fastpath import _invert_monotone_thresholds
    lam = np.array([-0.5, -0.5, 0.8])
    mean = np.array([0.0, 10.0, 0.0])
    scale = np.array([1.0, 1.0, 1.0])

    def tfun(x):
        return ((np.power(x + 1.0, lam) - 1.0) / lam - mean) / scale

    # node 0: thr below the saturation limit (= (0-1)/-0.5 = 2.0) -> some
    # finite inversion; node 1: thr ABOVE the shifted saturation limit ->
    # +inf (an infinite x still satisfies tfun(x) <= thr); node 2:
    # diverging lambda -> finite inversion
    thr = np.array([1.0, 0.0, 5.0])
    raw = _invert_monotone_thresholds(tfun, thr, saturates=lam < 0)
    assert np.isfinite(raw[0]) and raw[1] == np.inf and np.isfinite(raw[2])
    for x in (0.0, 1.0, 1e300, np.finfo(np.float64).max, np.inf):
        want = tfun(np.full(3, x)) <= thr
        got = np.full(3, x) <= raw
        assert np.array_equal(want, got), x


def test_predicated_tree_layout_fallback(installed):
    """Row counts beyond the slot budget fall back to the generic stacked
    descent — still bit-identical."""
    from repro.core.fastpath import _PredicatedTree
    sub = installed[("gemm", 4, "DecisionTree")]
    tree = sub.model.tree_
    pt = _PredicatedTree(tree)
    pt.CAP = 1                    # force the fallback for every row count
    rng = np.random.default_rng(9)
    ncols = int(tree.feature.max()) + 1
    Z = np.asfortranarray(rng.normal(size=(13, max(ncols, 1))))
    assert np.array_equal(pt.predict(Z), tree.predict(Z))


def test_parity_thread_knob_space(installed):
    """Thread-count spaces are detected as dims-independent (nt computed
    once at compile time) and still match the reference bit-for-bit."""
    base = installed[("gemm", 4, "LinearRegression")]
    space = thread_knob_space(8)
    sub = install_subroutine(
        "gemm", space, lambda dims, knob: 1e-6 * (1.0 + 64.0 / knob["nt"])
        + 1e-9 * dims[0], n_samples=10, dim_lo=16, dim_hi=256,
        max_footprint_bytes=10_000_000, candidates=("LinearRegression",),
        tune_trials=1, use_lof=False)
    cp = compile_predictor(sub)
    assert cp is not None and cp._nt_mode == "const"
    del base
    for dims in _dims_sweep("gemm"):
        assert np.array_equal(cp.predict_times(dims),
                              sub.predict_times(dims))
        assert cp.select(dims) == sub.select(dims)


def test_runtime_serves_fast_path_decisions(installed):
    """register() compiles; runtime.select decisions == reference select."""
    sub = installed[("gemm", 4, "LinearRegression")]
    rt = AdsalaRuntime()
    rt.register(sub)
    assert rt.predictor("gemm", 4, backend="cpu_blocked") is not None
    for dims in _dims_sweep("gemm", n_random=4):
        assert rt.select("gemm", dims, 4, backend="cpu_blocked") \
            == sub.select(dims)


def test_uncompilable_sub_falls_back_to_reference():
    rt = AdsalaRuntime()
    stub = StubSub("b0")
    rt.register(stub)
    assert rt.predictor("gemm", 4, backend="b0") is None
    assert rt.select("gemm", (32, 32, 32), 4, backend="b0") == stub.knob
    assert stub.evals == 1


# ---------------------------------------------------------------------------
# lock-free hit path under concurrency: stats stay exact
# ---------------------------------------------------------------------------

def test_lockfree_hits_stats_exact_under_stress():
    rt = AdsalaRuntime(cache_size=64)
    for name in ("b0", "b1"):
        rt.register(StubSub(name))
    default = Knob((("bm", 16), ("bn", 16)))
    shapes = [(32 * i, 32, 32) for i in range(1, 9)]
    # prefill so the stress is hit-dominated
    for name in ("b0", "b1"):
        for d in shapes:
            rt.select("gemm", d, 4, backend=name)
    prefill = rt.stats
    n_threads, n_iters = 8, 400
    errors = []

    def worker(tid):
        rng = np.random.default_rng(tid)
        try:
            for i in range(n_iters):
                d = shapes[int(rng.integers(len(shapes)))]
                be = ("b0", "b1")[int(rng.integers(2))]
                if i % 10 == 0:
                    rt.select_or_default("gemm", d, 4, default,
                                         backend="untuned")
                else:
                    rt.select("gemm", d, 4, backend=be)
        except Exception as e:           # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    s = rt.stats
    assert s.calls == prefill.calls + n_threads * n_iters
    # every call is exactly one of hit / model eval / default
    assert s.calls == s.cache_hits + s.model_evals + s.default_calls
    # aggregate counters == per-backend sums
    per = list(s.backends.values())
    for counter in ("calls", "cache_hits", "default_calls", "model_evals"):
        assert getattr(s, counter) == sum(getattr(b, counter) for b in per)
    # all stress selects after prefill were hits or defaults (no re-evals)
    assert s.model_evals == prefill.model_evals


# ---------------------------------------------------------------------------
# sharded miss path: same-key coalescing, per-(backend, op) locks
# ---------------------------------------------------------------------------

class SlowStubSub(StubSub):
    """Uncompilable sub whose reference select is slow enough that
    concurrent misses on one key overlap."""

    def select(self, dims):
        import time as _t
        _t.sleep(0.05)
        return super().select(dims)


def test_miss_coalescing_single_eval():
    """N concurrent misses on ONE key -> exactly one model evaluation; the
    other callers count as hits (they rode the in-flight computation)."""
    rt = AdsalaRuntime()
    stub = SlowStubSub("b0")
    rt.register(stub)
    n_threads = 6
    knobs, errors = [], []

    def worker():
        try:
            knobs.append(rt.select("gemm", (64, 64, 64), 4, backend="b0"))
        except Exception as e:        # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert stub.evals == 1
    assert all(k == stub.knob for k in knobs)
    s = rt.stats
    assert s.model_evals == 1
    assert s.cache_hits == n_threads - 1
    assert s.calls == s.cache_hits + s.model_evals + s.default_calls


def test_select_many_coalesces_with_concurrent_select():
    """select_many racing concurrent select calls on the same uncached key
    (the serving prewarm vs a stealing worker) must still cost exactly ONE
    model evaluation per distinct key — select_many's miss path joins the
    same in-flight protocol as the one-at-a-time path."""
    for trial in range(5):
        rt = AdsalaRuntime()
        stub = SlowStubSub("b0")
        rt.register(stub)
        dims_list = [(64, 64, 64), (96, 96, 96), (128, 128, 128)]
        results, errors = [], []

        def many():
            try:
                results.append(rt.select_many(
                    [("gemm", d, 4, "b0") for d in dims_list],
                    record_hits=False))
            except Exception as e:    # noqa: BLE001
                errors.append(e)

        def single(d):
            try:
                results.append(rt.select("gemm", d, 4, backend="b0"))
            except Exception as e:    # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=many),
                   threading.Thread(target=many)] + \
            [threading.Thread(target=single, args=(d,)) for d in dims_list]
        random.shuffle(threads)
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert stub.evals == len(dims_list), trial
        assert rt.stats.model_evals == len(dims_list), trial


def test_miss_shards_are_per_backend_op():
    rt = AdsalaRuntime()
    for name in ("b0", "b1"):
        rt.register(StubSub(name))
        rt.register(StubSub(name, op="symm"))
    rt.select("gemm", (32, 32, 32), 4, backend="b0")
    rt.select("gemm", (32, 32, 32), 4, backend="b1")
    rt.select("symm", (32, 32), 4, backend="b0")
    shards = rt._shards
    assert ("b0", "gemm") in shards and ("b1", "gemm") in shards \
        and ("b0", "symm") in shards
    assert shards[("b0", "gemm")] is not shards[("b1", "gemm")]
    # eval statistics live on the shards and aggregate exactly
    s = rt.stats
    assert s.model_evals == 3
    assert s.for_backend("b0").model_evals == 2
    assert s.for_backend("b1").model_evals == 1


# ---------------------------------------------------------------------------
# select_many == N x select
# ---------------------------------------------------------------------------

def _fresh_runtime(installed):
    rt = AdsalaRuntime()
    rt.register(installed[("gemm", 4, "LinearRegression")])
    rt.register(installed[("symm", 4, "DecisionTree")])
    return rt


def test_select_many_equivalent_to_selects(installed):
    gemm_dims = _dims_sweep("gemm", n_random=6)
    symm_dims = _dims_sweep("symm", n_random=6)
    reqs = [("gemm", d, 4, "cpu_blocked") for d in gemm_dims] \
         + [("symm", d, 4, "cpu_blocked") for d in symm_dims] \
         + [("gemm", gemm_dims[0], 4, "cpu_blocked")]   # duplicate key

    batched = _fresh_runtime(installed)
    got = batched.select_many(reqs)

    sequential = _fresh_runtime(installed)
    want = [sequential.select(op, d, b, backend=be)
            for op, d, b, be in reqs]
    assert got == want
    sb, ss = batched.stats, sequential.stats
    assert (sb.calls, sb.cache_hits, sb.model_evals) == \
        (ss.calls, ss.cache_hits, ss.model_evals)
    # a second batched pass is all hits
    assert batched.select_many(reqs) == want
    assert batched.stats.model_evals == sb.model_evals


def test_select_many_mixed_hits_and_unregistered(installed):
    rt = _fresh_runtime(installed)
    d0 = (64, 64, 64)
    warm = rt.select("gemm", d0, 4, backend="cpu_blocked")
    out = rt.select_many([
        ("gemm", d0, 4, "cpu_blocked"),          # hit
        ("gemm", (96, 96, 96), 4, "cpu_blocked"),  # miss
        ("trsm", (64, 64), 4, "cpu_blocked"),    # unregistered -> None
    ])
    assert out[0] == warm
    assert out[1] == rt.subroutine("gemm", 4, "cpu_blocked").select(
        (96, 96, 96))
    assert out[2] is None
    s = rt.stats
    assert s.model_evals == 2 and s.cache_hits == 1


def test_select_many_empty():
    assert AdsalaRuntime().select_many([]) == []


def test_select_many_record_hits_false_keeps_hits_out_of_stats(installed):
    rt = _fresh_runtime(installed)
    d0 = (64, 64, 64)
    rt.select("gemm", d0, 4, backend="cpu_blocked")
    before = rt.stats
    out = rt.select_many(
        [("gemm", d0, 4, "cpu_blocked"),                # cached: silent
         ("gemm", (96, 96, 96), 4, "cpu_blocked")],     # miss: recorded
        record_hits=False)
    assert out[0] is not None and out[1] is not None
    s = rt.stats
    assert s.cache_hits == before.cache_hits            # no synthetic hits
    assert s.model_evals == before.model_evals + 1      # real eval counted
    assert s.calls == before.calls + 1


def test_resolve_backend_reprobes_availability():
    """A memoized resolution must not outlive the backend's availability."""
    from repro.backends import (get_backend, register_backend,
                                resolve_backend, unregister_backend)
    from repro.backends.base import Backend

    class Flaky(Backend):
        name = "flaky"
        up = True

        def is_available(self):
            return self.up

        def knob_space(self, op, *, sizes=None):
            return get_backend("ref").knob_space(op)

        def execute(self, op, operands, knob=None, **kw):
            raise AssertionError("never executed in this test")

    be = Flaky()
    register_backend(be)
    try:
        assert resolve_backend("flaky") is be
        assert resolve_backend("flaky") is be           # memo hit
        be.up = False                                   # no registry change
        assert resolve_backend("flaky").name == "ref"   # falls back
        be.up = True
        assert resolve_backend("flaky") is be
    finally:
        unregister_backend("flaky")
