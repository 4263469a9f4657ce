"""End-to-end resilience tests: execution-time backend degradation, knob
quarantine (runtime + persistence round-trip), request deadlines, worker
supervision, close() abandonment semantics, eval-failure containment, and
the retuner's fault recovery + epsilon exploration."""

import time

import numpy as np
import pytest

from repro.backends import (get_backend, degradation_chain, resolve_backend,
                            reset_fallback_counts)
from repro.core import AdsalaRuntime, ModelRegistry, install_subroutine
from repro.core.knobs import Knob
from repro.kernels import ops
from repro.kernels.ops import run_op
from repro.serving import (BlasService, DeadlineExpiredError,
                           ExecutionFailedError, FaultPlan, FaultSpec,
                           InjectedFault, Retuner, RetuneConfig, ServeConfig,
                           ServiceClosedError)

OPS = ("gemm", "symm", "syrk", "syr2k", "trmm", "trsm")
DIMS = {"gemm": (16, 16, 16), "symm": (16, 16), "syrk": (16, 16),
        "syr2k": (16, 16), "trmm": (16, 16), "trsm": (16, 16)}


def make(op, dims, seed=0, dtype=np.float32):
    return get_backend("ref").make_operands(op, dims, dtype, seed=seed)


class FixedSub:
    """Stub subroutine whose model always selects one fixed knob."""

    def __init__(self, knob, backend="cpu_blocked", op="gemm",
                 dtype_bytes=4):
        self.backend = backend
        self.op = op
        self.dtype_bytes = dtype_bytes
        self.knob = knob
        self.artifact_version = 0

    def select(self, dims):
        return self.knob


def _cpu_knobs():
    """(default knob, one non-default knob) for cpu_blocked gemm."""
    be = get_backend("cpu_blocked")
    default = be.default_knob("gemm")
    space = be.knob_space("gemm")
    bad = next(c for c in space.candidates if c != default)
    return default, bad


@pytest.fixture(scope="module")
def tuned():
    """One real tuned artifact (flat-time timer keeps the install fast)."""
    space = ops.knob_space_for("gemm", sizes=(32, 64))
    return install_subroutine(
        "gemm", space, lambda dims, knob: 1e-3, n_samples=12,
        dim_lo=32, dim_hi=64, max_footprint_bytes=1_000_000,
        tune_trials=1, candidates=("LinearRegression",), use_lof=False,
        backend="pallas")


# ---------------------------------------------------------------------------
# degradation chain
# ---------------------------------------------------------------------------

def test_degradation_chain_shape():
    assert degradation_chain("pallas") == ("pallas", "cpu_blocked", "ref")
    assert degradation_chain("cpu_blocked") == ("cpu_blocked", "ref")
    # ref (and any name outside DEGRADE_ORDER) never degrades *up* onto an
    # accelerator path it did not ask for
    assert degradation_chain("ref") == ("ref",)
    assert degradation_chain("custom_plugin") == ("custom_plugin", "ref")


@pytest.mark.parametrize("backend", ["pallas", "cpu_blocked"])
@pytest.mark.parametrize("op", OPS)
def test_kernel_fault_degrades_to_ref_bit_identical(op, backend):
    """A kernel crash on every accelerator rung lands the bucket on ref,
    and the served results are bit-identical to a clean stacked ref run."""
    plan = FaultPlan([FaultSpec(site="kernel_execute", times=None,
                                match=lambda c: c["backend"] != "ref")])
    rt = AdsalaRuntime(faults=plan)
    cfg = ServeConfig(backend=backend, max_batch=4, linger_ms=1.0,
                      workers=1, min_steal=4, exec_retries=0,
                      retry_backoff_s=0.0)
    reqs = [make(op, DIMS[op], seed=i) for i in range(4)]
    with BlasService(runtime=rt, config=cfg, faults=plan) as svc:
        futs = [svc.submit(op, r) for r in reqs]
        outs = [np.asarray(f.result(timeout=60)) for f in futs]
    assert svc.stats.failed == 0 and svc.stats.completed == 4
    assert svc.stats.fallback_executions >= 1
    assert plan.fired("kernel_execute") >= 1
    # the accelerator rungs crashed BEFORE dispatch, so the degraded run is
    # the only execution — compare against a clean stacked ref call of the
    # exact same width (4 requests = full bucket, no padding)
    stacked = tuple(np.stack([r[i] for r in reqs])
                    for i in range(len(reqs[0])))
    clean = np.asarray(run_op(op, stacked, backend="ref", stacked=True))
    for i, out in enumerate(outs):
        assert np.array_equal(out, clean[i]), (op, backend, i)


def test_crash_storm_lands_every_op_on_ref_bit_identical():
    """Every accelerator launch of all six ops crashes while two workers
    serve them together: each bucket walks pallas → cpu_blocked → ref,
    every future resolves, none fails, and each result is bit-identical to
    a clean stacked ref run of its bucket."""
    n = 4
    plan = FaultPlan([FaultSpec(site="kernel_execute", times=None,
                                match=lambda c: c["backend"] != "ref")],
                     seed=0)
    rt = AdsalaRuntime(faults=plan)
    cfg = ServeConfig(backend="pallas", max_batch=n, linger_ms=20.0,
                      workers=2, min_steal=n, exec_retries=0,
                      retry_backoff_s=0.0)
    reqs = {op: [make(op, DIMS[op], seed=i) for i in range(n)] for op in OPS}
    with BlasService(runtime=rt, config=cfg, faults=plan) as svc:
        futs = {op: [svc.submit(op, r) for r in reqs[op]] for op in OPS}
        outs = {op: [np.asarray(f.result(timeout=120)) for f in futs[op]]
                for op in OPS}
    assert all(f.done() for fs in futs.values() for f in fs)
    assert svc.stats.failed == 0
    assert svc.stats.completed == n * len(OPS)
    assert svc.stats.fallback_executions >= len(OPS)
    # both accelerator rungs crash before dispatch, once per bucket
    assert plan.fired("kernel_execute") == 2 * svc.stats.fallback_executions
    for op in OPS:
        stacked = tuple(np.stack([r[i] for r in reqs[op]])
                        for i in range(len(reqs[op][0])))
        clean = np.asarray(run_op(op, stacked, backend="ref", stacked=True))
        for i, out in enumerate(outs[op]):
            assert np.array_equal(out, clean[i]), (op, i)


def test_transient_crash_retries_same_backend():
    plan = FaultPlan([FaultSpec(site="stacked_execute", times=1)])
    cfg = ServeConfig(backend="ref", max_batch=2, linger_ms=1.0, workers=1,
                      min_steal=2, exec_retries=1, retry_backoff_s=0.0)
    with BlasService(runtime=AdsalaRuntime(), config=cfg,
                     faults=plan) as svc:
        futs = [svc.submit("gemm", make("gemm", (16, 16, 16), seed=i))
                for i in range(2)]
        for f in futs:
            f.result(timeout=60)
    assert svc.stats.retries == 1
    assert svc.stats.completed == 2
    assert svc.stats.fallback_executions == 0   # same-backend recovery


def test_chain_exhausted_raises_typed_with_cause():
    plan = FaultPlan([FaultSpec(site="stacked_execute", times=None)])
    cfg = ServeConfig(backend="ref", max_batch=1, linger_ms=1.0, workers=1,
                      exec_retries=0, retry_backoff_s=0.0)
    with BlasService(runtime=AdsalaRuntime(), config=cfg,
                     faults=plan) as svc:
        fut = svc.submit("gemm", make("gemm", (16, 16, 16)))
        with pytest.raises(ExecutionFailedError) as ei:
            fut.result(timeout=60)
    assert isinstance(ei.value.__cause__, InjectedFault)
    assert svc.stats.failed == 1 and svc.stats.completed == 0


def test_bisection_isolates_poisoned_stack():
    """A stack that fails as a whole but succeeds per-request is bisected
    down to singles; no batchmate is sunk."""
    plan = FaultPlan([FaultSpec(site="stacked_execute", times=None,
                                match=lambda c: c["n"] > 1)])
    cfg = ServeConfig(backend="ref", max_batch=4, linger_ms=1.0, workers=1,
                      min_steal=4, exec_retries=0, retry_backoff_s=0.0)
    reqs = [make("gemm", (16, 16, 16), seed=i) for i in range(4)]
    with BlasService(runtime=AdsalaRuntime(), config=cfg,
                     faults=plan) as svc:
        futs = [svc.submit("gemm", r) for r in reqs]
        outs = [np.asarray(f.result(timeout=60)) for f in futs]
    assert svc.stats.failed == 0 and svc.stats.completed == 4
    for r, out in zip(reqs, outs):
        want = np.asarray(run_op("gemm", (r[0][None], r[1][None]),
                                 backend="ref", stacked=True))[0]
        assert np.array_equal(out, want)


# ---------------------------------------------------------------------------
# knob quarantine
# ---------------------------------------------------------------------------

def test_poisoned_knob_is_quarantined_and_bucket_served():
    """A knob that crashes every attempt while the backend's default runs
    clean is pinned on the KNOB: quarantined, and the probe result serves
    the bucket on the same backend."""
    default, bad = _cpu_knobs()
    plan = FaultPlan([FaultSpec(site="kernel_execute", times=None,
                                match=lambda c: c.get("knob") == bad)])
    rt = AdsalaRuntime(faults=plan)
    rt.register(FixedSub(bad))
    cfg = ServeConfig(backend="cpu_blocked", max_batch=2, linger_ms=1.0,
                      workers=1, min_steal=2, exec_retries=0,
                      retry_backoff_s=0.0, quarantine_ttl_s=60.0)
    reqs = [make("gemm", (16, 16, 16), seed=i) for i in range(2)]
    with BlasService(runtime=rt, config=cfg, faults=plan) as svc:
        futs = [svc.submit("gemm", r) for r in reqs]
        outs = [np.asarray(f.result(timeout=60), np.float64) for f in futs]
    assert svc.stats.quarantined_knobs == 1
    assert svc.stats.failed == 0 and svc.stats.completed == 2
    assert svc.stats.fallback_executions == 0   # served on cpu_blocked
    assert rt.is_quarantined("gemm", 4, "cpu_blocked", bad)
    assert rt.stats.quarantines == 1
    # the poisoned cached decision was invalidated in the same stroke
    assert rt.peek("gemm", (16, 16, 16), 4, backend="cpu_blocked") is None
    # and subsequent selections are forced onto the fallback, uncached
    assert rt.select("gemm", (16, 16, 16), 4,
                     backend="cpu_blocked") == default
    assert rt.stats.quarantine_forced >= 1
    for r, out in zip(reqs, outs):
        want = np.asarray(r[0] @ r[1], np.float64)
        rel = np.max(np.abs(out - want)) / (np.max(np.abs(want)) + 1e-9)
        assert rel < 5e-4


def test_quarantine_ttl_half_opens():
    default, bad = _cpu_knobs()
    rt = AdsalaRuntime()
    rt.register(FixedSub(bad))
    dims = (32, 32, 32)
    assert rt.select("gemm", dims, 4, backend="cpu_blocked") == bad
    rt.quarantine_knob("gemm", 4, "cpu_blocked", bad, fallback=default,
                       ttl_s=0.15)
    # while open: forced to the fallback, never cached
    assert rt.select("gemm", dims, 4, backend="cpu_blocked") == default
    assert rt.peek("gemm", dims, 4, backend="cpu_blocked") is None
    # exploration must refuse the benched knob
    assert not rt.override_decision("gemm", dims, 4, "cpu_blocked", bad)
    time.sleep(0.2)
    # half-open: the model's own pick is served — and cached — again
    assert not rt.is_quarantined("gemm", 4, "cpu_blocked", bad)
    assert rt.select("gemm", dims, 4, backend="cpu_blocked") == bad
    assert rt.peek("gemm", dims, 4, backend="cpu_blocked") == bad


def test_quarantine_round_trips_through_cache_persistence():
    """export_cache/import_cache must carry active quarantines across a
    restart and never resurrect a benched decision entry."""
    default, bad = _cpu_knobs()
    dims = (32, 32, 32)
    rt1 = AdsalaRuntime()
    rt1.register(FixedSub(bad))
    assert rt1.select("gemm", dims, 4, backend="cpu_blocked") == bad
    poisoned_entries = rt1.export_cache()    # decision w/ bad, no breaker
    rt1.quarantine_knob("gemm", 4, "cpu_blocked", bad, fallback=default,
                        ttl_s=60.0)
    q_entries = rt1.export_cache()
    assert any(e.get("quarantine") for e in q_entries)
    rt2 = AdsalaRuntime()
    rt2.register(FixedSub(bad))
    rt2.import_cache(q_entries + poisoned_entries)
    assert rt2.is_quarantined("gemm", 4, "cpu_blocked", bad)
    assert rt2.stats.import_drops_quarantine == 1
    assert rt2.peek("gemm", dims, 4, backend="cpu_blocked") is None
    assert rt2.select("gemm", dims, 4, backend="cpu_blocked") == default


# ---------------------------------------------------------------------------
# deadlines / lifecycle
# ---------------------------------------------------------------------------

def test_deadline_expires_waiting_request_only():
    cfg = ServeConfig(backend="ref", max_batch=8, linger_ms=150.0,
                      workers=1, min_steal=8)
    operands = make("gemm", (16, 16, 16))
    with BlasService(runtime=AdsalaRuntime(), config=cfg) as svc:
        f_dead = svc.submit("gemm", operands, deadline=0.01)
        f_live = svc.submit("gemm", operands)
        with pytest.raises(DeadlineExpiredError):
            f_dead.result(timeout=60)
        f_live.result(timeout=60)
    assert svc.stats.deadline_expired == 1
    assert svc.stats.completed == 1 and svc.stats.failed == 0


def test_deadline_validation():
    with BlasService(runtime=AdsalaRuntime(),
                     config=ServeConfig(backend="ref", workers=1)) as svc:
        with pytest.raises(ValueError):
            svc.submit("gemm", make("gemm", (16, 16, 16)), deadline=0)


def test_submit_after_close_raises_service_closed():
    svc = BlasService(runtime=AdsalaRuntime(),
                      config=ServeConfig(backend="ref", workers=1))
    svc.close()
    with pytest.raises(ServiceClosedError):
        svc.submit("gemm", make("gemm", (16, 16, 16)))
    svc.close()                       # idempotent


def test_close_fails_stuck_requests_instead_of_leaking():
    """A request stuck behind a hung backend past the close timeout is
    FAILED with ServiceClosedError — its caller never blocks forever."""
    plan = FaultPlan([FaultSpec(site="stacked_execute", exc=None,
                                latency_s=1.5)])
    cfg = ServeConfig(backend="ref", max_batch=1, linger_ms=1.0, workers=1)
    svc = BlasService(runtime=AdsalaRuntime(), config=cfg, faults=plan)
    fut = svc.submit("gemm", make("gemm", (16, 16, 16)))
    time.sleep(0.3)                   # let the worker claim and stall
    svc.close(timeout=0.2)
    with pytest.raises(ServiceClosedError):
        fut.result(timeout=1.0)
    assert svc.stats.failed == 1
    # let the stalled worker wake and exit before the interpreter tears
    # down (its late resolution must also be a harmless no-op)
    for w in svc._workers:
        w.join(timeout=5.0)
    assert svc.stats.completed == 0


def test_worker_death_recovers_without_request_loss():
    plan = FaultPlan([FaultSpec(site="worker", times=1)])
    cfg = ServeConfig(backend="ref", max_batch=4, linger_ms=1.0, workers=2,
                      min_steal=4)
    reqs = [make("gemm", (16, 16, 16), seed=i) for i in range(4)]
    with BlasService(runtime=AdsalaRuntime(), config=cfg,
                     faults=plan) as svc:
        futs = [svc.submit("gemm", r) for r in reqs]
        outs = [np.asarray(f.result(timeout=60), np.float64) for f in futs]
    assert plan.fired("worker") == 1
    assert svc.stats.worker_respawns >= 1
    assert svc.stats.completed == 4 and svc.stats.failed == 0
    for r, out in zip(reqs, outs):
        want = np.asarray(r[0] @ r[1], np.float64)
        rel = np.max(np.abs(out - want)) / (np.max(np.abs(want)) + 1e-9)
        assert rel < 5e-4


def test_worker_death_storm_fails_bucket_typed():
    """A bucket that kills every worker that claims it is failed after a
    bounded number of recoveries instead of crash-looping the pool."""
    plan = FaultPlan([FaultSpec(site="worker", times=None)])
    cfg = ServeConfig(backend="ref", max_batch=1, linger_ms=1.0, workers=1)
    with BlasService(runtime=AdsalaRuntime(), config=cfg,
                     faults=plan) as svc:
        fut = svc.submit("gemm", make("gemm", (16, 16, 16)))
        with pytest.raises(ExecutionFailedError, match="killed"):
            fut.result(timeout=60)
    assert svc.stats.worker_respawns >= 4
    assert svc.stats.failed == 1


# ---------------------------------------------------------------------------
# eval-failure containment / resolve fallback accounting
# ---------------------------------------------------------------------------

def test_eval_failure_serves_default_knob():
    default, bad = _cpu_knobs()
    plan = FaultPlan([FaultSpec(site="predictor_eval", times=None)])
    rt = AdsalaRuntime(faults=plan)
    rt.register(FixedSub(bad))
    got = rt.select_or_default("gemm", (32, 32, 32), 4, default,
                               backend="cpu_blocked")
    assert got == default
    assert rt.stats.eval_failures == 1
    assert rt.stats.default_calls == 1
    # a bare select() propagates — callers without a fallback must see it
    with pytest.raises(InjectedFault):
        rt.select("gemm", (48, 48, 48), 4, backend="cpu_blocked")


def test_select_many_isolates_failing_groups():
    default, bad = _cpu_knobs()
    plan = FaultPlan([FaultSpec(site="predictor_eval", times=None,
                                match=lambda c: c["op"] == "gemm")])
    rt = AdsalaRuntime(faults=plan)
    rt.register(FixedSub(bad))
    rt.register(FixedSub(bad, op="syrk"))
    out = rt.select_many([("gemm", (32, 32, 32), 4, "cpu_blocked"),
                          ("syrk", (32, 32), 4, "cpu_blocked")])
    assert out[0] is None             # failed group left untuned
    assert out[1] == bad              # healthy group still selected
    assert rt.stats.eval_failures >= 1


def test_resolve_fallbacks_surface_in_runtime_stats():
    reset_fallback_counts()
    assert resolve_backend("no_such_backend_xyz").name == "ref"
    counts = AdsalaRuntime().stats.resolve_fallbacks
    assert counts[("no_such_backend_xyz", "ref")] >= 1
    reset_fallback_counts()


# ---------------------------------------------------------------------------
# artifact-load fault isolation
# ---------------------------------------------------------------------------

def test_artifact_load_faults_are_isolated(tmp_path, tuned):
    plan = FaultPlan([FaultSpec(site="artifact_load", times=1)])
    reg = ModelRegistry(tmp_path, faults=plan)
    reg.save(tuned)
    (tmp_path / "pallas__zzz_b4.adsala").write_bytes(b"not msgpack")
    rt = AdsalaRuntime()
    # first hydration: the good artifact's load is fault-injected AND the
    # junk file fails to unpack — both recorded, neither aborts the scan
    assert reg.load_into(rt) == 0
    assert len(reg.last_load_errors) == 2
    assert not rt.has("gemm", 4, "pallas")
    # fault exhausted: the good artifact now loads, junk is still skipped
    assert reg.load_into(rt) == 1
    assert len(reg.last_load_errors) == 1
    assert "zzz" in reg.last_load_errors[0][0]
    assert rt.has("gemm", 4, "pallas")


# ---------------------------------------------------------------------------
# retuner: fault recovery + epsilon exploration
# ---------------------------------------------------------------------------

def test_retuner_survives_observe_faults():
    plan = FaultPlan([FaultSpec(site="retuner_observe", times=1)])
    r = Retuner(AdsalaRuntime(), faults=plan)
    assert r.step() == []
    assert r.stats.observe_failures == 1
    assert r.step() == []             # recovered
    assert r.stats.observe_failures == 1


def test_retuner_survives_refit_faults(tuned):
    from repro.serving.retune import _SubState
    plan = FaultPlan([FaultSpec(site="retuner_refit", times=None)])
    rt = AdsalaRuntime()
    rt.register(tuned)
    r = Retuner(rt, faults=plan)
    st = _SubState(cap=16)
    st.ewma, st.n = 10.0, 8           # force the drift trigger
    st.put((32, 32, 32), 0, 1.0)
    r._state[("pallas", "gemm", 4)] = st
    assert r.step() == []             # refit raised, old model kept serving
    assert r.stats.refit_failures == 1 and r.stats.errors == 1
    assert r.stats.retunes == 0
    assert "InjectedFault" in r.stats.last_error


def test_refit_failure_keeps_old_model_then_retunes_next_step(tuned):
    """A drift-triggered refit that raises once is counted, the old model's
    decisions keep serving, and the next step completes the retune."""
    pool = [(32, 32, 32), (48, 32, 64), (64, 48, 32), (32, 64, 48)]
    plan = FaultPlan([FaultSpec(site="retuner_refit", times=1)], seed=0)
    rt = AdsalaRuntime()
    rt.register(tuned)
    ret = Retuner(rt, config=RetuneConfig(min_samples=len(pool),
                                          tune_trials=1, seed=0),
                  faults=plan)
    before = {d: rt.select("gemm", d, 4, backend="pallas") for d in pool}
    for d in pool:                    # measured 4x the (flat) prediction
        rt.record_batch("gemm", d, 4, "pallas", 1, exec_seconds=4e-3,
                        exec_items=1)
    assert ret.step() == []
    assert ret.stats.refit_failures == 1 and ret.stats.retunes == 0
    assert {d: rt.select("gemm", d, 4, backend="pallas")
            for d in pool} == before
    assert ret.step() == [("pallas", "gemm", 4)]
    assert ret.stats.retunes == 1


def test_exploration_overrides_one_bucket_then_restores(tuned):
    rt = AdsalaRuntime()
    rt.register(tuned)
    dims = (32, 32, 32)
    base = rt.select("gemm", dims, 4, backend="pallas")
    rt.record_batch("gemm", dims, 4, "pallas", 4, exec_seconds=4e-3,
                    exec_items=4)
    r = Retuner(rt, config=RetuneConfig(explore_epsilon=0.9, seed=0))
    fired = 0
    for _ in range(25):               # seeded Bernoulli: bounded retry
        fired = r._explore()
        if fired:
            break
    assert fired == 1 and r.stats.explorations == 1
    explored = rt.peek("gemm", dims, 4, backend="pallas")
    assert explored is not None and explored != base
    assert explored in tuned.knob_space.candidates
    # the next pass restores the override BEFORE (maybe) placing a new one:
    # the served knob is never a stale override
    r._explore()
    cur = rt.peek("gemm", dims, 4, backend="pallas")
    if r._exploring:
        assert cur == next(iter(r._exploring.values()))
    else:
        assert cur is None            # restored: next select re-runs model
        assert rt.select("gemm", dims, 4, backend="pallas") == base


def test_exploration_excludes_quarantined_knobs(tuned):
    rt = AdsalaRuntime()
    rt.register(tuned)
    dims = (32, 32, 32)
    base = rt.select("gemm", dims, 4, backend="pallas")
    rt.record_batch("gemm", dims, 4, "pallas", 1, exec_seconds=1e-3,
                    exec_items=1)
    for cand in tuned.knob_space.candidates:
        if cand != base:
            rt.quarantine_knob("gemm", 4, "pallas", cand, fallback=base,
                               ttl_s=60.0)
    r = Retuner(rt, config=RetuneConfig(explore_epsilon=0.9, seed=1))
    assert sum(r._explore() for _ in range(20)) == 0
    assert r.stats.explorations == 0


def test_config_validation_new_fields():
    with pytest.raises(ValueError):
        ServeConfig(exec_retries=-1)
    with pytest.raises(ValueError):
        ServeConfig(retry_backoff_s=-0.1)
    with pytest.raises(ValueError):
        ServeConfig(quarantine_ttl_s=0.0)
    with pytest.raises(ValueError):
        RetuneConfig(explore_epsilon=1.0)
    with pytest.raises(ValueError):
        RetuneConfig(explore_epsilon=-0.1)
