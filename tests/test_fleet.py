"""Multi-process serving fleet: concurrent-writer journal safety, the
incremental JournalFollower, architecture-fingerprint artifact resolution,
file-based fleet membership, and the FleetService transport (round-trip
correctness, executor respawn, shared-journal decision coherence)."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import repro
from repro.core import AdsalaRuntime, ModelRegistry
from repro.core.durable import (DurableStore, JournalFollower,
                                append_journal, encode_record, read_records)
from repro.core.knobs import Knob
from repro.core.registry import (fingerprint_distance, fingerprint_slug,
                                 host_fingerprint)
from repro.distributed.elastic import FleetMembership

SRC = str(Path(repro.__file__).resolve().parents[1])


# ---------------------------------------------------------------------------
# satellite: flock-guarded append_journal under 4 concurrent processes
# ---------------------------------------------------------------------------

_HAMMER = """
import sys
sys.path.insert(0, {src!r})
from repro.core.durable import append_journal
wid, n, path = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
for i in range(n):
    append_journal(path, {{"writer": wid, "i": i}})
"""


def test_concurrent_append_journal_no_torn_or_dropped_records(tmp_path):
    """4 processes hammer one journal; read-back must see every record
    intact — zero drops, zero tears, no interleaving."""
    path = tmp_path / "state.json.journal"
    n_writers, n_each = 4, 200
    script = _HAMMER.format(src=SRC)
    procs = [subprocess.Popen([sys.executable, "-c", script,
                               str(w), str(n_each), str(path)])
             for w in range(n_writers)]
    for p in procs:
        assert p.wait(timeout=120) == 0
    records, dropped = read_records(path)
    assert dropped == 0
    assert len(records) == n_writers * n_each
    # every (writer, i) pair exactly once — an interleaved (torn) pair of
    # appends would corrupt both records, a lost wakeup would drop one
    seen = {(r["writer"], r["i"]) for r in records}
    assert len(seen) == n_writers * n_each
    # per-writer order is preserved (appends are atomic whole records)
    for w in range(n_writers):
        idxs = [r["i"] for r in records if r["writer"] == w]
        assert idxs == sorted(idxs)


# ---------------------------------------------------------------------------
# JournalFollower: incremental polls, torn tails, truncation
# ---------------------------------------------------------------------------

def test_follower_incremental_poll(tmp_path):
    path = tmp_path / "j.journal"
    f = JournalFollower(path)
    assert f.poll() == [] and not f.changed()     # missing file is empty
    append_journal(path, {"a": 1})
    assert f.changed()
    assert f.poll() == [{"a": 1}]
    assert not f.changed()
    assert f.poll() == []                          # nothing new
    append_journal(path, {"b": 2})
    append_journal(path, {"c": 3})
    assert [r for r in f.poll()] == [{"b": 2}, {"c": 3}]


def test_follower_carries_midappend_tail_then_completes(tmp_path):
    """A record observed mid-flush (no trailing newline, bad checksum so
    far) is carried, not dropped, and delivered once complete."""
    path = tmp_path / "j.journal"
    f = JournalFollower(path)
    full = "\n" + encode_record({"x": 42})
    with open(path, "ab") as fh:
        fh.write(full[:len(full) // 2].encode())
    assert f.poll() == []                          # partial: carried
    assert f.dropped == 0
    with open(path, "ab") as fh:
        fh.write(full[len(full) // 2:].encode())
    assert f.poll() == [{"x": 42}]
    assert f.dropped == 0


def test_follower_counts_terminated_torn_record(tmp_path):
    path = tmp_path / "j.journal"
    f = JournalFollower(path)
    with open(path, "ab") as fh:
        fh.write(b"\ndeadbeef {garbage")            # torn forever
    append_journal(path, {"ok": 1})                 # successor terminates it
    assert f.poll() == [{"ok": 1}]
    assert f.dropped == 1


def test_follower_resets_on_truncation(tmp_path):
    """snapshot() absorbs + deletes the journal; a follower that observes
    the shrink replays from offset 0 (idempotent downstream)."""
    store = DurableStore(tmp_path / "state.json")
    f = store.follower()
    store.append({"k": 1})
    assert f.poll() == [{"k": 1}]
    store.snapshot([{"k": 1}])                      # journal deleted
    store.append({"k": 2})                          # new journal, smaller
    assert f.poll() == [{"k": 2}]
    assert f.dropped == 0


# ---------------------------------------------------------------------------
# architecture fingerprints: slug/distance/resolution order
# ---------------------------------------------------------------------------

def test_host_fingerprint_shape_and_slug_determinism():
    fp = host_fingerprint()
    assert set(fp) == {"cpu_model", "machine", "cores", "cache_line"}
    assert fp["cores"] >= 1 and fp["cache_line"] > 0
    assert fingerprint_slug(fp) == fingerprint_slug(dict(fp))
    json.dumps(fp)                                  # JSON-safe


def test_fingerprint_distance_weighting():
    base = {"cpu_model": "EPYC 7B13", "machine": "x86_64",
            "cores": 16, "cache_line": 64}
    same = dict(base)
    other_model = dict(base, cpu_model="Xeon 8481C")
    other_isa = dict(base, machine="aarch64")
    wider = dict(base, cores=32)
    assert fingerprint_distance(base, same) == 0.0
    # model mismatch dominates ISA, which dominates core-count deltas
    assert fingerprint_distance(base, other_model) > \
        fingerprint_distance(base, other_isa) > \
        fingerprint_distance(base, wider) > 0.0
    # log2 core ratio: 16→32 as far as 32→64
    assert fingerprint_distance(base, wider) == pytest.approx(
        fingerprint_distance(wider, dict(base, cores=64)))


def test_resolve_fingerprint_exact_nearest_flat(tmp_path):
    reg = ModelRegistry(tmp_path)
    me = {"cpu_model": "EPYC 7B13", "machine": "x86_64",
          "cores": 16, "cache_line": 64}
    cousin = dict(me, cores=32)
    stranger = {"cpu_model": "Graviton3", "machine": "aarch64",
                "cores": 64, "cache_line": 64}
    # flat: no arch/ entries at all → the root itself
    assert reg.resolve_fingerprint(me).root == reg.root
    assert reg.last_fingerprint_resolution["mode"] == "flat"
    # exact: the calibrated slot for this fingerprint
    sub = reg.for_fingerprint(me, create=True)
    assert sub.root == reg.root / "arch" / fingerprint_slug(me)
    got = reg.resolve_fingerprint(me)
    assert got.root == sub.root
    assert reg.last_fingerprint_resolution["mode"] == "exact"
    # nearest: an uncalibrated host borrows the closest architecture
    reg.for_fingerprint(stranger, create=True)
    got = reg.resolve_fingerprint(cousin)
    assert got.root == sub.root                     # cousin ≫ stranger
    res = reg.last_fingerprint_resolution
    assert res["mode"] == "nearest" and res["slug"] == fingerprint_slug(me)
    assert res["distance"] == pytest.approx(1.0)    # |log2(16/32)|
    # roster lists both calibrated slots
    assert {s for s, _ in reg.fingerprints()} == \
        {fingerprint_slug(me), fingerprint_slug(stranger)}


# ---------------------------------------------------------------------------
# fleet membership (distributed/elastic.py seam)
# ---------------------------------------------------------------------------

def test_fleet_membership_register_heartbeat_stale(tmp_path):
    m = FleetMembership(tmp_path / "members", stale_s=0.3)
    m.register("exec-1", slug="x86")
    m.register("exec-2")
    names = {r["name"] for r in m.members()}
    assert names == {"exec-1", "exec-2"}
    assert all(r["pid"] == os.getpid() for r in m.members())
    time.sleep(0.35)
    m.heartbeat("exec-1")                           # keep one alive
    assert {r["name"] for r in m.members()} == {"exec-1"}
    assert {r["name"] for r in m.members(live_only=False)} == \
        {"exec-1", "exec-2"}
    m.deregister("exec-1")
    m.deregister("exec-1")                          # idempotent
    assert m.members() == []


def test_fleet_membership_skips_torn_records(tmp_path):
    root = tmp_path / "members"
    m = FleetMembership(root)
    m.register("good")
    (root / "torn.json").write_text('{"name": "to')
    assert [r["name"] for r in m.members()] == ["good"]


# ---------------------------------------------------------------------------
# cross-process decision coherence, single-process analogue: two live
# runtimes share one journal through followers
# ---------------------------------------------------------------------------

class StubSub:
    """Minimal TunedSubroutine stand-in: fixed-knob model with observable
    evaluation count (mirrors the stub in test_runtime_cache)."""

    def __init__(self, backend, op="gemm", dtype_bytes=4):
        self.backend, self.op, self.dtype_bytes = backend, op, dtype_bytes
        self.knob = Knob((("bm", 128), ("bn", 128)))
        self.evals = 0

    def select(self, dims):
        self.evals += 1
        return self.knob


def _register_stub(rt, backend="cpu_blocked", version=0):
    sub = StubSub(backend)
    sub.artifact_version = version
    rt.register(sub)
    return sub


def test_two_runtimes_share_decisions_via_journal(tmp_path):
    reg = ModelRegistry(tmp_path)
    rt_a = AdsalaRuntime(cache_size=32)
    rt_b = AdsalaRuntime(cache_size=32)
    _register_stub(rt_a)
    sub_b = _register_stub(rt_b)
    rt_a.decision_journal = reg.journal_decision
    follower = reg.journal_follower()
    # A decides two shapes (miss path → journal appends)
    rt_a.select("gemm", (64, 64, 64), 4, backend="cpu_blocked")
    rt_a.select("gemm", (128, 64, 64), 4, backend="cpu_blocked")
    # B absorbs them: zero model evals for the same shapes afterwards
    absorbed = rt_b.absorb_journal(follower.poll())
    assert absorbed == 2
    assert rt_b.stats.journal_absorbed == 2
    rt_b.select("gemm", (64, 64, 64), 4, backend="cpu_blocked")
    rt_b.select("gemm", (128, 64, 64), 4, backend="cpu_blocked")
    assert sub_b.evals == 0
    assert rt_b.stats.cache_hits == 2


def test_quarantine_is_benched_fleet_wide_via_journal(tmp_path):
    reg = ModelRegistry(tmp_path)
    rt_a = AdsalaRuntime()
    rt_b = AdsalaRuntime()
    rt_a.decision_journal = reg.journal_decision
    follower = reg.journal_follower()
    bad = Knob((("bm", 128), ("bn", 128)))
    fb = Knob((("bm", 64), ("bn", 64)))
    rt_a.quarantine_knob("gemm", 4, "cpu_blocked", bad, fallback=fb,
                         ttl_s=30.0)
    rt_b.absorb_journal(follower.poll())
    assert rt_b.is_quarantined("gemm", 4, "cpu_blocked", bad)


def test_absorb_journal_idempotent_own_records(tmp_path):
    """A member's own journaled decisions come back around the shared
    file; re-absorbing them must be a harmless overwrite."""
    reg = ModelRegistry(tmp_path)
    rt = AdsalaRuntime()
    sub = _register_stub(rt)
    rt.decision_journal = reg.journal_decision
    follower = reg.journal_follower()
    knob = rt.select("gemm", (64, 64, 64), 4, backend="cpu_blocked")
    assert rt.absorb_journal(follower.poll()) == 1
    assert rt.cache_len() == 1
    assert rt.select("gemm", (64, 64, 64), 4,
                     backend="cpu_blocked") == knob
    assert sub.evals == 1                           # never re-evaluated


# ---------------------------------------------------------------------------
# FleetService: transport round trip, respawn, warm join (spawned
# executor processes — each pays a jax import, so traffic is tiny)
# ---------------------------------------------------------------------------

pytestmark_slow = pytest.mark.skipif(
    os.environ.get("ADSALA_SKIP_FLEET") == "1",
    reason="fleet process tests disabled")


@pytest.fixture(scope="module")
def fleet_cls():
    from repro.serving import FleetConfig, FleetService
    return FleetService, FleetConfig


@pytestmark_slow
def test_fleet_round_trip_and_close(fleet_cls):
    FleetService, FleetConfig = fleet_cls
    from repro.serving import ServeConfig
    rng = np.random.default_rng(7)
    svc = FleetService(
        fleet=FleetConfig(processes=2, membership=False),
        config=ServeConfig(backend="cpu_blocked", max_batch=4,
                           linger_ms=1.0))
    try:
        futs = []
        for _ in range(12):
            a = rng.standard_normal((48, 32)).astype(np.float32)
            b = rng.standard_normal((32, 24)).astype(np.float32)
            futs.append((svc.submit("gemm", (a, b)), a @ b))
        for f, want in futs:
            np.testing.assert_allclose(f.result(timeout=120), want,
                                       rtol=2e-4, atol=2e-4)
        assert svc.stats.completed == 12 and svc.stats.failed == 0
        stats = svc.fleet_stats()
        assert len(stats) == 2 and all(d["alive"] for d in stats)
    finally:
        svc.close()
    # close is idempotent and a post-close submit is rejected
    svc.close()
    from repro.serving import ServiceClosedError
    with pytest.raises(ServiceClosedError):
        svc.submit("gemm", (np.eye(8, dtype=np.float32),) * 2)


@pytestmark_slow
def test_fleet_executor_refuses_device_backend(fleet_cls, monkeypatch):
    """Executors are CPU processes: each pins JAX to the CPU itself (the
    parent's environment here does not), a pallas bucket fails typed instead
    of running the Pallas interpreter, and the executor keeps serving."""
    FleetService, FleetConfig = fleet_cls
    from repro.serving import ExecutionFailedError, ServeConfig
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    svc = FleetService(
        fleet=FleetConfig(processes=1, membership=False),
        config=ServeConfig(backend="cpu_blocked", max_batch=1,
                           linger_ms=1.0))
    try:
        info = svc._executors[0].ready_info
        assert (info["platform"], info["jax_platforms"]) == ("cpu", "cpu")
        assert [d["platform"] for d in svc.fleet_stats()] == ["cpu"]
        a = np.eye(16, dtype=np.float32)
        fut = svc.submit("gemm", (a, a), backend="pallas")
        with pytest.raises(ExecutionFailedError,
                           match="ExecutorBackendError"):
            fut.result(timeout=120)
        ok = svc.submit("gemm", (a, a))
        np.testing.assert_allclose(ok.result(timeout=120), a, atol=1e-5)
        assert svc.stats.failed == 1 and svc.stats.completed == 1
        assert svc.stats.fallback_executions == 0
    finally:
        svc.close()


@pytestmark_slow
def test_fleet_executor_death_respawns_and_requeues(fleet_cls):
    FleetService, FleetConfig = fleet_cls
    from repro.serving import ServeConfig
    svc = FleetService(
        fleet=FleetConfig(processes=1, membership=False,
                          request_timeout_s=60.0),
        config=ServeConfig(backend="cpu_blocked", max_batch=2,
                           linger_ms=1.0))
    try:
        a = np.eye(16, dtype=np.float32)
        # murder the executor, then submit: the dispatcher must observe
        # the death, respawn into the same slot, and requeue the bucket
        svc._executors[0].proc.kill()
        svc._executors[0].proc.join(timeout=10)
        fut = svc.submit("gemm", (a, a))
        np.testing.assert_allclose(fut.result(timeout=180), a, atol=1e-5)
        assert svc.stats.worker_respawns >= 1
        assert svc.stats.completed == 1 and svc.stats.failed == 0
    finally:
        svc.close()


@pytestmark_slow
def test_fleet_warm_member_joins_with_zero_evals(fleet_cls, tmp_path):
    """The tentpole coherence claim, end to end: member 1 decides shapes
    against a real installed model (journaling each miss); a member added
    afterwards hydrates from the shared journal and never evaluates."""
    FleetService, FleetConfig = fleet_cls
    from repro.backends import get_backend
    from repro.core import install_backend
    from repro.serving import ServeConfig
    reg = ModelRegistry(tmp_path)
    sub_reg = reg.for_fingerprint(create=True)
    install_backend(get_backend("cpu_blocked"), ops=("gemm",),
                    n_samples=12, dim_lo=32, dim_hi=96,
                    max_footprint_bytes=1_000_000, tune_trials=1,
                    candidates=("LinearRegression",), registry=sub_reg,
                    seed=11)
    rng = np.random.default_rng(3)
    shapes = [(32, 32, 32), (48, 32, 32), (64, 48, 32)]
    svc = FleetService(
        fleet=FleetConfig(processes=1, registry_root=str(tmp_path)),
        config=ServeConfig(backend="cpu_blocked", max_batch=4,
                           linger_ms=1.0))
    try:
        futs = []
        for m, n, k in shapes:
            a = rng.standard_normal((m, k)).astype(np.float32)
            b = rng.standard_normal((k, n)).astype(np.float32)
            futs.append(svc.submit("gemm", (a, b)))
        for f in futs:
            f.result(timeout=180)
        first = svc.fleet_stats()[0]
        assert first["loaded"] == 1
        assert first["model_evals"] >= 1          # it really decided
        assert first["resolution"]["mode"] == "exact"
        info = svc.add_member()                   # ← the warm join
        assert info["warm_started"] >= len(shapes)
        # same shapes again: whoever serves them, NO member evaluates
        futs = []
        for m, n, k in shapes * 4:
            a = rng.standard_normal((m, k)).astype(np.float32)
            b = rng.standard_normal((k, n)).astype(np.float32)
            futs.append(svc.submit("gemm", (a, b)))
        for f in futs:
            f.result(timeout=180)
        stats = svc.fleet_stats()
        assert len(stats) == 2
        newcomer = stats[1]
        assert newcomer["model_evals"] == 0       # zero-eval warm join
        assert stats[0]["model_evals"] == first["model_evals"]
        # membership roster shows both executors
        members = FleetMembership(tmp_path / "members").members(
            live_only=False)
        assert len(members) == 2
    finally:
        svc.close()
