"""Durable snapshot+journal layer (``repro.core.durable``) and the
corruption-tolerant registry recovery built on it: checksummed record
round-trips, torn-tail journal semantics, injected torn writes, and
``load_decision_cache`` degrading to a counted cold start on every flavour
of damaged payload instead of propagating."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import repro
from repro.backends import get_backend
from repro.core import AdsalaRuntime, ModelRegistry
from repro.core.durable import (MAGIC, DurableStore, TornWrite,
                                append_journal, decode_line, encode_record,
                                is_durable, read_records, write_snapshot)
from repro.core.knobs import Knob
from repro.serving import BlasService, ServeConfig
from repro.serving.faults import FaultPlan, FaultSpec


class StubSub:
    def __init__(self, backend: str = "b0", op: str = "gemm",
                 dtype_bytes: int = 4) -> None:
        self.backend, self.op, self.dtype_bytes = backend, op, dtype_bytes
        self.knob = Knob((("bm", 128), ("bn", 128)))
        self.artifact_version = 0
        self.evals = 0

    def select(self, dims):
        self.evals += 1
        return self.knob


# ---------------------------------------------------------------------------
# record encoding: every damaged line decodes to None, never raises
# ---------------------------------------------------------------------------

def test_record_round_trip():
    rec = {"op": "gemm", "dims": [32, 32, 32], "knob": {"bm": 64}}
    assert decode_line(encode_record(rec)) == rec


def test_decode_line_rejects_damage():
    line = encode_record({"a": 1})
    assert decode_line("") is None
    assert decode_line(line[:-2]) is None              # truncated payload
    assert decode_line("00000000 " + line.split(" ", 1)[1]) is None
    assert decode_line("nospacehere") is None
    # a checksum-valid non-dict payload is still rejected
    import zlib
    payload = "[1,2,3]"
    crc = format(zlib.crc32(payload.encode()) & 0xFFFFFFFF, "08x")
    assert decode_line(f"{crc} {payload}") is None


def test_snapshot_round_trip(tmp_path):
    path = tmp_path / "state"
    recs = [{"k": i} for i in range(3)]
    write_snapshot(path, recs)
    assert is_durable(path)
    assert path.read_text().startswith(MAGIC)
    assert read_records(path) == (recs, 0)


def test_read_records_missing_file_is_empty(tmp_path):
    assert read_records(tmp_path / "nope") == ([], 0)
    assert not is_durable(tmp_path / "nope")


# ---------------------------------------------------------------------------
# journal: newline-prefixed appends — a torn tail never swallows successors
# ---------------------------------------------------------------------------

def test_journal_torn_tail_terminated_by_next_append(tmp_path):
    path = tmp_path / "state.journal"
    append_journal(path, {"k": 1})
    # simulate a crash mid-append: half of a record lands at the tail
    with open(path, "ab") as f:
        f.write(("\n" + encode_record({"k": 2}))[:12].encode())
    assert read_records(path) == ([{"k": 1}], 1)
    # the NEXT append's newline prefix terminates the torn tail: the new
    # record is intact, the torn one stays dropped
    append_journal(path, {"k": 3})
    assert read_records(path) == ([{"k": 1}, {"k": 3}], 1)


def test_injected_torn_snapshot_persists_truncated_payload(tmp_path):
    path = tmp_path / "state"
    write_snapshot(path, [{"k": 1}])
    # 80% of the payload: the cut lands inside the second record's line
    # (a smaller fraction would tear inside the '#' magic header, which
    # reads as a skipped comment rather than a counted drop)
    plan = FaultPlan([FaultSpec(site="snapshot_write", exc=TornWrite(0.8),
                                times=1)])
    with pytest.raises(TornWrite):
        write_snapshot(path, [{"k": 1}, {"k": 2}], faults=plan)
    # the torn payload clobbered the final path (the modelled crash never
    # reached the rename); recovery drops the torn tail, never raises
    recs, dropped = read_records(path)
    assert recs == [{"k": 1}] and dropped == 1
    # a clean rewrite fully repairs the file
    write_snapshot(path, [{"k": 9}])
    assert read_records(path) == ([{"k": 9}], 0)


def test_durable_store_snapshot_absorbs_journal(tmp_path):
    store = DurableStore(tmp_path / "state")
    store.append({"k": 1})
    store.append({"k": 2})
    assert store.load() == ([{"k": 1}, {"k": 2}], 0)
    store.snapshot([{"k": 3}])
    assert not store.journal_path.exists()
    assert store.load() == ([{"k": 3}], 0)


# ---------------------------------------------------------------------------
# registry recovery: every damaged payload costs warm starts, not startup
# ---------------------------------------------------------------------------

def test_load_decision_cache_garbage_payload_cold_starts(tmp_path):
    reg = ModelRegistry(tmp_path)
    reg.decision_cache_path.parent.mkdir(parents=True, exist_ok=True)
    reg.decision_cache_path.write_bytes(b"garbage {{{ not json")
    rt = AdsalaRuntime()
    assert reg.load_decision_cache(rt) == 0          # no JSONDecodeError
    assert reg.last_recovery["cold_start"] is True
    assert reg.last_recovery["dropped_records"] == 1
    assert rt.cache_len() == 0


def test_load_decision_cache_truncated_legacy_payload(tmp_path):
    reg = ModelRegistry(tmp_path)
    reg.decision_cache_path.parent.mkdir(parents=True, exist_ok=True)
    reg.decision_cache_path.write_text('{"version": 2, "entries": [')
    assert reg.load_decision_cache(AdsalaRuntime()) == 0
    assert reg.last_recovery["cold_start"] is True


def test_load_decision_cache_drops_corrupt_durable_record(tmp_path):
    reg = ModelRegistry(tmp_path)
    rt = AdsalaRuntime()
    rt.register(StubSub())
    for d in ((32, 32, 32), (64, 64, 64)):
        rt.select("gemm", d, 4, backend="b0")
    path = reg.save_decision_cache(rt)
    lines = path.read_text().splitlines()
    lines[2] = "00000000" + lines[2][8:]             # oldest entry: bad CRC
    path.write_text("\n".join(lines) + "\n")
    warm = AdsalaRuntime()
    warm.register(StubSub())
    reg2 = ModelRegistry(tmp_path)
    assert reg2.load_decision_cache(warm) == 1
    assert reg2.last_recovery["dropped_records"] == 1
    assert [tuple(e["dims"]) for e in warm.export_cache()] == [(64, 64, 64)]


def test_journal_replays_after_crash_without_snapshot(tmp_path):
    """Decisions journaled between snapshots survive a crash that never
    reached save_decision_cache — and the journal wins key collisions."""
    reg = ModelRegistry(tmp_path)
    rt = AdsalaRuntime()
    rt.register(StubSub())
    rt.decision_journal = reg.journal_decision
    rt.select("gemm", (32, 32, 32), 4, backend="b0")
    assert not reg.decision_cache_path.exists()      # no snapshot ever ran
    warm = AdsalaRuntime()
    warm.register(StubSub())
    reg2 = ModelRegistry(tmp_path)
    assert reg2.load_decision_cache(warm) == 1
    assert reg2.last_recovery["journal_records"] == 1
    assert warm.peek("gemm", (32, 32, 32), 4, backend="b0") is not None


def test_torn_journal_append_is_counted_not_raised(tmp_path):
    plan = FaultPlan([FaultSpec(site="journal_append", exc=TornWrite(0.5),
                                times=1)])
    reg = ModelRegistry(tmp_path, faults=plan)
    rt = AdsalaRuntime()
    rt.register(StubSub())
    rt.decision_journal = reg.journal_decision
    rt.select("gemm", (32, 32, 32), 4, backend="b0")   # torn append
    rt.select("gemm", (64, 64, 64), 4, backend="b0")   # clean append
    assert plan.fired("journal_append") == 1
    assert rt.stats.journal_failures == 1              # counted, not raised
    warm = AdsalaRuntime()
    warm.register(StubSub())
    reg2 = ModelRegistry(tmp_path)
    assert reg2.load_decision_cache(warm) == 1
    assert reg2.last_recovery["dropped_records"] == 1
    assert [tuple(e["dims"]) for e in warm.export_cache()] == [(64, 64, 64)]


def test_versions_sidecar_tolerates_damage(tmp_path):
    reg = ModelRegistry(tmp_path)
    # legacy plain-JSON sidecar still reads
    reg.versions_path.parent.mkdir(parents=True, exist_ok=True)
    reg.versions_path.write_text(json.dumps({"a.adsala": 2}))
    assert reg.artifact_version("a.adsala") == 2
    # garbage degrades to empty (versions restart; stale caches are then
    # dropped at warm start by the version gate, never replayed wrongly)
    reg.versions_path.write_bytes(b"\x00\xff garbage")
    assert reg.artifact_version("a.adsala") == 0
    # durable snapshot records merge with max()
    write_snapshot(reg.versions_path,
                   [{"versions": {"a.adsala": 3}},
                    {"versions": {"a.adsala": 5, "b.adsala": 1}}])
    assert reg.artifact_version("a.adsala") == 5
    assert reg.artifact_version("b.adsala") == 1


# ---------------------------------------------------------------------------
# import_cache: corrupt entries are counted drops, never exceptions
# ---------------------------------------------------------------------------

def test_import_cache_counts_corrupt_entries():
    rt = AdsalaRuntime()
    valid = {"backend": "b0", "op": "gemm", "dtype_bytes": 4,
             "dims": [32, 32, 32], "knob": {"bm": 64},
             "artifact_version": 0}
    garbage = ["not-a-dict", 17, {"no": "fields"},
               {"backend": "b0", "op": "gemm", "dtype_bytes": "x",
                "dims": [3], "knob": {"bm": 64}},
               {"backend": "b0", "op": "gemm", "dtype_bytes": 4,
                "dims": [32], "knob": "not-a-mapping"}]
    assert rt.import_cache([valid] + garbage) == 1
    assert rt.stats.import_drops_corrupt == len(garbage)
    assert rt.peek("gemm", (32, 32, 32), 4, backend="b0") is not None


def test_import_cache_counts_corrupt_quarantine_records():
    rt = AdsalaRuntime()
    bad_q = {"quarantine": 1, "backend": "b0", "op": "gemm",
             "dtype_bytes": 4, "knob": "not-a-mapping",
             "fallback_knob": {"bm": 64}, "ttl_s": 5.0}
    good_q = {"quarantine": 1, "backend": "b0", "op": "gemm",
              "dtype_bytes": 4, "knob": {"bm": 32},
              "fallback_knob": {"bm": 64}, "ttl_s": 60.0}
    assert rt.import_cache([bad_q, good_q]) == 0     # quarantines aren't
    assert rt.stats.import_drops_corrupt == 1        # decision imports
    assert rt.is_quarantined("gemm", 4, "b0", Knob((("bm", 32),)))


# ---------------------------------------------------------------------------
# crash recovery end to end: a writer really killed mid-snapshot, and a
# garbage snapshot beside an intact journal
# ---------------------------------------------------------------------------

#: shapes snapshotted before the kill vs journaled after it — recovery must
#: warm-start the union
SNAP_SHAPES = ((32, 32, 32), (48, 48, 48))
JOURNAL_SHAPES = ((64, 64, 64), (80, 80, 80))

#: the writer that is SIGKILLed: builds a snapshot, journals further
#: decisions and a knob quarantine, prints JOURNALED, then prints WRITING and
#: starts a second snapshot held before its temp file exists (an injected
#: ``snapshot_write`` latency) — the kill lands with the old snapshot and
#: the journal both on disk
_WRITER = """
import json, sys
from repro.backends import get_backend
from repro.core import AdsalaRuntime, ModelRegistry
from repro.serving.faults import FaultPlan, FaultSpec

class Sub:
    backend, op, dtype_bytes, artifact_version = "cpu_blocked", "gemm", 4, 0
    def __init__(self, knob):
        self.knob = knob
    def select(self, dims):
        return self.knob

root, snap, journal = sys.argv[1], *json.loads(sys.argv[2])
be = get_backend("cpu_blocked")
default = be.default_knob("gemm")
bad = next(c for c in be.knob_space("gemm").candidates if c != default)
rt = AdsalaRuntime()
rt.register(Sub(default))
reg = ModelRegistry(root)
rt.decision_journal = reg.journal_decision
for d in snap:
    rt.select("gemm", tuple(d), 4, backend="cpu_blocked")
reg.save_decision_cache(rt)
for d in journal:
    rt.select("gemm", tuple(d), 4, backend="cpu_blocked")
rt.quarantine_knob("gemm", 4, "cpu_blocked", bad, fallback=default,
                   ttl_s=60.0)
print("JOURNALED", flush=True)
plan = FaultPlan([FaultSpec(site="snapshot_write", exc=None, latency_s=30.0,
                            times=None)])
print("WRITING", flush=True)
ModelRegistry(root, faults=plan).save_decision_cache(rt)
sys.exit(3)
"""


def _cpu_knobs():
    """(model knob, quarantined knob): distinct cpu_blocked candidates, so
    the quarantine never drops a cached decision."""
    be = get_backend("cpu_blocked")
    default = be.default_knob("gemm")
    bad = next(c for c in be.knob_space("gemm").candidates if c != default)
    return default, bad


def _counting_sub(knob):
    sub = StubSub("cpu_blocked")
    sub.knob = knob
    return sub


def _sigkill_mid_snapshot(tmp_path):
    """SIGKILL a real writer inside the snapshot write window; a fresh
    process recovers the snapshot+journal union, the open quarantine, and
    serves every recovered shape with zero model evaluations."""
    default, bad = _cpu_knobs()
    src = str(Path(repro.__file__).resolve().parents[1])
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [src] + [p for p in [os.environ.get("PYTHONPATH")] if p])}
    proc = subprocess.Popen(
        [sys.executable, "-c", _WRITER, str(tmp_path),
         json.dumps([SNAP_SHAPES, JOURNAL_SHAPES])],
        stdout=subprocess.PIPE, text=True, env=env)
    # the test's own timeout: a writer that never reaches WRITING is killed,
    # which ends the read below
    watchdog = threading.Timer(120.0, proc.kill)
    watchdog.start()
    try:
        writing = any(line.strip() == "WRITING" for line in proc.stdout)
        time.sleep(0.3)                    # well inside the 30 s hold
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        watchdog.cancel()
        proc.stdout.close()
        if proc.poll() is None:
            proc.kill()

    rt = AdsalaRuntime()
    sub = _counting_sub(default)
    rt.register(sub)
    reg = ModelRegistry(tmp_path)
    imported = reg.load_decision_cache(rt)
    rec = reg.last_recovery
    shapes = SNAP_SHAPES + JOURNAL_SHAPES
    cfg = ServeConfig(backend="cpu_blocked", max_batch=1, linger_ms=0.5,
                      workers=1, min_steal=1, exec_retries=0,
                      retry_backoff_s=0.0)
    reqs = [get_backend("ref").make_operands("gemm", d, np.float32, seed=i)
            for i, d in enumerate(shapes)]
    with BlasService(runtime=rt, config=cfg) as svc:
        futs = [svc.submit("gemm", r) for r in reqs]
        outs = [np.asarray(f.result(timeout=120), np.float64) for f in futs]
    correct = all(
        np.max(np.abs(out - np.asarray(r[0] @ r[1], np.float64)))
        / (np.max(np.abs(np.asarray(r[0] @ r[1], np.float64))) + 1e-9)
        < 5e-4 for r, out in zip(reqs, outs))
    return {
        "killed_mid_write": writing
        and proc.returncode == -signal.SIGKILL,
        "recovered_decisions": imported,
        "snapshot_records": rec.get("snapshot_records"),
        "journal_records": rec.get("journal_records"),
        "dropped_records": rec.get("dropped_records"),
        "quarantine_recovered": rt.is_quarantined("gemm", 4, "cpu_blocked",
                                                  bad),
        "model_evals": sub.evals + rt.stats.model_evals,
        "lost_futures": sum(not f.done() for f in futs),
        "served_correct": correct and svc.stats.failed == 0,
    }


def _garbage_snapshot(tmp_path):
    """A non-JSON snapshot degrades to a counted cold start while the intact
    journal written after it still replays — never an exception."""
    default, _bad = _cpu_knobs()
    reg = ModelRegistry(tmp_path)
    rt = AdsalaRuntime()
    rt.register(_counting_sub(default))
    rt.decision_journal = reg.journal_decision
    rt.select("gemm", (32, 32, 32), 4, backend="cpu_blocked")
    reg.save_decision_cache(rt)            # the journal is truncated here
    rt.select("gemm", (64, 64, 64), 4, backend="cpu_blocked")  # journaled
    reg.decision_cache_path.write_bytes(b"garbage {{{ not json")
    warm = AdsalaRuntime()
    warm.register(_counting_sub(default))
    reg2 = ModelRegistry(tmp_path)
    imported = reg2.load_decision_cache(warm)
    return {
        "cold_start": reg2.last_recovery.get("cold_start"),
        "recovered_decisions": imported,
        "recovered_dims": [tuple(e["dims"]) for e in warm.export_cache()],
    }


#: scenario -> (run, the exact outcome it must produce)
RECOVERY_SCENARIOS = {
    "sigkill_mid_snapshot": (_sigkill_mid_snapshot, {
        "killed_mid_write": True,
        "recovered_decisions": len(SNAP_SHAPES) + len(JOURNAL_SHAPES),
        "snapshot_records": len(SNAP_SHAPES),
        "journal_records": len(JOURNAL_SHAPES) + 1,   # + the quarantine
        "dropped_records": 0,
        "quarantine_recovered": True,
        "model_evals": 0,
        "lost_futures": 0,
        "served_correct": True}),
    "garbage_snapshot": (_garbage_snapshot, {
        "cold_start": True,
        "recovered_decisions": 1,
        "recovered_dims": [(64, 64, 64)]}),
}


@pytest.mark.parametrize("scenario", sorted(RECOVERY_SCENARIOS))
def test_crash_recovery_scenario(scenario, tmp_path):
    run, want = RECOVERY_SCENARIOS[scenario]
    assert run(tmp_path) == want
