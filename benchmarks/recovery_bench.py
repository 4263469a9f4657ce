#!/usr/bin/env python
"""Crash-recovery harness: SIGKILL mid-write, torn journals, garbage files.

The durability contract (``repro.core.durable``: atomic checksummed
snapshots + an append-only journal between them) only earns trust if a
process really dying at the worst moment provably loses nothing it
promised to keep.  This bench kills for real and recovers for real:

  1. **SIGKILL mid-snapshot** — a child process builds warm state (a
     decision-cache snapshot, then journaled incremental decisions and an
     opened knob quarantine), starts a second snapshot, and is SIGKILLed
     inside the write window (an injected ``snapshot_write`` latency holds
     the writer with the old snapshot and the journal both still on disk).
     A fresh process must recover the union of snapshot + journal state —
     every decision warm (ZERO model evaluations on recovered shapes), the
     quarantine still open, zero torn records — and serve every request
     submitted against the recovered cache;
  2. **torn journal append** — an injected :class:`TornWrite` truncates one
     journal record mid-append: recovery must drop exactly that record
     (counted), keep its *successor* (appends are newline-prefixed, so a
     torn tail never swallows the next record), and the writer must count
     the failure without raising into the decision path;
  3. **garbage snapshot** — the snapshot file is replaced with non-JSON
     garbage: ``load_decision_cache`` must degrade to a counted cold start
     (never propagate) while the intact journal still replays;
  4. **corrupt snapshot record** — one checksummed record is damaged in
     place (bit rot): recovery drops exactly the damaged record and
     imports the survivors.

Every metric is structural (exact drop counts and pass/fail flags), so the
committed ``BENCH_recovery.json`` trajectory is gated exactly by
``scripts/bench_diff.py --recovery-fresh``.

    PYTHONPATH=src python benchmarks/recovery_bench.py --smoke
    PYTHONPATH=src python benchmarks/recovery_bench.py --json /tmp/r.json
    PYTHONPATH=src python benchmarks/recovery_bench.py --record pr9
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.backends import get_backend  # noqa: E402
from repro.core import AdsalaRuntime, ModelRegistry  # noqa: E402
from repro.core.durable import MAGIC, TornWrite  # noqa: E402
from repro.serving import (BlasService, FaultPlan, FaultSpec,  # noqa: E402
                           ServeConfig)

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_recovery.json"

#: shapes snapshotted before the kill vs journaled after it — recovery must
#: warm-start the union
SNAP_SHAPES = ((32, 32, 32), (48, 48, 48))
JOURNAL_SHAPES = ((64, 64, 64), (80, 80, 80))


class _CountingSub:
    """Fixed-knob model stand-in whose evaluations are observable — the
    zero-evals-after-recovery assertions hang off ``evals``."""

    def __init__(self, backend: str, knob, op: str = "gemm",
                 dtype_bytes: int = 4) -> None:
        self.backend, self.op, self.dtype_bytes = backend, op, dtype_bytes
        self.knob = knob
        self.artifact_version = 0
        self.evals = 0

    def select(self, dims):
        self.evals += 1
        return self.knob


def _knobs():
    """(model knob, quarantined knob) — both real cpu_blocked candidates,
    distinct so the quarantine never drops the cached decisions."""
    be = get_backend("cpu_blocked")
    default = be.default_knob("gemm")
    bad = next(c for c in be.knob_space("gemm").candidates if c != default)
    return default, bad


# ---------------------------------------------------------------------------
# child process: builds warm state, then dies mid-snapshot
# ---------------------------------------------------------------------------

def child_main(root: str) -> int:
    """Warm-state writer the parent SIGKILLs.  Protocol on stdout:
    ``JOURNALED`` once snapshot+journal are on disk, ``WRITING`` right
    before the held second snapshot (the kill window)."""
    default, bad = _knobs()
    rt = AdsalaRuntime()
    rt.register(_CountingSub("cpu_blocked", default))
    reg = ModelRegistry(root)
    rt.decision_journal = reg.journal_decision
    for d in SNAP_SHAPES:
        rt.select("gemm", d, 4, backend="cpu_blocked")
    reg.save_decision_cache(rt)            # snapshot absorbs SNAP_SHAPES
    for d in JOURNAL_SHAPES:               # journal-only increments
        rt.select("gemm", d, 4, backend="cpu_blocked")
    rt.quarantine_knob("gemm", 4, "cpu_blocked", bad, fallback=default,
                       ttl_s=60.0)         # journaled breaker
    print("JOURNALED", flush=True)
    # the second snapshot is held mid-write: the fault fires BEFORE the
    # temp file exists, so the kill lands with the old snapshot and the
    # journal both intact — the crash the durability contract is for
    plan = FaultPlan([FaultSpec(site="snapshot_write", exc=None,
                                latency_s=30.0, times=None)])
    reg2 = ModelRegistry(root, faults=plan)
    print("WRITING", flush=True)
    reg2.save_decision_cache(rt)
    return 3                               # only reached if the kill missed


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def scenario_sigkill(futures_seen: list) -> dict:
    """SIGKILL a real child inside the snapshot write window; recover the
    snapshot+journal union with zero model evals and zero lost futures."""
    default, bad = _knobs()
    with tempfile.TemporaryDirectory() as td:
        # the child tests persistence, not the device: pinned to the CPU,
        # it never competes with this process for a chip
        proc = subprocess.Popen(
            [sys.executable, __file__, "--child", td],
            stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        writing = False
        assert proc.stdout is not None
        for line in proc.stdout:
            if line.strip() == "WRITING":
                writing = True
                break
        time.sleep(0.3)                    # well inside the 30s hold
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        killed = writing and proc.returncode == -signal.SIGKILL

        rt = AdsalaRuntime()
        sub = _CountingSub("cpu_blocked", default)
        rt.register(sub)
        reg = ModelRegistry(td)
        imported = reg.load_decision_cache(rt)
        rec = dict(reg.last_recovery)

        shapes = SNAP_SHAPES + JOURNAL_SHAPES
        # the recovered cache serves real traffic: every shape warm
        cfg = ServeConfig(backend="cpu_blocked", max_batch=1, linger_ms=0.5,
                          workers=1, min_steal=1, exec_retries=0,
                          retry_backoff_s=0.0)
        be = get_backend("ref")
        reqs = [be.make_operands("gemm", d, np.float32, seed=i)
                for i, d in enumerate(shapes)]
        with BlasService(runtime=rt, config=cfg) as svc:
            futs = [svc.submit("gemm", r) for r in reqs]
            futures_seen.extend(futs)
            outs = [np.asarray(f.result(timeout=120), np.float64)
                    for f in futs]
        correct = all(
            np.max(np.abs(out - np.asarray(r[0] @ r[1], np.float64)))
            / (np.max(np.abs(np.asarray(r[0] @ r[1], np.float64))) + 1e-9)
            < 5e-4 for r, out in zip(reqs, outs))
        return {
            "sigkill_mid_write": bool(killed),
            "sigkill_recovered_decisions": bool(imported == len(shapes)),
            "sigkill_snapshot_records": int(rec.get("snapshot_records", -1)),
            "sigkill_journal_records": int(rec.get("journal_records", -1)),
            "sigkill_dropped_records": int(rec.get("dropped_records", -1)),
            "sigkill_quarantine_recovered": bool(
                rt.is_quarantined("gemm", 4, "cpu_blocked", bad)),
            "sigkill_zero_evals": bool(
                sub.evals == 0 and rt.stats.model_evals == 0),
            "sigkill_lost_futures": sum(not f.done() for f in futs),
            "sigkill_served_correct": bool(
                correct and svc.stats.failed == 0),
        }


def scenario_torn_journal() -> dict:
    """TornWrite truncates the FIRST journal append: recovery drops exactly
    that record, keeps its successor, and the writer counts the failure
    instead of raising into the decision path."""
    default, _bad = _knobs()
    with tempfile.TemporaryDirectory() as td:
        plan = FaultPlan([FaultSpec(site="journal_append",
                                    exc=TornWrite(0.5), times=1)])
        reg = ModelRegistry(td, faults=plan)
        rt = AdsalaRuntime()
        rt.register(_CountingSub("cpu_blocked", default))
        rt.decision_journal = reg.journal_decision
        rt.select("gemm", (32, 32, 32), 4, backend="cpu_blocked")  # torn
        rt.select("gemm", (64, 64, 64), 4, backend="cpu_blocked")  # clean

        warm = AdsalaRuntime()
        warm.register(_CountingSub("cpu_blocked", default))
        reg2 = ModelRegistry(td)
        imported = reg2.load_decision_cache(warm)
        rec = dict(reg2.last_recovery)
        survivor = [tuple(e["dims"]) for e in warm.export_cache()]
        return {
            "torn_journal_dropped": int(rec.get("dropped_records", -1)),
            "torn_journal_survivor_imported": bool(
                imported == 1 and survivor == [(64, 64, 64)]),
            "torn_journal_failure_counted": bool(
                rt.stats.journal_failures == 1),
            "torn_journal_injected": int(plan.fired("journal_append")),
        }


def scenario_garbage_snapshot() -> dict:
    """A non-JSON snapshot file degrades to a counted cold start while the
    intact journal still replays — never an exception."""
    default, _bad = _knobs()
    with tempfile.TemporaryDirectory() as td:
        reg = ModelRegistry(td)
        rt = AdsalaRuntime()
        rt.register(_CountingSub("cpu_blocked", default))
        rt.decision_journal = reg.journal_decision
        rt.select("gemm", (32, 32, 32), 4, backend="cpu_blocked")
        reg.save_decision_cache(rt)        # journal truncated here
        rt.select("gemm", (64, 64, 64), 4, backend="cpu_blocked")  # journal
        reg.decision_cache_path.write_bytes(b"garbage {{{ not json")

        warm = AdsalaRuntime()
        warm.register(_CountingSub("cpu_blocked", default))
        reg2 = ModelRegistry(td)
        try:
            imported = reg2.load_decision_cache(warm)
            raised = False
        except Exception:                  # noqa: BLE001 — contract breach
            imported, raised = -1, True
        rec = dict(reg2.last_recovery)
        return {
            "garbage_snapshot_cold_start": bool(
                not raised and rec.get("cold_start") is True),
            "garbage_snapshot_journal_replayed": bool(
                imported == 1 and [tuple(e["dims"])
                                   for e in warm.export_cache()]
                == [(64, 64, 64)]),
        }


def scenario_corrupt_snapshot_record() -> dict:
    """Bit rot in one checksummed snapshot record: recovery drops exactly
    the damaged record and imports the survivors."""
    default, _bad = _knobs()
    shapes = ((32, 32, 32), (48, 48, 48), (64, 64, 64))
    with tempfile.TemporaryDirectory() as td:
        reg = ModelRegistry(td)
        rt = AdsalaRuntime()
        rt.register(_CountingSub("cpu_blocked", default))
        for d in shapes:
            rt.select("gemm", d, 4, backend="cpu_blocked")
        path = reg.save_decision_cache(rt)
        lines = path.read_text().splitlines()
        assert lines[0] == MAGIC
        # lines[1] is the header record, lines[2] the oldest cache entry:
        # flip its checksum so exactly that record fails verification
        lines[2] = ("00000000" + lines[2][8:]) \
            if not lines[2].startswith("00000000") \
            else ("ffffffff" + lines[2][8:])
        path.write_text("\n".join(lines) + "\n")

        warm = AdsalaRuntime()
        warm.register(_CountingSub("cpu_blocked", default))
        reg2 = ModelRegistry(td)
        imported = reg2.load_decision_cache(warm)
        rec = dict(reg2.last_recovery)
        survivors = [tuple(e["dims"]) for e in warm.export_cache()]
        return {
            "corrupt_snapshot_dropped": int(rec.get("dropped_records", -1)),
            "corrupt_snapshot_survivors_imported": bool(
                imported == 2 and survivors == list(shapes[1:])),
        }


def run_scenarios() -> dict:
    futures_seen: list = []
    metrics: dict = {}
    metrics.update(scenario_sigkill(futures_seen))
    metrics.update(scenario_torn_journal())
    metrics.update(scenario_garbage_snapshot())
    metrics.update(scenario_corrupt_snapshot_record())
    metrics["hung_futures"] = sum(not f.done() for f in futures_seen)
    metrics["futures_submitted"] = len(futures_seen)
    return metrics


STRUCTURAL = (("sigkill_mid_write", True),
              ("sigkill_recovered_decisions", True),
              ("sigkill_dropped_records", 0),
              ("sigkill_quarantine_recovered", True),
              ("sigkill_zero_evals", True),
              ("sigkill_lost_futures", 0),
              ("sigkill_served_correct", True),
              ("torn_journal_dropped", 1),
              ("torn_journal_survivor_imported", True),
              ("torn_journal_failure_counted", True),
              ("garbage_snapshot_cold_start", True),
              ("garbage_snapshot_journal_replayed", True),
              ("corrupt_snapshot_dropped", 1),
              ("corrupt_snapshot_survivors_imported", True),
              ("hung_futures", 0))


def check(metrics: dict) -> list[str]:
    """Structural pass/fail list (empty = healthy)."""
    bad = [f"{k}={metrics[k]!r} (want {want!r})"
           for k, want in STRUCTURAL if metrics[k] != want]
    # the journal must really have carried the post-snapshot increments
    # (JOURNAL_SHAPES decisions + the quarantine record)
    want_journal = len(JOURNAL_SHAPES) + 1
    if metrics["sigkill_journal_records"] != want_journal:
        bad.append(f"sigkill_journal_records="
                   f"{metrics['sigkill_journal_records']} "
                   f"(want {want_journal})")
    if metrics["sigkill_snapshot_records"] != len(SNAP_SHAPES):
        bad.append(f"sigkill_snapshot_records="
                   f"{metrics['sigkill_snapshot_records']} "
                   f"(want {len(SNAP_SHAPES)})")
    return bad


def record_entry(entry_id: str, payload: dict, path: Path = BENCH_PATH):
    from common import record_trajectory_entry    # script-mode only module
    record_trajectory_entry(path, "recovery", entry_id, payload)
    print(f"[recovery_bench] recorded entry {entry_id!r} -> {path}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", metavar="DIR", default=None,
                   help=argparse.SUPPRESS)   # internal: the killed writer
    p.add_argument("--smoke", action="store_true",
                   help="CI preset (the scenarios are already small; this "
                        "flag exists for harness symmetry)")
    p.add_argument("--json", type=Path, default=None,
                   help="write metrics JSON here (bench_diff "
                        "--recovery-fresh input)")
    p.add_argument("--record", default=None, metavar="ENTRY",
                   help="append/refresh this entry in the committed "
                        "BENCH_recovery.json trajectory")
    args = p.parse_args(argv)
    if args.child is not None:
        return child_main(args.child)

    metrics = run_scenarios()
    for k, v in metrics.items():
        print(f"  {k:>36}: {v}")
    bad = check(metrics)

    if args.json is not None:
        args.json.write_text(json.dumps(
            {"summary": metrics, "smoke_baseline": metrics}, indent=1))
        print(f"[recovery_bench] wrote {args.json}")
    if args.record is not None:
        record_entry(args.record, {
            "host": {"platform": platform.platform(),
                     "python": platform.python_version()},
            "config": {"snap_shapes": [list(d) for d in SNAP_SHAPES],
                       "journal_shapes": [list(d) for d in JOURNAL_SHAPES]},
            "smoke_baseline": metrics,
        })

    if bad:
        print(f"[recovery_bench] FAILED: {'; '.join(bad)}")
        return 1
    print(f"[recovery_bench] OK — SIGKILL mid-write recovered "
          f"{len(SNAP_SHAPES)} snapshot + {len(JOURNAL_SHAPES)} journal "
          f"decisions and the open quarantine with zero model evals; torn "
          f"journal and corrupt/garbage snapshots dropped exactly the "
          f"damaged records")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
