"""The ``l3-f32.served`` driver end to end on the CPU at tiny sizes
(``BlasService`` on the ``pallas`` backend in interpret mode), and the
served cell's per-layer readers against hand counts.

Besides a sound run: the control (the reference at the precision below the
configuration's, in the program's place), an altered served result, and a
service that hands a stack's results to the wrong slots must each come out
as not correct.
"""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402
from bench.run import Run, execute  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NAME = "l3-f32.served"


@pytest.fixture(scope="module")
def installed(tmp_path_factory):
    """``l3-f32`` with a gemm and a syrk model trained on synthetic
    datasets, once for the module."""
    from tests.bench.test_bench_drivers import _dataset
    tmp = tmp_path_factory.mktemp("served-install")
    config = common.load_json(ROOT / "bench/configs/l3-f32.json")
    config["install"]["datasets"] = [
        _dataset(tmp, "gemm", "float32", [32, 32, 32], [96, 96, 96]),
        _dataset(tmp, "syrk", "float32", [32, 32], [96, 96])]
    config["install"]["models"] = str(tmp / "models")
    common.train_install(config, Path(config["install"]["models"]),
                         log=lambda m: None)
    return config


def served_cell(config: dict) -> dict:
    spec = common.benchmark()
    cell = dict({w["name"]: w for w in spec["workloads"]}[NAME])
    pool = [["gemm", [40, 56, 72]], ["syrk", [56, 40]],
            ["gemm", [72, 40, 48]], ["syrk", [48, 64]]]
    cell.update(config_data=copy.deepcopy(config),
                traffic_data={"driver": "blas_served", "rate_per_s": 40.0,
                              "zipf_a": 1.1, "pool": pool,
                              "variants": 3, "check_per_op": 1,
                              "check_stack": 3},
                end_to_end=[m for m in spec["end_to_end"]
                            if NAME in m.get("workloads", [NAME])],
                per_layer=[m for m in spec["per_layer"]
                           if NAME in m.get("workloads", [NAME])])
    return cell


def _run(cell, seconds=1.5, **kw):
    import jax
    from repro.core.runtime import global_runtime
    global_runtime().clear_cache()
    run = Run(cell, kw.pop("seed", 2 ** 31 + 17), peak=PEAK,
              log=lambda m: None, **kw)
    return run, execute(run, seconds, jax.devices(),
                        t_start=time.perf_counter())


def test_sound_run_is_correct(installed):
    run, res = _run(served_cell(installed))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "blas_tflops"}
    assert res["metrics"]["blas_tflops"]["value"] > 0
    assert run.counters["window_model_evals"] == 0
    assert 0 < run.raw["completed_in_window"] <= run.raw["submitted"]


def test_control_is_not_correct(installed):
    _, res = _run(served_cell(installed), control=True)
    assert not res["correct"], res["checks"]


def test_altered_served_result_is_not_correct(installed):
    _, res = _run(served_cell(installed),
                  faults={"answer": lambda x: x + 1e-3 * np.abs(x).max()})
    assert not res["correct"], res["checks"]


def _unstack_fault(monkeypatch, mix):
    """``run_op`` whose stacked results reach the service's slots through
    ``mix``: the fault sits between the stacked call and the futures."""
    from repro.kernels import ops
    real = ops.run_op

    def faulty(op, operands, *a, stacked=False, **kw):
        out = real(op, operands, *a, stacked=stacked, **kw)
        if stacked and out.shape[0] > 1:
            out = mix(np.asarray(out))
        return out

    monkeypatch.setattr(ops, "run_op", faulty)


@pytest.mark.parametrize("mix", ["swap_slots", "first_slot_for_all",
                                 "filler_for_the_first"])
def test_unstack_faults_are_not_correct(installed, monkeypatch, mix):
    """Each slot of a stack carries its own operands, so a result handed
    to the wrong slot shows: neighbours swapped, slot 0's result for every
    slot, or the last row's result (the padding row's, where the stack is
    padded) for the first request."""
    def filler(o):
        o = o.copy()
        o[0] = o[-1]
        return o
    _unstack_fault(monkeypatch, {
        "swap_slots": lambda o: np.roll(o, 1, axis=0),
        "first_slot_for_all": lambda o: np.broadcast_to(o[:1], o.shape),
        "filler_for_the_first": filler}[mix])
    run, res = _run(served_cell(installed))
    assert not res["correct"], res["checks"]
    assert res["failed"] == 0


def test_requests_carry_their_own_operands(installed):
    """Requests of one shape draw among several operand sets."""
    from bench.drivers.blas_served import variants
    t = served_cell(installed)["traffic_data"]
    v = variants(2 ** 31 + 17, t, 200)
    assert set(v.tolist()) == set(range(t["variants"]))


def test_arrivals_follow_the_rate_and_the_zipf_law():
    from bench.drivers.blas_served import arrivals
    times, picks = arrivals(7, {"rate_per_s": 2000.0, "zipf_a": 1.1}, 5.0,
                            16)
    assert abs(times.size / 5.0 - 2000.0) < 100.0
    assert np.all(np.diff(times) > 0) and times[-1] < 5.0
    counts = np.bincount(picks, minlength=16)
    assert counts[0] > counts[1] > counts[8]           # rank 1 hottest
    again = arrivals(7, {"rate_per_s": 2000.0, "zipf_a": 1.1}, 5.0, 16)
    assert np.array_equal(times, again[0]) and np.array_equal(picks,
                                                              again[1])


class _Run:
    def __init__(self, raw):
        self.raw = raw


def test_served_readers_hand_counts():
    q = common.metric_reader("queue_ms.served")
    pad = common.metric_reader("padded_share.served")
    raw = {"completed": 90, "failed": 10, "padded": 28, "queue_s": 0.45}
    assert q(_Run(raw)) == pytest.approx(1e3 * 0.45 / 90)
    assert pad(_Run(raw)) == pytest.approx(100.0 * 28 / 128)
    assert q(_Run(dict(raw, completed=0))) is None
    assert pad(_Run({"padded": 0})) is None
