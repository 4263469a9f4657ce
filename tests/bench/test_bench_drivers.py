"""The benchmark's drivers, end to end on the CPU at tiny sizes.

``bench/run.py`` refuses any device but a TPU, so these call its
``execute`` directly, with the Pallas kernels in interpret mode.  Each cell
here keeps the real cell's name (so the real limits judge it) and the
configuration's shapes of computation, at sizes a test can hold.  Besides
sound runs: the control (the reference at the precision below the
configuration's, put in the program's place) and faults planted where the
answers are produced must each come out as not correct.
"""

from __future__ import annotations

import json
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


def _dataset(tmp: Path, op: str, dtype: str, dims_lo, dims_hi) -> dict:
    """A small synthetic install dataset in the program's knob space."""
    from repro.backends import get_backend
    space = get_backend("pallas").knob_space(op, sizes=(128, 256, 512))
    rng = np.random.default_rng(0)
    dims = rng.integers(dims_lo, dims_hi, size=(10, len(dims_lo)))
    bm = np.array([k.dict["bm"] for k in space.candidates], float)
    times = 1e-6 * dims.prod(axis=1, keepdims=True) / bm[None, :] \
        * (1 + 0.05 * rng.random((10, len(space))))
    path = tmp / f"{op}_{dtype}.npz"
    np.savez(path, dims=dims, times=times,
             knobs=json.dumps([k.dict for k in space.candidates]),
             dtype_bytes=np.dtype(np.float32).itemsize
             if dtype == "float32" else 2, gather_seconds=0.0)
    return {"op": op, "dtype": dtype, "file": str(path)}


@pytest.fixture()
def artifacts(tmp_path):
    """A scratch directory for datasets and models, and no decision cached
    by an earlier test in the process-global runtime that the model
    programs decide through."""
    from repro.core.runtime import global_runtime
    global_runtime().clear_cache()
    return tmp_path


def _install(config: dict, tmp: Path) -> dict:
    config["install"]["models"] = str(tmp / f"models-{config['name']}")
    common.train_install(config, Path(config["install"]["models"]),
                         log=lambda m: None)
    return config


def l3_cell(tmp: Path) -> dict:
    config = common.load_json(ROOT / "bench/configs/l3-f32.json")
    config["install"]["datasets"] = [
        _dataset(tmp, op, "float32", [32] * nd, [96] * nd)
        for op, nd in (("gemm", 3), ("symm", 2), ("syrk", 2), ("syr2k", 2),
                       ("trmm", 2), ("trsm", 2))]
    pool = [["gemm", [40, 56, 72]], ["symm", [48, 40]], ["syrk", [56, 40]],
            ["syr2k", [40, 48]], ["trmm", [56, 48]], ["trsm", [48, 56]],
            ["gemm", [72, 40, 48]]]
    return _cell("l3-f32.stream", _install(config, tmp),
                 {"driver": "blas_stream", "pool": pool, "check_per_op": 1})


def qwen_config(tmp: Path) -> dict:
    config = common.load_json(ROOT / "bench/configs/qwen1.5-4b.json")
    # deep enough that float8's error grows as it does at full size
    config.update(hidden_size=128, intermediate_size=352,
                  num_attention_heads=4, num_key_value_heads=4,
                  num_hidden_layers=8, vocab_size=512, rope_theta=10000.0)
    config["install"]["datasets"] = [
        _dataset(tmp, "gemm", "bfloat16", [1, 64, 64], [64, 400, 600])]
    return _install(config, tmp)


def prefill_cell(tmp: Path) -> dict:
    return _cell("qwen1.5-4b.prefill", qwen_config(tmp),
                 {"driver": "prefill", "batch": 2, "prompt_len": 16,
                  "distinct_batches": 2, "check_calls": 1})


def decode_cell(tmp: Path) -> dict:
    return _cell("qwen1.5-4b.decode", qwen_config(tmp),
                 {"driver": "decode", "batch": 2, "prompt_len": 16,
                  "cycle_steps": 4, "check_seqs": 1})


def _cell(name: str, config: dict, traffic: dict) -> dict:
    spec = common.benchmark()
    cell = dict({w["name"]: w for w in spec["workloads"]}[name])
    cell.update(config_data=config, traffic_data=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if name in m.get("workloads", [name])],
                per_layer=[m for m in spec["per_layer"]
                           if name in m.get("workloads", [name])])
    return cell


def _run(cell, seconds=1.0, **kw):
    import jax
    run = Run(cell, kw.pop("seed", 2 ** 31 + 11), peak=PEAK,
              log=lambda m: None, **kw)
    return run, execute(run, seconds, jax.devices(),
                        t_start=time.perf_counter())


MAKERS = {"l3-f32.stream": l3_cell, "qwen1.5-4b.prefill": prefill_cell,
          "qwen1.5-4b.decode": decode_cell}


@pytest.mark.parametrize("name", sorted(MAKERS))
def test_sound_run_is_correct(name, artifacts):
    run, res = _run(MAKERS[name](artifacts))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in run.cell["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert run.counters["setup_model_evals"] > 0
    assert run.counters["window_model_evals"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", sorted(MAKERS))
def test_control_is_not_correct(name, artifacts):
    _, res = _run(MAKERS[name](artifacts), control=True)
    assert not res["correct"], res["checks"]


FAULTS = {
    "l3-f32.stream": {"answer": lambda x: x.at[0, 0].add(1.0)},
    "qwen1.5-4b.prefill": {"answer": lambda x: x[..., ::-1]},
    "qwen1.5-4b.decode": {"token": lambda t: (t + 1) % 512},
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_altered_answer_is_not_correct(name, artifacts):
    _, res = _run(MAKERS[name](artifacts), faults=FAULTS[name])
    assert not res["correct"], res["checks"]


def test_unchanged_decode_state_is_not_correct(artifacts):
    from bench.drivers.decode import _reset
    keep = {"state": _reset}
    _, res = _run(decode_cell(artifacts), seconds=2.0, faults=keep)
    assert not res["correct"], res["checks"]


def test_traced_run_reports_per_layer_metrics(artifacts):
    """On the CPU there is no TPU plane: readers that need device events
    find nothing and leave their metric out; the host's own are there."""
    run, res = _run(l3_cell(artifacts), trace=True)
    assert res["correct"]
    assert {"mfu.blas", "dispatch_us.blas"} <= set(res["metrics"])
    assert "kernel_roofline.blas" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
