"""End-to-end behaviour tests for the paper's system (ADSALA): the full
install → persist → runtime-dispatch → measured-speedup loop on this host's
black-box BLAS, plus the dry-run cell machinery at reduced scale."""

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import AdsalaRuntime, ModelRegistry, install_subroutine
from repro.core.timing import time_callable
from repro.kernels.cpu_blocked import make_operands, run_blocked
from repro.kernels.ops import knob_space_for

_SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(scope="module")
def tuned_gemm():
    """A real (wall-clock) ADSALA install on the numpy blocked GEMM —
    miniature version of the paper's installation phase."""
    space = knob_space_for("gemm", sizes=(32, 64, 128))
    cache = {}

    def timer(dims, knob):
        if cache.get("d") != dims:
            cache["d"] = dims
            cache["ops"] = make_operands("gemm", dims, np.float32,
                                         seed=hash(dims) % 999)
        return time_callable(lambda: run_blocked("gemm", cache["ops"], knob),
                             warmup=0, repeats=1)

    return install_subroutine(
        "gemm", space, timer, n_samples=25, dim_lo=32, dim_hi=256,
        max_footprint_bytes=2_000_000, dtype_bytes=4,
        candidates=("LinearRegression", "DecisionTree", "XGBoost"),
        tune_trials=2, seed=0)


def test_install_produces_valid_artifact(tuned_gemm):
    assert tuned_gemm.model_name in ("LinearRegression", "DecisionTree",
                                     "XGBoost")
    assert len(tuned_gemm.reports) == 3
    knob = tuned_gemm.select((128, 128, 128))
    assert {"bm", "bk", "bn", "variant"} <= set(knob.dict)


def test_measured_speedup_vs_default_on_holdout(tuned_gemm):
    """The paper's evaluation: speedup = t_default / (t_predicted + t_eval)
    on fresh Halton-sampled dims, with *measured* wall-clock.  We assert the
    tuned config is no slower than the default in aggregate (CPU timing
    noise makes per-point assertions flaky)."""
    from repro.core.halton import sample_dims
    default = tuned_gemm.dataset.knob_space.candidates[
        tuned_gemm.dataset.default_knob_index()]
    # dims ≥96 keep op time ≳10× the eval time — below that regime the
    # memo cache is the amortiser
    dims_list = sample_dims(8, 3, lo=96, hi=256, seed=99)
    # a median of 5 drops the runs a loaded host preempts (a median of 2
    # is their mean)
    t_def = t_tuned = 0.0
    for drow in dims_list:
        dims = tuple(int(v) for v in drow)
        operands = make_operands("gemm", dims, np.float32, seed=1)
        t0 = time.perf_counter()
        knob = tuned_gemm.select(dims)
        t_eval = time.perf_counter() - t0
        t_def += time_callable(
            lambda: run_blocked("gemm", operands, default), warmup=1,
            repeats=5)
        t_tuned += time_callable(
            lambda: run_blocked("gemm", operands, knob), warmup=1,
            repeats=5) + t_eval
    agg = t_def / t_tuned
    # single-core CI timing is noisy; this guards against gross regressions
    assert agg > 0.7, f"aggregate speedup {agg:.2f} unexpectedly poor"


def test_registry_runtime_end_to_end(tuned_gemm, tmp_path):
    reg = ModelRegistry(tmp_path)
    reg.save(tuned_gemm)
    rt = AdsalaRuntime()
    assert reg.load_into(rt) == 1
    k = rt.select("gemm", (96, 96, 96), dtype_bytes=4)
    assert k == tuned_gemm.select((96, 96, 96))
    assert rt.stats.calls == 1


def test_calibration_artifacts_exist_and_load():
    """Whatever calibration store the repo carries (runs/adsala) is loadable
    and drives the runtime for every backend-tagged artifact in it."""
    root = Path(__file__).resolve().parents[1] / "runs" / "adsala" / "models"
    if not root.exists():
        pytest.skip("calibration artifacts not present")
    reg = ModelRegistry(root)
    subs = reg.load_all()
    assert subs, "store exists but holds no artifacts"
    rt = AdsalaRuntime()
    assert reg.load_into(rt) == len(subs)
    assert set(rt.backends()) == {s.backend for s in subs}
    for sub in subs:
        dims = (200, 150, 100) if sub.op == "gemm" else (200, 150)
        knob = rt.select(sub.op, dims, dtype_bytes=sub.dtype_bytes,
                         backend=sub.backend)
        assert "bm" in knob.dict


@pytest.mark.slow
def test_dryrun_cell_small_mesh():
    """run_cell end-to-end on a tiny mesh in a subprocess (8 devices)."""
    prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
from pathlib import Path
import jax
import repro.launch.dryrun as dr
import repro.launch.mesh as mesh_mod
mesh_mod.make_production_mesh = \\
    lambda *, multi_pod=False: mesh_mod.make_host_mesh(data=4, model=2)
dr.make_production_mesh = mesh_mod.make_production_mesh
import repro.configs as C
small = C.get_smoke_config("llama3-8b")
dr.get_config = lambda name: small
import repro.configs.base as B
B.SHAPES["tiny_train"] = B.Shape("tiny_train", 128, 8, "train")
dr.SHAPES = B.SHAPES
rec = dr.run_cell("llama3-8b", "tiny_train", "single", Path("/tmp/drt"))
print(json.dumps({"status": rec["status"],
                  "bottleneck": rec["roofline"]["bottleneck"],
                  "flops": rec["roofline"]["hlo_flops"]}))
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["status"] == "ok" and res["flops"] > 0
