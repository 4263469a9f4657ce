"""Shared plumbing of the benchmark: where its files are, how a cell is found
by name, the compile cache, the device check, the installed decision
runtime and the seeds.  Everything that belongs to one configuration, one
traffic mix or one per-layer metric lives in a file of its own and is found
here by the name that ``BENCHMARK.json`` gives it."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, at a fixed path inside the checkout
#: (the path is part of the cache's key)
CACHE_DIR = BENCH / ".cache" / "jax"

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell's ``workloads`` entry with its configuration and traffic
    files loaded under ``config_data`` and ``traffic_data``."""
    spec = benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = dict(cells[name])
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    w["config_data"] = load_json(ROOT / cfg["file"])
    w["traffic_data"] = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    w["end_to_end"] = [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])]
    w["per_layer"] = [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])]
    return w


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return load_module(BENCH / "drivers" / f"{name}.py", f"bench_driver_{name}")


def metric_reader(name: str):
    """``read(run) -> value | None`` of one per-layer metric."""
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")).read


def enable_compile_cache() -> str:
    """JAX's persistent cache in the checkout, for every compile however
    short.  Set before JAX is imported, so that program code that reads
    ``JAX_COMPILATION_CACHE_DIR`` takes the same directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two 32-bit words (JAX keys take 32)."""
    seed = int(seed)
    if seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def jax_key(seed: int):
    import jax
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# the installed decision runtime (frozen chip-timed datasets -> models)
# ---------------------------------------------------------------------------

#: the tuner's settings: ``repro.launch.calibrate``'s command-line defaults
#: (every paper candidate, three tuning trials) and a fixed seed
TUNE_TRIALS = 3
TUNE_SEED = 0


def _dataset(entry: dict, sizes):
    import numpy as np
    from repro.backends import get_backend
    from repro.core.dataset import TimingDataset
    space = get_backend("pallas").knob_space(entry["op"], sizes=tuple(sizes))
    with np.load(ROOT / entry["file"]) as z:
        knobs = json.loads(str(z["knobs"]))
        if knobs != [k.dict for k in space.candidates]:
            raise SystemExit(f"{entry['file']}: its knobs are not the "
                             f"program's knob space for {entry['op']}")
        return TimingDataset(op=entry["op"], dims=z["dims"].astype(np.int64),
                             times=z["times"].astype(np.float64),
                             knob_space=space,
                             dtype_bytes=int(z["dtype_bytes"]),
                             gather_seconds=float(z["gather_seconds"]))


def train_install(config: dict, store: Path, log=print) -> None:
    """Train ``config``'s decision models from its frozen datasets with the
    program's ``install_subroutine`` and save them under ``store``."""
    from repro.core import ModelRegistry, install_subroutine
    inst = config["install"]
    reg = ModelRegistry(store)
    for e in inst["datasets"]:
        ds = _dataset(e, inst["sizes"])

        def no_timer(dims, knob):
            raise RuntimeError("the install datasets are frozen")

        sub = install_subroutine(
            e["op"], ds.knob_space, no_timer, dataset=ds,
            dtype_bytes=ds.dtype_bytes, tune_trials=TUNE_TRIALS,
            seed=TUNE_SEED, backend=inst["backend"])
        reg.save(sub)
        log(f"[install] trained {e['op']}/{e['dtype']}: {sub.model_name} "
            f"from {ds.n_samples} samples x {len(ds.knob_space)} knobs")


def install_runtime(config: dict, runtime=None, log=print):
    """``runtime`` (a new ``AdsalaRuntime`` by default) holding the decision
    models of ``config``'s install, loaded from the models committed beside
    its datasets.  They are trained once, not per run: the tuner's choice
    among its candidates weighs a measured evaluation time, so training in
    each checkout could pick different models on the two sides of a
    comparison."""
    from repro.core import AdsalaRuntime, ModelRegistry
    inst = config["install"]
    reg = ModelRegistry(ROOT / inst["models"])
    rt = AdsalaRuntime() if runtime is None else runtime
    loaded = reg.load_into(rt)
    if loaded != len(inst["datasets"]) or reg.last_load_errors:
        raise SystemExit(f"decision models in {inst['models']}: loaded "
                         f"{loaded} of {len(inst['datasets'])}, errors "
                         f"{reg.last_load_errors}")
    log(f"[setup] decision models from {inst['models']}: {loaded}")
    return rt
