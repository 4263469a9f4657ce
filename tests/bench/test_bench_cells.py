"""``BENCHMARK.json`` and the files it names: every cell, configuration,
traffic mix, limit and per-layer reader is where the harness looks for it,
and the entry point refuses a machine without a TPU."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                  "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_with_its_config_traffic_driver_and_limits(name):
    cell = common.cell(name)
    assert cell["chips"] in (1, 4)
    assert cell["config_data"]["name"] == cell["config"]
    drv = common.driver(cell["traffic_data"]["driver"])
    for fn in ("decision_keys", "setup", "window", "check"):
        assert callable(getattr(drv, fn))
    assert (ROOT / "bench/limits" / f"{name}.json").exists()
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    m = {x["name"]: x for x in SPEC["per_layer"]}[metric]
    assert callable(common.metric_reader(metric))
    assert m["moves"] in {x["name"] for x in SPEC["end_to_end"]}
    for w in m["workloads"]:
        moved = {x["name"] for x in common.cell(w)["end_to_end"]}
        assert m["moves"] in moved


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_install_datasets_match_the_knob_space(config):
    from repro.backends import get_backend
    data = json.loads((ROOT / config["file"]).read_text())
    inst = data["install"]
    for entry in inst["datasets"]:
        path = ROOT / entry["file"]
        if not path.exists():
            pytest.skip(f"{entry['file']} not gathered")
        space = get_backend("pallas").knob_space(entry["op"],
                                                 sizes=tuple(inst["sizes"]))
        with np.load(path) as z:
            assert json.loads(str(z["knobs"])) == [k.dict
                                                   for k in space.candidates]
            assert z["times"].shape == (len(z["dims"]), len(space))
            assert np.all(z["times"] > 0)


def test_no_cell_asks_for_a_sampled_dims():
    """A decision in a window is never a decision on a training point."""
    from bench import gather_install
    for c in SPEC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        used = gather_install.cell_keys(c["name"])
        for entry in data["install"]["datasets"]:
            path = ROOT / entry["file"]
            if not path.exists():
                continue
            with np.load(path) as z:
                sampled = {tuple(int(v) for v in d) for d in z["dims"]}
                key = (entry["op"], int(z["dtype_bytes"]))
            assert not sampled & used.get(key, set())


def test_entry_point_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
