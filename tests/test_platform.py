"""What the package does with JAX's platform before any work runs.

  * importing the serving stack initialises no JAX backend, so a process
    that imports it never claims a chip it does not use;
  * the ``pallas`` backend decides compiled vs interpret mode on first use,
    and only from the backend JAX really has;
  * the compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, or at
    the checkout's fixed ``.jax_cache/``;
  * a logical sharding never maps one mesh axis to two dims.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

_ROOT = Path(__file__).resolve().parents[1]


def _run(prog: str, **env) -> dict:
    """Run ``prog`` in a fresh CPU-pinned interpreter; returns the JSON it
    prints as its last line."""
    full = dict(os.environ, PYTHONPATH=str(_ROOT / "src"),
                JAX_PLATFORMS="cpu")
    for k, v in env.items():
        if v is None:
            full.pop(k, None)
        else:
            full[k] = v
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300, env=full)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_importing_the_stack_initialises_no_backend():
    res = _run("""
import json
import repro.backends, repro.kernels, repro.serving
from repro.backends import get_backend
from jax._src import xla_bridge
before = xla_bridge.backends_are_initialized()
mode = get_backend("pallas").interpret        # first use decides
print(json.dumps({"before": before, "mode": mode,
                  "after": xla_bridge.backends_are_initialized()}))
""")
    assert res == {"before": False, "mode": True, "after": True}


def test_pallas_mode_forced_and_refused(monkeypatch):
    import jax
    from repro.backends.pallas import PallasBackend
    assert PallasBackend(interpret=False).interpret is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        PallasBackend().interpret


def test_pallas_refuses_the_cpu_after_a_quiet_tpu_failure(monkeypatch):
    import jax
    from jax._src import xla_bridge
    from repro.backends.pallas import PallasBackend
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setitem(xla_bridge._backend_errors, "tpu",
                        "TPU initialization failed: device busy")
    with pytest.raises(RuntimeError, match="device busy"):
        PallasBackend().interpret


def test_pallas_refuses_a_quiet_tpu_failure_end_to_end():
    """With JAX_PLATFORMS unset on a host without a chip, JAX records the
    TPU's failed start and falls back to the CPU; the backend refuses it
    when libtpu is installed, and runs interpreted when it is not."""
    res = _run("""
import json
import jax
from repro.backends import get_backend
from jax._src import xla_bridge
try:
    mode = get_backend("pallas").interpret
except RuntimeError as e:
    mode = str(e)
print(json.dumps({"tpu_failed": "tpu" in xla_bridge._backend_errors,
                  "platform": jax.default_backend(), "mode": mode}))
""", JAX_PLATFORMS=None)
    if res["tpu_failed"]:
        assert "failed to start" in res["mode"]
    else:
        assert res["mode"] is (res["platform"] == "cpu")


_CACHE_PROG = """
import json
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(7)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_follows_the_environment(tmp_path):
    res = _run(_CACHE_PROG, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert res == {"path": str(tmp_path), "config": str(tmp_path)}
    assert any(tmp_path.iterdir())          # the compile was written there


def test_compile_cache_defaults_to_the_checkout():
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR
    assert CHECKOUT_CACHE_DIR == _ROOT / ".jax_cache"
    res = _run(_CACHE_PROG, JAX_COMPILATION_CACHE_DIR=None)
    assert res["path"] == res["config"] == str(CHECKOUT_CACHE_DIR)
    assert CHECKOUT_CACHE_DIR.is_dir()


@pytest.mark.parametrize("names,want", [
    # FSDP's 'data' already shards the batch dim: the embed dim replicates
    (("batch", None, "embed_fsdp"), P("data", None, None)),
    # batch_attn takes ('data', 'model'): heads may not take 'model' again
    (("batch_attn", None, "heads", None), P(("data", "model"), None, None,
                                            None)),
    (("batch", None, "heads", None), P("data", None, "model", None)),
])
def test_logical_spec_uses_each_mesh_axis_once(names, want):
    from repro.models.sharding import DEFAULT_RULES, logical_spec
    mesh = AbstractMesh((2, 2), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    rules = DEFAULT_RULES.replace(batch_attn=("data", "model"))
    assert logical_spec(rules, mesh, names, dims=(8, 8, 8, 8)[:len(names)]) \
        == want
