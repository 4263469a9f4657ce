"""Multi-device tests (8 virtual host devices via subprocess isolation —
the parent process must keep 1 device for the other tests).

Covers: sharded DP×TP train step on the real model, EP'd MoE, elastic
re-meshing (checkpoint on 8 devices → restore on 2), GPipe pipeline
parallelism, and the multi-pod mesh builder."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(body: str, timeout=600) -> dict:
    """Run ``body`` in a subprocess with 8 host devices; returns parsed JSON
    printed as the last line."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import AxisType
        from repro.launch.mesh import make_host_mesh
        AUTO = AxisType.Auto
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_dp_tp():
    res = _run("""
        from repro.configs import get_smoke_config
        from repro.data import SyntheticLMDataset, make_global_batch
        from repro.launch.train import TrainLoop
        from repro.checkpoint import Checkpointer
        from repro.optim import AdamWConfig
        import tempfile
        import jax
        mesh = make_host_mesh(data=4, model=2)
        cfg = get_smoke_config("llama3-8b")
        ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=32, global_batch=8)
        loop = TrainLoop(cfg=cfg, adamw=AdamWConfig(total_steps=8),
                         mesh=mesh, ckpt=Checkpointer(tempfile.mkdtemp()),
                         dataset=ds, ckpt_every=100, log_every=100)
        out = loop.run(6)
        losses = [h["loss"] for h in out["history"]]
        p = out["state"]["params"]["segments"][0]
        shardings = {str(x.sharding.spec)
                     for x in jax.tree.leaves(p) if hasattr(x, "sharding")}
        print(json.dumps({"final": out["final_step"],
                          "n_sharding_kinds": len(shardings),
                          "tp_active": any("model" in s for s in shardings)}))
    """)
    assert res["final"] == 6
    assert res["tp_active"]


@pytest.mark.slow
def test_moe_expert_parallel_runs_sharded():
    res = _run("""
        from repro.configs import get_smoke_config
        from repro.models import init_params, loss_fn
        from repro.launch.specs import rules_for
        import dataclasses, jax
        mesh = make_host_mesh(data=2, model=4)
        cfg = get_smoke_config("granite_moe_3b")   # 8 experts over 4-way EP
        rules = rules_for(mesh, "train")
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.ones((4, 32), jnp.int32),
                 "labels": jnp.ones((4, 32), jnp.int32)}
        with mesh:
            loss, m = jax.jit(lambda p, b: loss_fn(p, b, cfg, mesh=mesh,
                                                   rules=rules))(params, batch)
        print(json.dumps({"loss": float(loss), "aux": float(m["moe_aux"])}))
    """)
    assert res["loss"] > 0 and res["aux"] >= 0


@pytest.mark.slow
def test_elastic_checkpoint_reshard_8_to_2():
    res = _run("""
        from repro.checkpoint import Checkpointer
        from repro.distributed import abstract_like
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import tempfile, jax
        import numpy as np
        devs = jax.devices()
        mesh8 = jax.make_mesh((8,), ("data",), axis_types=(AUTO,))
        x = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                           NamedSharding(mesh8, P("data", None)))
        ck = Checkpointer(tempfile.mkdtemp())
        ck.save(3, {"x": x})
        # restore onto a 2-device mesh (elastic downscale)
        mesh2 = Mesh(np.array(devs[:2]), ("data",))
        target = abstract_like({"x": x}, mesh2, lambda p, l: P("data", None))
        restored = ck.restore(3, target)
        r = restored["x"]
        ok = bool(np.array_equal(np.asarray(r), np.asarray(x)))
        n_shards = len(r.sharding.device_set)
        print(json.dumps({"equal": ok, "n_shards": n_shards}))
    """)
    assert res["equal"] and res["n_shards"] == 2


@pytest.mark.slow
def test_gpipe_pipeline_matches_sequential():
    res = _run("""
        from repro.distributed import gpipe_forward, bubble_fraction
        import functools, jax
        import numpy as np
        mesh = jax.make_mesh((4,), ("stage",), axis_types=(AUTO,))
        S, B, D = 4, 8, 16
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.standard_normal((S, D, D)) / np.sqrt(D),
                         jnp.float32)
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
        stage_fn = lambda w, h: jnp.tanh(h @ w)
        out = gpipe_forward(stage_fn, Ws, x, mesh=mesh, n_microbatches=4)
        want = x
        for i in range(S):
            want = jnp.tanh(want @ Ws[i])
        err = float(jnp.max(jnp.abs(out - want)))
        print(json.dumps({"err": err}))
    """)
    assert res["err"] < 1e-5


@pytest.mark.slow
def test_multipod_mesh_axes():
    res = _run("""
        from repro.launch.mesh import make_host_mesh, batch_axes
        import jax
        m = make_host_mesh(data=4, model=2)
        print(json.dumps({"axes": list(m.axis_names),
                          "shape": [int(m.shape[a]) for a in m.axis_names],
                          "batch_axes": list(batch_axes(m))}))
    """)
    assert res["axes"] == ["data", "model"]
    assert res["shape"] == [4, 2]
    assert res["batch_axes"] == ["data"]


@pytest.mark.slow
def test_grad_compression_reduces_collective_operand_dtype():
    res = _run("""
        from repro.configs import get_smoke_config
        from repro.models import init_params, loss_fn
        from repro.optim import (AdamWConfig, adamw_update, init_adamw,
                                 init_error_feedback, compress_decompress)
        from repro.launch.specs import rules_for
        import jax
        mesh = make_host_mesh(data=8, model=1)
        cfg = get_smoke_config("qwen1.5-4b")
        rules = rules_for(mesh, "train")
        params = init_params(jax.random.PRNGKey(0), cfg)
        ef = init_error_feedback(params)
        batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                 "labels": jnp.ones((8, 32), jnp.int32)}

        def step(p, e, b):
            (_, _), g = jax.value_and_grad(
                lambda pp: loss_fn(pp, b, cfg, mesh=mesh, rules=rules),
                has_aux=True)(p)
            g, e = compress_decompress(g, e)
            return g, e

        with mesh:
            hlo = jax.jit(step).lower(params, ef, batch).compile().as_text()
        print(json.dumps({"int8_in_hlo": ("s8[" in hlo)}))
    """)
    assert res["int8_in_hlo"]
