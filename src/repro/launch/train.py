"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt runs/ckpt

Composes the full substrate: model zoo (--arch), deterministic resumable
data pipeline, AdamW (+ optional int8 gradient compression with error
feedback), sharded async atomic checkpointing with auto-resume, preemption
guard, straggler detection, and bounded retry with elastic re-mesh.  On the
CPU container use --smoke (reduced config); the same driver drives the
production mesh on real hardware.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLMDataset, make_global_batch
from repro.distributed import (PreemptionGuard, RetryPolicy,
                               StragglerDetector, best_mesh)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import batch_axes
from repro.launch.specs import (abstract_train_state, param_specs,
                                rules_for, tree_shardings)
from repro.models import init_params, loss_fn
from repro.optim import (AdamWConfig, adamw_update, compress_decompress,
                         init_adamw, init_error_feedback)

__all__ = ["TrainLoop", "main"]


@dataclasses.dataclass
class TrainLoop:
    cfg: object
    adamw: AdamWConfig
    mesh: object
    ckpt: Checkpointer
    dataset: object
    grad_compression: bool = False
    ckpt_every: int = 50
    log_every: int = 10
    straggler: StragglerDetector = dataclasses.field(
        default_factory=StragglerDetector)

    def __post_init__(self):
        self.rules = rules_for(self.mesh, "train")
        self._build_step()

    def _build_step(self):
        cfg, mesh, rules, adamw = self.cfg, self.mesh, self.rules, self.adamw
        compress = self.grad_compression

        def train_step(params, opt_state, ef, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg, mesh=mesh, rules=rules),
                has_aux=True)(params)
            if compress:
                grads, ef = compress_decompress(grads, ef)
            params, opt_state, om = adamw_update(params, grads, opt_state,
                                                 adamw)
            return params, opt_state, ef, {"loss": loss, **metrics, **om}

        self.step_fn = jax.jit(train_step, donate_argnums=(0, 1, 2))

    # -- state ------------------------------------------------------------
    def init_state(self, seed: int = 0):
        with self.mesh:
            aparams, _, pspecs = abstract_train_state(self.cfg, self.rules,
                                                      self.mesh)
            shardings = tree_shardings(aparams, pspecs, self.mesh)
            params = jax.jit(
                partial(init_params, cfg=self.cfg),
                out_shardings=shardings)(jax.random.PRNGKey(seed))
            opt_state = init_adamw(params)
            ef = (init_error_feedback(params) if self.grad_compression
                  else {"_": jnp.zeros(())})
        return {"params": params, "opt": opt_state, "ef": ef}

    def restore_or_init(self, seed: int = 0):
        state = self.init_state(seed)
        step = self.ckpt.latest_step()
        if step is None:
            return 0, state
        restored = self.ckpt.restore(step, state)
        print(f"[train] resumed from step {step}", flush=True)
        return step, restored

    # -- loop ----------------------------------------------------------------
    def run(self, steps: int, *, guard: PreemptionGuard | None = None,
            start_step: int | None = None, state=None) -> dict:
        guard = guard or PreemptionGuard(install_handlers=False)
        if state is None:
            start_step, state = self.restore_or_init()
        step = start_step or 0
        history = []
        baxes = batch_axes(self.mesh)
        while step < steps:
            t0 = time.perf_counter()
            batch = make_global_batch(self.dataset.batch_at(step), self.mesh,
                                      baxes)
            with self.mesh:
                p, o, ef, metrics = self.step_fn(state["params"],
                                                 state["opt"], state["ef"],
                                                 batch)
            state = {"params": p, "opt": o, "ef": ef}
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            if self.straggler.observe(dt):
                print(f"[train] straggler: step {step} took {dt:.2f}s "
                      f"(ewma {self.straggler.expected_step_seconds:.2f}s)",
                      flush=True)
            step += 1
            if step % self.log_every == 0 or step == steps:
                loss = float(metrics["loss"])
                history.append({"step": step, "loss": loss,
                                "sec_per_step": dt})
                print(f"[train] step {step} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms)", flush=True)
            if step % self.ckpt_every == 0 or step == steps:
                self.ckpt.save_async(step, state)
            if guard.preempted:
                print("[train] preemption signal — checkpoint + clean exit",
                      flush=True)
                self.ckpt.save(step, state)
                break
        self.ckpt.wait()
        return {"final_step": step, "history": history, "state": state}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="llama3-8b")
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt", default="runs/ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--grad-compression", action="store_true")
    p.add_argument("--max-retries", type=int, default=2)
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dataset = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq,
                                 global_batch=args.batch)
    adamw = AdamWConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(args.steps // 20, 5))
    guard = PreemptionGuard()

    def attempt(retry_i: int):
        # elastic: rebuild the mesh from live devices on every (re)try
        mesh = best_mesh(model_parallel=args.model_parallel)
        loop = TrainLoop(cfg=cfg, adamw=adamw, mesh=mesh,
                         ckpt=Checkpointer(args.ckpt),
                         dataset=dataset,
                         grad_compression=args.grad_compression,
                         ckpt_every=args.ckpt_every)
        return loop.run(args.steps, guard=guard)

    result = RetryPolicy(max_retries=args.max_retries).run(
        attempt,
        on_retry=lambda i, e, d: print(
            f"[train] attempt {i} failed ({e}); re-meshing in {d:.0f}s",
            flush=True))
    print(f"[train] done at step {result['final_step']}")
    return result


if __name__ == "__main__":
    main()
