#!/usr/bin/env python3
"""Two checks of the ``deepseek-v2-lite.prefill`` cell at its size, on the
chip, recorded in PERF.md and not part of a benchmark run.

    python3 bench/deepseek_checks.py --seed <n> decode [--steps 16]
    python3 bench/deepseek_checks.py --seed <n> capacity [--seconds 30]

``decode``: the cell's first batch of prompts is prefilled through
``ServeSession`` (routed, published widths) and decoded greedily ``steps``
tokens through the latent cache; with the program's state freed, the
reference's full forward over prompt and generated tokens gives the logits
at every decoded position, and each step's logits are compared with them
(``logits_rel_l2``, worst row, and the served token's gap below the
reference's best).  The last line is a JSON object of the numbers.

``capacity``: the cell, run as ``bench/run.py`` runs it, with the parent's
MoE layer planted in the dropless layer's place (a capacity slab of 1.25x
the mean load per sequence, rounded up to 64 rows, every token-slot past it
dropped): its checks must fail.  The last line is the run's result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402

CELL = "deepseek-v2-lite.prefill"


def decode(seed: int, steps: int) -> dict:
    import jax.numpy as jnp
    from bench import compare, deepseek
    from bench.drivers import moe_prefill
    from bench.reference import deepseek as ref
    from repro.core.runtime import global_runtime
    from repro.launch.serve import ServeSession
    cell = common.cell(CELL)
    config, t = cell["config_data"], cell["traffic_data"]
    common.install_runtime(config, runtime=global_runtime())
    cfg = deepseek.model_config(config)
    params = deepseek.make_params(config, seed)
    prompts = moe_prefill.prompts(seed, t, cfg.vocab)[0]
    sess = ServeSession(cfg=cfg, params=params,
                        max_len=t["prompt_len"] + steps + 8)
    logits, caches, _ = sess.prefill(prompts)
    got, toks = [np.asarray(logits[:, -1], np.float32)], []
    for _ in range(steps):
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        logits, caches = sess._decode(params, tok, caches, None)
        got.append(np.asarray(logits[:, -1], np.float32))
    del sess, params, caches, logits
    seq = np.concatenate([prompts] + toks, axis=1)
    pos = list(range(t["prompt_len"] - 1, t["prompt_len"] + steps))
    want = ref.logits_at(config, seed, seq, [pos] * len(seq))
    got = np.stack(got, axis=1)                       # (batch, steps+1, V)
    rel = [compare.rel_l2(got[:, i], np.stack([w[i] for w in want]))
           for i in range(steps + 1)]
    gap = [compare.widest_gap(np.stack([w[i] for w in want]),
                              got[:, i].argmax(-1)) for i in range(steps + 1)]
    return {"check": "decode", "seed": seed, "steps": steps,
            "logits_rel_l2_worst": max(rel), "logits_rel_l2": rel,
            "logit_gap_worst": max(gap)}


def capacity(seed: int, seconds: float) -> dict:
    import jax
    from bench import yardstick
    from bench.run import Run, execute
    from repro.models import moe
    moe._dropless = moe._capacity               # the parent's layer
    devices = jax.devices()
    run = Run(common.cell(CELL), seed,
              peak=yardstick.peaks(devices[0].device_kind))
    res = execute(run, seconds, devices, t_start=time.perf_counter())
    res["check"] = "capacity"
    return res


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("which", choices=("decode", "capacity"))
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args(argv)
    common.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("these checks run on a TPU", file=sys.stderr)
        return 1
    out = (decode(args.seed, args.steps) if args.which == "decode"
           else capacity(args.seed, args.seconds))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
