"""Closed-loop greedy decode of a batch: set-up prefills the prompts, then
the window runs the session's compiled decode step once per token, exactly
as ``ServeSession.generate`` does, reading each step's tokens back.  Every
``cycle_steps`` steps each cache's length is set back to the prompt length,
so the context cycles over [prompt_len, prompt_len + cycle_steps).  The seed
sets the weights and the prompt ids."""

from __future__ import annotations

import time

import numpy as np

from bench import common, compare, model, yardstick
from bench.reference import qwen as ref


def decision_keys(cell) -> list:
    t = cell["traffic_data"]
    return model.decision_keys(model.model_config(cell["config_data"]),
                               t["batch"], t["prompt_len"],
                               ("prefill", "decode"))


def _reset(caches, length: int):
    import jax.numpy as jnp
    return [dict(c, len=jnp.full_like(c["len"], length)) for c in caches]


def setup(run) -> None:
    import jax
    from repro.core.runtime import global_runtime
    from repro.launch.serve import ServeSession
    cell, st, t = run.cell, run.state, run.cell["traffic_data"]
    cfg = model.model_config(cell["config_data"])
    st["rt"] = rt = common.install_runtime(cell["config_data"],
                                           runtime=global_runtime(),
                                           log=run.log)
    st["params"] = jax.block_until_ready(
        model.make_params(cell["config_data"], run.seed))
    rng = np.random.default_rng([run.seed, 2])
    st["prompts"] = rng.integers(0, cfg.vocab,
                                 size=(t["batch"], t["prompt_len"]),
                                 dtype=np.int32)
    st["sess"] = sess = ServeSession(
        cfg=cfg, params=st["params"],
        max_len=t["prompt_len"] + t["cycle_steps"] + 8)
    evals0 = rt.stats.model_evals
    logits, caches, _ = sess.prefill(st["prompts"])
    st["tok0"] = sess._sample(logits[:, -1], 0.0, None)
    # compile the step, the sampling and the reset; then start the window
    # from the state the prefill left
    _, warm = sess._decode(st["params"], st["tok0"], caches, None)
    st["caches"] = jax.block_until_ready(_reset(warm, t["prompt_len"]))
    np.asarray(sess._sample(logits[:, -1], 0.0, None))
    run.counters["setup_model_evals"] = rt.stats.model_evals - evals0


def window(run, seconds: float) -> None:
    st, t = run.state, run.cell["traffic_data"]
    sess, params = st["sess"], st["params"]
    evals0 = st["rt"].stats.model_evals
    cycle, p0, batch = t["cycle_steps"], t["prompt_len"], t["batch"]
    caches, tok = st["caches"], st["tok0"]
    fed, served, itl, keep = [], [], [], {}
    t0 = time.perf_counter()
    t_end = t0 + seconds
    prev = t0
    step = 0
    with run.annotate("bench.window"):
        while prev < t_end:
            if step and step % cycle == 0:
                caches = _reset(caches, p0)
            fed.append(np.asarray(tok)[:, 0])
            logits, caches = sess._decode(params, tok, caches, None)
            caches = run.fault("state", caches, p0 + step % cycle)
            tok = run.fault("token", sess._sample(logits[:, -1], 0.0, None))
            served.append(np.asarray(tok)[:, 0])     # blocks, as generate
            now = time.perf_counter()
            itl.append(now - prev)
            prev = now
            if step % cycle == 0:
                keep["cycle_first"] = (step, logits)
            keep["last"] = (step, logits)
            step += 1
    win = prev - t0
    run.counters["window_model_evals"] = st["rt"].stats.model_evals - evals0
    run.attempted, run.failed, run.window_s = step, 0, win
    run.metrics["decode_tok_s"] = step * batch / win
    run.metrics["decode_itl_p95_ms"] = float(np.percentile(itl, 95) * 1e3)
    st.update(fed=fed, served=served, keep=keep, caches=None)
    cfgd = run.cell["config_data"]
    positions = [p0 + s % cycle for s in range(step)]
    run.raw.update(
        steps=step, itl_s=itl,
        model_flops=batch * sum(yardstick.decode_flops(cfgd, p)
                                for p in positions),
        gemm_calls=decode_gemms(cfgd, batch, step))


def decode_gemms(cfgd: dict, batch: int, steps: int) -> list:
    """[(dims, batch, count)] of the routed gemms of ``steps`` steps."""
    d, f = cfgd["hidden_size"], cfgd["intermediate_size"]
    n = cfgd["num_hidden_layers"]
    return [((1, d, d), batch, 4 * n * steps), ((1, d, f), batch, 2 * n * steps),
            ((1, f, d), batch, n * steps),
            ((1, d, cfgd["vocab_size"]), batch, steps)]


def check(run) -> list:
    """Sampled sequences (``check_seqs``, drawn from the seed): for the
    last cycle, and the one before it where the window reached it, the
    reference runs the prompt and the fed tokens and reads the gap of every
    served token; the logits kept from the first step of the last cycle and
    from the last step are compared whole."""
    import jax
    st, t = run.state, run.cell["traffic_data"]
    cycle, p0 = t["cycle_steps"], t["prompt_len"]
    rng = np.random.default_rng([run.seed, 3])
    seqs = sorted(rng.choice(t["batch"], size=t["check_seqs"],
                             replace=False).tolist())
    fed, served = np.stack(st["fed"]), np.stack(st["served"])
    steps = len(fed)
    last = (steps - 1) // cycle
    cycles = [c for c in (last - 1, last) if c >= 0]
    kept = {name: (s, np.asarray(jax.device_get(x), np.float32)[seqs, -1])
            for name, (s, x) in st["keep"].items()}
    prompts = st["prompts"]
    st.clear()                                   # free the program's state
    rows = [(b, c, c * cycle, min((c + 1) * cycle, steps))
            for c in cycles for b in seqs]
    tokens = np.zeros((len(rows), p0 + cycle), np.int32)
    for r, (b, _, lo, hi) in enumerate(rows):
        tokens[r, :p0 + hi - lo] = np.concatenate([prompts[b],
                                                   fed[lo:hi, b]])
    positions = [p0 + np.arange(hi - lo) for _, _, lo, hi in rows]
    cfgd = run.cell["config_data"]
    want = ref.logits_at(cfgd, run.seed, tokens, positions)
    if run.control:
        low = ref.logits_at(cfgd, run.seed, tokens, positions, lower=True)
        chosen = [x.argmax(axis=1) for x in low]
        kept = {k: (s, np.stack([low[_row(rows, b, s // cycle)][s % cycle]
                                 for b in seqs]))
                for k, (s, _) in kept.items()}
    else:
        chosen = [served[lo:hi, b] for b, _, lo, hi in rows]
    gap = compare.widest_gap(np.concatenate(want), np.concatenate(chosen))
    whole = 0.0
    for s, got in kept.values():
        wrows = np.stack([want[_row(rows, b, s // cycle)][s % cycle]
                          for b in seqs])
        whole = max(whole, compare.rel_l2(got, wrows))
    numbers = [("logit_gap", gap), ("logits_rel_l2", whole)]
    run.log(f"[check] seqs {seqs}, cycles {cycles}, {steps} steps: "
            f"{numbers}")
    return numbers


def _row(rows, b, c) -> int:
    return next(i for i, r in enumerate(rows) if r[0] == b and r[1] == c)
