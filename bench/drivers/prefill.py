"""Closed-loop prefill: one caller runs ``ServeSession.prefill`` on batches
of prompts back to back, each blocked on its last-token logits.  The seed
sets the weights and the prompt ids; the batch and prompt length are fixed
by the traffic file."""

from __future__ import annotations

import time

import numpy as np

from bench import common, compare, model, yardstick
from bench.reference import qwen as ref


def decision_keys(cell) -> list:
    t = cell["traffic_data"]
    return model.decision_keys(model.model_config(cell["config_data"]),
                               t["batch"], t["prompt_len"], ("prefill",))


def setup(run) -> None:
    import jax
    from repro.core.runtime import global_runtime
    from repro.launch.serve import ServeSession
    cell, st, t = run.cell, run.state, run.cell["traffic_data"]
    cfg = model.model_config(cell["config_data"])
    # ServeSession's programs decide through the process-global runtime
    st["rt"] = rt = common.install_runtime(cell["config_data"],
                                           runtime=global_runtime(),
                                           log=run.log)
    st["params"] = jax.block_until_ready(
        model.make_params(cell["config_data"], run.seed))
    rng = np.random.default_rng([run.seed, 2])
    st["prompts"] = rng.integers(
        0, cfg.vocab, size=(t["distinct_batches"], t["batch"],
                            t["prompt_len"]), dtype=np.int32)
    max_len = t["prompt_len"] + 8
    st["sess"] = ServeSession(cfg=cfg, params=st["params"], max_len=max_len)
    evals0 = rt.stats.model_evals
    jax.block_until_ready(st["sess"].prefill(st["prompts"][0])[0])
    run.counters["setup_model_evals"] = rt.stats.model_evals - evals0
    if run.trace:           # the same prefill through XLA's dot
        st["xla"] = ServeSession(cfg=model.unrouted(cfg),
                                 params=st["params"], max_len=max_len)
        jax.block_until_ready(st["xla"].prefill(st["prompts"][0])[0])


def window(run, seconds: float) -> None:
    import jax
    st, t = run.state, run.cell["traffic_data"]
    evals0 = st["rt"].stats.model_evals
    logits, calls, failed = [], 0, 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    done = t0
    with run.annotate("bench.window"):
        while done < t_end:
            try:
                out = st["sess"].prefill(st["prompts"][calls % len(
                    st["prompts"])])[0]
                logits.append(run.fault("answer", out.block_until_ready()))
            except Exception as e:              # counted, never hidden
                failed += 1
                logits.append(None)
                run.log(f"[window] prefill {calls} failed: {e!r}")
            calls += 1
            done = time.perf_counter()
    win = done - t0
    run.counters["window_model_evals"] = st["rt"].stats.model_evals - evals0
    run.attempted, run.failed, run.window_s = calls, failed, win
    tokens = (calls - failed) * t["batch"] * t["prompt_len"]
    run.metrics["prefill_tok_s"] = tokens / win
    st["logits"] = logits
    cfgd = run.cell["config_data"]
    run.raw.update(
        calls=calls, tokens=tokens,
        model_flops=(calls - failed) * t["batch"] * yardstick.prefill_flops(
            cfgd, t["prompt_len"]),
        gemm_calls=prefill_gemms(cfgd, t["batch"], t["prompt_len"],
                                 calls - failed))
    if run.trace:
        for tag, sess in (("bench.routed", st["sess"]),
                          ("bench.xla", st["xla"])):
            with run.annotate(tag):
                jax.block_until_ready(sess.prefill(st["prompts"][0])[0])


def prefill_gemms(cfgd: dict, batch: int, seq: int, calls: int) -> list:
    """[(dims, batch, count)] of the routed gemms of ``calls`` prefills."""
    d, f = cfgd["hidden_size"], cfgd["intermediate_size"]
    n = cfgd["num_hidden_layers"] * calls
    return [((seq, d, d), batch, 4 * n), ((seq, d, f), batch, 2 * n),
            ((seq, f, d), batch, n), ((1, d, cfgd["vocab_size"]), batch, calls)]


def check(run) -> list:
    """Sampled calls (``check_calls``, drawn from the seed): their
    last-token logits against the float32 reference over the same prompts."""
    import jax
    st, t = run.state, run.cell["traffic_data"]
    done = [i for i, x in enumerate(st["logits"]) if x is not None]
    rng = np.random.default_rng([run.seed, 3])
    picks = sorted(rng.choice(done, size=min(t["check_calls"], len(done)),
                              replace=False).tolist())
    prompts = np.concatenate([st["prompts"][i % len(st["prompts"])]
                              for i in picks])
    got = np.concatenate([np.asarray(jax.device_get(st["logits"][i]),
                                     np.float32)[:, -1] for i in picks])
    st.clear()                                  # free the program's state
    cfgd, last = run.cell["config_data"], [[t["prompt_len"] - 1]] * len(got)
    want = np.concatenate(ref.logits_at(cfgd, run.seed, prompts, last))
    if run.control:
        got = np.concatenate(ref.logits_at(cfgd, run.seed, prompts, last,
                                           lower=True))
    numbers = [("logit_gap", compare.widest_gap(want, got.argmax(axis=1))),
               ("logits_rel_l2", compare.rel_l2(got, want))]
    run.log(f"[check] calls {picks}: {numbers}")
    return numbers
