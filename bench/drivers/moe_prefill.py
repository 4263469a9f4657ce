"""Closed-loop prefill of a DeepSeek-V2 stage: one caller runs
``ServeSession.prefill`` on batches of prompts back to back, each blocked on
its last-token logits.  Prompt ids follow a truncated Zipf law over the
vocabulary's ranks (natural text's token skew), rank → id by a permutation
from the seed, so hot tokens route to hot experts and the groups of the
dropless expert gemms come out uneven.  The seed sets the weights, the
permutation and the ids; batch, prompt length and exponent come from the
traffic file.

Each prefill also leaves the program's routed-rows counter (its MoE layers'
token-slots per expert) on the device; the window keeps a reference to each
and reads them only after it ends."""

from __future__ import annotations

import time

import numpy as np

from bench import common, compare, deepseek, yardstick_moe
from bench.reference import deepseek as ref


def cache_len(t: dict) -> int:
    return t["prompt_len"] + 8


def decision_keys(cell) -> list:
    """[(op, dtype_bytes, dims)] the routed prefill asks for, traced at the
    cell's batch, prompt and cache length."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    from repro.roofline.harvest import Recorder
    t = cell["traffic_data"]
    cfg = deepseek.model_config(cell["config_data"])
    rec = Recorder()
    params = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {"tokens": jax.ShapeDtypeStruct((t["batch"], t["prompt_len"]),
                                            jnp.int32)}
    caches = jax.eval_shape(lambda: tf.init_decode_state(
        cfg, t["batch"], cache_len(t), dtype=jnp.dtype(cfg.compute_dtype)))
    jax.eval_shape(lambda p, b, c: tf.prefill(p, b, c, cfg, runtime=rec),
                   params, batch, caches)
    return [(op, nbytes, tuple(dims)) for _, op, nbytes, dims in rec.keys]


def prompts(seed: int, t: dict, vocab: int) -> np.ndarray:
    """(batches, batch, prompt_len) ids: ranks from a Zipf law of exponent
    ``zipf_exponent`` truncated to the vocabulary, mapped to ids by a
    permutation from the seed."""
    rng = np.random.default_rng([seed, 2])
    rank_to_id = rng.permutation(vocab).astype(np.int32)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** t["zipf_exponent"]
    ranks = rng.choice(vocab, p=p / p.sum(),
                       size=(t["distinct_batches"], t["batch"],
                             t["prompt_len"]))
    return rank_to_id[ranks]


def setup(run) -> None:
    import jax
    from repro.core.runtime import global_runtime
    from repro.launch.serve import ServeSession
    cell, st, t = run.cell, run.state, run.cell["traffic_data"]
    cfg = deepseek.model_config(cell["config_data"])
    # ServeSession's programs decide through the process-global runtime
    st["rt"] = rt = common.install_runtime(cell["config_data"],
                                           runtime=global_runtime(),
                                           log=run.log)
    st["params"] = deepseek.make_params(cell["config_data"], run.seed)
    st["prompts"] = prompts(run.seed, t, cfg.vocab)
    st["sess"] = ServeSession(cfg=cfg, params=st["params"],
                              max_len=cache_len(t))
    evals0 = rt.stats.model_evals
    jax.block_until_ready(st["sess"].prefill(st["prompts"][0])[0])
    run.counters["setup_model_evals"] = rt.stats.model_evals - evals0
    if run.trace:           # the same prefill through XLA's dot and ragged dot
        st["xla"] = ServeSession(cfg=deepseek.model_config(
            cell["config_data"], routed=False), params=st["params"],
            max_len=cache_len(t))
        jax.block_until_ready(st["xla"].prefill(st["prompts"][0])[0])


def window(run, seconds: float) -> None:
    import jax
    st, t = run.state, run.cell["traffic_data"]
    sess = st["sess"]
    evals0 = st["rt"].stats.model_evals
    logits, rows, calls, failed = [], [], 0, 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    done = t0
    with run.annotate("bench.window"):
        while done < t_end:
            try:
                out = sess.prefill(st["prompts"][calls % len(st["prompts"])])[0]
                logits.append(run.fault("answer", out.block_until_ready()))
                rows.append(getattr(sess, "routed_rows", None))
            except Exception as e:              # counted, never hidden
                failed += 1
                logits.append(None)
                run.log(f"[window] prefill {calls} failed: {e!r}")
            calls += 1
            done = time.perf_counter()
    win = done - t0
    run.counters["window_model_evals"] = st["rt"].stats.model_evals - evals0
    run.attempted, run.failed, run.window_s = calls, failed, win
    ok = calls - failed
    tokens = ok * t["batch"] * t["prompt_len"]
    run.metrics["prefill_tok_s"] = tokens / win
    st["logits"] = logits
    cfgd = run.cell["config_data"]
    rows = [np.asarray(jax.device_get(r)) for r in rows if r is not None]
    run.raw.update(
        calls=calls, tokens=tokens,
        model_flops=ok * t["batch"] * yardstick_moe.prefill_flops(
            cfgd, t["prompt_len"]),
        gemm_calls=yardstick_moe.prefill_gemms(
            cfgd, t["batch"], t["prompt_len"], cache_len(t), ok),
        routed_rows=rows or None)
    if rows:
        last = rows[-1].astype(np.float64)
        skew = last.max(axis=1) / last.mean(axis=1)
        run.counters["routed_rows_skew"] = [round(float(v), 3) for v in skew]
        run.log(f"[window] routed rows of the last prefill, max/mean per "
                f"MoE layer: {run.counters['routed_rows_skew']}; empty "
                f"experts {int((last == 0).sum())}")
    if run.trace:
        for tag, s in (("bench.routed", sess), ("bench.xla", st["xla"])):
            with run.annotate(tag):
                jax.block_until_ready(s.prefill(st["prompts"][0])[0])


def check(run) -> list:
    """Sampled calls (``check_calls``, drawn from the seed): their
    last-token logits against the float32 reference over the same prompts
    (``logits_rel_l2``, worst row; the widest logit gap is printed), and the
    rows each expert of each MoE layer computed, from the program's
    routed-rows counter, against the token-slots the reference routes to it
    (``routed_rows_rel_l1``: the summed absolute difference over the
    reference's slots, worst call).  Dropping nothing is part of the
    configuration, so a program without the counter, or with one that does
    not cover every call, reads ``inf`` there."""
    import jax
    st, t = run.state, run.cell["traffic_data"]
    done = [i for i, x in enumerate(st["logits"]) if x is not None]
    rng = np.random.default_rng([run.seed, 3])
    picks = sorted(rng.choice(done, size=min(t["check_calls"], len(done)),
                              replace=False).tolist())
    prompts_ = np.concatenate([st["prompts"][i % len(st["prompts"])]
                               for i in picks])
    got = np.concatenate([np.asarray(jax.device_get(st["logits"][i]),
                                     np.float32)[:, -1] for i in picks])
    # the counters of the picked calls (one per call that did not fail)
    counted = run.raw.get("routed_rows")
    got_rows = ([counted[done.index(i)] for i in picks]
                if counted and len(counted) == len(done) else None)
    st.clear()                                  # free the program's state
    cfgd, last = run.cell["config_data"], [[t["prompt_len"] - 1]] * len(got)
    want_rows: list = []
    want = np.concatenate(ref.logits_at(cfgd, run.seed, prompts_, last,
                                        rows=want_rows))
    if run.control:
        want_rows_lower: list = []
        got = np.concatenate(ref.logits_at(cfgd, run.seed, prompts_, last,
                                           lower=True, rows=want_rows_lower))
        got_rows = [np.stack([layer[n * t["batch"]:(n + 1) * t["batch"]]
                              .sum(0) for layer in want_rows_lower])
                    for n in range(len(picks))]
    numbers = [("logit_gap", compare.widest_gap(want, got.argmax(axis=1))),
               ("logits_rel_l2", compare.rel_l2(got, want))]
    worst = float("inf") if got_rows is None else 0.0
    for n, rows in enumerate(got_rows or []):
        ref_rows = np.stack([layer[n * t["batch"]:(n + 1) * t["batch"]]
                             .sum(0) for layer in want_rows])
        worst = max(worst, float(np.abs(np.asarray(rows, np.int64)
                                        - ref_rows).sum() / ref_rows.sum()))
    numbers.append(("routed_rows_rel_l1", worst))
    run.log(f"[check] calls {picks}: {numbers}")
    return numbers
