"""DeepSeek-V2 on the serving path against the benchmark's plain reference,
and the ``deepseek-v2-lite.prefill`` driver end to end, on the CPU at tiny
sizes (Pallas kernels in interpret mode).

The configuration keeps the published file's keys, rope scaling and routing
rules at a test's widths.  Besides sound runs: the control (the reference
at the precision below the configuration's), an altered answer, and the
parent's capacity layer (capacity factor 1.25, tokens past it dropped)
planted in the dropless layer's place must each come out as not correct.
"""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common, deepseek  # noqa: E402
from bench.reference import deepseek as ref  # noqa: E402
from bench.run import Run, execute  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 31 + 29


def small_config(tmp: Path | None = None) -> dict:
    """The published configuration file at a test's widths (every rule and
    the YaRN values kept); its install, if ``tmp``, trained from synthetic
    datasets of both ops."""
    config = common.load_json(ROOT / "bench/configs/deepseek-v2-lite.json")
    config.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                  intermediate_size=160, n_routed_experts=8,
                  num_experts_per_tok=2, moe_intermediate_size=48,
                  num_hidden_layers=3, num_key_value_heads=4,
                  vocab_size=256)
    if tmp is not None:
        from tests.bench.test_bench_drivers import _dataset
        config["install"]["datasets"] = [
            _dataset(tmp, "gemm", "bfloat16", [1, 16, 16], [300, 300, 600]),
            _dataset(tmp, "grouped_gemm", "bfloat16", [16, 16, 16, 4],
                     [600, 200, 200, 12])]
        config["install"]["models"] = str(tmp / "models-deepseek")
        common.train_install(config, Path(config["install"]["models"]),
                             log=lambda m: None)
    return config


@pytest.fixture(scope="module")
def installed(tmp_path_factory):
    """The small configuration with its install trained once for the
    module (training is most of a driver test's time)."""
    return small_config(tmp_path_factory.mktemp("deepseek-install"))


@pytest.fixture()
def artifacts(installed):
    """No decision cached by an earlier test in the process-global runtime
    that the model programs decide through."""
    from repro.core.runtime import global_runtime
    global_runtime().clear_cache()
    return copy.deepcopy(installed)


def test_serve_prefill_then_decode_matches_reference():
    """ServeSession (routed, interpret mode) prefills a prompt and decodes
    through the cache; every step's logits agree with the reference's full
    forward over the same tokens."""
    import jax.numpy as jnp
    from repro.launch.serve import ServeSession
    config = small_config()
    config["torch_dtype"] = "float32"       # held to float32 rounding
    cfg = deepseek.model_config(config)
    params = deepseek.make_params(config, SEED)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=(2, 24), dtype=np.int32)
    sess = ServeSession(cfg=cfg, params=params, max_len=32)
    logits, caches, _ = sess.prefill(prompt)
    got, toks = [np.asarray(logits[:, -1])], []
    for _ in range(4):
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        logits, caches = sess._decode(params, tok, caches, None)
        got.append(np.asarray(logits[:, -1]))
    assert sess.routed_rows.shape == (2, 8)
    assert int(sess.routed_rows.sum()) == 2 * 2 * 24 * 2   # every slot
    seq = np.concatenate([prompt] + toks, axis=1)
    want = ref.logits_at(config, SEED, seq, [list(range(23, 28))] * 2)
    got = np.stack(got, axis=1)                            # (2, 5, V)
    for n in range(2):
        np.testing.assert_allclose(got[n], want[n], rtol=2e-4, atol=2e-4)


def test_reference_yarn_tables_match_program():
    """The reference's YaRN tables (its own code) and the program's."""
    from repro.models.layers import yarn, yarn_softmax_factor
    config = common.load_json(ROOT / "bench/configs/deepseek-v2-lite.json")
    cos, sin, scale = ref.yarn_tables(config, 5000)
    cfg = deepseek.model_config(config)
    freqs, mult = yarn(cfg.rope_scaling, 64, cfg.rope_theta)
    ang = np.arange(5000)[:, None] * freqs[None, :]
    np.testing.assert_allclose(cos, np.cos(ang) * mult, rtol=1e-12,
                               atol=1e-12)
    np.testing.assert_allclose(sin, np.sin(ang) * mult, rtol=1e-12,
                               atol=1e-12)
    assert scale == pytest.approx(192 ** -0.5 * yarn_softmax_factor(
        cfg.rope_scaling), rel=1e-12)


def test_weights_match_program_layout_and_reference_layers():
    import jax.numpy as jnp
    config = small_config()
    params = deepseek.make_params(config, SEED)
    key = common.jax_key(SEED)
    lay = deepseek.layer(config, key, 2)
    seg = params["segments"][1]
    np.testing.assert_array_equal(np.asarray(seg["moe"]["wg"][1]),
                                  np.asarray(lay["ewg"]))
    np.testing.assert_array_equal(np.asarray(seg["attn"]["wq"]["w"][1]),
                                  np.asarray(lay["wq"]))
    assert seg["moe"]["router"]["w"].dtype == jnp.float32
    assert seg["moe"]["wd"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# the driver end to end
# ---------------------------------------------------------------------------

def prefill_cell(config: dict) -> dict:
    spec = common.benchmark()
    name = "deepseek-v2-lite.prefill"
    cell = dict({w["name"]: w for w in spec["workloads"]}[name])
    # a steeper Zipf law than the cell's: at this size the capacity
    # layer's 64-row alignment leaves room the published skew never fills
    cell.update(config_data=config,
                traffic_data={"driver": "moe_prefill", "batch": 2,
                              "prompt_len": 128, "distinct_batches": 2,
                              "zipf_exponent": 2.0, "check_calls": 1},
                end_to_end=[m for m in spec["end_to_end"]
                            if name in m.get("workloads", [name])],
                per_layer=[m for m in spec["per_layer"]
                           if name in m.get("workloads", [name])])
    return cell


def _run(cell, seconds=1.0, **kw):
    import jax
    run = Run(cell, kw.pop("seed", SEED), peak=PEAK, log=lambda m: None,
              **kw)
    return run, execute(run, seconds, jax.devices(),
                        t_start=time.perf_counter())


def test_sound_run_is_correct(artifacts):
    run, res = _run(prefill_cell(artifacts))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "prefill_tok_s"}
    assert run.counters["setup_model_evals"] > 0
    assert run.counters["window_model_evals"] == 0
    rows = run.raw["routed_rows"]
    assert len(rows) == res["attempted"]
    assert all(r.shape == (2, 8) and r.sum() == 2 * 2 * 128 * 2
               for r in rows)


def test_control_is_not_correct(artifacts):
    _, res = _run(prefill_cell(artifacts), control=True)
    assert not res["correct"], res["checks"]


def test_altered_answer_is_not_correct(artifacts):
    _, res = _run(prefill_cell(artifacts),
                  faults={"answer": lambda x: x[..., ::-1]})
    assert not res["correct"], res["checks"]


def test_capacity_layer_is_not_correct(artifacts, monkeypatch):
    """The parent's layer: a capacity slab of 1.25x the mean load, every
    token-slot past it dropped, in the dropless layer's place."""
    from repro.models import moe
    monkeypatch.setattr(moe, "_dropless", moe._capacity)
    run, res = _run(prefill_cell(artifacts))
    assert run.counters["routed_rows_skew"]
    assert dict((n, v["value"]) for n, v in res["checks"].items())
    assert not res["correct"], res["checks"]


def test_missing_counter_is_not_correct(artifacts, monkeypatch):
    """Without the routed-rows counter the rows check reads inf, so the
    cell is not judged on logits alone."""
    from repro.launch.serve import ServeSession
    real = ServeSession.prefill

    def uncounted(self, *a, **kw):
        out = real(self, *a, **kw)
        self.routed_rows = None
        return out

    monkeypatch.setattr(ServeSession, "prefill", uncounted)
    _, res = _run(prefill_cell(artifacts))
    assert res["checks"]["routed_rows_rel_l1"]["value"] == float("inf")
    assert not res["correct"], res["checks"]
