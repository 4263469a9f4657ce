"""The grouped (ragged-M) gemm op, the dropless MoE layer, YaRN, and their
plumbing: the decision key and Table-III row of the grouped gemm, its knob
space's persistence, and the serve session's routed-rows counter."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends.conformance import oracle, rel_err
from repro.configs import get_smoke_config
from repro.kernels.grouped_gemm import group_metadata, grouped_gemm_pallas
from repro.models import moe
from repro.models.layers import Ctx, rope, yarn, yarn_softmax_factor

# ---------------------------------------------------------------------------
# the grouped gemm against the float64 oracle
# ---------------------------------------------------------------------------

#: (group sizes, k, n): uneven groups with an empty one; a group larger
#: than a row tile; a group of one row; empty groups first and last; one
#: group; a ragged contraction and output
GROUPS = {
    "uneven_with_empty": ([5, 0, 130, 3, 0, 62], 96, 80),
    "larger_than_a_tile": ([300, 7, 200], 64, 136),
    "one_row_groups": ([1, 1, 0, 1, 40, 1], 128, 64),
    "empty_first_and_last": ([0, 0, 9, 140, 0], 72, 48),
    "single_group": ([257], 129, 130),
}


def _operands(sizes, k, n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    m = int(sum(sizes))
    return (rng.standard_normal((m, k)).astype(dtype),
            rng.standard_normal((len(sizes), k, n)).astype(dtype),
            np.asarray(sizes, np.int32))


@pytest.mark.parametrize("case", sorted(GROUPS))
@pytest.mark.parametrize("backend", ["pallas", "ref"])
def test_grouped_gemm_matches_the_float64_oracle(case, backend):
    from repro.backends import get_backend
    sizes, k, n = GROUPS[case]
    xs = _operands(sizes, k, n)
    be = get_backend(backend)
    got = np.asarray(be.execute("grouped_gemm", be.prepare(xs),
                                be.default_knob("grouped_gemm")))
    assert got.shape == (sum(sizes), n)
    assert rel_err(got, oracle("grouped_gemm", xs)) < 5e-6


@pytest.mark.parametrize("bm, bk, bn", [(128, 128, 128), (256, 128, 256),
                                        (128, 256, 128)])
def test_grouped_gemm_tiles_agree(bm, bk, bn):
    sizes, k, n = GROUPS["uneven_with_empty"]
    x, w, s = (jnp.asarray(v) for v in _operands(sizes, k, n))
    got = grouped_gemm_pallas(x, w, s, bm=bm, bk=bk, bn=bn, interpret=True)
    assert rel_err(got, oracle("grouped_gemm", (x, w, s))) < 5e-6


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_grouped_gemm_reads_a_layer_of_a_stack(case):
    """Given a stack of layers' expert weights ``(L, G, k, n)`` and a layer
    index, the kernel equals the kernel on that layer's slice bit for bit,
    empty groups included."""
    sizes, k, n = GROUPS[case]
    x, w, s = (jnp.asarray(v) for v in _operands(sizes, k, n))
    stack = jnp.stack([w, -0.5 * w, w[::-1]])
    for i in range(stack.shape[0]):
        want = grouped_gemm_pallas(x, stack[i], s, interpret=True)
        got = grouped_gemm_pallas(x, stack, s, layer=jnp.int32(i),
                                  interpret=True)
        assert jnp.array_equal(got, want), (case, i)


def test_grouped_gemm_bfloat16_accumulates_in_float32():
    sizes, k, n = [700, 0, 324], 1024, 128
    xs = _operands(sizes, k, n, dtype=jnp.bfloat16)
    got = grouped_gemm_pallas(*(jnp.asarray(v) for v in xs), interpret=True)
    assert got.dtype == jnp.bfloat16
    assert rel_err(got, oracle("grouped_gemm", xs)) < 1e-2


def test_group_metadata_visits_no_empty_group():
    """Each visit computes one group's part of one row tile; an empty group
    gets none, so none of its weight is read, and visits past the last
    repeat its blocks."""
    sizes = jnp.asarray([0, 5, 130, 0, 1, 0, 120], jnp.int32)
    offsets, gids, tids, visits = group_metadata(sizes, 256, 128)
    v = int(visits)
    assert gids.shape == (2 + 7 - 1,)
    pairs = list(zip(np.asarray(gids)[:v].tolist(),
                     np.asarray(tids)[:v].tolist()))
    # rows 0-4 group 1, 5-134 group 2 (tiles 0 and 1), 135 group 4,
    # 136-255 group 6
    assert pairs == [(1, 0), (2, 0), (2, 1), (4, 1), (6, 1)]
    assert not {0, 3, 5} & set(np.asarray(gids).tolist())
    assert np.all(np.asarray(gids)[v:] == 6) and np.all(
        np.asarray(tids)[v:] == 1)
    assert np.asarray(offsets).tolist() == [0, 0, 5, 135, 135, 136, 136,
                                            256]


def test_run_op_decides_on_m_k_n_g():
    """The decision key is (m, k, n, g); the group sizes are data."""
    from repro.roofline.harvest import Recorder
    from repro.kernels.ops import dims_of, run_op
    xs = _operands([3, 0, 20], 64, 32)
    assert dims_of("grouped_gemm", tuple(v.shape for v in xs)) == \
        (23, 64, 32, 3)
    rec = Recorder()
    run_op("grouped_gemm", tuple(jnp.asarray(v) for v in xs),
           runtime=rec, interpret=True)
    assert rec.keys == [("pallas", "grouped_gemm", 4, (23, 64, 32, 3))]


# ---------------------------------------------------------------------------
# knob space, features, persistence
# ---------------------------------------------------------------------------

def test_grouped_knob_space_and_parallelism():
    from repro.kernels.ops import default_knob, knob_space_for
    space = knob_space_for("grouped_gemm")
    assert space.name == "grouped_blocks" and len(space) == 27
    knob = [c for c in space if c.dict["bm"] == 512 and c.dict["bn"] == 256][0]
    # (ceil(49152/512) + 64 - 1) visits x ceil(1408/256) column tiles
    assert space.parallelism(knob, (49152, 2048, 1408, 64)) == \
        (96 + 63) * 6
    d = default_knob("grouped_gemm").dict
    assert (d["bm"], d["bk"], d["bn"]) == (128, 128, 128)


def test_grouped_table_iii_row():
    from repro.core.features import (build_features, feature_names,
                                     fill_features_into, footprint_words)
    dims = (49152, 2048, 1408, 64)
    m, k, n, g = dims
    X = build_features("grouped_gemm", np.array([dims]), np.array([7.0]))
    row = dict(zip(feature_names(4), X[0]))
    assert row["g*k*n"] == g * k * n and row["m*k*n/nt"] == m * k * n / 7
    assert row["footprint"] == footprint_words("grouped_gemm", dims) == \
        m * k + g * k * n + m * n
    nt = np.array([3.0, 5.0, 7.0])
    cols = np.arange(X.shape[1])
    out = np.empty((3, cols.size))
    fill_features_into("grouped_gemm", dims, nt, cols, out)
    ref = build_features("grouped_gemm", np.tile(dims, (3, 1)), nt)
    assert np.array_equal(out, ref)


def test_grouped_knob_space_survives_the_registry(tmp_path):
    """A trained grouped-gemm model reloads with its parallelism, and the
    runtime's compiled decision equals the reference decision."""
    from repro.backends import get_backend
    from repro.core import AdsalaRuntime, ModelRegistry, install_subroutine
    space = get_backend("pallas").knob_space("grouped_gemm",
                                             sizes=(128, 256))

    def timer(dims, knob):
        m, k, n, g = dims
        d = knob.dict
        return 1e-9 * m * k * n / d["bm"] * (1 + g / 64) + 1e-6 * d["bn"]

    sub = install_subroutine("grouped_gemm", space, timer, n_samples=12,
                             dim_lo=64, dim_hi=512, dtype_bytes=2,
                             backend="pallas", tune_trials=1,
                             candidates=("LinearRegression", "DecisionTree"))
    ModelRegistry(tmp_path).save(sub)
    rt = AdsalaRuntime()
    assert ModelRegistry(tmp_path).load_into(rt) == 1
    dims = (300, 200, 100, 8)
    assert rt.select("grouped_gemm", dims, 2, backend="pallas") == \
        sub.select(dims)


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------

def _moe_cfg(arch="granite_moe_3b", **kw):
    return dataclasses.replace(get_smoke_config(arch),
                               compute_dtype="float32", **kw)


def _per_expert_loop(p, x, cfg):
    """Every token through each of its top-k experts, one by one."""
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xf @ np.asarray(p["router"]["w"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xf)
    silu = lambda v: v / (1 + np.exp(-v))
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[: cfg.top_k]
        w = probs[t, top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for e, we in zip(top, w):
            g = xf[t] @ np.asarray(p["wg"][e], np.float64)
            u = xf[t] @ np.asarray(p["wu"][e], np.float64)
            out[t] += we * ((silu(g) * u) @ np.asarray(p["wd"][e],
                                                       np.float64))
    return out.reshape(x.shape)


def _skewed(p, hot: int = 0, by: float = 8.0):
    """Router weights that send (nearly) every token to expert ``hot``."""
    w = p["router"]["w"]
    return dict(p, router={"w": w.at[:, hot].add(by)})


@pytest.mark.parametrize("routed", [False, True])
@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("skew", [False, True])
def test_dropless_layer_matches_a_per_expert_loop(routed, norm, skew):
    cfg = _moe_cfg(n_shared_experts=0, norm_topk_prob=norm,
                   use_pallas_gemm=routed, gemm_interpret=True)
    p = moe.init_moe(jax.random.PRNGKey(1), cfg)
    # past 64 tokens a sequence, so that the capacity slab (64-row aligned)
    # can overflow
    S = 160
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, cfg.d_model))
    if skew:
        p = _skewed(p, by=0.5)
        x = x + 2.0 * jnp.sign(x[:, :1])           # tokens alike
    out, aux, rows = moe.moe_layer(p, x, Ctx(cfg))
    want = _per_expert_loop(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)
    assert int(rows.sum()) == 2 * S * cfg.top_k and np.isfinite(aux)
    if skew:
        # the capacity slab drops here: its hottest expert is over C
        capped, kept = moe._capacity(p, x, *moe._route(p, x, cfg)[1:],
                                     Ctx(cfg))
        assert int(kept.sum()) < int(rows.sum())
        assert not np.allclose(np.asarray(capped), want, rtol=1e-2,
                               atol=1e-2)


def test_norm_topk_prob_both_ways():
    cfg = _moe_cfg()
    p = moe.init_moe(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, cfg.d_model))
    probs, top_p, _ = moe._route(p, x, cfg)
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 1.0, rtol=1e-6)
    raw = dataclasses.replace(cfg, norm_topk_prob=False)
    _, top_raw, top_e = moe._route(p, x, raw)
    np.testing.assert_array_equal(
        np.asarray(top_raw),
        np.take_along_axis(np.asarray(probs), np.asarray(top_e), -1))
    assert float(top_raw.sum(-1).max()) < 1.0
    assert get_smoke_config("deepseek_v2_lite").norm_topk_prob is False
    assert get_smoke_config("granite_moe_3b").norm_topk_prob is True


def test_granite_smoke_numbers_unchanged():
    """granite (renormalised top-k, as before) where the parent's capacity
    drops nothing: the dropless layer gives the capacity layer's numbers."""
    cfg = _moe_cfg()
    p = moe.init_moe(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    out, _ = moe.moe_ffn(p, x, Ctx(cfg))
    _, top_p, top_e = moe._route(p, x, cfg)
    capped, kept = moe._capacity(p, x, top_p, top_e, Ctx(cfg))
    assert int(kept.sum()) == 2 * 16 * cfg.top_k        # nothing dropped
    shared = moe.mlp(p["shared"], x, Ctx(cfg)) if "shared" in p else 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(capped + shared),
                               rtol=1e-5, atol=1e-6)


def test_serve_session_keeps_the_routed_rows_counter():
    from repro.launch.serve import ServeSession, init_serving_params
    for arch, want in (("deepseek_v2_lite", (2, 8)), ("qwen15_4b", None)):
        cfg = get_smoke_config(arch)
        sess = ServeSession(cfg=cfg, params=init_serving_params(cfg),
                            max_len=24)
        assert sess.routed_rows is None
        sess.prefill(np.zeros((2, 16), np.int32))
        if want is None:
            assert sess.routed_rows is None
        else:
            assert sess.routed_rows.shape == want
            assert int(sess.routed_rows.sum()) == 2 * 16 * cfg.top_k * (
                cfg.n_layers - cfg.first_dense_layers)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_frequency_endpoints_and_softmax_scale():
    from repro.configs.deepseek_v2_lite import YARN
    freqs, mult = yarn(YARN, 64, 1e4)
    # below the correction range the original frequencies, above it the
    # interpolated ones (divided by the factor); mscale = mscale_all_dim
    dim_fast = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e4))
    dim_slow = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e4))
    low, high = math.floor(dim_fast), math.ceil(dim_slow)
    orig = 1.0 / 1e4 ** (np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:low + 1], orig[:low + 1], rtol=1e-15)
    np.testing.assert_allclose(freqs[high:], orig[high:] / 40, rtol=1e-15)
    assert np.all(np.diff(freqs) < 0)
    assert mult == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert yarn_softmax_factor(YARN) == pytest.approx(m * m, rel=1e-15)
    assert yarn_softmax_factor(YARN) == pytest.approx(1.5897, abs=1e-4)


def test_no_scaling_is_bit_identical():
    """A config without rope scaling rotates and scales as before."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, 3, 16))
    pos = jnp.arange(12)[None, :]
    half = 8
    freqs = 1.0 / (1e4 ** (np.arange(0, half) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    old = jnp.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                           x[..., half:] * cos + x[..., :half] * sin], -1)
    assert jnp.array_equal(rope(x, pos, theta=1e4), old)
    from repro.models.layers import flash_attention
    q = jax.random.normal(jax.random.PRNGKey(8), (1, 8, 2, 16))
    a = flash_attention(q, q, q, causal=True, q_chunk=4, k_chunk=4)
    b = flash_attention(q, q, q, causal=True, q_chunk=4, k_chunk=4,
                        scale=1.0 / math.sqrt(16))
    assert jnp.array_equal(a, b)
