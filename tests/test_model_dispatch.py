"""ADSALA-dispatched model inference (PR 6).

Contracts:

  * routing every dense matmul of the transformer through
    ``run_op``/:class:`AdsalaRuntime` is **bitwise** identical to the plain
    ``x @ w`` path — for the dense, MoE and MLA families, on forward,
    prefill and decode_step — whenever every contraction dim fits one
    k-tile (≤ 128: the f32 accumulation is then a single exact jnp.dot);
  * ``run_op``/the kernels take leading-batch activations *natively*
    (3-D a against a shared 2-D weight — no reshape-collapse, no per-item
    loop over copies);
  * a routed matmul whose items have fewer rows than one row tile folds
    every leading axis into the gemm's M: one 2-D ``run_op`` call, so a
    decode step's ``(B, 1, d)`` activation reads each weight once, not
    once per sequence;
  * the ahead-of-time harvest (``roofline.harvest``) sees every decision
    key the routed programs will request — including the skinny
    ``(B, d, n)`` decode GEMMs — with zero model evaluations;
  * install → ``select_many`` → ``save_decision_cache`` offline, then a
    fresh runtime hydrated from the registry serves prefill + decode with
    **zero** runtime model evaluations;
  * a routed segment's weights are read in place from their stack: the
    gemm given a stack and a layer index equals the gemm on the sliced
    layer bit for bit, asks the same decision key and runs the same knob,
    and a routed decode trace counts its seven layer weights in place and
    the output head materialised.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import conformance, resolve_backend
from repro.configs import get_smoke_config
from repro.core.oracle import oracle_time
from repro.core.registry import ModelRegistry
from repro.core.runtime import AdsalaRuntime
from repro.core.tuner import install_subroutine
from repro.kernels import ops
from repro.kernels.gemm import gemm_pallas
from repro.models import transformer as tf
from repro.models.layers import Ctx, routed_matmul
from repro.roofline.costing import prune_dominated_candidates
from repro.roofline.harvest import (Recorder, dot_call_sites,
                                    harvest_decision_keys)
from repro.core.runtime import DEFAULT_BACKEND

#: dense / MoE / MLA — one routed arch per family
ARCHS = ("qwen15_4b", "granite_moe_3b", "deepseek_v2_lite")


def _cfg(arch):
    """Parity config: every contraction dim (d_model, d_ff, moe_d_ff,
    kv_lora, heads·v_head_dim) ≤ 128 → single k-tile → bitwise."""
    return dataclasses.replace(get_smoke_config(arch),
                               compute_dtype="float32",
                               capacity_factor=8.0, d_ff=128)


@pytest.fixture
def held_experts(monkeypatch):
    """Holds the unrouted path's expert gemm to the grouped kernel (the
    routed path's, at the default knob), so the whole-model comparison
    below is bitwise: routing then changes no bit in any other linear.
    The expert gemm's own exchange, the kernel for ``ragged_dot``, is
    compared exactly on integer-valued operands in
    ``test_grouped_kernel_equals_ragged_dot_exactly``: on real-valued ones
    XLA's CPU ``ragged_dot`` sums each dot in another order."""
    from repro.kernels.grouped_gemm import grouped_gemm_pallas
    from repro.models import moe
    real = moe._grouped_matmul
    kb = ops.default_knob("grouped_gemm").dict

    def held(rows, w, sizes, ctx):
        if ctx.routes_gemm(rows):
            return real(rows, w, sizes, ctx)
        return grouped_gemm_pallas(rows, w, sizes, bm=kb["bm"], bk=kb["bk"],
                                   bn=kb["bn"], interpret=True)

    monkeypatch.setattr(moe, "_grouped_matmul", held)


def _batch(cfg, B, S, seed=0):
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(seed),
                                          (B, S), 0, cfg.vocab)}
    if cfg.vision_tokens:
        batch["vision"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (B, cfg.vision_tokens, 32))
    return batch


# ---------------------------------------------------------------------------
# bit parity: routed == unrouted on forward / prefill / decode_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_routed_forward_bit_identical(arch, held_experts):
    cfg = _cfg(arch)
    rcfg = dataclasses.replace(cfg, use_pallas_gemm=True)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, 2, 16)
    ref, _ = tf.forward(params, batch, cfg)
    rt = AdsalaRuntime()
    out, _ = tf.forward(params, batch, rcfg, runtime=rt)
    assert jnp.array_equal(ref, out), \
        f"maxdiff {float(jnp.max(jnp.abs(ref - out)))}"
    # untuned runtime: every decision fell through to the default knob
    assert rt.stats.for_backend("pallas").model_evals == 0
    assert rt.stats.for_backend("pallas").default_calls > 0


class _SlicedCache:
    """The cache a block got before the layer scan carried the stack: one
    layer's leaves, sliced out of the stack, updated with one
    ``dynamic_update_slice`` each and restacked by the caller."""

    def __init__(self, layer: dict):
        self.layer = layer

    @property
    def len(self):
        return self.layer["len"]

    def __getitem__(self, name):
        return self.layer[name]

    def write(self, start, **rows):
        S = next(iter(rows.values())).shape[1]
        layer = dict(self.layer, len=start + S)
        for name, new in rows.items():
            layer[name] = jax.lax.dynamic_update_slice_in_dim(
                layer[name], new.astype(layer[name].dtype), start, axis=1)
        return _SlicedCache(layer)


def _layer_by_layer(params, x, caches, ctx):
    """Every segment's blocks in a Python loop, each layer's params and
    cache sliced out and the caches restacked: the reference for the
    carried cache.  Returns (x, new caches)."""
    new_caches = []
    for si, (kind, repeat) in enumerate(ctx.cfg.segments()):
        layers = []
        for i in range(repeat):
            at = lambda t: t[i]                             # noqa: E731
            x, view, _, _ = tf._apply_block(
                kind, jax.tree.map(at, params["segments"][si]), x, ctx,
                _SlicedCache(jax.tree.map(at, caches[si])))
            layers.append(view.layer)
        new_caches.append(jax.tree.map(lambda *t: jnp.stack(t), *layers))
    return x, new_caches


@partial(jax.jit, static_argnums=3)
def _reference_prefill(params, batch, caches, cfg):
    ctx = Ctx(cfg)
    x, caches = _layer_by_layer(params, tf._embed_inputs(params, batch, ctx),
                                caches, ctx)
    return tf._logits(params, x[:, -1:], ctx), caches


@partial(jax.jit, static_argnums=3)
def _reference_decode_step(params, token, caches, cfg):
    ctx = Ctx(cfg)
    x, caches = _layer_by_layer(params, tf.embed(params["embed"], token, ctx),
                                caches, ctx)
    return tf._logits(params, x, ctx), caches


def _assert_same_caches(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert jnp.array_equal(g, w)


@pytest.mark.parametrize("S,T", [(16, 20), (13, 28)],
                         ids=["last-tile", "mid-tile"])
@pytest.mark.parametrize("arch", ARCHS)
def test_routed_prefill_decode_bit_identical(arch, S, T, held_experts):
    """Routed and unrouted, the scan that carries each segment's cache gives
    the logits and every cache leaf, bit for bit, of the blocks run layer by
    layer on caches sliced out and restacked: after a prefill of ``S``
    tokens and after each of two decode steps from that nonzero length,
    whose rows land in the cache's last row tile (shifted back to fit the
    ``T`` positions) or inside a tile."""
    cfg = _cfg(arch)
    rcfg = dataclasses.replace(cfg, use_pallas_gemm=True)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    B = 2
    batch = _batch(cfg, B, S)
    lu, cu = tf.prefill(params, batch, tf.init_decode_state(cfg, B, T), cfg)
    rt = AdsalaRuntime()
    lr, cr = tf.prefill(params, batch, tf.init_decode_state(rcfg, B, T),
                        rcfg, runtime=rt)
    lw, cw = _reference_prefill(params, batch, tf.init_decode_state(cfg, B, T),
                                cfg)
    assert jnp.array_equal(lu, lr) and jnp.array_equal(lu, lw)
    _assert_same_caches(cu, cw)
    _assert_same_caches(cr, cw)
    tok = jnp.argmax(lu[:, -1:], -1).astype(jnp.int32)
    for _ in range(2):
        du, cu = tf.decode_step(params, tok, cu, cfg)
        dr, cr = tf.decode_step(params, tok, cr, rcfg, runtime=rt)
        dw, cw = _reference_decode_step(params, tok, cw, cfg)
        assert jnp.array_equal(du, dr) and jnp.array_equal(du, dw)
        _assert_same_caches(cu, cw)
        _assert_same_caches(cr, cw)
        tok = jnp.argmax(du[:, -1:], -1).astype(jnp.int32)
    assert int(cw[0]["len"][0]) == S + 2


def _expert_gemm_keys():
    """(arch, (m, k, n, E)) of every expert gemm the parity tests run:
    gate/up and down, at prefill (2 × 16 tokens) and decode (2 tokens)."""
    out = []
    for arch in ARCHS:
        cfg = _cfg(arch)
        if cfg.family != "moe":
            continue
        for t in (32, 2):
            m = t * cfg.top_k
            out += [(arch, (m, cfg.d_model, cfg.moe_d_ff, cfg.n_experts)),
                    (arch, (m, cfg.moe_d_ff, cfg.d_model, cfg.n_experts))]
    return out


@pytest.mark.parametrize("arch, dims", _expert_gemm_keys())
def test_grouped_kernel_equals_ragged_dot_exactly(arch, dims):
    """The routed expert gemm against the unrouted one, bit for bit, on
    integer-valued operands (every partial sum exact, so no summation order
    shows), with uneven groups and an empty one."""
    from repro.models.layers import Ctx as _Ctx
    from repro.models.moe import _grouped_matmul
    from repro.models.sharding import DEFAULT_RULES
    m, k, n, g = dims
    rng = np.random.default_rng(m * k + n)
    p = rng.random(g)
    p[1] = 0.0                                         # an empty group
    sizes = jnp.asarray(rng.multinomial(m, p / p.sum()), jnp.int32)
    x = jnp.asarray(rng.integers(-4, 5, (m, k)), jnp.float32)
    w = jnp.asarray(rng.integers(-4, 5, (g, k, n)), jnp.float32)
    cfg = _cfg(arch)
    rcfg = dataclasses.replace(cfg, use_pallas_gemm=True,
                               gemm_interpret=True)
    un = _grouped_matmul(x, w, sizes, _Ctx(cfg, None, DEFAULT_RULES))
    rctx = _Ctx(rcfg, None, DEFAULT_RULES, AdsalaRuntime())
    assert rctx.routes_gemm(x)
    routed = _grouped_matmul(x, w, sizes, rctx)
    assert jnp.array_equal(un, routed)
    assert jnp.array_equal(un, jax.lax.ragged_dot(x, w, sizes))


def test_routing_respects_config_gates():
    cfg = _cfg("qwen15_4b")
    from repro.models.sharding import DEFAULT_RULES
    x = jnp.ones((2, 8, cfg.d_model))
    w = jnp.ones((cfg.d_model, 32))
    # unrouted config → plain matmul (trivially, no pallas trace)
    ctx = Ctx(cfg, None, DEFAULT_RULES)
    assert not ctx.routes_gemm(x)
    assert jnp.array_equal(routed_matmul(x, w, ctx), x @ w)
    # routed config but a live mesh → sharded einsum path stays untouched
    rcfg = dataclasses.replace(cfg, use_pallas_gemm=True)
    assert not Ctx(rcfg, object(), DEFAULT_RULES).routes_gemm(x)
    # routed, meshless → dispatches (and still matches bitwise)
    rctx = Ctx(rcfg, None, DEFAULT_RULES)
    assert rctx.routes_gemm(x)
    assert jnp.array_equal(routed_matmul(x, w, rctx), x @ w)


def _routed_ctx():
    from repro.models.sharding import DEFAULT_RULES
    rcfg = dataclasses.replace(_cfg("qwen15_4b"), use_pallas_gemm=True,
                               gemm_interpret=True)
    return Ctx(rcfg, None, DEFAULT_RULES)


#: (activation shape, the one gemm operand ``routed_matmul`` hands
#: ``run_op``): items under one 128-row tile fold into M; items that fill
#: row tiles stay one stack
ROUTED_SHAPES = [((4, 1, 64), (4, 64)), ((2, 3, 8, 64), (48, 64)),
                 ((2, 128, 64), (2, 128, 64)),
                 ((2, 3, 128, 64), (6, 128, 64))]
ROUTED_IDS = ["decode-3d", "fold-4d", "stack-3d", "stack-4d"]


@pytest.mark.parametrize("shape,_", ROUTED_SHAPES, ids=ROUTED_IDS)
def test_routed_matmul_high_rank_leading_batch(shape, _):
    """Leading axes fold into M (or, for items that fill row tiles, into
    one stack axis) outside any jit loop: the result matches ``x @ w`` bit
    for bit (one k-tile) in interpret mode."""
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    if shape[-2] >= 128:
        # XLA's CPU dot sums a 128-row operand in another order than the
        # kernel's tile: small integers make every order exact
        x, w = jnp.round(4 * x), jnp.round(4 * w)
    got = routed_matmul(x, w, _routed_ctx())
    assert got.shape == shape[:-1] + (32,)
    assert jnp.array_equal(got, x @ w)


@pytest.mark.parametrize("shape,operand", ROUTED_SHAPES, ids=ROUTED_IDS)
def test_routed_matmul_is_one_gemm(monkeypatch, shape, operand):
    """One ``run_op`` gemm a routed matmul: a ``(B, 1, d)`` decode
    activation is a plain 2-D gemm of ``(B, d)`` rows against the shared
    weight, never a stack of one-row items."""
    calls = []
    real = ops.run_op

    def spy(op, operands, **kw):
        calls.append((op, tuple(x.shape for x in operands), kw))
        return real(op, operands, **kw)

    monkeypatch.setattr(ops, "run_op", spy)
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    got = routed_matmul(x, w, _routed_ctx())
    assert got.shape == shape[:-1] + (32,)
    assert len(calls) == 1
    op, shapes, kw = calls[0]
    assert op == "gemm" and shapes == (operand, (64, 32))
    assert "stacked" not in kw


# ---------------------------------------------------------------------------
# native leading-batch gemm (shared 2-D weight, no collapse/copy)
# ---------------------------------------------------------------------------

def test_gemm_pallas_shared_weight_batched():
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 33, 96))
    b = jax.random.normal(jax.random.PRNGKey(1), (96, 160))
    got = gemm_pallas(a, b, bm=128, bk=128, bn=128, interpret=True)
    want = a @ b
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_run_op_stacked_shared_weight():
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 17, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 48))
    got = ops.run_op("gemm", (a, b), interpret=True)
    # the float32 reference's accumulation order is XLA's, not ours: hold
    # the kernel to the float64 oracle within the f32 conformance tolerance
    want = conformance.oracle("gemm", (np.asarray(a), np.asarray(b)))
    assert conformance.rel_err(got, want) < conformance.tolerance_for(
        np.float32)


def test_run_op_stacked_both_batched():
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 16, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (3, 64, 32))
    got = ops.run_op("gemm", (a, b), interpret=True)
    assert jnp.array_equal(got, jnp.einsum("bmk,bkn->bmn", a, b))


@pytest.mark.parametrize("backend", ("ref", "cpu_blocked"))
def test_execute_stacked_shared_weight_other_backends(backend):
    be = resolve_backend(backend)
    rng = np.random.default_rng(0)
    a = rng.standard_normal((3, 20, 24)).astype(np.float32)
    b = rng.standard_normal((24, 16)).astype(np.float32)
    got = np.asarray(be.execute_stacked("gemm", (a, b)))
    want = a.astype(np.float64) @ b.astype(np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_dims_of_ignores_leading_batch():
    assert ops.dims_of("gemm", ((5, 33, 64), (64, 96))) == (33, 64, 96)
    assert ops.dims_of("gemm", ((33, 64), (64, 96))) == (33, 64, 96)


# ---------------------------------------------------------------------------
# a layer's weight read in place from its segment's stack
# ---------------------------------------------------------------------------

#: (activation shape, stacked weight (L, k, n)): a 2-D activation, a batched
#: one against the shared stack, a ragged contraction (k not a multiple of
#: bk), and ragged rows and columns
STACK_CASES = {"2d": ((200, 256), (3, 256, 384)),
               "batched-shared": ((2, 130, 256), (3, 256, 128)),
               "ragged-k": ((64, 200), (2, 200, 256)),
               "ragged-mn": ((33, 128), (4, 128, 160))}


@pytest.mark.parametrize("case", sorted(STACK_CASES))
def test_gemm_pallas_reads_a_layer_of_a_stack(case):
    """The gemm given the stack and a layer index equals the gemm given
    that layer's slice, bit for bit, for every layer."""
    a_shape, w_shape = STACK_CASES[case]
    a = jax.random.normal(jax.random.PRNGKey(0), a_shape)
    stack = jax.random.normal(jax.random.PRNGKey(1), w_shape)
    for i in range(w_shape[0]):
        want = gemm_pallas(a, stack[i], bm=128, bk=128, bn=128,
                           interpret=True)
        got = gemm_pallas(a, stack, layer=jnp.int32(i), bm=128, bk=128,
                          bn=128, interpret=True)
        assert jnp.array_equal(got, want), (case, i)


class _KeyedKnobs(Recorder):
    """Records each decision key and answers with a knob that depends on
    the key, so a call that keyed differently would run other tiles."""

    def select_or_default(self, op, dims, dtype_bytes, default, *,
                          backend=DEFAULT_BACKEND):
        super().select_or_default(op, dims, dtype_bytes, default,
                                  backend=backend)
        cands = ops.knob_space_for(op).candidates
        return cands[sum(dims) % len(cands)]


def _stack_operands(op):
    rng = np.random.default_rng(0)
    if op == "gemm":
        return (rng.standard_normal((40, 200)).astype(np.float32),
                rng.standard_normal((3, 200, 300)).astype(np.float32))
    x, w, sizes = resolve_backend("pallas").make_operands(
        "grouped_gemm", (300, 136, 200, 6))
    return x, np.stack([w, 2 * w]), sizes


@pytest.mark.parametrize("op", ["gemm", "grouped_gemm"])
def test_stacked_weight_call_keys_and_runs_as_its_slice(op, monkeypatch):
    """``run_op`` given a stack and ``layer=`` asks the decision key of the
    sliced call, runs the knob chosen for it, and returns its result bit for
    bit: the frozen installs choose as before."""
    launched = []
    real = ops._launch

    def spy(kernel, *args, **kw):
        launched.append((kw["bm"], kw["bk"], kw["bn"]))
        return real(kernel, *args, **kw)

    monkeypatch.setattr(ops, "_launch", spy)
    x, stack, *rest = (jnp.asarray(v) for v in _stack_operands(op))
    layer = jnp.int32(1)
    assert ops.dims_of(op, tuple(v.shape for v in (x, stack, *rest))) == \
        ops.dims_of(op, tuple(v.shape for v in (x, stack[1], *rest)))
    sliced, in_place = _KeyedKnobs(), _KeyedKnobs()
    want = ops.run_op(op, (x, stack[1], *rest), runtime=sliced,
                      interpret=True)
    got = ops.run_op(op, (x, stack, *rest), layer=layer, runtime=in_place,
                     interpret=True)
    assert in_place.keys == sliced.keys and len(sliced.keys) == 1
    assert launched[0] == launched[1]
    assert jnp.array_equal(got, want)
    # the ref backend has no in-place read: it gets the layer sliced out
    ref = ops.run_op(op, (x, stack, *rest), backend="ref", layer=layer)
    assert jnp.array_equal(ref, ops.run_op(op, (x, stack[1], *rest),
                                           backend="ref"))


def test_harvest_keys_match_the_sliced_programs():
    """The routed programs ask the same decision keys whether their kernels
    read each layer in place (pallas) or get it sliced out (ref)."""
    cfg = _cfg("deepseek_v2_lite")

    def keys(backend):
        got = harvest_decision_keys(
            dataclasses.replace(cfg, gemm_backend=backend), batch_size=2,
            seq_len=16)
        return sorted(k[1:] for k in got)

    assert keys("pallas") == keys("ref")


def _decode_trace(cfg, *, mesh=None) -> AdsalaRuntime:
    rt = AdsalaRuntime()
    params = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    caches = jax.eval_shape(lambda: tf.init_decode_state(cfg, 2, 20))
    token = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    jax.eval_shape(lambda p, t, c: tf.decode_step(p, t, c, cfg, mesh=mesh,
                                                  runtime=rt),
                   params, token, caches)
    return rt


def test_routed_decode_reads_layer_weights_in_place():
    """One trace of qwen's decode step: the segment's seven routed weights
    (q, k, v, o, gate, up, down) in place, the output head, which is not
    stacked, materialised."""
    cfg = dataclasses.replace(_cfg("qwen15_4b"), use_pallas_gemm=True)
    assert len(cfg.segments()) == 1 and not cfg.tie_embeddings
    stats = _decode_trace(cfg).stats
    assert (stats.routed_in_place, stats.routed_materialised) == (7, 1)


def test_decode_counts_cache_writes():
    """One trace of qwen's decode step: its one block writes its rows into
    the carried stack and writes no state back whole; rwkv6's blocks write
    their whole state back."""
    cfg = dataclasses.replace(_cfg("qwen15_4b"), use_pallas_gemm=True)
    stats = _decode_trace(cfg).stats
    assert (stats.cache_rows_in_place, stats.cache_written_whole) == (1, 0)
    stats = _decode_trace(get_smoke_config("rwkv6_1p6b")).stats
    assert stats.cache_rows_in_place == 0 and stats.cache_written_whole > 0


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("routed", [False, True], ids=["unrouted", "routed"])
@pytest.mark.parametrize("layers", [3, 8], ids=["flat", "nested-remat"])
def test_decode_step_carries_the_cache(routed, layers):
    """qwen's decode-step jaxpr: no scan takes a cache leaf in ``xs`` or
    returns one in ``ys``, and every ``dynamic_update_slice`` into a k or v
    stack writes one row tile of the new row, ``(1, B, ROW_TILE, KH, D)``,
    never a layer.  With eight layers the config's nested remat would
    regroup the scan."""
    from repro.models.layers import ROW_TILE
    cfg = dataclasses.replace(_cfg("qwen15_4b"), use_pallas_gemm=routed,
                              n_layers=layers, remat="nested")
    B, T = 2, 20
    params = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    caches = jax.eval_shape(lambda: tf.init_decode_state(cfg, B, T))
    stack = caches[0]["k"].shape
    assert stack == (layers, B, T, cfg.kv_heads, cfg.hd())
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: tf.decode_step(p, t, c, cfg,
                                       runtime=AdsalaRuntime()))(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32), caches).jaxpr
    writes, scans = [], 0
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "scan":
            n = eqn.params["num_consts"] + eqn.params["num_carry"]
            sliced = (eqn.invars[n:]
                      + eqn.outvars[eqn.params["num_carry"]:])
            assert not [v for v in sliced
                        if v.aval.shape[-4:] == stack[1:]], eqn
            scans += 1
        if eqn.primitive.name == "dynamic_update_slice":
            operand, update = (v.aval.shape for v in eqn.invars[:2])
            assert operand != stack[1:]
            if operand == stack:
                writes.append(update)
    assert scans >= 1
    assert writes == [(1, B, ROW_TILE) + stack[3:]] * 2


@pytest.mark.parametrize("where", ["mesh", "unrouted"])
def test_nothing_read_in_place_off_the_routed_path(where, monkeypatch):
    """Under a mesh (GSPMD partitions plain matmuls) and in an unrouted
    config the scan keeps slicing the stacked params: no layer view is made
    and no call is routed."""
    views = []
    real = tf._layer_params
    monkeypatch.setattr(tf, "_layer_params",
                        lambda *a: views.append(1) or real(*a))
    cfg = _cfg("qwen15_4b")
    mesh = None
    if where == "mesh":
        cfg = dataclasses.replace(cfg, use_pallas_gemm=True)
        auto = jax.sharding.AxisType.Auto
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(auto, auto))
    stats = _decode_trace(cfg, mesh=mesh).stats
    assert (stats.routed_in_place, stats.routed_materialised) == (0, 0)
    assert not views


# ---------------------------------------------------------------------------
# ahead-of-time harvest
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_harvest_covers_decode_gemms(arch):
    cfg = _cfg(arch)
    keys = harvest_decision_keys(cfg, batch_size=2, seq_len=16)
    assert keys, "routed model harvested no decision keys"
    assert all(k[0] == "pallas" and k[1] in ("gemm", "grouped_gemm")
               for k in keys)
    # the MoE archs' dropless expert gemms: (m = tokens·top_k, k, n, E)
    grouped = [k[3] for k in keys if k[1] == "grouped_gemm"]
    if cfg.family == "moe":
        assert (2 * 16 * cfg.top_k, cfg.d_model, cfg.moe_d_ff,
                cfg.n_experts) in grouped
        assert (2 * cfg.top_k, cfg.moe_d_ff, cfg.d_model,
                cfg.n_experts) in grouped          # decode: one token each
    else:
        assert not grouped
    # the skinny decode-step GEMMs (m = one token per sequence, folded:
    # m = batch_size) must be present — missing them means the first
    # decode pays a cold model eval.  The output head is left out: prefill's
    # last-token head has the same key.
    assert any(k[3][0] == 2 and k[3][2] != cfg.vocab for k in keys
               if k[1] == "gemm")
    # deterministic: same trace → same keys, no duplicates
    assert keys == harvest_decision_keys(cfg, batch_size=2, seq_len=16)
    assert len(set(keys)) == len(keys)


def test_recorder_is_pure_bookkeeping():
    rec = Recorder()
    from repro.kernels.ops import default_knob
    d = default_knob("gemm")
    assert rec.select_or_default("gemm", (8, 8, 8), 4, d) is d
    assert rec.keys == [("pallas", "gemm", 4, (8, 8, 8))]
    assert rec.stats.model_evals == 0


def test_harvest_unrouted_config_is_empty_vs_routed():
    cfg = _cfg("qwen15_4b")
    # harvest forces the routed path regardless of the input config's flag
    routed = harvest_decision_keys(
        dataclasses.replace(cfg, use_pallas_gemm=True), seq_len=16)
    assert harvest_decision_keys(cfg, seq_len=16) == routed


def test_dot_call_sites_sees_unrouted_matmuls():
    cfg = _cfg("qwen15_4b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, 2, 16)
    sites = dot_call_sites(lambda p, b: tf.forward(p, b, cfg), params, batch)
    assert sites and all(s[0] == "gemm" and len(s[1]) == 3 for s in sites)


def test_prune_dominated_candidates():
    be = resolve_backend("pallas")
    space = be.knob_space("gemm", sizes=(128, 256, 512))
    dims = [(4096, 2048, 2048), (1, 2048, 2048)]
    pruned = prune_dominated_candidates("gemm", space, dims, dtype_bytes=2,
                                        slack=0.15)
    assert 0 < len(pruned) < len(space)
    # each site's oracle argmin must survive the prune
    for d in dims:
        best = min(space.candidates,
                   key=lambda c: oracle_time("gemm", d, c, dtype_bytes=2))
        assert best in pruned.candidates
    # parallelism definition (the nt-analogue feature) is preserved
    k = pruned.candidates[0]
    assert pruned.parallelism(k, dims[0]) == space.parallelism(k, dims[0])
    # empty dims list = nothing to prove = untouched space
    assert prune_dominated_candidates("gemm", space, []) is space


# ---------------------------------------------------------------------------
# offline prewarm → zero runtime model evaluations
# ---------------------------------------------------------------------------

def test_prewarm_serves_with_zero_model_evals(tmp_path):
    B, S = 2, 16
    rcfg = dataclasses.replace(_cfg("qwen15_4b"), use_pallas_gemm=True)
    backend = resolve_backend("pallas")
    keys = harvest_decision_keys(rcfg, batch_size=B, seq_len=S,
                                 programs=("prefill", "decode"))
    db = keys[0][2]
    space = prune_dominated_candidates(
        "gemm", backend.knob_space("gemm", sizes=(128, 256)),
        [k[3] for k in keys], dtype_bytes=db)
    registry = ModelRegistry(tmp_path)
    install_rt = AdsalaRuntime()
    sub = install_subroutine(
        "gemm", space,
        lambda dims, knob: oracle_time("gemm", dims, knob, dtype_bytes=db),
        n_samples=30, dim_lo=16, dim_hi=256, dtype_bytes=db,
        backend="pallas", tune_trials=2)
    registry.save(sub)
    install_rt.register(sub)
    install_rt.select_many([(op, dims, b, be) for (be, op, b, dims) in keys],
                          record_hits=False)
    registry.save_decision_cache(install_rt)

    params = tf.init_params(jax.random.PRNGKey(0), rcfg)
    batch = _batch(rcfg, B, S)

    def serve(runtime) -> int:
        caches = tf.init_decode_state(rcfg, B, S + 4)
        logits, caches = tf.prefill(params, batch, caches, rcfg,
                                    runtime=runtime)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        tf.decode_step(params, tok, caches, rcfg, runtime=runtime)
        return int(runtime.stats.for_backend("pallas").model_evals)

    # without the persisted cache every distinct shape pays one model eval
    # (seven gemm keys across prefill and decode at this config)
    cold = AdsalaRuntime()
    registry.load_into(cold, backend="pallas")
    assert serve(cold) == len(keys) == 7
    # with it: all trace-time decisions are cache hits — zero evals
    warm = AdsalaRuntime()
    registry.load_into(warm, backend="pallas")
    assert registry.load_decision_cache(warm) == len(keys)
    assert serve(warm) == 0
