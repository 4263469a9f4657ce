"""Model assembler: segment-scanned layer stacks for all 10 architectures.

The layer stack is a list of (block_kind, repeat) segments (configs/base.py);
per-segment params are stacked along a leading layer axis and consumed by
``jax.lax.scan`` — HLO size stays O(#segments) regardless of depth, which is
what keeps 512-device dry-run compiles tractable.  Decode caches are pytrees
stacked the same way and threaded through the scan as xs/ys.

Where the matmuls are routed (one host, ``use_pallas_gemm``) the scan runs
over the layer index and the segment's params stay whole, loop-invariant:
each routed weight reaches its kernel as a :class:`~.layers.LayerSlice`
and is read in place, since a Pallas call cannot fuse a slice of its
operand and XLA would otherwise copy every layer's weights out of the stack
on every forward pass.  Everything else takes its layer with
``dynamic_index_in_dim``, which XLA fuses into the consumer.

Block kinds:
  attn        pre-LN GQA attention + MLP            (dense, vlm backbone)
  moe         pre-LN attention (GQA or MLA) + MoE   (granite-moe, deepseek)
  mamba2      pre-LN Mamba2 mixer                   (zamba2 tail)
  zamba_super k× mamba2 + one SHARED attn+MLP block (zamba2)
  rwkv6       self-contained RWKV6 block            (rwkv6)
  enc         bidirectional attention + MLP          (whisper encoder)
  dec_cross   causal self-attn + cross-attn + MLP    (whisper decoder)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from .layers import (Ctx, LayerSlice, attention, cross_entropy, embed,
                     init_attention, init_embedding, init_mlp, init_norm,
                     linear, mlp, rmsnorm, routed_matmul)
from .mamba2 import init_mamba2, init_mamba2_state, mamba2_mixer
from .mla import init_mla, init_mla_cache, mla_attention
from .moe import init_moe, moe_layer
from .rwkv6 import init_rwkv6, init_rwkv6_state, rwkv6_block

__all__ = ["init_params", "forward", "loss_fn", "init_decode_state",
           "prefill", "decode_step", "param_count"]


# ---------------------------------------------------------------------------
# per-block init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if kind in ("attn", "enc"):
        att = (init_mla(ks[0], cfg) if (cfg.use_mla and kind == "attn")
               else init_attention(ks[0], cfg))
        return {"ln1": init_norm(d, cfg.param_dtype), "attn": att,
                "ln2": init_norm(d, cfg.param_dtype),
                "mlp": init_mlp(ks[1], d, cfg.d_ff, mlp_type=cfg.mlp_type,
                                dtype=cfg.param_dtype)}
    if kind == "moe":
        att = (init_mla(ks[0], cfg) if cfg.use_mla
               else init_attention(ks[0], cfg))
        return {"ln1": init_norm(d, cfg.param_dtype), "attn": att,
                "ln2": init_norm(d, cfg.param_dtype),
                "moe": init_moe(ks[1], cfg)}
    if kind == "mamba2":
        return {"ln": init_norm(d, cfg.param_dtype),
                "mixer": init_mamba2(ks[0], cfg)}
    if kind == "rwkv6":
        return init_rwkv6(ks[0], cfg)
    if kind == "zamba_super":
        inner = jax.vmap(lambda k: _init_block(k, cfg, "mamba2"))(
            jax.random.split(ks[0], cfg.shared_attn_every))
        return {"mamba": inner,
                "in_proj": {"w": (jax.random.normal(ks[1], (2 * d, d)) /
                                  math.sqrt(2 * d)).astype(cfg.param_dtype)}}
    if kind == "dec_cross":
        return {"ln1": init_norm(d, cfg.param_dtype),
                "attn": init_attention(ks[0], cfg),
                "ln_x": init_norm(d, cfg.param_dtype),
                "xattn": init_attention(ks[1], cfg),
                "ln2": init_norm(d, cfg.param_dtype),
                "mlp": init_mlp(ks[2], d, cfg.d_ff, mlp_type=cfg.mlp_type,
                                dtype=cfg.param_dtype)}
    raise ValueError(kind)


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    params: dict = {"embed": init_embedding(ks[0], cfg.vocab, cfg.d_model,
                                            cfg.param_dtype),
                    "final_norm": init_norm(cfg.d_model, cfg.param_dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab)) * 0.02
                  ).astype(cfg.param_dtype)}
    segs = []
    for i, (kind, repeat) in enumerate(cfg.segments()):
        seg_keys = jax.random.split(jax.random.fold_in(ks[2], i), repeat)
        segs.append(jax.vmap(lambda k, kd=kind: _init_block(k, cfg, kd))(
            seg_keys))
    params["segments"] = segs
    if cfg.family == "hybrid":
        params["shared_attn"] = {
            "ln1": init_norm(cfg.d_model, cfg.param_dtype),
            "attn": init_attention(ks[3], cfg),
            "ln2": init_norm(cfg.d_model, cfg.param_dtype),
            "mlp": init_mlp(ks[4], cfg.d_model, cfg.d_ff,
                            mlp_type=cfg.mlp_type, dtype=cfg.param_dtype)}
    if cfg.family == "audio":
        enc_keys = jax.random.split(ks[5], cfg.n_enc_layers)
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: _init_block(k, cfg, "enc"))(enc_keys),
            "norm": init_norm(cfg.d_model, cfg.param_dtype)}
    if cfg.family == "vlm":
        params["vision_proj"] = {
            "w": (jax.random.normal(ks[6], (cfg.d_model, cfg.d_model)) /
                  math.sqrt(cfg.d_model)).astype(cfg.param_dtype)}
    return params


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# per-block apply — returns (x, new_cache, aux, rows): rows is an MoE block's
# token-slots per expert (E,), None for every other block
# ---------------------------------------------------------------------------

def _shared_attn_block(shared_p, in_proj, x, x0, ctx, cache):
    cat = jnp.concatenate([x, x0], axis=-1)
    u = routed_matmul(cat, ctx.cast(in_proj["w"]), ctx)
    a, new_cache = attention(shared_p["attn"], rmsnorm(shared_p["ln1"], u),
                             ctx, cache=cache)
    u = u + a
    u = u + mlp(shared_p["mlp"], rmsnorm(shared_p["ln2"], u), ctx)
    return x + u, new_cache


def _apply_block(kind: str, p: dict, x, ctx: Ctx, cache, *, shared=None,
                 x0=None, enc_out=None):
    cfg = ctx.cfg
    zero = jnp.zeros((), jnp.float32)
    if kind in ("attn", "enc"):
        if cfg.use_mla and kind == "attn":
            a, nc = mla_attention(p["attn"], rmsnorm(p["ln1"], x), ctx,
                                  cache=cache)
        else:
            a, nc = attention(p["attn"], rmsnorm(p["ln1"], x), ctx,
                              causal=(kind == "attn"), cache=cache,
                              use_rope=(cfg.family != "audio"))
        x = x + a
        x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), ctx)
        return x, nc, zero, None
    if kind == "moe":
        if cfg.use_mla:
            a, nc = mla_attention(p["attn"], rmsnorm(p["ln1"], x), ctx,
                                  cache=cache)
        else:
            a, nc = attention(p["attn"], rmsnorm(p["ln1"], x), ctx,
                              cache=cache)
        x = x + a
        m, aux, rows = moe_layer(p["moe"], rmsnorm(p["ln2"], x), ctx)
        return x + m, nc, aux, rows
    if kind == "mamba2":
        m, ns = mamba2_mixer(p["mixer"], rmsnorm(p["ln"], x), ctx,
                             state=cache)
        return x + m, ns, zero, None
    if kind == "rwkv6":
        y, ns = rwkv6_block(p, x, ctx, state=cache)
        return y, ns, zero, None
    if kind == "zamba_super":
        mamba_cache = cache["mamba"] if cache is not None else None

        def inner(carry, xs):
            h = carry
            pp = xs[0] if cache is not None else xs
            cc = xs[1] if cache is not None else None
            h, nc2, _, _ = _apply_block("mamba2", pp, h, ctx, cc)
            return h, nc2

        xs = (p["mamba"], mamba_cache) if cache is not None else p["mamba"]
        x, new_mamba = jax.lax.scan(inner, x, xs)
        attn_cache = cache["attn"] if cache is not None else None
        x, new_attn = _shared_attn_block(shared, p["in_proj"], x, x0, ctx,
                                         attn_cache)
        nc = ({"mamba": new_mamba, "attn": new_attn}
              if cache is not None else None)
        return x, nc, zero, None
    if kind == "dec_cross":
        a, nc = attention(p["attn"], rmsnorm(p["ln1"], x), ctx, cache=cache,
                          use_rope=False)
        x = x + a
        c, _ = attention(p["xattn"], rmsnorm(p["ln_x"], x), ctx,
                         kv_x=enc_out, causal=False, use_rope=False)
        x = x + c
        x = x + mlp(p["mlp"], rmsnorm(p["ln2"], x), ctx)
        return x, nc, zero, None
    raise ValueError(kind)


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _nest_factors(repeat: int) -> tuple[int, int]:
    """Factor repeat = r1·r2 minimising r1+r2 (nested-scan remat grouping)."""
    best = (1, repeat)
    for a in range(2, int(math.isqrt(repeat)) + 1):
        if repeat % a == 0:
            best = (repeat // a, a)
    return best


def _scan_stack(body, carry, xs, repeat: int, cfg: ModelConfig):
    """Scan ``body`` over a layer stack with the configured remat scheme.

    remat="nested": two-level scan — outer body is checkpointed, so only
    ⌈repeat/r2⌉ inter-layer carries survive to the backward pass instead of
    ``repeat`` (the dominant activation-memory term at depth; §Perf).
    """
    if cfg.remat == "nested" and repeat >= 8:
        r1, r2 = _nest_factors(repeat)
        if r1 > 1 and r2 > 1:
            xs2 = jax.tree.map(
                lambda t: t.reshape(r1, r2, *t.shape[1:]), xs)
            inner_body = jax.checkpoint(body)

            @jax.checkpoint
            def outer(c, xs_grp):
                return jax.lax.scan(inner_body, c, xs_grp)

            carry, ys = jax.lax.scan(outer, carry, xs2)
            ys = jax.tree.map(
                lambda t: t.reshape(repeat, *t.shape[2:]), ys) \
                if ys is not None else None
            return carry, ys
    return jax.lax.scan(_maybe_remat(body, cfg), carry, xs)


#: an MoE layer's expert stacks, ``(E, d, f)`` a layer: the grouped gemm's
#: weights
_EXPERT_STACKS = ("wg", "wu", "wd")


def _routed_weight(path, stack) -> bool:
    """Whether a routed matmul consumes this stacked leaf: a linear's 2-D
    weight (``w``, stacked ``(L, k, n)``) or an MoE layer's expert stack
    (stacked ``(L, E, k, n)``)."""
    name = getattr(path[-1], "key", None)
    return ((name == "w" and stack.ndim == 3)
            or (name in _EXPERT_STACKS and stack.ndim == 4))


def _layer_params(seg_p, i):
    """Layer ``i`` of a segment's stacked params: each routed weight a
    :class:`LayerSlice` of its stack, every other leaf sliced out."""
    return jax.tree_util.tree_map_with_path(
        lambda path, t: (LayerSlice(t, i) if _routed_weight(path, t)
                         else jax.lax.dynamic_index_in_dim(t, i,
                                                           keepdims=False)),
        seg_p)


def _run_segments(params, x, ctx: Ctx, caches=None, *, x0=None,
                  enc_out=None):
    """Scan every segment; returns (x, new_caches|None, aux_sum, rows):
    ``rows`` holds each MoE layer's token-slots per expert, ``(L_moe, E)``
    int32 over the MoE segments in order, or None without one.  A routed
    config scans the layer index with the params closed over (module
    docstring); any other scans the stacked params."""
    cfg = ctx.cfg
    in_place = ctx.routes_gemm(x)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    rows = []
    shared = params.get("shared_attn")
    for si, (kind, repeat) in enumerate(cfg.segments()):
        seg_p = params["segments"][si]
        seg_c = caches[si] if caches is not None else None

        def body(carry, xs, kind=kind, seg_p=seg_p):
            h, aux = carry
            pp, cc = xs
            if in_place:
                pp = _layer_params(seg_p, pp)
            h, nc, a, r = _apply_block(kind, pp, h, ctx, cc, shared=shared,
                                       x0=x0, enc_out=enc_out)
            # inter-block activation layout (SP shards seq here) — this is
            # also the layout of the saved scan carries
            h = ctx.cons(h, "batch", "seq", "embed")
            return (h, aux + a), (nc, r)

        xs = (jnp.arange(repeat) if in_place else seg_p, seg_c)
        (x, aux_total), (seg_nc, seg_rows) = _scan_stack(
            body, (x, aux_total), xs, repeat, cfg)
        if caches is not None:
            new_caches.append(seg_nc)
        if seg_rows is not None:
            rows.append(seg_rows)
    rows = jnp.concatenate(rows) if rows else None
    return x, new_caches, aux_total, rows


# ---------------------------------------------------------------------------
# sinusoidal positions (whisper — no RoPE)
# ---------------------------------------------------------------------------

def _sinusoid(seq: int, d: int, offset=0):
    pos = offset + jnp.arange(seq)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2).astype(jnp.float32) *
                  (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d))
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def _run_encoder(params, frames, ctx: Ctx):
    """Whisper encoder over stubbed frame embeddings (B, enc_seq, D)."""
    x = frames.astype(ctx.cfg.compute_dtype)
    x = x + _sinusoid(x.shape[1], x.shape[2]).astype(x.dtype)[None]

    def body(h, pp):
        h, _, _, _ = _apply_block("enc", pp, h, ctx, None)
        return h, None

    x, _ = jax.lax.scan(_maybe_remat(body, ctx.cfg),
                        x, params["encoder"]["blocks"])  # unit: 'enc'
    return rmsnorm(params["encoder"]["norm"], x)


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, ctx: Ctx):
    cfg = ctx.cfg
    x = embed(params["embed"], batch["tokens"], ctx)
    if cfg.family == "vlm":
        vis = routed_matmul(batch["vision"].astype(x.dtype),
                            ctx.cast(params["vision_proj"]["w"]), ctx)
        x = jnp.concatenate([vis, x], axis=1)
        x = ctx.cons(x, "batch", "seq", "embed")
    if cfg.family == "audio":
        x = x + _sinusoid(x.shape[1], x.shape[2]).astype(x.dtype)[None]
    return x


def _logits(params, x, ctx: Ctx):
    x = rmsnorm(params["final_norm"], x)
    if "lm_head" in params:
        w = ctx.cast(params["lm_head"]["w"])
    else:
        w = ctx.cast(params["embed"]["table"]).T
    with jax.named_scope("lm_head"):
        logits = routed_matmul(x, w, ctx)
    return ctx.cons(logits, "batch", None, "vocab")


def forward(params, batch, cfg: ModelConfig, *, mesh=None, rules=None,
            runtime=None):
    """batch: {tokens (B,S); [frames|vision]} → (logits, aux).
    ``runtime`` — AdsalaRuntime serving the routed matmuls' knob decisions
    when the config routes (None → the process-global runtime)."""
    from .sharding import DEFAULT_RULES
    ctx = Ctx(cfg, mesh, rules or DEFAULT_RULES, runtime)
    x = _embed_inputs(params, batch, ctx)
    enc_out = (_run_encoder(params, batch["frames"], ctx)
               if cfg.family == "audio" else None)
    x, _, aux, _ = _run_segments(params, x, ctx, x0=x, enc_out=enc_out)
    return _logits(params, x, ctx), aux


def loss_fn(params, batch, cfg: ModelConfig, *, mesh=None, rules=None,
            runtime=None, moe_aux_coef: float = 0.01):
    from .sharding import DEFAULT_RULES
    from .layers import chunked_cross_entropy
    ctx = Ctx(cfg, mesh, rules or DEFAULT_RULES, runtime)
    x = _embed_inputs(params, batch, ctx)
    enc_out = (_run_encoder(params, batch["frames"], ctx)
               if cfg.family == "audio" else None)
    x, _, aux, _ = _run_segments(params, x, ctx, x0=x, enc_out=enc_out)
    labels = batch["labels"]
    if cfg.family == "vlm":   # vision prefix carries no LM loss
        pad = jnp.full(batch["vision"].shape[:2], -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    x = rmsnorm(params["final_norm"], x)
    w = (ctx.cast(params["lm_head"]["w"]) if "lm_head" in params
         else ctx.cast(params["embed"]["table"]).T)
    if cfg.ce_chunk:
        ce = chunked_cross_entropy(x, w, labels, chunk=cfg.ce_chunk)
    else:
        logits = ctx.cons(x @ w, "batch", "seq", "vocab")
        ce = cross_entropy(logits, labels)
    return ce + moe_aux_coef * aux, {"ce": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# serving: decode state, prefill, decode_step
# ---------------------------------------------------------------------------

def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      dtype):
    hd = cfg.hd()
    kv_cache = lambda: {"k": jnp.zeros((batch, max_len, cfg.kv_heads, hd),
                                       dtype),
                        "v": jnp.zeros((batch, max_len, cfg.kv_heads, hd),
                                       dtype),
                        "len": jnp.zeros((), jnp.int32)}
    if kind in ("attn", "moe", "dec_cross", "enc"):
        return (init_mla_cache(cfg, batch, max_len, dtype)
                if (cfg.use_mla and kind in ("attn", "moe")) else kv_cache())
    if kind == "mamba2":
        return init_mamba2_state(cfg, batch, dtype)
    if kind == "rwkv6":
        return init_rwkv6_state(cfg, batch, dtype)
    if kind == "zamba_super":
        inner = init_mamba2_state(cfg, batch, dtype)
        stacked = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (cfg.shared_attn_every,) + t.shape),
            inner)
        return {"mamba": stacked, "attn": kv_cache()}
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> list:
    caches = []
    for kind, repeat in cfg.segments():
        one = _init_block_cache(cfg, kind, batch, max_len, dtype)
        caches.append(jax.tree.map(
            lambda t: jnp.broadcast_to(t, (repeat,) + t.shape).copy(), one))
    return caches


def prefill(params, batch, caches, cfg: ModelConfig, *, mesh=None,
            rules=None, runtime=None, return_rows: bool = False):
    """Run the prompt through the model filling caches.
    Returns (last-token logits, new caches), and with ``return_rows`` also
    each MoE layer's token-slots per expert, ``(L_moe, E)`` int32 (None for
    a model without MoE layers)."""
    from .sharding import DEFAULT_RULES
    ctx = Ctx(cfg, mesh, rules or DEFAULT_RULES, runtime)
    x = _embed_inputs(params, batch, ctx)
    enc_out = (_run_encoder(params, batch["frames"], ctx)
               if cfg.family == "audio" else None)
    x, new_caches, _, rows = _run_segments(params, x, ctx, caches=caches,
                                           x0=x, enc_out=enc_out)
    logits = _logits(params, x[:, -1:], ctx)
    return (logits, new_caches, rows) if return_rows else (logits,
                                                           new_caches)


def decode_step(params, token, caches, cfg: ModelConfig, *, mesh=None,
                rules=None, runtime=None, enc_out=None, x0=None, pos=0):
    """One-token step. token: (B, 1) int32 → (logits (B,1,V), new caches).
    ``pos`` — absolute position (whisper sinusoidal embedding offset)."""
    from .sharding import DEFAULT_RULES
    ctx = Ctx(cfg, mesh, rules or DEFAULT_RULES, runtime)
    x = embed(params["embed"], token, ctx)
    if cfg.family == "audio" and enc_out is None:
        raise ValueError("whisper decode needs enc_out from prefill")
    if cfg.family == "audio":
        x = x + _sinusoid(1, x.shape[2], offset=pos).astype(x.dtype)[None]
    x0 = x if x0 is None else x0
    x, new_caches, _, _ = _run_segments(params, x, ctx, caches=caches,
                                        x0=x0, enc_out=enc_out)
    return _logits(params, x, ctx), new_caches
