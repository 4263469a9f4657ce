"""Multi-head Latent Attention (DeepSeek-V2) — low-rank-compressed KV cache.

Two execution forms, as in production DeepSeek serving:

  * prefill/train — the latent c_kv is expanded through W_kb/W_vb to full
    per-head K/V and runs through blockwise flash attention (MXU-dense).
  * decode — the *absorbed* form: q_nope is folded through W_kb so scores
    are taken directly against the (T, kv_lora) latent cache, and the
    attention context is expanded through W_vb only once per step.  The KV
    cache holds kv_lora + qk_rope floats/token — 576 vs. 2·H·192 = 6144 for
    an equivalent GQA cache (the paper-V2 compression claim).

With ``cfg.rope_scaling`` (YaRN, as DeepSeek-V2 publishes it) both forms
rotate with YaRN's blended frequencies and multiply the softmax scale
``(qk_nope + qk_rope)^-0.5`` by ``mscale(factor, mscale_all_dim)²``.  The
whole layer runs under the ``mla_attn`` name scope.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import Ctx, LayerCache, init_linear, init_norm, linear, \
    rmsnorm, rope, flash_attention, weight_array, yarn_softmax_factor

__all__ = ["init_mla", "mla_attention", "init_mla_cache"]


def init_mla(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    h, nope, rp, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, h * (nope + rp), dtype=cfg.param_dtype),
        "wkv_a": init_linear(ks[1], d, cfg.kv_lora + rp,
                             dtype=cfg.param_dtype),
        "kv_norm": init_norm(cfg.kv_lora, cfg.param_dtype),
        "wkv_b": init_linear(ks[2], cfg.kv_lora, h * (nope + vd),
                             dtype=cfg.param_dtype),
        "wo": init_linear(ks[3], h * vd, d, dtype=cfg.param_dtype),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def _project_q(p, x, cfg, ctx):
    B, S, _ = x.shape
    h, nope, rp = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = linear(p["wq"], x, ctx).reshape(B, S, h, nope + rp)
    # head-parallel region — seq unsharded here (SP boundary)
    q = ctx.cons(q, "batch", None, "heads", None)
    return q[..., :nope], q[..., nope:]


def mla_attention(p: dict, x, ctx: Ctx, *,
                  cache: LayerCache | None = None):
    """Returns (out, new_cache|None); ``cache`` is a :class:`LayerCache`
    of ``c_kv``, ``k_rope`` and ``len``."""
    with jax.named_scope("mla_attn"):
        return _mla(p, x, ctx, cache)


def _mla(p: dict, x, ctx: Ctx, cache):
    cfg = ctx.cfg
    B, S, _ = x.shape
    h, nope, rp, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.v_head_dim
    scale = 1.0 / math.sqrt(nope + rp)
    flash_scale = None            # flash_attention's own 1/√(nope+rp)
    ys = cfg.rope_scaling
    if ys is not None:
        scale = flash_scale = scale * yarn_softmax_factor(ys)

    kv_a = linear(p["wkv_a"], x, ctx)
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., :cfg.kv_lora])
    k_rope_new = kv_a[..., cfg.kv_lora:]                     # (B,S,rp) 1 head
    q_nope, q_rope = _project_q(p, x, cfg, ctx)

    if cache is None:
        positions = jnp.arange(S)[None, :]
        q_rope = rope(q_rope, positions, theta=cfg.rope_theta, scaling=ys)
        k_rope = rope(k_rope_new[:, :, None, :], positions,
                      theta=cfg.rope_theta, scaling=ys)[:, :, 0]
        # expand latent → per-head K/V, dense attention (prefill/train form)
        kv = linear(p["wkv_b"], c_kv, ctx).reshape(B, S, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, h, rp))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(q, k, v, causal=True,
                              q_chunk=cfg.attn_q_chunk,
                              k_chunk=cfg.attn_k_chunk,
                              causal_skip=cfg.causal_skip,
                              unroll=cfg.unroll_attn, scale=flash_scale)
        out = linear(p["wo"], out.reshape(B, S, h * vd), ctx,
                     out_logical="embed")
        return out, None

    # ---- cached path: update the latent cache, then attend ------------------
    start = cache.len
    positions = start + jnp.arange(S)[None, :]
    q_rope = rope(q_rope, positions, theta=cfg.rope_theta, scaling=ys)
    k_rope_new = rope(k_rope_new[:, :, None, :], positions,
                      theta=cfg.rope_theta, scaling=ys)[:, :, 0]
    new_cache = cache.write(start, c_kv=c_kv, k_rope=k_rope_new)
    c, kr = new_cache["c_kv"], new_cache["k_rope"]

    if S > 1:
        # prefill: expand latent → per-head K/V, blockwise flash (the
        # absorbed form would materialise S×T scores — 8.6 GB/dev at 32k)
        T = c.shape[1]
        kv = linear(p["wkv_b"], ctx.cast(c), ctx).reshape(B, T, h, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(ctx.cast(kr)[:, :, None, :],
                                      (B, T, h, rp))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(q, k, v, causal=True, q_offset=start,
                              q_chunk=cfg.attn_q_chunk,
                              k_chunk=cfg.attn_k_chunk,
                              kv_valid_len=jnp.full((B,), start + S),
                              unroll=cfg.unroll_attn, scale=flash_scale)
        out = linear(p["wo"], out.reshape(B, S, h * vd), ctx,
                     out_logical="embed")
        return out, new_cache

    # ---- decode: absorbed form over the latent cache -----------------------
    w_b = ctx.cast(weight_array(p["wkv_b"]["w"])).reshape(cfg.kv_lora, h,
                                                          nope + vd)
    w_kb, w_vb = w_b[..., :nope], w_b[..., nope:]
    # absorb: q_c[b,s,h,l] = Σ_n q_nope·W_kb[l,h,n]
    q_c = jnp.einsum("bshn,lhn->bshl", q_nope, w_kb)
    scores = (jnp.einsum("bshl,btl->bsht", q_c, ctx.cast(c)) +
              jnp.einsum("bshr,btr->bsht", q_rope, ctx.cast(kr))) * scale
    T = c.shape[1]
    k_pos = jnp.arange(T)[None, None, None, :]
    valid = k_pos < (start + S)
    q_pos = (positions[:, :, None, None])
    causal_ok = k_pos <= q_pos
    scores = jnp.where(valid & causal_ok, scores.astype(jnp.float32), -1e30)
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx_c = jnp.einsum("bsht,btl->bshl", attn, ctx.cast(c))
    out = jnp.einsum("bshl,lhv->bshv", ctx_c, w_vb)
    out = linear(p["wo"], out.reshape(B, S, h * vd), ctx, out_logical="embed")
    return out, new_cache
