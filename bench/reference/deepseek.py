"""Plain reference of DeepSeek-V2 (the configuration's layers) in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: token
embedding; per layer RMSNorm, multi-head latent attention without q-LoRA
(queries ``h @ wq``; the latent ``c = RMSNorm(h @ wkv_a[:, :lora])`` and a
single rotary key ``h @ wkv_a[:, lora:]``; keys and values ``c @ wkv_b``;
YaRN rotary positions on the rotary parts, half-split pairing as the
configuration's ``assumed`` says; causal softmax at ``(nope+rope)^-0.5 ·
mscale(factor, mscale_all_dim)²``; output projection), residual, RMSNorm,
then a dense SwiGLU FFN (the first ``first_k_dense_replace`` layers) or the
MoE FFN (softmax router over every expert, greedy top-k, weights not
renormalised unless ``norm_topk_prob``, each routed expert applied to
exactly the tokens routed to it and weighted, plus the shared experts as
one SwiGLU of ``n_shared · moe_intermediate`` width), residual; final
RMSNorm and the output head.  No kernels, no cache, no batching tricks, no
capacity.

It regenerates the weights from the seed itself (``bench/deepseek.py``), one
layer at a time, attends one sequence at a time and runs each expert over
its own tokens, so that it fits beside nothing.

The control (``lower=True``) is this same reference with every weight
product taken in float8 (e4m3, one scale per weight and one per activation
row): the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench import common, deepseek
from bench.reference.qwen import _q8

#: rows of an expert's gather are padded up to a power of two, at least this
_MIN_ROWS = 64


def _mm(x, w, lower: bool):
    import jax.numpy as jnp
    if lower:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_tables(config: dict, T: int):
    """(cos, sin) of shape (T, rope/2) and the softmax scale, float64,
    from the published YaRN formulas (DeepSeek-V2's rotary embedding)."""
    ys = config["rope_scaling"]
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    factor = float(ys["factor"])
    orig = ys["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = inter * (1.0 - mask) + extra * mask
    mult = _mscale(factor, ys["mscale"]) / _mscale(factor, ys["mscale_all_dim"])
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    m = _mscale(factor, ys["mscale_all_dim"])
    scale = (config["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return np.cos(ang) * mult, np.sin(ang) * mult, scale


def _rope(x, cos, sin):
    """x: (T, ..., D) with positions 0..T-1 on the first axis."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _flat(config: dict) -> tuple:
    return tuple((k, v) for k, v in config.items()
                 if isinstance(v, (int, float, str, bool)))


@functools.lru_cache(maxsize=None)
def _attn_fn(key_cfg: tuple, lower: bool):
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[key_cfg]
    s = deepseek.dims(config)
    h, nope, rp, vd, lora = s["h"], s["nope"], s["rope"], s["vd"], s["lora"]
    eps = config["rms_norm_eps"]

    def seq(x, w, cos, sin, scale):
        """One sequence x: (T, d) → attention output (T, d)."""
        T = x.shape[0]
        hn = _rms(x, w["ln1"], eps)
        q = _mm(hn, w["wq"], lower).reshape(T, h, nope + rp)
        kv_a = _mm(hn, w["wkv_a"], lower)
        c = _rms(kv_a[:, :lora], w["kv_norm"], eps)
        k_rope = _rope(kv_a[:, lora:], cos, sin)                  # (T, rp)
        kv = _mm(c, w["wkv_b"], lower).reshape(T, h, nope + vd)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)],
                            -1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[:, None, :], (T, h, rp))], -1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * scale
        sc = jnp.where(np.tril(np.ones((T, T), bool))[None], sc, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                       kv[..., nope:])
        return _mm(a.reshape(T, h * vd), w["wo"], lower)

    def f(x, key, index, cos, sin, scale):
        w = {n: deepseek.leaf(config, key, n, index).astype(jnp.float32)
             for n in deepseek.ATTN_LEAVES}
        out = jax.lax.map(lambda xs: seq(xs, w, cos, sin, scale), x)
        return x + out

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _dense_fn(key_cfg: tuple, lower: bool):
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[key_cfg]

    def f(x, key, index):
        w = {n: deepseek.leaf(config, key, n, index).astype(jnp.float32)
             for n in ("ln2",) + deepseek.DENSE_LEAVES}
        hn = _rms(x, w["ln2"], config["rms_norm_eps"])
        return x + _mm(jax.nn.silu(_mm(hn, w["wg"], lower))
                       * _mm(hn, w["wu"], lower), w["wd"], lower)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _route_fn(key_cfg: tuple, lower: bool):
    """(normed input, shared experts' output, top-k weights, top-k ids)."""
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[key_cfg]
    K = config["num_experts_per_tok"]

    def f(x, key, index):
        w = {n: deepseek.leaf(config, key, n, index).astype(jnp.float32)
             for n in ("ln2", "router", "swg", "swu", "swd")}
        hn = _rms(x, w["ln2"], config["rms_norm_eps"])
        probs = jax.nn.softmax(_mm(hn, w["router"], lower), -1)
        top_p, top_e = jax.lax.top_k(probs, K)
        if config["norm_topk_prob"]:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        shared = _mm(jax.nn.silu(_mm(hn, w["swg"], lower))
                     * _mm(hn, w["swu"], lower), w["swd"], lower)
        return hn, shared, top_p, top_e

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _experts_fn(key_cfg: tuple):
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[key_cfg]

    def f(key, index):
        return {n: deepseek.leaf(config, key, n, index).astype(jnp.float32)
                for n in ("ewg", "ewu", "ewd")}

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _expert_apply(lower: bool):
    """out[idx[r]] += wt[r] · expert(hn[idx[r]]) for the rows routed to
    one expert (padded rows carry weight 0)."""
    import jax

    def f(out, hn, idx, wt, wg, wu, wd):
        xe = hn[idx]
        y = _mm(jax.nn.silu(_mm(xe, wg, lower)) * _mm(xe, wu, lower), wd,
                lower)
        return out.at[idx].add(wt[:, None] * y)

    return jax.jit(f)


def _moe(x, key, index, key_cfg, lower, rows: list):
    """One MoE FFN over x (N, T, d): every expert over its own tokens.
    Appends the layer's token-slots per sequence and expert, (N, E), to
    ``rows``."""
    import jax.numpy as jnp
    N, T, d = x.shape
    hn, shared, top_p, top_e = _route_fn(key_cfg, lower)(x, key, index)
    hn, top_p = hn.reshape(N * T, d), np.asarray(top_p).reshape(N * T, -1)
    top_e = np.asarray(top_e).reshape(N * T, -1)
    ew = _experts_fn(key_cfg)(key, index)
    E = ew["ewg"].shape[0]
    rows.append(np.stack([np.bincount(t.reshape(-1), minlength=E)
                          for t in top_e.reshape(N, -1)]))
    out = jnp.zeros_like(hn)
    apply = _expert_apply(lower)
    for e in range(E):
        tok, slot = np.nonzero(top_e == e)
        if not tok.size:
            continue
        rows = max(_MIN_ROWS, 1 << int(tok.size - 1).bit_length())
        idx = np.zeros(rows, np.int32)
        wt = np.zeros(rows, np.float32)
        idx[:tok.size], wt[:tok.size] = tok, top_p[tok, slot]
        out = apply(out, hn, jnp.asarray(idx), jnp.asarray(wt),
                    ew["ewg"][e], ew["ewu"][e], ew["ewd"][e])
    return x + shared + out.reshape(N, T, d)


@functools.lru_cache(maxsize=None)
def _head_fn(key_cfg: tuple, lower: bool):
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[key_cfg]

    def f(x, key):
        fn = deepseek.leaf(config, key, "final_norm").astype(jnp.float32)
        head = deepseek.leaf(config, key, "lm_head").astype(jnp.float32)
        return _mm(_rms(x, fn, config["rms_norm_eps"]), head, lower)

    return jax.jit(f)


#: configurations by their flattened items (jitted functions close over them)
_CONFIGS: dict = {}


def logits_at(config: dict, seed: int, tokens: np.ndarray,
              positions: list, *, lower: bool = False, rows=None) -> list:
    """``tokens``: (N, T) ids; ``positions``: per row, the 0-based positions
    whose next-token logits are wanted.  Returns a float32 (len, V) array
    per row.  ``rows``, a list if given, receives each MoE layer's
    token-slots per sequence and expert, (N, E), in layer order."""
    import jax
    import jax.numpy as jnp
    key_cfg = _flat(config)
    _CONFIGS[key_cfg] = config
    key = common.jax_key(seed)
    s = deepseek.dims(config)
    rows = [] if rows is None else rows
    with jax.default_matmul_precision("highest"):
        cos, sin, scale = yarn_tables(config, tokens.shape[1])
        cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)
        x = jax.jit(lambda key, ids: deepseek.leaf(config, key, "embed")
                    .astype(jnp.float32)[ids])(key, jnp.asarray(tokens))
        attn = _attn_fn(key_cfg, lower)
        for i in range(s["L"]):
            x = attn(x, key, jnp.int32(i), cos, sin, jnp.float32(scale))
            if i < s["dense"]:
                x = _dense_fn(key_cfg, lower)(x, key, jnp.int32(i))
            else:
                x = _moe(x, key, jnp.int32(i), key_cfg, lower, rows)
        head = _head_fn(key_cfg, lower)
        return [np.asarray(jax.device_get(head(
            x[n, jnp.asarray(np.asarray(p, np.int32))], key)))
            for n, p in enumerate(positions)]
