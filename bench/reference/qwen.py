"""Plain reference of a dense decoder of the Qwen1.5 (Qwen2) family in
float32 ``jax.numpy``: token embedding; per layer RMSNorm, attention with
QKV bias and rotary positions (rotate-half, as published), causal softmax,
output projection, residual, RMSNorm, SwiGLU MLP, residual; final RMSNorm
and the output head.  No kernels, no cache, no batching tricks.  Every
product runs at full float32 precision (``Precision.HIGHEST``).

It regenerates the weights from the seed itself (``bench/weights.py``), one
layer at a time, and runs the whole sequence through a layer before the next
is made, so that it fits beside nothing.

The control (``lower=True``) is this same reference with every weight
product taken in float8 (e4m3, one scale per weight and one per activation
row): the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import numpy as np

from bench import common, weights

FP8_MAX = 448.0


def _q8(x, axis):
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, lower: bool):
    import jax
    import jax.numpy as jnp
    if lower:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, theta):
    """x: (N, T, H, D), positions 0..T-1."""
    import jax.numpy as jnp
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = jnp.asarray(np.arange(T)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_items: tuple, lower: bool):
    import jax
    import jax.numpy as jnp
    config = dict(cfg_items)
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    hp = jax.lax.Precision.HIGHEST

    def f(x, key, index):
        w = {k: v.astype(jnp.float32)
             for k, v in weights.layer(config, key, index).items()}
        N, T, d = x.shape
        hd = d // H
        h = _rms(x, w["ln1"], eps)
        q = (_mm(h, w["wq"], lower) + w["bq"]).reshape(N, T, H, hd)
        k = (_mm(h, w["wk"], lower) + w["bk"]).reshape(N, T, KV, hd)
        v = (_mm(h, w["wv"], lower) + w["bv"]).reshape(N, T, KV, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = H // KV
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=hp) / np.sqrt(hd)
        causal = np.tril(np.ones((T, T), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=hp)
        x = x + _mm(a.reshape(N, T, H * hd), w["wo"], lower)
        h = _rms(x, w["ln2"], eps)
        g = _mm(h, w["wg"], lower)
        u = _mm(h, w["wu"], lower)
        return x + _mm(jax.nn.silu(g) * u, w["wd"], lower)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_items: tuple, lower: bool):
    import jax
    import jax.numpy as jnp
    config = dict(cfg_items)

    def f(x, key):
        t = weights.top(config, key)
        h = _rms(x, t["final_norm"].astype(jnp.float32),
                 config["rms_norm_eps"])
        return _mm(h, t["lm_head"].astype(jnp.float32), lower)

    return jax.jit(f)


def _embed(config, key, tokens):
    import jax
    import jax.numpy as jnp

    def f(key, ids):
        return weights.top(config, key)["embed"].astype(jnp.float32)[ids]

    return jax.jit(f)(key, tokens)


def _flat(config: dict) -> tuple:
    return tuple((k, v) for k, v in config.items()
                 if isinstance(v, (int, float, str, bool)))


def logits_at(config: dict, seed: int, tokens: np.ndarray,
              positions: list, *, lower: bool = False) -> list:
    """``tokens``: (N, T) ids; ``positions``: per row, the 0-based positions
    whose next-token logits are wanted.  Returns a float32 (len, V) array
    per row."""
    import jax
    import jax.numpy as jnp
    key = common.jax_key(seed)
    items = _flat(config)
    layer = _layer_fn(items, lower)
    x = _embed(config, key, jnp.asarray(tokens))
    for i in range(config["num_hidden_layers"]):
        x = layer(x, key, jnp.int32(i))
    rows = [x[n, jnp.asarray(np.asarray(p, np.int32))]
            for n, p in enumerate(positions)]
    head = _head_fn(items, lower)
    out = [np.asarray(jax.device_get(head(r, key))) for r in rows]
    return out
