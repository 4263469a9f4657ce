"""Shared neural-net layers: norms, RoPE, embeddings, linears (optionally
routed through the ADSALA-tuned Pallas GEMM), SwiGLU/GELU MLPs, and
memory-bounded blockwise (flash-style) attention with GQA/MQA support.

All modules are pure functions over param dicts.  ``Ctx`` threads the model
config, mesh and logical sharding rules through the stack.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from .sharding import ShardingRules, DEFAULT_RULES, constrain

__all__ = ["Ctx", "LayerSlice", "LayerCache", "weight_array",
           "routed_operand", "init_linear", "linear", "routed_matmul",
           "init_norm", "rmsnorm", "init_embedding", "embed", "rope", "yarn",
           "yarn_softmax_factor", "init_attention",
           "attention", "init_mlp", "mlp", "cross_entropy",
           "flash_attention"]


@dataclasses.dataclass
class Ctx:
    cfg: ModelConfig
    mesh: object = None               # jax.sharding.Mesh | None
    rules: ShardingRules = DEFAULT_RULES
    runtime: object = None            # AdsalaRuntime | None (None → global)

    def cast(self, x):
        """``x`` in the compute dtype.  A :class:`LayerSlice` already in it
        stays a view of its stack; one that needs a cast is sliced out."""
        if isinstance(x, LayerSlice):
            if x.dtype == jnp.dtype(self.cfg.compute_dtype):
                return x
            x = x.array()
        return x.astype(self.cfg.compute_dtype)

    def cons(self, x, *names):
        if self.mesh is None:
            return x
        return constrain(x, self.rules, self.mesh, *names)

    def routes_gemm(self, x) -> bool:
        """Whether a dense matmul on ``x`` goes through the tuned runtime:
        opt-in via config, single-host only (the sharded path keeps jnp
        matmuls so GSPMD can partition them)."""
        return (self.cfg.use_pallas_gemm and self.mesh is None
                and x.ndim >= 2)


class LayerSlice:
    """Layer ``index`` of a segment's stacked weight ``stack`` ``(L, ...)``,
    left unsliced: a routed matmul hands the stack and the index to its
    kernel, which reads the layer's tiles in place.  ``shape``, ``ndim``
    and ``dtype`` are the layer's; :meth:`array` slices it out for any other
    consumer."""

    __slots__ = ("stack", "index")

    def __init__(self, stack, index):
        self.stack, self.index = stack, index

    @property
    def shape(self):
        return self.stack.shape[1:]

    @property
    def ndim(self):
        return self.stack.ndim - 1

    @property
    def dtype(self):
        return self.stack.dtype

    def array(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.index,
                                            keepdims=False)


#: the positions in one row tile of a TPU layout (the second-minor dim of
#: an (8, 128) tile): :meth:`LayerCache.write` writes a single row as its
#: whole tile
ROW_TILE = 8


class LayerCache:
    """Layer ``index`` of a segment's decode cache ``stack``, which the layer
    scan carries whole: ``{name: (L, B, T, ...), "len": (L,)}``.  A block
    reads the layer's length with :attr:`len`, writes its new rows with
    :meth:`write` (one ``dynamic_update_slice`` into each stack, in place),
    and reads the layer's rows back from the updated view by name, a
    ``dynamic_index_in_dim`` that XLA can fuse into the consumer."""

    __slots__ = ("stack", "index")

    def __init__(self, stack: dict, index):
        self.stack, self.index = stack, index

    @property
    def len(self):
        return jax.lax.dynamic_index_in_dim(self.stack["len"], self.index,
                                            keepdims=False)

    def __getitem__(self, name: str):
        return jax.lax.dynamic_index_in_dim(self.stack[name], self.index,
                                            keepdims=False)

    def write(self, start, **rows) -> "LayerCache":
        """The view after writing each leaf's new rows ``(B, S, ...)`` at
        positions ``start .. start + S`` of this layer, one
        ``dynamic_update_slice`` into its stack, and setting the layer's
        length to ``start + S``.

        A single row (a decode step) goes in as the whole row tile that
        holds it, :data:`ROW_TILE` positions whose other rows are read and
        written back unchanged.  Written alone, a row makes XLA give the
        carried stack another layout than the one it arrives in, and the
        layer loop then copies the whole cache into that layout and back
        out on every step (a TPU v5e compile of qwen1.5-4b's decode
        step)."""
        stack = dict(self.stack)
        for name, new in rows.items():
            t = stack[name]
            new = new.astype(t.dtype)[None]
            S, T = new.shape[2], t.shape[2]
            at = start
            if S == 1 and T >= ROW_TILE:
                at = jnp.clip(start // ROW_TILE * ROW_TILE, 0, T - ROW_TILE)
                tile = jax.lax.dynamic_slice(
                    t, (self.index, 0, at) + (0,) * (t.ndim - 3),
                    new.shape[:2] + (ROW_TILE,) + new.shape[3:])
                pos = jnp.arange(ROW_TILE).reshape(
                    (1, 1, ROW_TILE) + (1,) * (t.ndim - 3))
                # the row where dynamic_update_slice would put it: clamped
                # into the cache
                row = jnp.minimum(start, T - 1) - at
                new = jnp.where(pos == row, new, tile)
            stack[name] = jax.lax.dynamic_update_slice(
                t, new, (self.index, 0, at) + (0,) * (t.ndim - 3))
        stack["len"] = jax.lax.dynamic_update_index_in_dim(
            stack["len"], start + S, self.index, 0)
        return LayerCache(stack, self.index)


def runtime_of(ctx: Ctx):
    """The runtime that serves ``ctx``'s routed calls and counts its
    trace-time events (``None`` → the process-global runtime)."""
    from repro.core.runtime import global_runtime
    return ctx.runtime if ctx.runtime is not None else global_runtime()


def weight_array(w):
    """``w`` as an array: a :class:`LayerSlice` sliced out of its stack."""
    return w.array() if isinstance(w, LayerSlice) else w


def routed_operand(w, ctx: Ctx) -> tuple:
    """``(weight operand, run_op keywords)`` for a routed call on ``w``: a
    :class:`LayerSlice` passes its whole stack with ``layer=``.  Counts the
    call on the runtime's ``routed_in_place`` or ``routed_materialised``
    (at trace time, once per traced call site)."""
    in_place = isinstance(w, LayerSlice)
    runtime_of(ctx).count_routed(in_place)
    kw = {}
    if ctx.cfg.gemm_interpret is not None:
        kw["interpret"] = ctx.cfg.gemm_interpret
    if in_place:
        kw["layer"] = w.index
        return w.stack, kw
    return w, kw


#: the routed gemm's least row block: ``kernels/ops.py:gemm`` rounds bm up
#: to a multiple of it
_ROW_TILE = 128


def routed_matmul(x, w, ctx: Ctx):
    """``x @ w`` dispatched through :func:`repro.kernels.ops.run_op` — knob
    selection, decision cache, and backend keying all come from the ADSALA
    runtime carried on ``ctx`` (``None`` → the process-global runtime).

    Items of fewer rows than one row tile (a decode step's one-row
    sequences, a prefill's last-token head) fold every leading axis into
    the gemm's M: ``(..., m, d) @ (d, n)`` runs as one 2-D
    ``(prod(...)·m, d)`` gemm whose row tiles they share, so the weight is
    read once, not once per item.  Items that fill row tiles stay one
    stack against the shared weight: folding them reads it no fewer times
    when bm divides m, and XLA then lays out the ops around the 2-D result
    worse (relayout copies of q, k and v: 7% more device time in a
    qwen1.5-4b prefill of 4 x 1024 on a TPU v5e).  ``w`` may be a
    :class:`LayerSlice`, which the kernel reads in place.  The
    interpret/compiled kernel mode comes from ``cfg.gemm_interpret``
    (``None`` → the backend auto-detects the host).  Falls back to plain
    ``x @ w`` when the config does not route.
    """
    if not ctx.routes_gemm(x) or w.ndim != 2:
        return x @ weight_array(w)
    from repro.kernels import ops as kops
    w, kw = routed_operand(w, ctx)
    m, d = x.shape[-2:]
    x2 = (x.reshape(-1, d) if m < _ROW_TILE or x.ndim == 2
          else x.reshape(-1, m, d))
    y = kops.run_op("gemm", (x2, w), backend=ctx.cfg.gemm_backend,
                    runtime=ctx.runtime, **kw)
    return y.reshape(*x.shape[:-1], y.shape[-1])


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# linear / norm / embedding
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype="float32", scale: float | None = None) -> dict:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _init(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict, x, ctx: Ctx, *, out_logical: str | None = None):
    w = ctx.cast(p["w"])
    y = routed_matmul(x, w, ctx)
    if "b" in p:
        y = y + ctx.cast(p["b"])
    if out_logical is not None:
        # 'embed' outputs are inter-block activations → carry the SP seq
        # sharding; head/mlp-parallel outputs leave seq unsharded.
        seq_name = "seq" if out_logical == "embed" else None
        y = ctx.cons(y, "batch", seq_name, out_logical)
    return y


def init_norm(d: int, dtype="float32") -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(
        jnp.float32)).astype(dt)


def init_embedding(key, vocab: int, d: int, dtype="float32") -> dict:
    return {"table": _init(key, (vocab, d), 0.02, dtype)}


def embed(p: dict, ids, ctx: Ctx):
    x = ctx.cast(p["table"])[ids]
    return ctx.cons(x, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(scaling, dim: int, theta: float):
    """DeepSeek-V2's YaRN rotary embedding for rotary width ``dim``:
    ``(inverse frequencies (dim/2,), cos/sin multiplier)``.  The frequencies
    blend the interpolated ``θ^(-i/half)/factor`` and the original
    ``θ^(-i/half)`` with a linear ramp over the correction range that
    ``beta_fast``/``beta_slow`` rotations give over the original context;
    the multiplier is ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, with ``mscale(s, m) = 0.1·m·ln s + 1``."""
    half = dim // 2
    extra = 1.0 / (theta ** (np.arange(0, half) / half))
    inter = extra / scaling.factor

    def correction_dim(rotations):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    freqs = inter * (1.0 - keep) + extra * keep
    mult = (_yarn_mscale(scaling.factor, scaling.mscale)
            / _yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    return freqs, mult


def yarn_softmax_factor(scaling) -> float:
    """What YaRN multiplies the softmax scale by: ``mscale(factor,
    mscale_all_dim)²`` (1 when ``mscale_all_dim`` is 0)."""
    if not scaling.mscale_all_dim:
        return 1.0
    return _yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def rope(x, positions, *, theta: float = 1e4, scaling=None):
    """x: (..., S, H, D) rotated by ``positions`` (..., S); ``scaling`` (a
    :class:`~repro.configs.base.RopeScaling`) selects YaRN's frequencies
    and cos/sin multiplier."""
    d = x.shape[-1]
    half = d // 2
    mult = 1.0
    if scaling is None:
        freqs = 1.0 / (theta ** (np.arange(0, half) / half))
    else:
        freqs, mult = yarn(scaling, d, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise (flash-style) attention — memory-bounded for 32k+ contexts
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool, q_offset: int | jax.Array = 0,
                    q_chunk: int = 1024, k_chunk: int = 1024,
                    kv_valid_len=None, causal_skip: bool = False,
                    unroll: int = 1, scale: float | None = None):
    """Online-softmax attention over kv chunks.

    q: (B, S, H, D); k, v: (B, T, KH, D) with H = G·KH (GQA groups).
    ``q_offset`` — absolute position of q[0] (decode: cache length).
    ``kv_valid_len`` — optional (B,) number of valid cache entries.
    ``causal_skip`` — unrolled-q variant that skips fully-masked kv blocks
    (≈½ the FLOPs at long context; §Perf hillclimb knob).
    ``scale`` — the softmax scale (``None`` → ``1/√D``).

    Never materialises more than (B, Cq, H, Ck) scores.
    """
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                     # may differ from D (MLA)
    G = H // KH
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, S)
    k_chunk = min(k_chunk, T)
    nq = -(-S // q_chunk)
    nk = -(-T // k_chunk)
    Sp, Tp = nq * q_chunk, nk * k_chunk
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))

    # inputs stay low-precision; f32 only inside the chunk step (accumulators
    # and softmax) — the flash-kernel memory/precision contract.
    qc = q.reshape(B, nq, q_chunk, KH, G, D)
    kc = k.reshape(B, nk, k_chunk, KH, D)
    vc = v.reshape(B, nk, k_chunk, KH, Dv)
    NEG = jnp.float32(-1e30)

    def kv_step(carry, j, qi_block, i):
        m, l, acc = carry
        kj = jax.lax.dynamic_index_in_dim(kc, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vc, j, 1, keepdims=False)
        # scores: (B, Cq, G, KH, Ck), f32 accumulation from bf16 operands
        s = jnp.einsum("bqhgd,bkhd->bqghk", qi_block, kj,
                       preferred_element_type=jnp.float32) * scale
        q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        k_pos = j * k_chunk + jnp.arange(k_chunk)
        mask = jnp.ones((q_chunk, k_chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        mask &= (k_pos < T)[None, :]
        s = jnp.where(mask[None, :, None, None, :], s, NEG)
        if kv_valid_len is not None:
            ok = k_pos[None, :] < kv_valid_len[:, None]        # (B, Ck)
            s = jnp.where(ok[:, None, None, None, :], s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(s <= NEG * 0.5, 0.0, p)   # fully-masked-block guard
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqghk,bkhd->bqghd", p.astype(v.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    # checkpoint the kv step: its O(Cq·Ck) score/softmax intermediates are
    # recomputed in the backward pass instead of being saved per kv block
    # (flash-attention memory contract).
    kv_step_ckpt = jax.checkpoint(kv_step)

    def q_block(i):
        qi = jax.lax.dynamic_index_in_dim(qc, i, 1, keepdims=False)
        # qi: (B, Cq, KH, G, D) = b q h g d for the einsum
        init = (jnp.full((B, q_chunk, G, KH), NEG),
                jnp.zeros((B, q_chunk, G, KH)),
                jnp.zeros((B, q_chunk, G, KH, Dv)))
        if causal_skip and causal and isinstance(q_offset, int):
            # static upper bound on reachable kv blocks for this q block
            hi = min(nk, ((q_offset + (i + 1) * q_chunk - 1) // k_chunk) + 1)
            (m, l, acc), _ = jax.lax.scan(
                lambda c, j: kv_step_ckpt(c, j, qi, i), init, jnp.arange(hi),
                unroll=min(unroll, hi))
        else:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, j: kv_step_ckpt(c, j, qi, i), init, jnp.arange(nk),
                unroll=min(unroll, nk))
        out_i = acc / jnp.maximum(l[..., None], 1e-30)
        # cast before stacking across q blocks (halves the stacked buffer)
        return out_i.transpose(0, 1, 3, 2, 4).astype(q.dtype)

    if causal_skip and causal and isinstance(q_offset, int):
        outs = [q_block(i) for i in range(nq)]               # unrolled
        out = jnp.stack(outs, axis=1)
    else:
        _, out = jax.lax.scan(lambda c, i: (c, q_block(i)), None,
                              jnp.arange(nq))
        out = out.transpose(1, 0, 2, 3, 4, 5)
    # (B, nq, Cq, KH, G, Dv) → heads h = kh·G + g, matching the q projection
    out = out.reshape(B, Sp, KH * G, Dv)[:, :S]
    return out.astype(q.dtype)


def _dense_decode_attention(q, k, v, start):
    """Single-shot attention for decode (S==1): one einsum over the whole
    cache — partitions cleanly under GSPMD whether the cache is sharded on
    kv_heads or on sequence (SP fallback), unlike a scanned chunk loop.
    q: (B,S,H,D); k,v: (B,T,KH,Dk/Dv); valid positions are < start+S."""
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    # operands stay low-precision (no whole-cache f32 copies); f32 accum
    q_ = q.reshape(B, S, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqghk", q_, k,
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(T)[None, None, None, None, :]
    q_pos = (start + jnp.arange(S))[None, :, None, None, None]
    s = jnp.where(k_pos <= q_pos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqghk,bkhd->bqghd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.transpose(0, 1, 3, 2, 4).reshape(B, S, KH * G, -1)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, d_model: int | None = None,
                   cross: bool = False) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.hd()
    ks = jax.random.split(key, 5)
    p = {
        "wq": init_linear(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias,
                          dtype=cfg.param_dtype),
        "wk": init_linear(ks[1], d, cfg.kv_heads * hd, bias=cfg.qkv_bias,
                          dtype=cfg.param_dtype),
        "wv": init_linear(ks[2], d, cfg.kv_heads * hd, bias=cfg.qkv_bias,
                          dtype=cfg.param_dtype),
        "wo": init_linear(ks[3], cfg.n_heads * hd, d,
                          dtype=cfg.param_dtype,
                          scale=1.0 / math.sqrt(cfg.n_heads * hd)),
    }
    return p


def attention(p: dict, x, ctx: Ctx, *, kv_x=None, causal: bool = True,
              positions=None, cache: LayerCache | None = None,
              use_rope: bool = True):
    """GQA attention. ``cache`` (decode): a :class:`LayerCache` of ``k``,
    ``v`` ``(L, B, T, KH, D)`` and ``len`` — the new rows are written into
    it and the updated view is returned alongside the output."""
    cfg = ctx.cfg
    B, S, _ = x.shape
    hd = cfg.hd()
    kv_in = x if kv_x is None else kv_x
    with jax.named_scope("attn_qkv"):
        q = linear(p["wq"], x, ctx).reshape(B, S, cfg.n_heads, hd)
        k = linear(p["wk"], kv_in, ctx).reshape(B, kv_in.shape[1],
                                                cfg.kv_heads, hd)
        v = linear(p["wv"], kv_in, ctx).reshape(B, kv_in.shape[1],
                                                cfg.kv_heads, hd)
    # head-parallel region: seq deliberately unsharded here (under SP rules
    # this boundary is the all-gather / reduce-scatter pair).  batch_attn
    # may span ('data','model') when heads don't divide the TP axis.
    q = ctx.cons(q, "batch_attn", None, "heads", None)
    k = ctx.cons(k, "batch_attn", "kv_seq", "kv_heads", None)
    v = ctx.cons(v, "batch_attn", "kv_seq", "kv_heads", None)

    new_cache = None
    if cache is not None:
        start = cache.len                             # scalar int32
        if positions is None:
            positions = start + jnp.arange(S)[None, :]
        if use_rope:
            q = rope(q, positions, theta=cfg.rope_theta)
            k = rope(k, positions, theta=cfg.rope_theta)
        new_cache = cache.write(start, k=k, v=v)
        ck, cv = new_cache["k"], new_cache["v"]
        if S == 1:
            out = _dense_decode_attention(q, ck.astype(q.dtype),
                                          cv.astype(q.dtype), start)
        else:
            valid = jnp.full((B,), start + S)
            out = flash_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                  causal=causal, q_offset=start,
                                  q_chunk=min(cfg.attn_q_chunk, S),
                                  k_chunk=cfg.attn_k_chunk,
                                  kv_valid_len=valid,
                                  unroll=cfg.unroll_attn)
    else:
        if positions is None:
            positions = jnp.arange(S)[None, :].repeat(B, 0)
        if use_rope:
            q = rope(q, positions, theta=cfg.rope_theta)
            k = rope(k, positions, theta=cfg.rope_theta)
        out = flash_attention(q, k, v, causal=causal,
                              q_chunk=cfg.attn_q_chunk,
                              k_chunk=cfg.attn_k_chunk,
                              causal_skip=cfg.causal_skip,
                              unroll=cfg.unroll_attn)
    out = ctx.cons(out, "batch_attn", None, "heads", None)
    with jax.named_scope("attn_out"):
        out = linear(p["wo"], out.reshape(B, S, cfg.n_heads * hd), ctx,
                     out_logical="embed")
    return (out, new_cache) if cache is not None else (out, None)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, *, mlp_type: str = "swiglu",
             dtype="float32") -> dict:
    ks = jax.random.split(key, 3)
    if mlp_type == "swiglu":
        return {"wg": init_linear(ks[0], d, d_ff, dtype=dtype),
                "wu": init_linear(ks[1], d, d_ff, dtype=dtype),
                "wd": init_linear(ks[2], d_ff, d, dtype=dtype)}
    return {"w1": init_linear(ks[0], d, d_ff, dtype=dtype),
            "w2": init_linear(ks[1], d_ff, d, dtype=dtype)}


def mlp(p: dict, x, ctx: Ctx):
    if "wg" in p:
        with jax.named_scope("mlp_gate_up"):
            h = jax.nn.silu(linear(p["wg"], x, ctx, out_logical="mlp")) * \
                linear(p["wu"], x, ctx, out_logical="mlp")
        with jax.named_scope("mlp_down"):
            return linear(p["wd"], h, ctx, out_logical="embed")
    with jax.named_scope("mlp_gate_up"):
        h = jax.nn.gelu(linear(p["w1"], x, ctx, out_logical="mlp"))
    with jax.named_scope("mlp_down"):
        return linear(p["w2"], h, ctx, out_logical="embed")


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, *, z_loss: float = 0.0):
    """Mean next-token CE in f32; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * lse ** 2
    mask = (labels >= 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_cross_entropy(x, w, labels, *, chunk: int = 2048,
                          z_loss: float = 0.0, unroll: bool = False):
    """CE fused with the LM head, scanned over seq chunks so the (B, S, V)
    f32 logits tensor is never materialised — each chunk's logits are
    recomputed in the backward pass (jax.checkpoint).  Dominant memory term
    of the train step at 128k vocab; §Perf."""
    B, S, D = x.shape
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xs = (x.reshape(B, nc, chunk, D).swapaxes(0, 1),
          labels.reshape(B, nc, chunk).swapaxes(0, 1))

    @jax.checkpoint
    def body(carry, inp):
        tot, cnt = carry
        xc, lc = inp
        logits = (xc @ w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                 axis=-1)[..., 0]
        nll = lse - ll
        if z_loss:
            nll = nll + z_loss * lse ** 2
        mask = (lc >= 0).astype(jnp.float32)
        return (tot + (nll * mask).sum(), cnt + mask.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs,
                                 unroll=unroll)
    return tot / jnp.maximum(cnt, 1.0)
