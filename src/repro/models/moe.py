"""Mixture-of-Experts FFN: dropless on one device, capacity slab on a mesh.

Routing (both paths) runs in float32: softmax over the E experts, greedy
top-k, the top-k weights renormalised only when ``cfg.norm_topk_prob``.

Without a mesh the layer is **dropless**: the ``T·top_k`` token-slots are
sorted by expert, the group sizes taken, and gate/up and down run as grouped
gemms over the sorted rows (``run_op("grouped_gemm")`` when the config
routes, ``jax.lax.ragged_dot`` when not), each expert applied to exactly the
tokens routed to it; the rows are un-sorted, weighted and summed over k.
Nothing is padded and nothing is dropped, so a skewed prompt computes what
the published model computes.

On a mesh (the sharded path) the layer keeps its capacity slab.  Dispatch
there avoids the O(tokens·E·capacity) one-hot einsums of the classic
Mesh-TF formulation (which would *double* the model's FLOPs at 32k context —
see DESIGN.md roofline notes): tokens are routed by argsort over expert ids,
position-in-expert comes from segment arithmetic on the sorted array, and
dispatch/combine are scatter/gather (data movement, no FLOPs).

Per-sequence grouping keeps dispatch local to the data shard; the expert
einsum's (experts → 'model') sharding constraint induces the all-to-all.
Fixed capacity C = ⌈S·top_k/E · capacity_factor⌉ with token dropping
(standard at scale).  Both paths return the router's load-balance auxiliary
loss, for the trainer to add, and the rows each expert computed (the routed-
rows counter).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import (Ctx, init_linear, init_mlp, linear, mlp,
                     routed_operand, weight_array)

__all__ = ["init_moe", "moe_ffn", "moe_layer"]


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": init_linear(ks[0], d, e, dtype="float32"),  # router in f32
        "wg": (jax.random.normal(ks[1], (e, d, f)) * scale).astype(cfg.param_dtype),
        "wu": (jax.random.normal(ks[2], (e, d, f)) * scale).astype(cfg.param_dtype),
        "wd": (jax.random.normal(ks[3], (e, f, d)) * (1.0 / jnp.sqrt(f))
               ).astype(cfg.param_dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared_experts * f,
                               mlp_type="swiglu", dtype=cfg.param_dtype)
    return p


def _expert_matmul(t, w, ctx: Ctx):
    """Per-expert matmul ``einsum("becd,edf->becf", t, w)`` with optional
    ADSALA dispatch: when the config routes GEMMs, the (B,E,C,·) slab is
    folded to an expert-major stack (E, B·C, ·) and executed as one stacked
    ``run_op("gemm", ...)`` call — one knob decision covers all experts.
    A layer's expert stack is sliced out: the stacked gemm batches ``w``."""
    w = weight_array(w)
    if not ctx.routes_gemm(t):
        return jnp.einsum("becd,edf->becf", t, w)
    from repro.kernels import ops as kops
    B, E, C, D = t.shape
    w, kw = routed_operand(w, ctx)
    t3 = t.swapaxes(0, 1).reshape(E, B * C, D)
    y = kops.run_op("gemm", (t3, w), backend=ctx.cfg.gemm_backend,
                    runtime=ctx.runtime, stacked=True, **kw)
    return y.reshape(E, B, C, -1).swapaxes(0, 1)


def _positions_in_expert(e_flat: jax.Array) -> jax.Array:
    """For each slot (sorted-stable by expert id), its rank within its
    expert.  e_flat: (G, S*K) int32 → (G, S*K) int32."""
    sk = e_flat.shape[-1]
    order = jnp.argsort(e_flat, axis=-1, stable=True)
    se = jnp.take_along_axis(e_flat, order, axis=-1)
    idx = jnp.arange(sk)[None, :]
    boundary = jnp.concatenate(
        [jnp.ones_like(se[:, :1], bool), se[:, 1:] != se[:, :-1]], axis=-1)
    seg_start = jax.lax.cummax(jnp.where(boundary, idx, 0), axis=1)
    pos_sorted = idx - seg_start
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(pos_sorted, inv, axis=-1)


def _route(p: dict, x, cfg: ModelConfig):
    """(probs (B,S,E), top-k weights (B,S,K), top-k experts (B,S,K)), f32."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        weight_array(p["router"]["w"]),
                        precision=jax.lax.Precision.HIGHEST)       # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)                # greedy
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def _aux_loss(probs, top_e, E: int):
    """Switch-style load-balance loss: E · Σ_e f_e · p̄_e."""
    density = jnp.mean(jax.nn.one_hot(top_e[..., 0], E, dtype=jnp.float32),
                       axis=(0, 1))
    return E * jnp.sum(density * probs.mean(axis=(0, 1)))


def _grouped_matmul(rows, w, sizes, ctx: Ctx):
    """``rows[r] @ w[expert(r)]`` over rows sorted by expert; ``w`` may be
    a :class:`~repro.models.layers.LayerSlice`, read in place."""
    if not ctx.routes_gemm(rows):
        return jax.lax.ragged_dot(rows, weight_array(w), sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(rows.dtype)
    from repro.kernels import ops as kops
    w, kw = routed_operand(w, ctx)
    return kops.run_op("grouped_gemm", (rows, w, sizes),
                       backend=ctx.cfg.gemm_backend, runtime=ctx.runtime,
                       **kw)


def _dropless(p: dict, x, top_p, top_e, ctx: Ctx):
    """Every token-slot through its expert: (out (B,S,D), rows (E,))."""
    cfg = ctx.cfg
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    with jax.named_scope("moe_dispatch"):
        e_flat = top_e.reshape(-1)                                # (T·K,)
        order = jnp.argsort(e_flat, stable=True)
        sizes = jnp.zeros((E,), jnp.int32).at[e_flat].add(1)
        rows = x.reshape(B * S, D)[order // K]                   # by expert
    with jax.named_scope("moe_experts"):
        wg, wu, wd = ctx.cast(p["wg"]), ctx.cast(p["wu"]), ctx.cast(p["wd"])
        h = jax.nn.silu(_grouped_matmul(rows, wg, sizes, ctx)) * \
            _grouped_matmul(rows, wu, sizes, ctx)
        y = _grouped_matmul(h, wd, sizes, ctx)
    with jax.named_scope("moe_combine"):
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = y[inv].reshape(B, S, K, D)
        out = jnp.einsum("bskd,bsk->bsd", y.astype(jnp.float32),
                         top_p).astype(x.dtype)
    return out, sizes


def _capacity(p: dict, x, top_p, top_e, ctx: Ctx):
    """The sharded path's capacity slab: (out (B,S,D), rows kept (E,))."""
    cfg = ctx.cfg
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(-(-S * K // E) * cfg.capacity_factor))
    if S > 1:
        C = -(-C // 64) * 64      # align for capacity ("slot") sharding

    # --- slot bookkeeping ----------------------------------------------------
    e_flat = top_e.reshape(B, S * K)                              # (B, SK)
    w_flat = top_p.reshape(B, S * K)
    pos = _positions_in_expert(e_flat)                            # (B, SK)
    keep = (pos < C)
    dest = jnp.where(keep, e_flat * C + pos, E * C)               # drop → pad row
    kept = jnp.zeros((E,), jnp.int32).at[e_flat].add(keep.astype(jnp.int32))

    # --- dispatch (scatter, batch-local) --------------------------------------
    x_slots = jnp.repeat(x, K, axis=1).reshape(B, S * K, D)       # token s → K slots
    x_slots = ctx.cons(x_slots, "batch", None, "embed")
    dest = ctx.cons(dest, "batch", None)
    buf = jnp.zeros((B, E * C + 1, D), x.dtype)
    buf = ctx.cons(buf, "batch", None, None)
    bidx = jnp.arange(B)[:, None]
    buf = buf.at[bidx, dest].add(x_slots * keep[..., None].astype(x.dtype))
    buf = ctx.cons(buf, "batch", None, None)
    buf = buf[:, : E * C].reshape(B, E, C, D)
    # EP when experts divide the TP axis; otherwise slot-parallel over the
    # capacity dim (expert_cap → 'model') with replicated expert weights
    buf = ctx.cons(buf, "batch", "experts", "expert_cap", None)

    # --- expert FFN (EP over 'model') ----------------------------------------
    wg, wu, wd = (ctx.cast(p["wg"]), ctx.cast(p["wu"]), ctx.cast(p["wd"]))
    h = jax.nn.silu(_expert_matmul(buf, wg, ctx)) * \
        _expert_matmul(buf, wu, ctx)
    y = _expert_matmul(h, wd, ctx)
    y = ctx.cons(y, "batch", "experts", "expert_cap", None)

    # --- combine (gather) ------------------------------------------------------
    y = y.reshape(B, E * C, D)
    y = jnp.concatenate([y, jnp.zeros((B, 1, D), y.dtype)], axis=1)
    gathered = jnp.take_along_axis(y, dest[..., None], axis=1)    # (B,SK,D)
    gathered = gathered * (w_flat * keep)[..., None].astype(y.dtype)
    out = gathered.reshape(B, S, K, D).sum(axis=2)
    return ctx.cons(out, "batch", "seq", "embed"), kept


def moe_layer(p: dict, x, ctx: Ctx):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar, rows (E,) int32: the
    token-slots each expert computed)."""
    cfg = ctx.cfg
    with jax.named_scope("moe_router"):
        probs, top_p, top_e = _route(p, x, cfg)
        aux = _aux_loss(probs, top_e, cfg.n_experts)
    if ctx.mesh is None:
        out, rows = _dropless(p, x, top_p, top_e, ctx)
    else:
        out, rows = _capacity(p, x, top_p, top_e, ctx)
    if "shared" in p:
        out = out + mlp(p["shared"], x, ctx)
    return out, aux, rows


def moe_ffn(p: dict, x, ctx: Ctx):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    out, aux, _ = moe_layer(p, x, ctx)
    return out, aux
