"""Sharding-spec assignment for params, optimizer state, caches, and inputs.

Params are classified by pytree path (suffix patterns) into column-parallel,
row-parallel, expert, embedding, … logical layouts; ``logical_spec`` then
maps logical names → mesh axes under the active :class:`ShardingRules` and
silently degrades to replication where a dim doesn't divide (MQA kv=1,
odd vocab sizes).  Leading layer-stack dims (from segment scanning) are
always unsharded.

Serve-time KV caches choose between head-sharding (kv_heads divisible by the
model axis) and sequence-sharding (the SP fallback for MQA/low-kv archs and
the MLA latent cache) — decided per-arch at spec time.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, Shape
from repro.models.sharding import (DEFAULT_RULES, MULTIPOD_RULES,
                                   ShardingRules, logical_spec,
                                   mesh_axis_size)

__all__ = ["param_logical_names", "param_specs", "tree_shardings",
           "cache_specs", "input_specs", "rules_for", "abstract_params",
           "abstract_train_state", "abstract_caches"]

_ROW_PARALLEL = ("wo", "wd", "w2", "out_proj", "cm_wv")
_REPLICATED_SUFFIX = ("scale", "bias", "b", "A_log", "D", "dt_bias", "u",
                      "mu", "mu_x", "w0", "ln_scale", "ln_bias", "cm_mu_k",
                      "cm_mu_r")


def rules_for(mesh: Mesh, shape_kind: str,
              cfg: ModelConfig | None = None) -> ShardingRules:
    """Default rules per execution kind: train uses FSDP over 'data' and
    Megatron-style sequence parallelism (seq → 'model' between blocks: the
    saved scan carries shrink by the TP degree — the difference between
    fitting HBM and not at 4k×256); serve keeps
    weights replicated across 'data' (no per-step gather) UNLESS the bf16
    weights would exceed ~6 GB/chip under TP alone (internvl2-76b), in which
    case serve keeps the FSDP axis and pays the per-layer gather."""
    base = MULTIPOD_RULES if "pod" in mesh.axis_names else DEFAULT_RULES
    if cfg is not None and cfg.n_heads % mesh.shape["model"] != 0:
        # heads can't shard over TP → partition attention compute by batch
        # over ('data','model') instead of replicating it model-axis-wide
        base = base.replace(batch_attn=base.axes_for("batch") + ("model",))
    if cfg is not None and cfg.n_experts and             cfg.n_experts % mesh.shape["model"] != 0:
        # experts can't shard over TP → slot-parallel expert compute
        base = base.replace(expert_cap=("model",))
    if shape_kind in ("train", "prefill"):
        base = base.replace(seq=("model",))
    if shape_kind in ("prefill", "decode"):
        keep_fsdp = False
        if cfg is not None:
            per_chip = (2 * total_params(cfg)) / mesh.shape["model"]
            keep_fsdp = per_chip > 6e9
        if not keep_fsdp:
            base = base.replace(embed_fsdp=())
    return base


def total_params(cfg: ModelConfig) -> int:
    """Approximate TOTAL parameter count (all experts for MoE)."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    hd = cfg.hd()
    emb = V * d * (1 if cfg.tie_embeddings else 2)
    attn = d * cfg.n_heads * hd + 2 * d * cfg.kv_heads * hd + \
        cfg.n_heads * hd * d
    if cfg.use_mla:
        attn = (d * cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                + d * (cfg.kv_lora + cfg.qk_rope_dim)
                + cfg.kv_lora * cfg.n_heads *
                (cfg.qk_nope_dim + cfg.v_head_dim)
                + cfg.n_heads * cfg.v_head_dim * d)
    if cfg.family == "ssm":
        per = 5 * d * d + 2 * d * cfg.d_ff
    elif cfg.family == "hybrid":
        d_in = cfg.ssm_expand * d
        per = 2 * d * d_in + d_in * d
    elif cfg.n_experts:
        ff = 3 * d * (cfg.moe_d_ff or cfg.d_ff) * \
            (cfg.n_experts + cfg.n_shared_experts)
        per = attn + ff
    else:
        ff = (3 if cfg.mlp_type == "swiglu" else 2) * d * cfg.d_ff
        per = attn + ff
    total = emb + L * per
    if cfg.family == "audio":
        total += cfg.n_enc_layers * (4 * d * d + 2 * d * cfg.d_ff)
    return int(total)


def param_logical_names(path: str, ndim: int) -> tuple:
    """Trailing logical axis names for a param leaf at ``path``."""
    parts = path.split("/")
    leafname = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""

    if path.endswith("embed/table"):
        return ("vocab", "embed_fsdp")
    if path.endswith("lm_head/w"):
        return ("embed_fsdp", "vocab")
    if parent == "moe" or (len(parts) > 1 and parts[-1] in
                           ("wg", "wu", "wd") and ndim == 3):
        if leafname in ("wg", "wu"):
            return ("experts", "embed_fsdp", "expert_mlp")
        if leafname == "wd":
            return ("experts", "expert_mlp", "embed_fsdp")
    if leafname == "conv_w":
        return (None, "ssm_inner")
    if leafname == "conv_b":
        return ("ssm_inner",)
    if leafname in _REPLICATED_SUFFIX or "lora" in leafname:
        return (None,) * min(ndim, 3)
    if leafname == "w":
        if parent in _ROW_PARALLEL:
            return ("o_in", "embed_fsdp")
        if parent == "wkv_b":
            return (None, "qkv")
        if parent == "in_proj":
            return ("embed_fsdp", "ssm_inner")
        if parent == "router":
            return (None, None)
        return ("embed_fsdp", "qkv")      # column-parallel default
    if leafname == "b":
        return ("qkv",)
    return (None,) * min(ndim, 2)


def _spec_for(path: str, leaf, rules: ShardingRules, mesh: Mesh) -> P:
    trailing = param_logical_names(path, leaf.ndim)
    trailing = trailing[: leaf.ndim]
    names = (None,) * (leaf.ndim - len(trailing)) + tuple(trailing)
    return logical_spec(rules, mesh, names, dims=leaf.shape)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def param_specs(params_abstract, rules: ShardingRules, mesh: Mesh):
    """PartitionSpec tree mirroring a (possibly abstract) param tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abstract)
    return treedef.unflatten(
        [_spec_for(_path_str(p), leaf, rules, mesh) for p, leaf in flat])


def tree_shardings(tree_abstract, specs, mesh: Mesh):
    return jax.tree.map(lambda _, s: NamedSharding(mesh, s), tree_abstract,
                        specs)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def cache_logical_names(cfg: ModelConfig, path: str, ndim: int,
                        mesh: Mesh) -> tuple:
    model_n = mesh.shape["model"]
    heads_shardable = cfg.kv_heads % model_n == 0
    leaf = path.split("/")[-1]
    if leaf in ("k", "v"):
        if heads_shardable:
            return ("batch", None, "kv_heads", None)
        return ("batch", "kv_seq_model", None, None)    # SP fallback
    if leaf in ("c_kv", "k_rope"):
        return ("batch", "kv_seq_model", None)          # MLA latent cache
    if leaf == "ssm":
        return ("batch", "heads", None, None)
    if leaf == "conv":
        return ("batch", None, "ssm_inner")
    if leaf == "S":
        return ("batch", "heads", None, None)
    if leaf in ("tm_prev", "cm_prev"):
        return ("batch", None)
    if leaf == "len":
        return ()
    return (None,) * ndim


def cache_specs(cfg: ModelConfig, caches_abstract, rules: ShardingRules,
                mesh: Mesh):
    rules = rules.replace(kv_seq_model=("model",))
    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_abstract)
    out = []
    for p, leaf in flat:
        names = cache_logical_names(cfg, _path_str(p), leaf.ndim, mesh)
        names = ((None,) * (leaf.ndim - len(names)) + tuple(names)
                 )[-leaf.ndim:] if leaf.ndim else ()
        out.append(logical_spec(rules, mesh, names, dims=leaf.shape))
    return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: Shape, mesh: Mesh):
    """ShapeDtypeStruct stand-ins (weak-type-correct, sharded, no
    allocation) for every model input of this (arch × shape) cell."""
    from repro.launch.mesh import batch_axes
    B = shape.global_batch
    S = shape.seq_len
    baxes = batch_axes(mesh)
    bspec = (baxes if B % mesh_axis_size(mesh, baxes) == 0 else None)

    def tok(b, s):
        return jax.ShapeDtypeStruct(
            (b, s), np.int32,
            sharding=NamedSharding(mesh, P(bspec, None)))

    def dense(b, s, d):
        return jax.ShapeDtypeStruct(
            (b, s, d), np.float32,
            sharding=NamedSharding(mesh, P(bspec, None, None)))

    if shape.kind == "train" or shape.kind == "prefill":
        seq_tokens = S - (cfg.vision_tokens if cfg.family == "vlm" else 0)
        batch = {"tokens": tok(B, seq_tokens)}
        if shape.kind == "train":
            batch["labels"] = tok(B, seq_tokens)
        if cfg.family == "vlm":
            batch["vision"] = dense(B, cfg.vision_tokens, cfg.d_model)
        if cfg.family == "audio":
            batch["frames"] = dense(B, cfg.enc_seq, cfg.d_model)
        return batch
    # decode: one new token against a seq_len cache
    batch = {"tokens": tok(B, 1)}
    if cfg.family == "audio":
        batch["enc_out"] = dense(B, cfg.enc_seq, cfg.d_model)
    return batch


# ---------------------------------------------------------------------------
# abstract state builders (eval_shape — no allocation)
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig, rules: ShardingRules, mesh: Mesh):
    from repro.models import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(shapes, rules, mesh)
    return jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
        shapes, specs), specs


def abstract_train_state(cfg: ModelConfig, rules: ShardingRules, mesh: Mesh):
    """(abstract params, abstract AdamW state) with matching shardings."""
    from repro.optim import init_adamw
    aparams, pspecs = abstract_params(cfg, rules, mesh)
    astate = jax.eval_shape(init_adamw, aparams)
    # moments inherit param specs; step is replicated
    mu = jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
        astate.mu, pspecs)
    nu = jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
        astate.nu, pspecs)
    step = jax.ShapeDtypeStruct((), np.int32,
                                sharding=NamedSharding(mesh, P()))
    from repro.optim import AdamWState
    return aparams, AdamWState(step=step, mu=mu, nu=nu), pspecs


def abstract_caches(cfg: ModelConfig, batch: int, max_len: int,
                    rules: ShardingRules, mesh: Mesh, dtype="bfloat16"):
    from repro.models import init_decode_state
    shapes = jax.eval_shape(
        lambda: init_decode_state(cfg, batch, max_len, dtype=dtype))
    specs = cache_specs(cfg, shapes, rules, mesh)
    return jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype, sharding=NamedSharding(mesh, sp)),
        shapes, specs), specs
