"""DeepSeek-V2 plumbing of the benchmark: the program's ``ModelConfig`` from
a configuration file, and random weights from the seed.

Weights.  Every leaf is drawn from its own key, ``fold_in(seed_key, leaf
index)``, and every layer of a per-layer leaf from ``fold_in(leaf key,
layer)``, with ``layer`` the layer's index in the whole stack (0 is the
dense layer, 1.. the MoE layers).  So the program's stacked tree
(:func:`make_params`, made on the device one leaf at a time) and the
reference's one layer at a time (:func:`layer`) hold the same numbers.

Scales as in ``bench/weights.py``: matrices N(0, 1/d_in) (an expert stack
``(E, d_in, d_out)`` per expert), norm scales 1 + N(0, 0.1^2), the embedding
N(0, 1) and the output head N(0, 1/d).  Served leaves are rounded to the
configuration's dtype; the router stays float32, as the program keeps it.
"""

from __future__ import annotations

import math

from bench import common

#: per-layer leaves of every layer (MLA attention and its norms)
ATTN_LEAVES = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ln2")
#: the dense layers' FFN
DENSE_LEAVES = ("wg", "wu", "wd")
#: the MoE layers' router, routed experts and shared experts
MOE_LEAVES = ("router", "ewg", "ewu", "ewd", "swg", "swu", "swd")
TOP_LEAVES = ("embed", "final_norm", "lm_head")
ORDER = ATTN_LEAVES + DENSE_LEAVES + MOE_LEAVES + TOP_LEAVES
NORMS = ("ln1", "ln2", "kv_norm", "final_norm")


def dims(config: dict) -> dict:
    """The configuration's sizes under short names."""
    return {"d": config["hidden_size"], "h": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "vd": config["v_head_dim"],
            "lora": config["kv_lora_rank"], "ff": config["intermediate_size"],
            "E": config["n_routed_experts"],
            "K": config["num_experts_per_tok"],
            "f": config["moe_intermediate_size"],
            "shared": config["n_shared_experts"],
            "V": config["vocab_size"], "L": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"]}


def shapes(config: dict) -> dict:
    s = dims(config)
    d, h, E, f = s["d"], s["h"], s["E"], s["f"]
    sf = s["shared"] * f
    return {"ln1": (d,), "wq": (d, h * (s["nope"] + s["rope"])),
            "wkv_a": (d, s["lora"] + s["rope"]), "kv_norm": (s["lora"],),
            "wkv_b": (s["lora"], h * (s["nope"] + s["vd"])),
            "wo": (h * s["vd"], d), "ln2": (d,),
            "wg": (d, s["ff"]), "wu": (d, s["ff"]), "wd": (s["ff"], d),
            "router": (d, E), "ewg": (E, d, f), "ewu": (E, d, f),
            "ewd": (E, f, d), "swg": (d, sf), "swu": (d, sf), "swd": (sf, d),
            "embed": (s["V"], d), "final_norm": (d,), "lm_head": (d, s["V"])}


def _draw(key, name: str, shape: tuple, dtype):
    import jax
    import jax.numpy as jnp
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        x = 1.0 + 0.1 * z
    elif name == "embed":
        x = z
    else:
        x = z / math.sqrt(shape[-2])
    return x if name == "router" else x.astype(dtype)


def _leaf_key(key, name: str):
    import jax
    return jax.random.fold_in(key, ORDER.index(name))


def leaf(config: dict, key, name: str, index=None, dtype=None):
    """One leaf (of layer ``index`` for a per-layer leaf), in the served
    dtype (or ``dtype``; the router is always float32)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.dtype(config["torch_dtype"])
    k = _leaf_key(key, name)
    if index is not None:
        k = jax.random.fold_in(k, index)
    return _draw(k, name, shapes(config)[name], dtype)


def layer(config: dict, key, index: int, dtype=None) -> dict:
    """Layer ``index``'s leaves by name."""
    s = dims(config)
    names = ATTN_LEAVES + (DENSE_LEAVES if index < s["dense"]
                           else MOE_LEAVES)
    return {n: leaf(config, key, n, index, dtype) for n in names}


def model_config(config: dict, *, routed: bool = True):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, RopeScaling
    s, ys = dims(config), config["rope_scaling"]
    if ys["type"] != "yarn" or config["topk_method"] != "greedy" \
            or config["scoring_func"] != "softmax" \
            or config["routed_scaling_factor"] != 1 \
            or config["q_lora_rank"] is not None:
        raise SystemExit("the program runs DeepSeek-V2 with YaRN, greedy "
                         "softmax routing, scaling 1 and no q-LoRA")
    return ModelConfig(
        name=config["name"], family="moe", n_layers=s["L"], d_model=s["d"],
        n_heads=s["h"], kv_heads=config["num_key_value_heads"],
        d_ff=s["ff"], vocab=s["V"], rope_theta=float(config["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(ys["factor"]),
            original_max_position_embeddings=int(
                ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]),
            beta_slow=float(ys["beta_slow"]), mscale=float(ys["mscale"]),
            mscale_all_dim=float(ys["mscale_all_dim"])),
        tie_embeddings=config["tie_word_embeddings"],
        n_experts=s["E"], top_k=s["K"], n_shared_experts=s["shared"],
        moe_d_ff=s["f"], first_dense_layers=s["dense"],
        norm_topk_prob=config["norm_topk_prob"],
        use_mla=True, kv_lora=s["lora"], qk_nope_dim=s["nope"],
        qk_rope_dim=s["rope"], v_head_dim=s["vd"],
        param_dtype=config["torch_dtype"],
        compute_dtype=config["torch_dtype"], use_pallas_gemm=routed)


def make_params(config: dict, seed: int):
    """The served weights in the program's parameter layout (checked
    against it), made on the device one leaf at a time: a stacked leaf's
    layers are drawn in turn inside one jitted call, so no float32 copy of
    a whole stack is ever held."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    cfg = model_config(config)
    s = dims(config)
    key = common.jax_key(seed)

    def stack(name, layers):
        f = jax.jit(lambda key: jax.lax.map(
            lambda i: leaf(config, key, name, i), jnp.asarray(layers)))
        return jax.block_until_ready(f(key))

    def top(name):
        return jax.block_until_ready(jax.jit(
            lambda key: leaf(config, key, name))(key))

    dense = list(range(s["dense"]))
    moe = list(range(s["dense"], s["L"]))

    def attn(layers):
        g = {n: stack(n, layers) for n in ATTN_LEAVES}
        return {"ln1": {"scale": g["ln1"]},
                "attn": {"wq": {"w": g["wq"]}, "wkv_a": {"w": g["wkv_a"]},
                         "kv_norm": {"scale": g["kv_norm"]},
                         "wkv_b": {"w": g["wkv_b"]}, "wo": {"w": g["wo"]}},
                "ln2": {"scale": g["ln2"]}}

    segs = []
    if dense:
        seg = attn(dense)
        seg["mlp"] = {n: {"w": stack(n, dense)} for n in DENSE_LEAVES}
        segs.append(seg)
    seg = attn(moe)
    seg["moe"] = {"router": {"w": stack("router", moe)},
                  "wg": stack("ewg", moe), "wu": stack("ewu", moe),
                  "wd": stack("ewd", moe),
                  "shared": {"wg": {"w": stack("swg", moe)},
                             "wu": {"w": stack("swu", moe)},
                             "wd": {"w": stack("swd", moe)}}}
    segs.append(seg)
    params = {"embed": {"table": top("embed")},
              "final_norm": {"scale": top("final_norm")},
              "lm_head": {"w": top("lm_head")}, "segments": segs}
    want = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise SystemExit("the benchmark's weights do not match the "
                         "program's parameter layout")
    return params
