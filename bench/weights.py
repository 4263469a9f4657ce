"""Random weights of a dense decoder from the seed, in the served dtype.

Every leaf is drawn from its own key, ``fold_in(seed_key, leaf index)``,
and every layer of a stacked leaf from ``fold_in(leaf key, layer)``.  So the
program's whole stacked tree (:func:`make`, one jitted call on the device)
and the reference's one layer at a time (:func:`layer`) hold the same
numbers, and neither takes anything the other made.

Scales: matrices N(0, 1/d_in) so activations keep their size through the
stack; biases N(0, 0.02^2); norm scales 1 + N(0, 0.1^2), so that a norm's
scale is not the identity; the embedding N(0, 1) and the output head
N(0, 1/d), so that logits have about unit spread.
"""

from __future__ import annotations

import math

#: (name, kind) of the per-layer leaves, in key order
LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "wg", "wu", "wd")
TOP_LEAVES = ("embed", "final_norm", "lm_head")


def _shapes(config: dict) -> dict:
    d, f = config["hidden_size"], config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // h
    v = config["vocab_size"]
    return {"ln1": (d,), "wq": (d, h * hd), "bq": (h * hd,),
            "wk": (d, kv * hd), "bk": (kv * hd,), "wv": (d, kv * hd),
            "bv": (kv * hd,), "wo": (h * hd, d), "ln2": (d,),
            "wg": (d, f), "wu": (d, f), "wd": (f, d),
            "embed": (v, d), "final_norm": (d,), "lm_head": (d, v)}


def _draw(key, name: str, shape: tuple, dtype):
    import jax
    import jax.numpy as jnp
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.1 * z
    elif name.startswith("b"):
        x = 0.02 * z
    elif name == "embed":
        x = z
    else:
        x = z / math.sqrt(shape[0])
    return x.astype(dtype)


def _leaf_key(key, name: str):
    import jax
    order = LAYER_LEAVES + TOP_LEAVES
    return jax.random.fold_in(key, order.index(name))


def layer(config: dict, key, index, dtype=None) -> dict:
    """Layer ``index``'s leaves by name, in the served dtype (or ``dtype``)."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.dtype(config["torch_dtype"])
    shapes = _shapes(config)
    return {n: _draw(jax.random.fold_in(_leaf_key(key, n), index), n,
                     shapes[n], dtype) for n in LAYER_LEAVES}


def top(config: dict, key, dtype=None) -> dict:
    import jax.numpy as jnp
    dtype = dtype or jnp.dtype(config["torch_dtype"])
    shapes = _shapes(config)
    return {n: _draw(_leaf_key(key, n), n, shapes[n], dtype)
            for n in TOP_LEAVES}


def make(config: dict, key):
    """The whole tree in the program's layout, made in one jitted call."""
    import jax
    import jax.numpy as jnp

    def build(key):
        t = top(config, key)
        ls = jax.vmap(lambda i: layer(config, key, i))(
            jnp.arange(config["num_hidden_layers"]))
        seg = {"ln1": {"scale": ls["ln1"]},
               "attn": {"wq": {"w": ls["wq"], "b": ls["bq"]},
                        "wk": {"w": ls["wk"], "b": ls["bk"]},
                        "wv": {"w": ls["wv"], "b": ls["bv"]},
                        "wo": {"w": ls["wo"]}},
               "ln2": {"scale": ls["ln2"]},
               "mlp": {"wg": {"w": ls["wg"]}, "wu": {"w": ls["wu"]},
                       "wd": {"w": ls["wd"]}}}
        return {"embed": {"table": t["embed"]},
                "final_norm": {"scale": t["final_norm"]},
                "lm_head": {"w": t["lm_head"]},
                "segments": [seg]}

    return jax.jit(build)(key)
