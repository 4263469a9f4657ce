"""Model plumbing shared by the model drivers: the program's ``ModelConfig``
built from a configuration file, the weights made on the device from the
seed, and the decision keys a cell's routed matmuls ask for."""

from __future__ import annotations

import dataclasses

from bench import common, weights


def model_config(config: dict, *, routed: bool = True):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        qkv_bias=config["qkv_bias"], mlp_type="swiglu",
        rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        param_dtype=config["torch_dtype"], compute_dtype=config["torch_dtype"],
        use_pallas_gemm=routed)


def unrouted(cfg):
    return dataclasses.replace(cfg, use_pallas_gemm=False)


def make_params(config: dict, seed: int):
    """The served weights, made on the device in one jitted call from the
    seed, in the program's parameter layout (checked against it)."""
    import jax
    from repro.models import init_params
    cfg = model_config(config)
    want = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
    params = weights.make(config, common.jax_key(seed))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise SystemExit("the benchmark's weights do not match the "
                         "program's parameter layout")
    return params


def decision_keys(cfg, batch: int, seq: int, programs) -> list:
    """[(op, dtype_bytes, dims)] that the routed programs ask for."""
    from repro.roofline.harvest import harvest_decision_keys
    keys = harvest_decision_keys(cfg, batch_size=batch, seq_len=seq,
                                 programs=programs)
    return [(op, nbytes, tuple(dims)) for _, op, nbytes, dims in keys]
