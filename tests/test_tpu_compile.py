"""Compile rehearsal for one TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib and compiles for a described
topology: it refuses what interpret mode accepts (unaligned slices, kernels
that need more VMEM than a core has, programs that do not fit HBM).  These
tests compile the Pallas kernels of the main path at real widths, and
whole routed programs at full width (qwen1.5-4b's prefill and decode step,
DeepSeek-V2-Lite's first pipeline stage's prefill), for ``v5e:2x2``'s
first chip, and read what the compiler made of the weights and the decode
cache.  Nothing executes.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.knobs import Knob
from repro.kernels import ops

HBM_BYTES = 16e9      # one v5e chip (Google Cloud, "TPU v5e")

#: (op, dtype, dims, bm, variant) — the six ops in both dtypes at 4096,
#: every variant at 128- and 512-blocks, a ragged gemm
KERNEL_CELLS = (
    [(op, dt, 4096, 128, "full")
     for op in ("gemm", "symm", "syrk", "syr2k", "trmm", "trsm")
     for dt in ("float32", "bfloat16")]
    + [(op, "float32", 4096, bm, v)
       for op in ("syrk", "syr2k", "trmm")
       for bm in (128, 512) for v in ("tri", "tri_packed")]
    + [(op, "float32", 4096, 512, "full")
       for op in ("gemm", "symm", "syrk", "syr2k", "trmm", "trsm")]
    + [("gemm", "float32", (4000, 3000, 2500), 128, "full")]
)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The first chip, with JAX's persistent compilation cache off: an
    entry written for a described device cannot be read back without it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operand_shapes(op: str, dims) -> list[tuple[int, ...]]:
    if op == "gemm":
        m, k, n = (dims,) * 3 if isinstance(dims, int) else dims
        return [(m, k), (k, n)]
    return [(dims, dims)] * (1 if op == "syrk" else 2)


def _knob(bm: int, variant: str) -> Knob:
    return Knob(tuple(sorted({"bm": bm, "bk": bm, "bn": bm,
                              "variant": variant}.items())))


def _compile_kernel(one_chip, op, dtype, shapes, knob, **kw):
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(dtype), sharding=one_chip)
            for s in shapes]
    fn = jax.jit(lambda *x: ops.run_op(op, x, knob=knob, interpret=False,
                                       **kw))
    return fn.lower(*args).compile()


@pytest.mark.parametrize(
    "op,dtype,dims,bm,variant", KERNEL_CELLS,
    ids=[f"{c[0]}-{c[1]}-{c[2] if isinstance(c[2], int) else 'ragged'}"
         f"-b{c[3]}-{c[4]}" for c in KERNEL_CELLS])
def test_kernel_compiles_for_v5e(one_chip, op, dtype, dims, bm, variant):
    compiled = _compile_kernel(one_chip, op, dtype,
                               _operand_shapes(op, dims), _knob(bm, variant))
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_shaped_stacked_gemm_compiles_for_v5e(one_chip):
    """(B, 1, d) activations against a shared (d, n) weight: one stacked
    pallas_call (``run_op(..., stacked=True)``'s shared-weight path)."""
    compiled = _compile_kernel(one_chip, "gemm", "bfloat16",
                               [(4, 1, 2560), (2560, 6912)],
                               _knob(128, "full"), stacked=True)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(2560, 6912), (6912, 2560)],
                         ids=["up", "down"])
def test_decode_shaped_folded_gemm_compiles_for_v5e(one_chip, k, n):
    """The routed decode step's projection after the fold: 4 one-row
    sequences as one (4, k) x (k, n) bf16 gemm at the installed decode
    knob (bm 128, bk 128, bn 512)."""
    knob = Knob((("bk", 128), ("bm", 128), ("bn", 512), ("variant", "full")))
    compiled = _compile_kernel(one_chip, "gemm", "bfloat16",
                               [(4, k), (k, n)], knob)
    assert "tpu_custom_call" in compiled.as_text()


def test_routed_qwen15_4b_prefill_fits_one_v5e(one_chip):
    """The whole routed prefill step of qwen1.5-4b at its published width
    and depth (bf16 weights, 4 x 128 prompt tokens), compiled from
    ``jax.eval_shape`` shapes: the kernels compile and the program's
    arguments, outputs and temporaries fit one chip's HBM."""
    from repro.configs import get_config
    from repro.models import init_decode_state, init_params, prefill

    cfg = dataclasses.replace(get_config("qwen1.5-4b"),
                              param_dtype="bfloat16", use_pallas_gemm=True,
                              gemm_interpret=False)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (40, 2560, 151936)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    caches = on_chip(jax.eval_shape(
        lambda: init_decode_state(cfg, 4, 152, dtype=jnp.bfloat16)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32)})
    step = jax.jit(lambda p, b, c: prefill(p, b, c, cfg),
                   donate_argnums=(2,))
    compiled = step.lower(params, batch, caches).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 7.5e9     # the bf16 weights
    assert total < HBM_BYTES


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (49152, 2048, 1408, 512, 512, 512),
    (49152, 1408, 2048, 256, 512, 256),
    (49152, 2048, 1408, 128, 128, 128),
    (24, 1408, 2048, 128, 512, 512),
], ids=["gate-b512", "down-ragged-k", "gate-b128", "decode-rows"])
def test_grouped_gemm_compiles_for_v5e(one_chip, m, k, n, bm, bk, bn):
    """The dropless expert gemm at DeepSeek-V2-Lite's prefill and decode
    keys (64 experts, bf16), its group tables as scalar prefetch."""
    knob = Knob((("bk", bk), ("bm", bm), ("bn", bn), ("variant", "full")))
    args = [jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)]
    fn = jax.jit(lambda *x: ops.run_op("grouped_gemm", x, knob=knob,
                                       interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def _on_chip(tree, one_chip):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), tree)


@pytest.fixture(scope="module")
def deepseek_stage_prefill(one_chip):
    """The benchmark cell's program: the routed prefill of DeepSeek-V2-Lite's
    first pipeline stage (9 layers at published widths, all 64 experts,
    bf16) over 4 x 2048 tokens into a 2056-long latent cache, compiled."""
    from repro.configs import get_config
    from repro.models import init_decode_state, init_params, prefill

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"), n_layers=9,
                              param_dtype="bfloat16", use_pallas_gemm=True,
                              gemm_interpret=False)
    params = _on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    caches = _on_chip(jax.eval_shape(
        lambda: init_decode_state(cfg, 4, 2056, dtype=jnp.bfloat16)),
        one_chip)
    batch = _on_chip({"tokens": jax.ShapeDtypeStruct((4, 2048), jnp.int32)},
                     one_chip)
    step = jax.jit(lambda p, b, c: prefill(p, b, c, cfg, return_rows=True),
                   donate_argnums=(2,))
    return step.lower(params, batch, caches).compile()


@pytest.fixture(scope="module")
def qwen15_4b_decode(one_chip):
    """The decode cell's program: one routed greedy step of qwen1.5-4b at
    published width and depth, 4 sequences against a 1096-long cache,
    compiled."""
    from repro.configs import get_config
    from repro.models import decode_step, init_decode_state, init_params

    cfg = dataclasses.replace(get_config("qwen1.5-4b"),
                              param_dtype="bfloat16", use_pallas_gemm=True,
                              gemm_interpret=False)
    params = _on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    caches = _on_chip(jax.eval_shape(
        lambda: init_decode_state(cfg, 4, 1096, dtype=jnp.bfloat16)),
        one_chip)
    token = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg),
                   donate_argnums=(2,))
    return step.lower(params, token, caches).compile()


def test_routed_deepseek_v2_lite_stage_prefill_fits_one_v5e(
        deepseek_stage_prefill):
    """The grouped and dense kernels of the stage's prefill compile and
    everything fits one chip's HBM."""
    compiled = deepseek_stage_prefill
    assert 'name="grouped_gemm"' in compiled.as_text() or \
        "grouped_gemm" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 10e9      # 5.2e9 bf16 parameters
    assert total < HBM_BYTES


def _ops_with_output(hlo: str, shape: str) -> set[str]:
    """The HLO ops whose output is a bf16 array of ``shape``."""
    return set(re.findall(r"= bf16\[%s\]\{[^}]*\} ([\w-]+)\(" % shape, hlo))


#: (program, one layer's routed weight shapes, their stacks' shapes, the
#: compiled temporaries of the program that sliced every layer's weights
#: out of their stacks: jaxlib 0.9.0, default knobs)
IN_PLACE = {
    "qwen15_4b_decode": (("2560,2560", "2560,6912", "6912,2560"),
                         ("40,2560,2560", "40,2560,6912", "40,6912,2560"),
                         3_488_468_992),
    "deepseek_stage_prefill": (("64,2048,1408", "64,1408,2048"),
                               ("8,64,2048,1408", "8,64,1408,2048"),
                               3_495_348_224),
}


@pytest.mark.parametrize("program", sorted(IN_PLACE))
def test_routed_weights_are_read_in_place_on_v5e(program, request):
    """A routed kernel reads its layer's weight tiles from the segment's
    stack: no op writes one layer's weight (a Pallas call cannot fuse the
    slice of its operand, so a slice would be a copy on every forward
    pass), a stack is only ever a parameter or a loop value, never copied,
    and the temporaries are no larger than the slicing program's."""
    compiled = request.getfixturevalue(program)
    hlo = compiled.as_text()
    layer_shapes, stack_shapes, sliced_temp = IN_PLACE[program]
    for shape in layer_shapes:
        assert not _ops_with_output(hlo, shape), shape
    for shape in stack_shapes:
        assert _ops_with_output(hlo, shape) <= {"parameter",
                                                 "get-tuple-element"}, shape
    assert compiled.memory_analysis().temp_size_in_bytes <= sliced_temp


def test_decode_cache_is_written_in_place_on_v5e(qwen15_4b_decode):
    """The decode step's k and v stacks, carried through the layer loop,
    are only ever a parameter, a loop value or the in-place write of the
    new row's tile: no op copies the whole cache (into the layout a loop
    would prefer, and back out), and the temporaries hold less than one
    layer's keys."""
    compiled = qwen15_4b_decode
    assert _ops_with_output(compiled.as_text(), "40,4,1096,20,128") == {
        "parameter", "get-tuple-element", "dynamic-update-slice"}
    layer_keys = 4 * 1096 * 20 * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_keys


@pytest.mark.parametrize("m,k,n,bk", [(4, 10944, 2048, 512),
                                      (4, 2816, 2048, 512),
                                      (3, 2036, 1146, 256)],
                         ids=["dense-down", "shared-down", "install-point"])
def test_few_row_bf16_gemm_with_ragged_k_compiles_for_v5e(one_chip, m, k, n,
                                                          bk):
    """A decode step's bf16 gemm of a few rows whose k is not a multiple of
    bk: the ragged-tail mask selects in float32, where a bf16 select of
    fewer rows than a packed sublane group is refused by Mosaic."""
    knob = Knob((("bk", bk), ("bm", 128), ("bn", 512), ("variant", "full")))
    compiled = _compile_kernel(one_chip, "gemm", "bfloat16",
                               [(m, k), (k, n)], knob)
    assert "tpu_custom_call" in compiled.as_text()
