"""The benchmark's arithmetic against hand counts, its peaks table, and
the trace reduction on a small synthetic trace."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, tracing, yardstick  # noqa: E402

QWEN = {"hidden_size": 2560, "intermediate_size": 6912,
        "num_attention_heads": 20, "num_key_value_heads": 20,
        "num_hidden_layers": 40, "vocab_size": 151936}


@pytest.mark.parametrize("op, dims, flops", [
    ("gemm", (2, 3, 4), 2 * 2 * 3 * 4),
    ("symm", (3, 5), 2 * 3 * 3 * 5),
    ("syrk", (4, 7), 7 * 4 * 5),
    ("syr2k", (4, 7), 2 * 7 * 4 * 5),
    ("trmm", (3, 5), 3 * 3 * 5),
    ("trsm", (3, 5), 3 * 3 * 5),
])
def test_l3_flops_hand_counts(op, dims, flops):
    assert yardstick.l3_flops(op, dims) == flops


@pytest.mark.parametrize("op, dims, words", [
    ("gemm", (2, 3, 4), 2 * 3 + 3 * 4 + 2 * 4),
    ("symm", (3, 5), 6 + 2 * 15),
    ("syrk", (4, 7), 28 + 10),
    ("syr2k", (4, 7), 56 + 10),
    ("trmm", (3, 5), 6 + 30),
    ("trsm", (3, 5), 6 + 30),
])
def test_l3_bytes_hand_counts(op, dims, words):
    assert yardstick.l3_bytes(op, dims, 4) == 4 * words


def test_qwen_matmul_params_and_flops_per_token():
    # q, k, v, o: 4 x 2560^2; gate, up, down: 3 x 2560 x 6912; 40 layers
    per_layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert yardstick.matmul_params(QWEN) == 40 * per_layer == 3_171_942_400
    head = 2 * 2560 * 151936
    # token at position 1023 attends to 1024 positions: 4 * d * 1024 a layer
    assert yardstick.decode_flops(QWEN, 1023) == \
        2 * 40 * per_layer + 40 * 4 * 2560 * 1024 + head
    # a 1024-token prompt: the stack for every token, causal attention
    # (sum of 1..1024 positions), the head on the last token only
    attn = 40 * 4 * 2560 * (1024 * 1025 // 2)
    assert yardstick.prefill_flops(QWEN, 1024) == \
        2 * 40 * per_layer * 1024 + attn + head
    assert 6.5e9 < yardstick.prefill_flops(QWEN, 1024) / 1024 < 6.6e9


def test_gemm_bytes_count_a_shared_weight_once():
    assert yardstick.gemm_bytes((1, 8, 16), 2, batch=4) == \
        2 * (4 * (8 + 16) + 8 * 16)
    assert yardstick.gemm_bytes((1, 8, 16), 2, batch=4, shared_b=False) == \
        2 * (4 * (8 + 16) + 4 * 8 * 16)


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert yardstick.roofline_seconds(1000.0, 50.0, peak) == 10.0
    assert yardstick.roofline_seconds(100.0, 50.0, peak) == 5.0


def test_peaks_know_the_v5e_and_refuse_others():
    p = yardstick.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        yardstick.peaks("TPU v4")
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")


def _trace() -> tracing.Trace:
    op = tracing.Op
    ops = [op(0, "%fusion.1 = f32[8] fusion(x)", 100, 200),
           op(0, '%gemm_pallas.7 = f32[8] custom-call(x), '
                 'custom_call_target="tpu_custom_call"', 150, 300),
           op(0, "%copy.2 = f32[8] copy(y)", 500, 600),
           op(0, '%gemm_pallas.9 = f32[8] custom-call(y), '
                 'custom_call_target="tpu_custom_call"', 800, 900),
           op(0, "%add_fusion.4 = f32[8] fusion(%tpu_custom_call.9)", 900,
              900),
           op(0, "fusion.3", 2000, 2100)]         # outside the window
    spans = [("bench.window", 0, 1000), ("dispatch", 300, 500),
             ("wait", 600, 800)]
    return tracing.Trace(ops=ops, devices=1, spans=spans)


def test_busy_is_the_union_of_op_intervals():
    tr = _trace()
    lo, hi = tr.segment("bench.window")
    assert (lo, hi) == (0, 1000)
    # [100, 300] + [500, 600] + [800, 900]
    assert tr.busy_s(lo, hi) == pytest.approx(400e-9)
    assert tracing.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]


def test_kernel_time_and_op_kinds():
    tr = _trace()
    assert tr.kernel_s(0, 1000) == pytest.approx(250e-9)
    kinds = dict(tr.top_ops(0, 1000))
    assert kinds == pytest.approx({"kernel:gemm_pallas": 250e-9,
                                   "fusion": 100e-9, "copy": 100e-9,
                                   "add_fusion": 0.0})


def test_idle_gaps_are_named_by_the_host_span():
    gaps = _trace().idle_gaps(0, 1000)
    assert gaps[0] == ["dispatch", pytest.approx(200e-9)]
    assert gaps[1] == ["wait", pytest.approx(200e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [100e-9, 100e-9, 200e-9, 200e-9])


def test_widest_gap_and_rel_l2():
    want = [[0.0, 2.0, 1.0], [3.0, 0.0, 1.0]]
    assert compare.widest_gap(want, [1, 0]) == 0.0
    assert compare.widest_gap(want, [2, 1]) == 3.0
    assert compare.rel_l2([[0.0, 2.0]], [[0.0, 1.0]]) == pytest.approx(1.0)
