"""The DeepSeek-V2 prefill cell's arithmetic (``bench/yardstick_moe.py``)
and its per-layer readers against hand counts on a small synthetic trace;
each reader reads None where the program keeps no routed-rows counter."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common, tracing, yardstick_moe  # noqa: E402

CONFIG = common.load_json(ROOT / "bench/configs/deepseek-v2-lite.json")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_token_flops_hand_count():
    d, h, L = 2048, 16, 9
    proj = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    per_pos = 2 * h * (192 + 128)              # scores and values a key
    dense = 3 * d * 10944
    moe = d * 64 + 3 * d * 1408 * (6 + 2)     # router, top-6 and 2 shared
    want = 2 * proj * L + per_pos * 1024 * L + 2 * dense + 2 * moe * 8
    assert yardstick_moe.token_flops(CONFIG, 1023) == want
    # the issue's count: 1.59 GFLOP a token, 13.0 TFLOP a 4 x 2048 prefill
    assert 1.58e9 < want < 1.60e9
    assert 12.9e12 < 4 * yardstick_moe.prefill_flops(CONFIG, 2048) < 13.1e12


def test_prefill_gemms_hand_count():
    calls = yardstick_moe.prefill_gemms(CONFIG, 4, 2048, 2056, 3)
    by_dims = {dims: (b, c) for dims, b, c in calls}
    assert by_dims[(2048, 2048, 3072)] == (4, 27)         # wq, 9 layers
    assert by_dims[(2056, 512, 4096)] == (4, 27)          # wkv_b on the cache
    assert by_dims[(2048, 2048, 10944)] == (4, 6)         # dense gate, up
    assert by_dims[(2048, 2816, 2048)] == (4, 24)         # shared down
    assert by_dims[(4, 2048, 102400)] == (1, 3)           # folded head


def test_grouped_calls_hand_count():
    rows = np.array([[3, 0, 5], [8, 0, 0]])               # 2 layers, 3 experts
    cfg = dict(CONFIG, n_routed_experts=3, hidden_size=4,
               moe_intermediate_size=2)
    calls = yardstick_moe.grouped_calls(cfg, rows)
    assert len(calls) == 6
    # layer 0, gate: m = 8 rows, k 4, n 2, two touched experts
    assert calls[0] == (2.0 * 8 * 4 * 2, 2.0 * (2 * 4 * 2 + 8 * 4 + 8 * 2))
    # layer 1, down: one touched expert (k 2, n 4)
    assert calls[5] == (2.0 * 8 * 2 * 4, 2.0 * (1 * 2 * 4 + 8 * 2 + 8 * 4))


class _Run:
    def __init__(self, raw, tr=None, window_s=1.0):
        self.raw, self.tr, self.window_s = raw, tr, window_s
        self.peak, self.cell = PEAK, {"config_data": CONFIG}


def _trace(kernel_ns: dict) -> tracing.Trace:
    ops, t = [], 0.0
    for name, ns in kernel_ns.items():
        ops.append(tracing.Op(0, f'%{name}.1 = f32[] custom-call(), '
                                 f'custom_call_target="tpu_custom_call"',
                              t, t + ns))
        t += ns
    ops.append(tracing.Op(0, "%fusion.2 = f32[] fusion()", t, t + 1e6))
    return tracing.Trace(ops=ops, devices=1,
                         spans=[("bench.window", 0.0, 2e9),
                                ("bench.routed", 0.0, 1e9),
                                ("bench.xla", 1e9, 2e9)])


def test_grouped_roofline_reads_the_counter():
    read = common.metric_reader("grouped_roofline.moe_prefill")
    rows = [np.full((8, 64), 4 * 2048 * 6 // 64)] * 2     # two prefills
    need = yardstick_moe.grouped_roofline_s(CONFIG, rows, PEAK)
    # 8 layers x 3 gemms x 2 prefills, compute-bound at M 49152
    assert need == pytest.approx(2 * 8 * 3 * 2.0 * 49152 * 2048 * 1408
                                 / PEAK["bf16_flops"])
    tr = _trace({"grouped_gemm": 2 * need * 1e9, "gemm": 5e6})
    assert read(_Run({"routed_rows": rows}, tr)) == pytest.approx(50.0)
    assert read(_Run({"routed_rows": None}, tr)) is None
    assert read(_Run({}, tr)) is None


def test_kernel_roofline_counts_the_dense_gemms_only():
    read = common.metric_reader("kernel_roofline.moe_prefill")
    calls = yardstick_moe.prefill_gemms(CONFIG, 4, 2048, 2056, 1)
    need = yardstick_moe.gemm_roofline_s(calls, PEAK)
    tr = _trace({"gemm": 4 * need * 1e9, "grouped_gemm": 7e8})
    assert read(_Run({"gemm_calls": calls}, tr)) == pytest.approx(25.0)
    assert read(_Run({"gemm_calls": calls}, _trace({}))) is None


def test_mfu_and_vs_xla():
    """The prefill cells' shared readers on this driver's fields: the
    whole step's model operations, and the routed and XLA segments."""
    mfu = common.metric_reader("mfu.prefill")
    assert mfu(_Run({"model_flops": 19.7e12}, window_s=1.0)) == \
        pytest.approx(10.0)
    vs = common.metric_reader("vs_xla.prefill")
    tr = tracing.Trace(ops=[tracing.Op(0, "%a.1 = f32[] fusion()", 0, 1e8),
                            tracing.Op(0, "%b.1 = f32[] fusion()", 1e9,
                                       1e9 + 1.5e8)],
                       devices=1, spans=[("bench.routed", 0.0, 1e9),
                                         ("bench.xla", 1e9, 2e9)])
    assert vs(_Run({}, tr)) == pytest.approx(1.5)
