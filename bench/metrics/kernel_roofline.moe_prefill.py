"""The dense routed gemm kernels' share of their roofline in the window
(%): the least time each of the prefills' dense gemm calls (MLA
projections, the dense layer, the shared experts, the head;
``bench/yardstick_moe.py``) needs at the chip's peaks, summed, over the
summed device time of the ``gemm`` kernels' events."""

from bench import tracing, yardstick_moe


def read(run):
    lo, hi = run.tr.segment("bench.window")
    kernel = sum(o.end - o.start for o in run.tr.ops_in(lo, hi)
                 if tracing.op_kind(o) == "kernel:gemm") / 1e9
    if kernel <= 0:
        return None
    return 100.0 * yardstick_moe.gemm_roofline_s(run.raw["gemm_calls"],
                                                 run.peak) / kernel
