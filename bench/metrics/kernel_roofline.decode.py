"""The routed gemm kernels' share of their roofline in the window (%): the
least time each gemm's operations and bytes need at the chip's peaks (a
weight shared by the stacked sequences counted once), summed, over the
kernels' summed device time."""

from bench import yardstick


def read(run):
    lo, hi = run.tr.segment("bench.window")
    kernel = run.tr.kernel_s(lo, hi)
    if kernel <= 0:
        return None
    need = sum(count * yardstick.roofline_seconds(
        2.0 * batch * dims[0] * dims[1] * dims[2],
        yardstick.gemm_bytes(dims, 2, batch), run.peak)
        for dims, batch, count in run.raw["gemm_calls"])
    return 100.0 * need / kernel
