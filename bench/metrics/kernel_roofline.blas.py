"""The Pallas kernels' share of their roofline in the window (%): the least
time each call's operations and bytes need at the chip's peaks, summed,
over the kernels' summed device time.  Float32 work is rated against the
bf16 peak (no float32 peak is published), so it cannot reach 100%."""


def read(run):
    lo, hi = run.tr.segment("bench.window")
    kernel = run.tr.kernel_s(lo, hi)
    return 100.0 * run.raw["roofline_s"] / kernel if kernel > 0 else None
