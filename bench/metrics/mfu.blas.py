"""The stream's operations per second over the chip's bf16 peak (%)."""


def read(run):
    return 100.0 * run.raw["flops"] / run.window_s / run.peak["bf16_flops"]
