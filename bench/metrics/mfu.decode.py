"""Model operations per token times tokens per second, over the chip's
bf16 peak (%): the whole step's share of the peak."""


def read(run):
    return (100.0 * run.raw["model_flops"] / run.window_s
            / run.peak["bf16_flops"])
