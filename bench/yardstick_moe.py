"""The benchmark's arithmetic for a DeepSeek-V2 prefill, kept apart from
the program: the operations a token needs (active parameters and causal
attention), the dense routed gemms a prefill calls, and the operations and
bytes of its grouped expert gemms from the routed-rows counter.  Every count
is algorithmic, from the configuration's shapes."""

from __future__ import annotations

from bench import deepseek, yardstick


def _attn_proj(s: dict) -> int:
    """Weights of one layer's MLA projections that every token multiplies."""
    d, h = s["d"], s["h"]
    return (d * h * (s["nope"] + s["rope"]) + d * (s["lora"] + s["rope"])
            + s["lora"] * h * (s["nope"] + s["vd"]) + h * s["vd"] * d)


def token_flops(config: dict, position: int) -> float:
    """One token at 0-based ``position`` through the stack, no head: MLA
    projections, causal attention against itself and the positions before
    it, the dense FFN or the router, the top-k routed experts and the shared
    experts."""
    s = deepseek.dims(config)
    d, f = s["d"], s["f"]
    n_moe = s["L"] - s["dense"]
    proj = 2.0 * _attn_proj(s) * s["L"]
    attn = 2.0 * s["h"] * (s["nope"] + s["rope"] + s["vd"]) \
        * (position + 1) * s["L"]
    dense = 2.0 * 3 * d * s["ff"] * s["dense"]
    moe = 2.0 * (d * s["E"] + 3 * d * f * (s["K"] + s["shared"])) * n_moe
    return proj + attn + dense + moe


def prefill_flops(config: dict, prompt_len: int) -> float:
    """One prompt: every token through the stack and the output head on the
    last token only (what a prefill returns)."""
    return (sum(token_flops(config, p) for p in range(prompt_len))
            + yardstick.head_flops(config))


def prefill_gemms(config: dict, batch: int, seq: int, cache_len: int,
                  calls: int) -> list:
    """[(dims, batch, count)] of the dense routed gemms of ``calls``
    prefills: per layer the MLA projections (the latent's expansion runs
    over the whole cache), the dense layer's FFN, the MoE layers' shared
    experts, and the folded last-token head."""
    s = deepseek.dims(config)
    d, h, n_moe = s["d"], s["h"], s["L"] - s["dense"]
    sf = s["shared"] * s["f"]
    L = s["L"] * calls
    return [((seq, d, h * (s["nope"] + s["rope"])), batch, L),
            ((seq, d, s["lora"] + s["rope"]), batch, L),
            ((cache_len, s["lora"], h * (s["nope"] + s["vd"])), batch, L),
            ((seq, h * s["vd"], d), batch, L),
            ((seq, d, s["ff"]), batch, 2 * s["dense"] * calls),
            ((seq, s["ff"], d), batch, s["dense"] * calls),
            ((seq, d, sf), batch, 2 * n_moe * calls),
            ((seq, sf, d), batch, n_moe * calls),
            ((batch, d, s["V"]), 1, calls)]


def gemm_roofline_s(calls: list, peak: dict, itemsize: int = 2) -> float:
    """Least time of ``prefill_gemms``' calls at the chip's peaks."""
    return sum(count * yardstick.roofline_seconds(
        2.0 * b * m * k * n, yardstick.gemm_bytes((m, k, n), itemsize, b),
        peak) for (m, k, n), b, count in calls)


def grouped_calls(config: dict, rows) -> list:
    """[(flops, bytes)] of one prefill's grouped expert gemms, from its
    routed-rows counter ``rows`` (L_moe, E): per MoE layer gate and up
    (k = d, n = f) and down (k = f, n = d).  Operations ``2·Σ_e rows_e·k·n``;
    bytes the touched experts' weights (an expert of no rows reads none)
    plus the rows in and out, at the served width (2 bytes)."""
    s = deepseek.dims(config)
    d, f = s["d"], s["f"]
    out = []
    for layer in rows:
        m = float(sum(layer))
        touched = float(sum(1 for r in layer if r > 0))
        for k, n, times in ((d, f, 2), (f, d, 1)):
            out += [(2.0 * m * k * n,
                     2.0 * (touched * k * n + m * k + m * n))] * times
    return out


def grouped_roofline_s(config: dict, rows_per_call: list,
                       peak: dict) -> float:
    """Least time of every grouped gemm of the prefills whose counters are
    ``rows_per_call``."""
    return sum(max(fl / peak["bf16_flops"], by / peak["hbm_bytes_per_s"])
               for rows in rows_per_call
               for fl, by in grouped_calls(config, rows))
