"""The benchmark's arithmetic, kept apart from the program: the chip's
peaks, the operations and bytes a Level-3 call needs, and the operations a
dense decoder needs per token.  Every count is algorithmic (what the
mathematics needs), not what a kernel happens to compute."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Level-3 BLAS (free dims as in the paper's Table I)
# ---------------------------------------------------------------------------

def l3_flops(op: str, dims) -> float:
    """Floating-point operations of one call (LAPACK Working Note 41)."""
    if op == "gemm":
        m, k, n = dims
        return 2.0 * m * k * n
    a, b = dims
    if op == "symm":                 # C = sym(A) B, A a x a
        return 2.0 * a * a * b
    if op == "syrk":                 # C = A A^T, A a x b; one triangle
        return 1.0 * b * a * (a + 1)
    if op == "syr2k":
        return 2.0 * b * a * (a + 1)
    if op in ("trmm", "trsm"):       # A a x a triangular, B a x b
        return 1.0 * a * a * b
    raise ValueError(op)


def l3_bytes(op: str, dims, itemsize: int) -> float:
    """Bytes a call must move at least: each operand read once, the result
    written once; a triangular or symmetric operand is read as one triangle
    and a symmetric result written as one."""
    if op == "gemm":
        m, k, n = dims
        words = m * k + k * n + m * n
    else:
        a, b = dims
        tri = a * (a + 1) / 2
        words = {"symm": tri + 2 * a * b,
                 "syrk": a * b + tri,
                 "syr2k": 2 * a * b + tri,
                 "trmm": tri + 2 * a * b,
                 "trsm": tri + 2 * a * b}[op]
    return float(words) * itemsize


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# dense decoder (RMSNorm, attention with QKV bias, SwiGLU)
# ---------------------------------------------------------------------------

def matmul_params(config: dict) -> int:
    """Weights that every token multiplies, per layer stack (no head)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return per_layer * config["num_hidden_layers"]


def attention_flops(config: dict, position: int) -> float:
    """Scores and weighted values of one token at 0-based ``position``
    against itself and the positions before it (causal), all layers."""
    d = config["num_attention_heads"] * (config["hidden_size"]
                                         // config["num_attention_heads"])
    return 4.0 * d * (position + 1) * config["num_hidden_layers"]


def head_flops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def prefill_flops(config: dict, prompt_len: int) -> float:
    """One prompt: every token through the stack, causal attention, and the
    output head on the last token only (what a prefill returns)."""
    stack = 2.0 * matmul_params(config) * prompt_len
    attn = sum(attention_flops(config, p) for p in range(prompt_len))
    return stack + attn + head_flops(config)


def decode_flops(config: dict, position: int) -> float:
    """One generated token at 0-based ``position``."""
    return (2.0 * matmul_params(config) + attention_flops(config, position)
            + head_flops(config))


def gemm_bytes(dims, itemsize: int, batch: int = 1,
               shared_b: bool = True) -> float:
    """A (stacked) gemm ``(batch, m, k) @ (k, n)``: activations and outputs
    per item, a shared weight once."""
    m, k, n = dims
    b_words = k * n if shared_b else batch * k * n
    return float(batch * (m * k + m * n) + b_words) * itemsize
