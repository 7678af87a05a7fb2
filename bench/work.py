"""Work counts from shapes: model FLOPs per token and kernel bytes."""
from __future__ import annotations

import re

import numpy as np

DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1, "s8": 1,
               "u8": 1, "f16": 2, "s16": 2, "u16": 2}


def flops_per_token(s: dict, seq: int) -> float:
    """Forward plus backward FLOPs per trained token (3x the forward, a
    product counted as 2): the projections, the gated MLP and the tied
    head, and causal attention's QK and AV over (seq + 1) / 2 keys on
    average. Recomputation is not counted; the count is the same in every
    numeric mode."""
    d, L, V = s["d_model"], s["n_layers"], s["vocab_size"]
    q, kv, f = s["n_heads"] * s["d_head"], s["n_kv_heads"] * s["d_head"], s["d_ff"]
    dense = L * (d * q + 2 * d * kv + q * d + 3 * d * f) + d * V
    attn = L * 2 * q * (seq + 1) / 2
    return 3.0 * 2.0 * (dense + attn)


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in an HLO fragment, such as
    ``f32[1024,576]{1,0}``."""
    total = 0
    for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", text):
        if dt in DTYPE_BYTES:
            n = int(np.prod([int(x) for x in dims.split(",") if x] or [1]))
            total += n * DTYPE_BYTES[dt]
    return total
