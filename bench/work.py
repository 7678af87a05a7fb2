"""Work counts from shapes: the bytes a kernel moves. Model FLOPs per
token are each architecture's (``flops_per_token``, ``bench/arch.py``)."""
from __future__ import annotations

import re

import numpy as np

DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1, "s8": 1,
               "u8": 1, "f16": 2, "s16": 2, "u16": 2}


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in an HLO fragment, such as
    ``f32[1024,576]{1,0}``."""
    total = 0
    for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", text):
        if dt in DTYPE_BYTES:
            n = int(np.prod([int(x) for x in dims.split(",") if x] or [1]))
            total += n * DTYPE_BYTES[dt]
    return total
