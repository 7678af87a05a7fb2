"""Pallas TPU kernel: fused row-softmax in PA arithmetic (paper §3.3).

Each grid step processes a (rows-block, full-row) tile in VMEM and fuses the
whole PA softmax: rowmax -> PAM by log2(e) -> paexp2 -> rowsum -> padiv.
The rows-block size resolves from the shared ``kernels/autotune.py`` table
(op ``"pa_softmax"``, keyed by the (rows, cols) bucket) — the same tuning
mechanism the matmul and fused-attention kernels use; the default is the
seed's 8 x up-to-4096 cols = 128 KB/tile. Rows longer than the column
budget fall back to the jnp composition in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import floatbits as _fb
from ..pa_prims import _LOG2E, get_prims


def _kernel(x_ref, o_ref, *, fmt_name: str = "f32"):
    pp = get_prims(fmt_name)
    x = x_ref[...]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = pp.paexp2(pp.pam(x - m, jnp.full_like(x, _LOG2E)))
    # Row sums accumulate in f32 (exact bf16 embedding; no-op cast for f32)
    # and round back to the carrier once for the normalising padiv.
    s = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True).astype(x.dtype)
    o_ref[...] = pp.padiv(e, jnp.broadcast_to(s, e.shape))


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "fmt_name"))
def pa_softmax_rows(x, *, rows: int = 8, interpret: bool = True,
                    fmt_name: str = "f32"):
    """PA softmax over the last axis of a 2D array (rows fit VMEM).

    ``rows`` is the grid's row-block size; callers resolve it from the
    shared autotune table (see ops.py) — pass explicitly to override.
    ``fmt_name`` selects the FloatFormat: "bf16" runs the fused chain
    natively in the int16 carrier with bf16 HBM traffic.
    """
    fmt = _fb.FORMATS[fmt_name]
    r, c = x.shape
    rp = -(-r // rows) * rows
    xp = jnp.pad(x.astype(fmt.dtype), ((0, rp - r), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, fmt_name=fmt_name),
        grid=(rp // rows,),
        in_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, c), fmt.dtype),
        interpret=interpret,
        name="pa_softmax",
    )(xp)
    return out[:r]
