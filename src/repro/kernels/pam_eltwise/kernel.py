"""Pallas TPU kernel: fused elementwise PA ops (pam / padiv / paexp2 / palog2).

One VMEM-tiled elementwise pass over flattened operands — the TPU analogue
of the paper's elementwise CUDA kernels. Tiles are (8, 1024) f32 = 32 KB per
operand: sublane-aligned (8) x lane-aligned (1024 = 8*128), three live tiles
(a, b, out) < 100 KB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import floatbits as _fb
from ..pa_prims import get_prims

_ROWS, _COLS = 8, 1024
_TILE = _ROWS * _COLS


def _bin_fn(op: str, fmt_name: str):
    # "lmul" is the L-Mul product — PAM with the offset folded into the
    # re-bias, any format.
    pp = get_prims(fmt_name, lmul=(op == "lmul"))
    return {"pam": pp.pam, "lmul": pp.pam, "padiv": pp.padiv}[op]


def _un_fn(op: str, fmt_name: str):
    pp = get_prims(fmt_name)
    return {"paexp2": pp.paexp2, "palog2": pp.palog2}[op]


def _bin_kernel(a_ref, b_ref, o_ref, *, op, fmt_name):
    o_ref[...] = _bin_fn(op, fmt_name)(a_ref[...], b_ref[...])


def _un_kernel(a_ref, o_ref, *, op, fmt_name):
    o_ref[...] = _un_fn(op, fmt_name)(a_ref[...])


@functools.partial(jax.jit, static_argnames=("op", "interpret", "fmt_name"))
def eltwise_binary(a, b, *, op: str = "pam", interpret: bool = True,
                   fmt_name: str = "f32"):
    dt = _fb.FORMATS[fmt_name].dtype
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a.astype(dt), shape).reshape(-1)
    b = jnp.broadcast_to(b.astype(dt), shape).reshape(-1)
    n = a.size
    npad = -(-n // _TILE) * _TILE
    av = jnp.pad(a, (0, npad - n)).reshape(-1, _COLS)
    bv = jnp.pad(b, (0, npad - n)).reshape(-1, _COLS)
    out = pl.pallas_call(
        functools.partial(_bin_kernel, op=op, fmt_name=fmt_name),
        grid=(av.shape[0] // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, _COLS), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS, _COLS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, _COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(av.shape, dt),
        interpret=interpret,
        name=f"pam_eltwise_{op}",
    )(av, bv)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("op", "interpret", "fmt_name"))
def eltwise_unary(a, *, op: str = "paexp2", interpret: bool = True,
                  fmt_name: str = "f32"):
    dt = _fb.FORMATS[fmt_name].dtype
    shape = a.shape
    a = a.astype(dt).reshape(-1)
    n = a.size
    npad = -(-n // _TILE) * _TILE
    av = jnp.pad(a, (0, npad - n)).reshape(-1, _COLS)
    out = pl.pallas_call(
        functools.partial(_un_kernel, op=op, fmt_name=fmt_name),
        grid=(av.shape[0] // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, _COLS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, _COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(av.shape, dt),
        interpret=interpret,
        name=f"pam_eltwise_{op}",
    )(av)
    return out.reshape(-1)[:n].reshape(shape)
