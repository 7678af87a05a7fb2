"""Pallas kernel: fused piecewise-affine AdamW update (DESIGN.md §5).

One grid step consumes a (rows, cols) tile of each update operand — param,
grad, and both moments — and runs the whole PA AdamW chain
(``ref.pa_adamw_math``) in VMEM: clip-scale PAM, moment updates,
paexp2/palog2 bias correction, pasqrt, padiv, lr apply, decoupled weight
decay. Moments decode (``astype(f32)``) and encode (round-to-nearest-even
``astype(bf16)``) inside the kernel, so bf16 optimizer state never exists
in f32 form in HBM. The value-level composition this replaces materialised
~15 intermediate tensors per parameter; the kernel's HBM traffic is the
theoretical floor — read p/g/m/v once, write p/m/v once.

Buffers are donated: ``input_output_aliases`` maps the padded p/m/v inputs
onto the corresponding outputs, so the update is in-place at the XLA buffer
level (HomebrewNLP-Jax's fused-step / MaxText's donated-buffer posture).

The leaf driver flattens a parameter leaf to a (rows·cols)-padded
(R, cols) plane and runs a 1-D grid over row blocks; tile params resolve
from ``kernels/autotune.py`` (op ``"pam_optim"``, keyed by the element
count bucket). The step's f32 scalars (bias corrections, lr, lr ·̂ wd,
clip scale — ``ref.pa_adamw_scalars``) ride in one (5,) vector whose
BlockSpec pins every grid step to the same block; the kernel broadcasts
each to the tile before any bit math (the TPU bitcasts vectors only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import floatbits as _fb
from repro.core.pam import ValueOps
from .ref import pa_adamw_math


def _kernel(s_ref, p_ref, g_ref, m_ref, v_ref, op_ref, om_ref, ov_ref, *,
            b1, b2, eps, apply_scale, fmt_name="f32"):
    fmt = _fb.FORMATS[fmt_name]
    cdt = fmt.dtype
    shape = p_ref.shape
    bc1, bc2, lr, lr_wd, scale = (jnp.full(shape, s_ref[i], jnp.float32)
                                  for i in range(5))
    pf = p_ref[...].astype(cdt)
    g = g_ref[...].astype(cdt)
    m32 = m_ref[...].astype(cdt)             # bf16 moment decode (f32 mode)
    v32 = v_ref[...].astype(cdt)
    new_p, m_new, v_new = pa_adamw_math(pf, g, m32, v32, bc1, bc2, lr, lr_wd,
                                        scale, b1=b1, b2=b2, eps=eps,
                                        apply_scale=apply_scale,
                                        ops=ValueOps(fmt.widened))
    op_ref[...] = new_p.astype(op_ref.dtype)
    om_ref[...] = m_new.astype(om_ref.dtype)  # bf16 moment encode
    ov_ref[...] = v_new.astype(ov_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "apply_scale", "rows", "cols", "interpret",
    "fmt_name"))
def pa_adamw_leaf_pallas(p, g, m, v, scalars, *, b1, b2, eps, apply_scale,
                         rows: int = 8, cols: int = 1024,
                         interpret: bool = True, fmt_name: str = "f32"):
    """Fused PA AdamW update of one parameter leaf.

    p: any shape/dtype; g: same shape (decoded to the compute format); m/v:
    moment leaves (f32 or bf16); scalars: (5,) f32 = [bc1, bc2, lr,
    lr ·̂ wd, clip_scale]. Returns (new_p, new_m, new_v) with the input
    dtypes. Zero-padding is inert: a padded element has g = m = v = p = 0,
    and the PA chain maps it to 0. ``fmt_name="bf16"`` runs the whole chain
    in bf16 on the widened int32 carrier, and the gradient plane streams
    through HBM at bf16 width.
    """
    gdt = jnp.float32 if fmt_name == "f32" else _fb.FORMATS[fmt_name].dtype
    shape, n = p.shape, p.size
    # Clamp the row-block to what the leaf needs (small leaves would
    # otherwise pad to a full default plane), sublane-aligned: 16 covers
    # bf16 moment/gradient tiles, 8 suffices when everything is f32.
    sub = (8 if all(jnp.dtype(x).itemsize >= 4
                    for x in (p.dtype, m.dtype, v.dtype, gdt)) else 16)
    rows = max(sub, min(rows, -(-max(n, 1) // cols)))
    rows = -(-rows // sub) * sub
    tile = rows * cols
    npad = -(-max(n, 1) // tile) * tile

    def plane(x, dt):
        flat = jnp.asarray(x, dt).reshape(-1)
        return jnp.pad(flat, (0, npad - n)).reshape(-1, cols)

    pv = plane(p, p.dtype)
    gv = plane(g, gdt)
    mv = plane(m, m.dtype)
    vv = plane(v, v.dtype)
    rtot = npad // cols

    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_kernel, b1=b1, b2=b2, eps=eps,
                          apply_scale=apply_scale, fmt_name=fmt_name),
        grid=(rtot // rows,),
        in_specs=[pl.BlockSpec((5,), lambda i: (0,)),
                  pl.BlockSpec((rows, cols), lambda i: (i, 0)),
                  pl.BlockSpec((rows, cols), lambda i: (i, 0)),
                  pl.BlockSpec((rows, cols), lambda i: (i, 0)),
                  pl.BlockSpec((rows, cols), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0)),
                   pl.BlockSpec((rows, cols), lambda i: (i, 0)),
                   pl.BlockSpec((rows, cols), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rtot, cols), pv.dtype),
                   jax.ShapeDtypeStruct((rtot, cols), mv.dtype),
                   jax.ShapeDtypeStruct((rtot, cols), vv.dtype)],
        # donate the padded p/m/v planes onto their outputs (in-place update)
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="pa_adamw",
    )(scalars, pv, gv, mv, vv)

    def unplane(x, dt):
        return x.reshape(-1)[:n].reshape(shape).astype(dt)

    return (unplane(new_p, p.dtype), unplane(new_m, m.dtype),
            unplane(new_v, v.dtype))
