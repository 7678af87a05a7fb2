"""Shared PA AdamW update math + the jnp engine (DESIGN.md §5).

``pa_adamw_math`` is the single elementwise definition of the fused
piecewise-affine AdamW step (paper §2.6): clip-scale apply, m/v moment
updates, ``pasqrt``, ``padiv``, lr apply and decoupled weight decay —
every multiplication/division/sqrt a PA op, every power-of-two scale an
exact exponent add; the ``paexp2``/``palog2`` bias corrections are O(1)
scalars (``pa_adamw_scalars``). Both execution engines call this exact
function — the Pallas kernel traces it per VMEM tile (``kernel.py``) with
the format's int32-carrier ``ValueOps``, the jnp engine maps it over
leaves with the native-carrier ones — so the engines agree bit for bit,
with each other and with the independent numpy reference
``pa_adamw_numpy`` (checked on the chip by ``chip_smoke.py``). In f32 both
are bit-identical to the frozen value-level seed chain
(``benchmarks/seed_reference.seed_pa_adamw_update``, the pre-fusion
``adamw_update`` PA branch), which used the same ``pam_value``/
``padiv_value`` compositions op for op.

The optimizer is value-level (never differentiated through), so the raw
value forwards are used directly — no ``custom_vjp`` wrappers.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import floatbits as _fb
from repro.core.pam import (ValueOps, pam_value, paexp2_value,
                            palog2_value)


def pa_adamw_scalars(t, lr, *, b1, b2, wd):
    """The O(1) scalar schedule of one step, in f32: bias corrections
    ``1 - b^t`` with b^t = paexp2(t ·̂ palog2 b), plus ``lr ·̂ wd``. Both
    engines compute it once per step, outside the per-element chain."""
    bc1 = 1.0 - paexp2_value(pam_value(t, palog2_value(np.float32(b1))))
    bc2 = 1.0 - paexp2_value(pam_value(t, palog2_value(np.float32(b2))))
    return bc1, bc2, pam_value(lr, np.float32(wd))


def pa_adamw_math(pf, g, m32, v32, bc1, bc2, lr, lr_wd, scale, *, b1, b2,
                  eps, apply_scale, ops):
    """One fused PA AdamW step on operands of one compute format; returns
    (new_p, m_new, v_new) in that format (caller encodes back to the
    storage dtypes). ``ops`` is the format's ``ValueOps``.

    ``bc1``/``bc2``/``lr``/``lr_wd``/``scale`` come from
    ``pa_adamw_scalars`` (f32, scalars or broadcast tiles); ``b1``/``b2``/
    ``eps`` are static python floats baked in as immediates.
    ``apply_scale`` is static: the clip scale is a PAM when
    ``grad_clip > 0`` and entirely absent otherwise (bit parity with the
    unscaled seed path — PAM by 1.0 would still flush denormal gradients).
    Every float add and subtract is rounded to the format (``ops.round``),
    so no compiler can carry a narrow format's sums in f32.
    """
    if apply_scale:
        g = ops.pam(g, scale)
    m_new = ops.round(ops.pam(np.float32(b1), m32)
                      + ops.pam(np.float32(1 - b1), g))
    v_new = ops.round(ops.pam(np.float32(b2), v32)
                      + ops.pam(np.float32(1 - b2), ops.pam(g, g)))
    mhat = ops.padiv(m_new, bc1)
    vhat = ops.padiv(v_new, bc2)
    den = ops.pasqrt(vhat)
    upd = ops.padiv(mhat, ops.round(
        den + jnp.asarray(np.float32(eps), den.dtype)))
    new_p = ops.round(ops.round(pf - ops.pam(lr, upd)) - ops.pam(lr_wd, pf))
    return new_p, m_new, v_new


def pa_adamw_leaf_ref(p, g, m, v, bc1, bc2, lr, lr_wd, scale, *, b1, b2,
                      eps, apply_scale, fmt_name="f32"):
    """jnp engine for one leaf: decode to the compute format, shared math,
    encode back to the storage dtypes (bf16 moments round-to-nearest-even,
    as the kernel's in-VMEM encode does). ``fmt_name="bf16"`` runs the
    whole chain natively in the int16 carrier."""
    fmt = _fb.FORMATS[fmt_name]
    pf, g32, m32, v32 = (jnp.asarray(x).astype(fmt.dtype)
                         for x in (p, g, m, v))
    new_p, m_new, v_new = pa_adamw_math(pf, g32, m32, v32, bc1, bc2, lr,
                                        lr_wd, scale, b1=b1, b2=b2, eps=eps,
                                        apply_scale=apply_scale,
                                        ops=ValueOps(fmt))
    return (new_p.astype(p.dtype), m_new.astype(m.dtype),
            v_new.astype(v.dtype))



def _numpy_pa_ops(fmt_name):
    """The value-level PA ops of one format written again in numpy: integer
    bit math on the format's bit patterns, and every float add, subtract
    and cast rounded once to the format (bf16 through ``ml_dtypes``).
    Finite operands, zero or normal: zero tests read the exponent field,
    which is what flush-to-zero hardware sees."""
    import types

    import ml_dtypes

    dt, ut, e, mb = {"f32": (np.float32, np.uint32, 8, 23),
                     "bf16": (ml_dtypes.bfloat16, np.uint16, 8, 7)}[fmt_name]
    sign_m = 1 << (e + mb)
    mag_m, man_m = sign_m - 1, (1 << mb) - 1
    bias, min_norm = ((1 << (e - 1)) - 1) << mb, 1 << mb
    max_fin, inf = (((1 << e) - 2) << mb) | man_m, ((1 << e) - 1) << mb

    def bits(x):
        return np.asarray(x, dt).view(ut).astype(np.int64)

    def floats(i):
        return np.asarray(i, np.int64).astype(ut).view(dt)

    def clamp(mag):
        return np.where(mag < min_norm, 0, np.minimum(mag, max_fin))

    def zero(i):
        return (i & inf) == 0

    def pam(a, b):
        ai, bi = bits(a), bits(b)
        s = (ai ^ bi) & sign_m
        mag = clamp((ai & mag_m) + (bi & mag_m) - bias)
        return floats(np.where(zero(ai) | zero(bi), s, s | mag))

    def padiv(a, b):
        ai, bi = bits(a), bits(b)
        s = (ai ^ bi) & sign_m
        mag = clamp((ai & mag_m) - (bi & mag_m) + bias)
        return floats(np.where(zero(ai), s,
                               np.where(zero(bi), s | inf, s | mag)))

    def palog2(a):
        ai = bits(a)
        out = ((ai - bias).astype(np.float32).astype(dt)
               * np.asarray(2.0 ** -mb, dt))
        return np.where(zero(ai), np.asarray(-np.inf, dt), out)

    def paexp2(a):
        a = np.asarray(a, dt)
        ac = np.clip(a, dt(-16384.0), dt(16384.0))
        n = np.floor(ac)
        man = np.round((ac - n).astype(np.float32) * 2.0 ** mb).astype(
            np.int64)
        ex = n.astype(np.int64) + (man >> mb) + (bias >> mb)
        mag = np.where(ex <= 0, 0,
                       np.minimum((ex << mb) | (man & man_m), max_fin))
        return np.where(a >= 128, np.asarray(np.inf, dt), floats(mag))

    def pasqrt(a):
        y = palog2(a)                  # then y / 2, exactly, in the exponent
        i = bits(y)
        mag = (i & mag_m) - min_norm
        half = floats((i & sign_m) | np.where(mag < min_norm, 0, mag))
        return paexp2(np.where((y == 0) | ~np.isfinite(y), y, half))

    return types.SimpleNamespace(dt=dt, pam=pam, padiv=padiv, palog2=palog2,
                                 paexp2=paexp2, pasqrt=pasqrt)


def pa_adamw_numpy(p, g, m, v, t, lr, scale, *, b1, b2, eps, weight_decay,
                   fmt_name="f32"):
    """Independent numpy reference of ``pa_adamw_update`` for one leaf (the
    PA definitions of ``_numpy_pa_ops``, not this module's jnp code). It
    checks both engines on a device, where the compiler, not this code,
    decides where bf16 intermediates round. Scalars ``t``/``lr``/``scale``
    are f32; ``scale=None`` skips the clip scale. Returns (new_p, new_m,
    new_v) in the input dtypes."""
    f32, ops = _numpy_pa_ops("f32"), _numpy_pa_ops(fmt_name)
    dt = ops.dt
    t, lr = np.float32(t), np.float32(lr)
    # the O(1) schedule in f32 (pa_adamw_scalars), then rounded to dt
    bc1 = np.float32(1.0) - f32.paexp2(f32.pam(t, f32.palog2(np.float32(b1))))
    bc2 = np.float32(1.0) - f32.paexp2(f32.pam(t, f32.palog2(np.float32(b2))))
    lr_wd = f32.pam(lr, np.float32(weight_decay))
    c = lambda x: np.asarray(np.float32(x)).astype(dt)
    pf, g, m32, v32 = (np.asarray(x).astype(dt) for x in (p, g, m, v))
    if scale is not None:
        g = ops.pam(g, c(scale))
    m_new = ops.pam(c(b1), m32) + ops.pam(c(1 - b1), g)
    v_new = ops.pam(c(b2), v32) + ops.pam(c(1 - b2), ops.pam(g, g))
    upd = ops.padiv(ops.padiv(m_new, c(bc1)),
                    ops.pasqrt(ops.padiv(v_new, c(bc2))) + c(eps))
    new_p = pf - ops.pam(c(lr), upd) - ops.pam(c(lr_wd), pf)
    return (new_p.astype(np.asarray(p).dtype), m_new.astype(np.asarray(m).dtype),
            v_new.astype(np.asarray(v).dtype))
