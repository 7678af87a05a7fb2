"""Piecewise-affine arithmetic (Kosson & Jaggi, 2023), written plainly.

The benchmark's own copy of the paper's definitions, independent of the
code under test: float32 bit patterns added (PAM) or subtracted (PADIV)
as integers, Mitchell's piecewise-affine exp2/log2, and the two derivative
families of the paper's Table 1 ("approx": the analytic derivative of the
approximated op evaluated in PA arithmetic; "exact": the power-of-two
derivative of the PA function itself).

Every value here is float32. A lower precision, for the control, is
applied by the caller rounding inputs and outputs (``Arith.rnd``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

SIGN = np.int32(-(1 << 31))
MAG = np.int32(0x7FFFFFFF)
EXPF = np.int32(0x7F800000)
MANF = np.int32(0x007FFFFF)
BIAS = np.int32(127 << 23)
MIN_NORM = np.int32(1 << 23)
MAX_FIN = np.int32(0x7F7FFFFF)
LOG2E = np.float32(1.4426950408889634)
LN2 = np.float32(0.6931471805599453)


def f32(x):
    return jnp.asarray(x, jnp.float32)


def _bits(x):
    return jax.lax.bitcast_convert_type(f32(x), jnp.int32)


def _flt(i):
    return jax.lax.bitcast_convert_type(i, jnp.float32)


def _clamp(mag):
    """Flush underflow to zero, clamp overflow to the largest finite; a
    value below -BIAS can only be a wrapped overflow."""
    ovf = mag < -BIAS
    mag = jnp.where(mag < MIN_NORM, np.int32(0), jnp.minimum(mag, MAX_FIN))
    return jnp.where(ovf, MAX_FIN, mag)


# -- values ------------------------------------------------------------------

def pam_v(a, b):
    """A ·̂ B: sign xor, magnitudes added, bias removed (paper Eq. 5-8)."""
    a, b = jnp.broadcast_arrays(f32(a), f32(b))
    ai, bi = _bits(a), _bits(b)
    s = (ai ^ bi) & SIGN
    out = _flt(s | _clamp((ai & MAG) + (bi & MAG) - BIAS))
    return jnp.where((a == 0) | (b == 0), _flt(s), out)


def padiv_v(a, b):
    """A ÷̂ B: magnitudes subtracted, bias added (paper Eq. 14-17)."""
    a, b = jnp.broadcast_arrays(f32(a), f32(b))
    ai, bi = _bits(a), _bits(b)
    s = (ai ^ bi) & SIGN
    out = _flt(s | _clamp((ai & MAG) - (bi & MAG) + BIAS))
    out = jnp.where(b == 0, _flt(s | EXPF), out)
    return jnp.where(a == 0, _flt(s), out)


def paexp2_v(a):
    """2^floor(A) (1 + A - floor(A)) (paper Eq. 9)."""
    a = f32(a)
    ac = jnp.clip(a, -16384.0, 16384.0)
    n = jnp.floor(ac)
    man = jnp.round((ac - n) * np.float32(2.0 ** 23)).astype(jnp.int32)
    e = n.astype(jnp.int32) + (man >> 23) + np.int32(127)
    mag = (e << 23) | (man & MANF)
    mag = jnp.where(e <= 0, np.int32(0), jnp.minimum(mag, MAX_FIN))
    return jnp.where(a >= 128.0, np.float32(np.inf), _flt(mag))


def palog2_v(a):
    """E_A + M_A for A > 0 (paper Eq. 10): the bit pattern less the bias,
    read as a number of 2^-23 steps."""
    a = f32(a)
    out = (_bits(a) - BIAS).astype(jnp.float32) * np.float32(2.0 ** -23)
    return jnp.where(a == 0, np.float32(-np.inf), out)


def pow2_scale(x, k):
    """x * 2^k by an integer add to the exponent field; underflow flushes,
    overflow clamps, zero and non-finite x pass through."""
    x = f32(x)
    i = _bits(x)
    mag = (i & MAG) + (jnp.asarray(k, jnp.int32) << 23)
    mag = jnp.where(mag < MIN_NORM, np.int32(0), jnp.minimum(mag, MAX_FIN))
    return jnp.where((x == 0) | ~jnp.isfinite(x), x, _flt((i & SIGN) | mag))


def _exponent(x):
    return ((_bits(x) & EXPF) >> 23) - np.int32(127)


def pam_dfactor(a, b):
    """d(A ·̂ B)/dA = sign(B) 2^(E_B + carry(M_A + M_B)); 0 where B is 0."""
    a, b = jnp.broadcast_arrays(f32(a), f32(b))
    ai, bi = _bits(a), _bits(b)
    k = _exponent(b) + (((ai & MANF) + (bi & MANF)) >> 23)
    mag = jnp.clip(k + 127, 1, 254) << 23
    return jnp.where(b == 0, np.float32(0), _flt((bi & SIGN) | mag))


def padiv_dfactor(a, b):
    """d(A ÷̂ B)/dA = sign(B) 2^(-E_B - borrow(M_A < M_B))."""
    a, b = jnp.broadcast_arrays(f32(a), f32(b))
    ai, bi = _bits(a), _bits(b)
    k = -_exponent(b) - ((ai & MANF) < (bi & MANF)).astype(jnp.int32)
    mag = jnp.clip(k + 127, 1, 254) << 23
    return _flt((bi & SIGN) | mag)


# -- differentiable ops -------------------------------------------------------

def _sum_to(g, shape):
    """Reduce a broadcast cotangent back to an operand's shape."""
    shape = tuple(shape)
    if g.shape == shape:
        return g
    lead = g.ndim - len(shape)
    if lead:
        g = g.sum(axis=tuple(range(lead)))
    axes = tuple(i for i, (n, s) in enumerate(zip(g.shape, shape))
                 if s == 1 and n != 1)
    if axes:
        g = g.sum(axis=axes, keepdims=True)
    return g.reshape(shape)


def _binary(value, da, db):
    @jax.custom_vjp
    def op(a, b):
        return value(a, b)

    def fwd(a, b):
        return value(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return (_sum_to(da(a, b, g), jnp.shape(a)),
                _sum_to(db(a, b, g), jnp.shape(b)))

    op.defvjp(fwd, bwd)
    return op


def _unary(value, da):
    @jax.custom_vjp
    def op(a):
        return value(a)

    def fwd(a):
        return value(a), a

    def bwd(a, g):
        return (_sum_to(da(a, g), jnp.shape(a)),)

    op.defvjp(fwd, bwd)
    return op


_neg_quot = lambda a, b, g: -padiv_v(pam_v(a, g), pam_v(b, b))

OPS = {
    ("pam", "approx"): _binary(pam_v, lambda a, b, g: pam_v(b, g),
                               lambda a, b, g: pam_v(a, g)),
    ("pam", "exact"): _binary(
        pam_v, lambda a, b, g: pam_v(pam_dfactor(a, b), g),
        lambda a, b, g: pam_v(pam_dfactor(b, a), g)),
    ("padiv", "approx"): _binary(padiv_v, lambda a, b, g: padiv_v(g, b),
                                 _neg_quot),
    ("padiv", "exact"): _binary(
        padiv_v, lambda a, b, g: pam_v(padiv_dfactor(a, b), g), _neg_quot),
    ("paexp2", "approx"): _unary(
        paexp2_v, lambda a, g: pam_v(pam_v(paexp2_v(a), LN2), g)),
    ("paexp2", "exact"): _unary(
        paexp2_v, lambda a, g: pow2_scale(
            g, jnp.floor(jnp.clip(a, -16384.0, 16384.0)).astype(jnp.int32))),
    ("palog2", "approx"): _unary(
        palog2_v, lambda a, g: padiv_v(g, pam_v(a, LN2))),
    ("palog2", "exact"): _unary(
        palog2_v, lambda a, g: pow2_scale(g, -_exponent(a))),
}


def pam(a, b, d="approx"):
    return OPS[("pam", d)](f32(a), f32(b))


def padiv(a, b, d="approx"):
    return OPS[("padiv", d)](f32(a), f32(b))


def paexp2(a, d="approx"):
    return OPS[("paexp2", d)](f32(a))


def palog2(a, d="approx"):
    return OPS[("palog2", d)](f32(a))


def pasqrt(a, d="approx"):
    """paexp2(palog2(A) / 2) (paper Eq. 20); the halving is exact."""
    return paexp2(palog2(a, d) * np.float32(0.5), d)


# -- PAM matrix product ------------------------------------------------------

_BUDGET = 1 << 25       # products materialised per contraction chunk


def _pam_mm2(a, b):
    """(M, K) ·̂ (K, N) -> (M, N): every product a PAM, sums in float32,
    the contraction split into chunks so a chunk's products fit the
    budget."""
    m, k = a.shape
    n = b.shape[1]
    kc = max(1, min(k, _BUDGET // max(1, m * n)))
    steps = -(-k // kc)
    pad = steps * kc - k
    if pad:                                   # zero operands add nothing
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    ac = jnp.moveaxis(a.reshape(m, steps, kc), 1, 0)          # (steps, M, kc)
    bc = b.reshape(steps, kc, n)

    def body(acc, xs):
        x, y = xs
        return acc + jnp.sum(pam_v(x[:, :, None], y[None]), axis=1), None

    acc, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.float32), (ac, bc))
    return acc


def pam_matmul_v(a, b):
    """jnp.matmul semantics (broadcast batch dims) with PAM products."""
    a, b = f32(a), f32(b)
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    if not batch:
        return _pam_mm2(a, b)
    a = jnp.broadcast_to(a, batch + a.shape[-2:]).reshape((-1,) + a.shape[-2:])
    b = jnp.broadcast_to(b, batch + b.shape[-2:]).reshape((-1,) + b.shape[-2:])
    return jax.vmap(_pam_mm2)(a, b).reshape(batch + (a.shape[-2], b.shape[-1]))


@jax.custom_vjp
def pam_matmul(a, b):
    """PAM matmul with the paper's approx backward: dA = G ·̂ Bᵀ and
    dB = Aᵀ ·̂ G, each a PAM matmul."""
    return pam_matmul_v(a, b)


def _mm_fwd(a, b):
    return pam_matmul_v(a, b), (a, b)


def _mm_bwd(res, g):
    a, b = res
    da = pam_matmul_v(g, jnp.swapaxes(b, -1, -2)).astype(a.dtype)
    db = pam_matmul_v(jnp.swapaxes(a, -1, -2), g).astype(b.dtype)
    return _sum_to(da, a.shape), _sum_to(db, b.shape)


pam_matmul.defvjp(_mm_fwd, _mm_bwd)
