"""Piecewise-affine scalar operations (paper §2.2–§2.5).

All ops are bit-exact implementations of the paper's definitions:

  * ``pam``    — A ·̂ B, int32 addition of bit patterns (Mogami's trick)
  * ``padiv``  — A ÷̂ B, int32 subtraction of bit patterns
  * ``paexp2`` / ``palog2`` — Mitchell's piecewise-affine exp2/log2
  * ``paexp`` / ``palog`` / ``pasqrt`` — derived via the base-2 pair

Each op is a ``jax.custom_vjp`` pair per derivative type (paper Table 1):
``deriv="exact"`` uses the true (piecewise-constant, power-of-two) derivative
of the PA function; ``deriv="approx"`` mimics the analytic derivative of the
op being approximated, evaluated with PA arithmetic. Both backward passes are
themselves multiplication-free (power-of-two scales are exact under PAM).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import floatbits as fb

_LOG2E = np.float32(1.4426950408889634)   # log2(e)
_LN2 = np.float32(0.6931471805599453)     # ln(2)

# ---------------------------------------------------------------------------
# Format dispatch (FloatFormat engine family, DESIGN.md §11).
# ---------------------------------------------------------------------------

def _f32(x):
    return jnp.asarray(x, jnp.float32)


_FMT_BY_DTYPE = {
    jnp.dtype(jnp.float32): fb.FLOAT32,
    jnp.dtype(jnp.bfloat16): fb.BFLOAT16,
    jnp.dtype(jnp.float16): fb.FLOAT16,
}


def _operand_fmt(*xs) -> fb.FloatFormat:
    """FloatFormat implied by the operands of a PA op.

    Non-scalar float arrays vote with their dtype and must all agree —
    mixing bf16 with f32 tensors raises a TypeError (cast explicitly at the
    boundary; silent promotion would hide an f32 round-trip). Scalars
    (python numbers, numpy scalars, 0-d arrays — e.g. the np.float32
    constants in core/nn.py) carry no vote and follow the array operand,
    so ``pam(bf16_activations, _LOG2E)`` stays bf16-native. With no array
    operand at all the historical f32 coercion applies.
    """
    votes, scalars = {}, {}
    for x in xs:
        dt = getattr(x, "dtype", None)
        if dt is None:
            continue
        f = _FMT_BY_DTYPE.get(jnp.dtype(dt))
        if f is None:
            continue    # int / f64 operands fall back to the f32 coercion
        (votes if np.ndim(x) else scalars).setdefault(f.name, f)
    if len(votes) > 1:
        raise TypeError(
            "PA ops require operands of one float format, got "
            f"{sorted(votes)}; cast to a single dtype explicitly "
            "(e.g. x.astype(jnp.float32)) before the op")
    if votes:
        return next(iter(votes.values()))
    if len(scalars) == 1:
        return next(iter(scalars.values()))
    return fb.FLOAT32


def _value_zero(x, xi, fmt):
    """Operand-is-zero test. f32 keeps the float compare (bit-identical to
    the seed); narrow carriers test the exponent field so the denormal
    flush documented by the absint domain is explicit in bits."""
    if fmt.width == 32:
        return x == 0
    return (xi & fmt.EXP_MASK) == fmt.np_carrier(0)


# ---------------------------------------------------------------------------
# Raw (non-differentiable) forward values.
# ---------------------------------------------------------------------------

def _pam_like_value(a, b, fmt, fold):
    """Shared PAM-family forward: sign-XOR, carrier magnitude add, re-bias
    by ``fold``, clamp. ``fold = BIAS_SHIFTED`` is plain PAM;
    ``BIAS_SHIFTED - LMUL_OFFSET`` is the L-Mul product."""
    a, b = jnp.asarray(a, fmt.dtype), jnp.asarray(b, fmt.dtype)
    ai, bi = fb.bits(a, fmt), fb.bits(b, fmt)
    sign = (ai ^ bi) & fmt.SIGN_MASK
    mag = (ai & fmt.MAG_MASK) + (bi & fmt.MAG_MASK) - fold
    # The carrier wraps in the intermediate cancel (mod 2^width); a final
    # value below -BIAS can only come from a true exponent overflow ->
    # clamp, while [-BIAS, MIN_NORM) is a genuine underflow -> flush. The
    # two negative ranges are disjoint in EVERY supported carrier
    # (hypothesis-found edge case; int16 analogue in DESIGN.md §11).
    ovf = mag < -fmt.BIAS_SHIFTED
    mag = jnp.where(mag < fmt.MIN_NORM, 0, jnp.minimum(mag, fmt.MAX_FINITE))
    mag = jnp.where(ovf, fmt.MAX_FINITE, mag)
    out = fb.floats(sign | mag, fmt)
    zero = _value_zero(a, ai, fmt) | _value_zero(b, bi, fmt)
    ac, bc = fb.cmp_view(a, fmt), fb.cmp_view(b, fmt)
    inf = jnp.isinf(ac) | jnp.isinf(bc)
    out = jnp.where(zero, fb.floats(sign, fmt), out)                # signed zero
    out = jnp.where(inf, fb.floats(sign | fmt.INF_BITS, fmt), out)  # signed inf
    nan = jnp.isnan(ac) | jnp.isnan(bc) | (inf & zero)              # 0 * inf -> nan
    return jnp.where(nan, jnp.asarray(jnp.nan, fmt.dtype), out)


def pam_value(a, b):
    """Bit-exact PAM forward: sign-XOR, carrier magnitude add, re-bias,
    clamp. Dispatches on operand dtype (f32 -> int32 bit math, bf16/f16 ->
    int16 native)."""
    fmt = _operand_fmt(a, b)
    return _pam_like_value(a, b, fmt, fmt.BIAS_SHIFTED)


def lmul_value(a, b):
    """L-Mul forward ("Addition is All You Need", Eq. 7): PAM with the
    +2^-l mantissa offset folded into the re-bias constant. Error band
    [-161/2209, +1/16] (kernels/pa_prims.py has the derivation)."""
    fmt = _operand_fmt(a, b)
    return _pam_like_value(
        a, b, fmt, fmt.np_carrier(int(fmt.BIAS_SHIFTED) - int(fmt.LMUL_OFFSET)))


def padiv_value(a, b):
    """Bit-exact PA division: carrier magnitude subtract, re-bias, clamp."""
    return _padiv_value(a, b, _operand_fmt(a, b))


def _padiv_value(a, b, fmt):
    a, b = jnp.asarray(a, fmt.dtype), jnp.asarray(b, fmt.dtype)
    ai, bi = fb.bits(a, fmt), fb.bits(b, fmt)
    sign = (ai ^ bi) & fmt.SIGN_MASK
    mag = (ai & fmt.MAG_MASK) - (bi & fmt.MAG_MASK) + fmt.BIAS_SHIFTED
    # same disjoint-ranges overflow test as pam_value
    ovf = mag < -fmt.BIAS_SHIFTED
    mag = jnp.where(mag < fmt.MIN_NORM, 0, jnp.minimum(mag, fmt.MAX_FINITE))
    mag = jnp.where(ovf, fmt.MAX_FINITE, mag)
    out = fb.floats(sign | mag, fmt)
    az = _value_zero(a, ai, fmt)
    bz = _value_zero(b, bi, fmt)
    ac, bc = fb.cmp_view(a, fmt), fb.cmp_view(b, fmt)
    out = jnp.where(az, fb.floats(sign, fmt), out)                      # 0/b
    out = jnp.where(bz, fb.floats(sign | fmt.INF_BITS, fmt), out)       # a/0
    out = jnp.where(jnp.isinf(ac), fb.floats(sign | fmt.INF_BITS, fmt), out)
    out = jnp.where(jnp.isinf(bc), fb.floats(sign, fmt), out)           # a/inf
    nan = (jnp.isnan(ac) | jnp.isnan(bc)
           | (az & bz)
           | (jnp.isinf(ac) & jnp.isinf(bc)))
    return jnp.where(nan, jnp.asarray(jnp.nan, fmt.dtype), out)


def paexp2_value(a):
    """paexp2(A) = 2^floor(A) * (1 + A - floor(A))   (paper Eq. 9)."""
    return _paexp2_value(a, _operand_fmt(a))


def _paexp2_value(a, fmt):
    a = jnp.asarray(a, fmt.dtype)
    # Clamp the range used for bit manipulation: anything <= -150 underflows
    # to 0 and anything >= 128 overflows to inf regardless, and the clamp
    # keeps floor()/int conversion well-defined for +-inf / huge mask values.
    # (+-16384 = 2^14 is exact in every supported format.)
    ac = jnp.clip(a, -16384.0, 16384.0)
    n = jnp.floor(fb.cmp_view(ac, fmt)).astype(fmt.dtype)
    f = ac - n                                  # in [0, 1): pure float subtract
    man = jnp.round(fb.cmp_view(f * jnp.asarray(2.0**fmt.man_bits, fmt.dtype),
                                fmt)).astype(fmt.carrier)
    carry = man >> fmt.man_bits                 # f rounded up to exactly 1.0
    out = fb.compose(fmt.np_carrier(0), n.astype(fmt.carrier) + carry,
                     man & fmt.MAN_MASK, fmt)
    a = fb.cmp_view(a, fmt)
    out = jnp.where(a >= 128.0, jnp.asarray(jnp.inf, fmt.dtype), out)
    return jnp.where(jnp.isnan(a), jnp.asarray(jnp.nan, fmt.dtype), out)


def palog2_value(a):
    """palog2(A) = E_A + M_A for A > 0  (paper Eq. 10).

    Computed as (bits(A) - bits(1.0)) * 2^-man_bits — an int subtract and an
    exact power-of-two scale (multiplication-free)."""
    return _palog2_value(a, _operand_fmt(a))


def _palog2_value(a, fmt):
    a = jnp.asarray(a, fmt.dtype)
    ai = fb.bits(a, fmt)
    out = ((ai - fmt.BIAS_SHIFTED).astype(fmt.dtype)
           * jnp.asarray(2.0**-fmt.man_bits, fmt.dtype))
    out = jnp.where(_value_zero(a, ai, fmt), -jnp.asarray(jnp.inf, fmt.dtype), out)
    a = fb.cmp_view(a, fmt)
    out = jnp.where(a < 0, jnp.asarray(jnp.nan, fmt.dtype), out)
    return jnp.where(jnp.isnan(a), jnp.asarray(jnp.nan, fmt.dtype), out)


def pasqrt_value(a):
    """Value-level pasqrt(A) = paexp2(palog2(A) ÷ 2) (paper Eq. 20); the ÷2
    is an exact power-of-two exponent shift. Matches the ``pasqrt``
    custom-vjp op's forward bit for bit."""
    return _pasqrt_value(a, _operand_fmt(a))


def _pasqrt_value(a, fmt):
    return _paexp2_value(fb.pow2_mul(_palog2_value(a, fmt), -1, fmt), fmt)


class ValueOps:
    """The value-level PA ops bound to one FloatFormat: the same functions
    as ``pam_value`` & co. without the dtype dispatch. Kernels bind the
    widened format (``fmt.widened``), whose results equal the dispatched
    ops on the same inputs bit for bit. Binary operands broadcast to one
    shape first (a TPU kernel bitcasts vectors, never scalars)."""

    def __init__(self, fmt: fb.FloatFormat):
        self.fmt = fmt

    def _cast(self, a, b):
        return jnp.broadcast_arrays(jnp.asarray(a, self.fmt.dtype),
                                    jnp.asarray(b, self.fmt.dtype))

    def pam(self, a, b):
        a, b = self._cast(a, b)
        return _pam_like_value(a, b, self.fmt, self.fmt.BIAS_SHIFTED)

    def padiv(self, a, b):
        return _padiv_value(*self._cast(a, b), self.fmt)

    def paexp2(self, a):
        return _paexp2_value(a, self.fmt)

    def palog2(self, a):
        return _palog2_value(a, self.fmt)

    def pasqrt(self, a):
        return _pasqrt_value(a, self.fmt)

    def round(self, x):
        """The result ``x`` of a float add or subtract, rounded to the
        format (to nearest, ties to even) by integer ops on its f32 bits.
        A compiler may keep a narrow format's intermediates in f32 (XLA
        does, on the CPU and the TPU) and a Mosaic kernel cannot reduce
        precision, so this pins the rounding the PA definition has. Exact
        for bf16, whose exponent range is f32's; the identity for f32."""
        fmt = self.fmt
        if fmt.width == 32:
            return x
        shift = 23 - fmt.man_bits
        xf = jnp.asarray(x).astype(jnp.float32)
        i = jax.lax.bitcast_convert_type(xf, jnp.int32)
        i = ((i + np.int32((1 << (shift - 1)) - 1) + ((i >> shift) & 1))
             & np.int32(-(1 << shift)))
        r = jax.lax.bitcast_convert_type(i, jnp.float32)
        return jnp.where(jnp.isnan(xf), xf, r).astype(fmt.dtype)


# -- Exact-derivative scale factors (all signed powers of two) --------------

def _pam_carry(a, b, fmt=fb.FLOAT32):
    """1{M_A + M_B >= 1} as the carrier int."""
    return ((fb.mantissa_field(a, fmt) + fb.mantissa_field(b, fmt))
            >> fmt.man_bits).astype(fmt.carrier)


def pam_exact_dfactor(a, b):
    """d(A ·̂ B)/dA = (-1)^{S_B} 2^{E_B + 1{M_A+M_B>=1}} (paper Table 1)."""
    fmt = _operand_fmt(a, b)
    a, b = jnp.asarray(a, fmt.dtype), jnp.asarray(b, fmt.dtype)
    k = fb.exponent(b, fmt) + _pam_carry(a, b, fmt)
    mag = jnp.clip(k + fmt.exp_bias, 1, (1 << fmt.exp_bits) - 2).astype(fmt.carrier) << fmt.man_bits
    out = fb.floats(fb.sign_bits(b, fmt) | mag, fmt)
    return jnp.where(_value_zero(b, fb.bits(b, fmt), fmt),
                     jnp.zeros((), fmt.dtype), out)


def _padiv_borrow(a, b, fmt=fb.FLOAT32):
    """1{M_A - M_B < 0} as the carrier int."""
    return (fb.mantissa_field(a, fmt) < fb.mantissa_field(b, fmt)).astype(fmt.carrier)


def padiv_exact_dfactor(a, b):
    """d(A ÷̂ B)/dA = (-1)^{S_B} 2^{-E_B - 1{M_A-M_B<0}}."""
    fmt = _operand_fmt(a, b)
    a, b = jnp.asarray(a, fmt.dtype), jnp.asarray(b, fmt.dtype)
    k = -fb.exponent(b, fmt) - _padiv_borrow(a, b, fmt)
    mag = jnp.clip(k + fmt.exp_bias, 1, (1 << fmt.exp_bits) - 2).astype(fmt.carrier) << fmt.man_bits
    return fb.floats(fb.sign_bits(b, fmt) | mag, fmt)


# ---------------------------------------------------------------------------
# custom_vjp wiring.
# ---------------------------------------------------------------------------

def _unbroadcast(g, shape):
    if g.shape == tuple(shape):
        return g
    ndiff = g.ndim - len(shape)
    if ndiff:
        g = g.sum(axis=tuple(range(ndiff)))
    axes = tuple(i for i, (gs, s) in enumerate(zip(g.shape, shape)) if s == 1 and gs != 1)
    if axes:
        g = g.sum(axis=axes, keepdims=True)
    return g.reshape(shape)


def _make_binary(value_fn, da_fn, db_fn, name):
    @jax.custom_vjp
    def op(a, b):
        return value_fn(a, b)

    def fwd(a, b):
        return value_fn(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return (_unbroadcast(da_fn(a, b, g), jnp.shape(a)),
                _unbroadcast(db_fn(a, b, g), jnp.shape(b)))

    op.defvjp(fwd, bwd)
    op.__name__ = name
    return op


def _make_unary(value_fn, da_fn, name):
    @jax.custom_vjp
    def op(a):
        return value_fn(a)

    def fwd(a):
        return value_fn(a), a

    def bwd(a, g):
        return (_unbroadcast(da_fn(a, g), jnp.shape(a)),)

    op.defvjp(fwd, bwd)
    op.__name__ = name
    return op


# Backward rules, paper Table 1. All grads are evaluated with value-level PA
# ops so the backward pass itself is multiplication-free.
_pam_exact = _make_binary(
    pam_value,
    lambda a, b, g: pam_value(pam_exact_dfactor(a, b), g),
    lambda a, b, g: pam_value(pam_exact_dfactor(b, a), g),
    "pam_exact")

_pam_approx = _make_binary(
    pam_value,
    lambda a, b, g: pam_value(b, g),
    lambda a, b, g: pam_value(a, g),
    "pam_approx")

# L-Mul is an *approximation of multiplication*, so only the approx
# derivative family exists (the "exact" piecewise derivative of the offset
# product is the same power-of-two ladder as PAM's and adds nothing);
# core/modes.py gates impl="lmul" to deriv="approx" accordingly. The
# backward products themselves use L-Mul for engine consistency.
_lmul_approx = _make_binary(
    lmul_value,
    lambda a, b, g: lmul_value(b, g),
    lambda a, b, g: lmul_value(a, g),
    "lmul_approx")

_padiv_exact = _make_binary(
    padiv_value,
    lambda a, b, g: pam_value(padiv_exact_dfactor(a, b), g),
    lambda a, b, g: jnp.negative(padiv_value(pam_value(a, g), pam_value(b, b))),
    "padiv_exact")

_padiv_approx = _make_binary(
    padiv_value,
    lambda a, b, g: padiv_value(g, b),
    lambda a, b, g: jnp.negative(padiv_value(pam_value(a, g), pam_value(b, b))),
    "padiv_approx")

_paexp2_exact = _make_unary(
    paexp2_value,
    lambda a, g: fb.pow2_mul(g, jnp.floor(jnp.clip(a, -16384.0, 16384.0)).astype(jnp.int32)),
    "paexp2_exact")

_paexp2_approx = _make_unary(
    paexp2_value,
    lambda a, g: pam_value(pam_value(paexp2_value(a), _LN2), g),
    "paexp2_approx")

_palog2_exact = _make_unary(
    palog2_value,
    lambda a, g: fb.pow2_mul(g, jnp.negative(fb.exponent(a))),
    "palog2_exact")

_palog2_approx = _make_unary(
    palog2_value,
    lambda a, g: padiv_value(g, pam_value(a, _LN2)),
    "palog2_approx")

_BY_DERIV = {
    ("pam", "exact"): _pam_exact, ("pam", "approx"): _pam_approx,
    ("lmul", "approx"): _lmul_approx,
    ("padiv", "exact"): _padiv_exact, ("padiv", "approx"): _padiv_approx,
    ("paexp2", "exact"): _paexp2_exact, ("paexp2", "approx"): _paexp2_approx,
    ("palog2", "exact"): _palog2_exact, ("palog2", "approx"): _palog2_approx,
}


# ---------------------------------------------------------------------------
# Public API. Each op resolves the FloatFormat from its operands
# (_operand_fmt) and coerces scalars to it; for f32 operands this is the
# historical jnp.float32 coercion, bit for bit.
# ---------------------------------------------------------------------------

def _coerced(fmt, *xs):
    return tuple(jnp.asarray(x, fmt.dtype) for x in xs)


def pam(a, b, deriv: str = "approx"):
    """Piecewise-affine multiplication A ·̂ B (paper Eq. 5–8)."""
    return _BY_DERIV[("pam", deriv)](*_coerced(_operand_fmt(a, b), a, b))


def lmul(a, b, deriv: str = "approx"):
    """L-Mul product (PAM + 2^-l mantissa offset); approx deriv only."""
    return _BY_DERIV[("lmul", deriv)](*_coerced(_operand_fmt(a, b), a, b))


def padiv(a, b, deriv: str = "approx"):
    """Piecewise-affine division A ÷̂ B (paper Eq. 14–17)."""
    return _BY_DERIV[("padiv", deriv)](*_coerced(_operand_fmt(a, b), a, b))


def paexp2(a, deriv: str = "approx"):
    """Piecewise-affine 2**A (paper Eq. 9)."""
    return _BY_DERIV[("paexp2", deriv)](*_coerced(_operand_fmt(a), a))


def palog2(a, deriv: str = "approx"):
    """Piecewise-affine log2(A), A > 0 (paper Eq. 10)."""
    return _BY_DERIV[("palog2", deriv)](*_coerced(_operand_fmt(a), a))


def paexp(a, deriv: str = "approx"):
    """paexp(A) = paexp2(log2(e) ·̂ A)  (paper Eq. 18)."""
    return paexp2(pam(a, _LOG2E, deriv), deriv)


def palog(a, deriv: str = "approx"):
    """palog(A) = palog2(A) ÷̂ log2(e)  (paper Eq. 19)."""
    return padiv(palog2(a, deriv), _LOG2E, deriv)


def pasqrt(a, deriv: str = "approx"):
    """pasqrt(A) = paexp2(palog2(A) ÷̂ 2)  (paper Eq. 20). The ÷2 is an exact
    power-of-two scale."""
    return paexp2(fb.pow2_mul(palog2(a, deriv), -1), deriv)


def parecip(a, deriv: str = "approx"):
    """1 ÷̂ A — reciprocal as PA division."""
    return padiv(jnp.float32(1.0), a, deriv)


# §2.7 error compensation: pam(pam(a, b), alpha) reduces the mean/worst-case
# relative error. ALPHA_MEAN zeroes the *mean* relative error over uniformly
# distributed mantissas (numerically integrated); ALPHA_MINMAX centres the
# error band [-1/9, 0] -> [-1/17, +1/17].
ALPHA_MEAN = np.float32(1.0396729)     # 1 / E[pam(a,b)/(ab)], measured over
                                       # uniform mantissas (see benchmarks)
ALPHA_MINMAX = np.float32(18.0 / 17.0)


def pam_compensated(a, b, alpha=ALPHA_MEAN, deriv: str = "approx"):
    """PAM with a constant corrective PAM (paper §2.7)."""
    return pam(pam(a, b, deriv), jnp.float32(alpha), deriv)
