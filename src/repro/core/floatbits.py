"""Bit-level float-format helpers underlying all piecewise-affine (PA)
arithmetic.

Everything here operates on IEEE-754-style floats via integer-carrier bit
manipulation (``lax.bitcast_convert_type``). These are the primitives from
which PAM (piecewise affine multiplication, Kosson & Jaggi 2023 / Mogami 2020)
and its relatives are assembled.

Layout of a float32:  [ S(1) | E(8) | M(23) ]   value = (-1)^S 2^(E-127) (1+M/2^23)

The field layout is abstracted by :class:`FloatFormat` (DESIGN.md §11):
sign/exponent/mantissa widths, bias, and the same-width integer *carrier*
dtype whose adds realise PAM. ``FLOAT32`` is the historical f32/int32
instance; ``BFLOAT16``/``FLOAT16`` carry the bit algebra in int16. The
module-level f32 constants below are retained verbatim (and pinned equal to
``FLOAT32``'s fields) so every pre-refactor call site keeps its exact
immediates — the f32 path is bit-identical by construction.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Bit-field constants (int32 domain).
# ---------------------------------------------------------------------------
SIGN_MASK = np.int32(-(2**31))          # 0x80000000
MAG_MASK = np.int32(0x7FFFFFFF)         # exponent+mantissa magnitude bits
EXP_MASK = np.int32(0x7F800000)
MAN_MASK = np.int32(0x007FFFFF)
MAN_BITS = 23
EXP_BIAS = 127
BIAS_SHIFTED = np.int32(EXP_BIAS << MAN_BITS)      # 0x3F800000 == bits of 1.0f
MIN_NORM = np.int32(1 << MAN_BITS)                 # smallest normal magnitude
MAX_FINITE = np.int32(0x7F7FFFFF)                  # largest finite magnitude
MAX_EXP_FIELD = np.int32(254 << MAN_BITS)          # largest finite exp field
INF_BITS = np.int32(0x7F800000)

# Zero sentinel for the PAM matmul engines (core/matmul.py and
# kernels/pam_matmul/kernel.py — keep in sync, DESIGN.md §2.3). Replaces the
# magnitude of a ZERO operand on the side whose partner's magnitude has the
# bias folded in (partner range [MIN_NORM - BIAS_SHIFTED, MAX_FINITE -
# BIAS_SHIFTED] ⊂ (-2^30, 2^30)): sentinel + partner then stays inside
# [INT32_MIN, 0) — always flushed by the underflow select, never wrapped.
# It does NOT work against a raw (un-bias-subtracted) magnitude, whose
# range reaches 2^31-ish: that side's zeros need an explicit mask. (No pair
# of int32 sentinels can cover both sides: flushing against a raw magnitude
# needs S < MIN_NORM - MAX_FINITE ~ -2^31 + 2^23, and two such sentinels
# wrap past INT32_MIN when both operands are zero.)
PAM_ZERO_SENTINEL = np.int32(-(1 << 30))


# ---------------------------------------------------------------------------
# FloatFormat: layout-generic bit-field description (DESIGN.md §11).
# ---------------------------------------------------------------------------

def _lmul_l(man_bits: int) -> int:
    """L-Mul offset exponent l(m) ("Addition is All You Need", Eq. 7):
    l(m) = m for m <= 3, 3 for m == 4, 4 for m > 4."""
    if man_bits <= 3:
        return man_bits
    if man_bits == 4:
        return 3
    return 4


@dataclasses.dataclass(frozen=True)
class FloatFormat:
    """Bit layout of one IEEE-754-style float format plus its derived PA
    constants, all spelled in the format's integer *carrier* dtype (int32
    for f32, int16 for bf16/f16) so kernel bodies close over same-width
    immediates and every PAM add runs at native lane width.

    Derived-constant semantics mirror the module-level f32 constants; the
    zero sentinel generalises the f32 derivation at PAM_ZERO_SENTINEL:
    ``-(2^(width-2))`` keeps sentinel + bias-folded-partner inside
    ``[carrier_min, 0)`` — always flushed, never wrapped — for any layout
    whose magnitudes occupy width-1 bits. ``LMUL_OFFSET`` is the L-Mul
    mantissa correction ``2^(man_bits - l(man_bits))`` added to the PAM
    magnitude sum (equivalently: a bias fold of ``BIAS_SHIFTED -
    LMUL_OFFSET``).

    ``wide=True`` is the same layout carried in int32 instead: ``bits``
    sign-extends the 16-bit pattern, ``floats`` truncates it back, and the
    constants are the same signed values as int32. TPU kernels use it
    because the v5e vector unit has no 16-bit integer compares or shifts
    (and no bf16 compares); every carrier op that stays inside the int16
    range gives the same result in int32, and the ops that wrap in int16
    land on the same clamp or flush (DESIGN.md §11).
    """

    name: str
    width: int
    exp_bits: int
    man_bits: int
    wide: bool = False

    def __post_init__(self):
        set_ = object.__setattr__
        if self.width == 32:
            dtype, storage = jnp.float32, jnp.int32
        elif self.width == 16 and self.exp_bits == 8:
            dtype, storage = jnp.bfloat16, jnp.int16
        elif self.width == 16 and self.exp_bits == 5:
            dtype, storage = jnp.float16, jnp.int16
        else:
            raise ValueError(f"unsupported float layout: {self!r}")
        carrier = jnp.int32 if self.wide else storage
        np_carrier = np.dtype(carrier).type
        assert 1 + self.exp_bits + self.man_bits == self.width
        m, e = self.man_bits, self.exp_bits
        bias = (1 << (e - 1)) - 1
        set_(self, "exp_bias", bias)
        set_(self, "dtype", dtype)
        set_(self, "storage", storage)
        set_(self, "carrier", carrier)
        set_(self, "np_carrier", np_carrier)
        set_(self, "SIGN_MASK", np_carrier(-(1 << (self.width - 1))))
        set_(self, "MAG_MASK", np_carrier((1 << (self.width - 1)) - 1))
        set_(self, "EXP_MASK", np_carrier(((1 << e) - 1) << m))
        set_(self, "MAN_MASK", np_carrier((1 << m) - 1))
        set_(self, "BIAS_SHIFTED", np_carrier(bias << m))
        set_(self, "MIN_NORM", np_carrier(1 << m))
        set_(self, "MAX_EXP_FIELD", np_carrier(((1 << e) - 2) << m))
        set_(self, "MAX_FINITE",
             np_carrier((((1 << e) - 2) << m) | ((1 << m) - 1)))
        set_(self, "INF_BITS", np_carrier(((1 << e) - 1) << m))
        set_(self, "ZERO_SENTINEL", np_carrier(-(1 << (self.width - 2))))
        set_(self, "LMUL_L", _lmul_l(m))
        set_(self, "LMUL_OFFSET", np_carrier(1 << (m - _lmul_l(m))))

    @property
    def widened(self) -> "FloatFormat":
        """This layout on an int32 carrier (itself for 32-bit formats)."""
        if self.width == 32 or self.wide:
            return self
        return _WIDENED[self.name]


FLOAT32 = FloatFormat("f32", 32, 8, 23)
BFLOAT16 = FloatFormat("bf16", 16, 8, 7)
FLOAT16 = FloatFormat("f16", 16, 5, 10)

FORMATS = {f.name: f for f in (FLOAT32, BFLOAT16, FLOAT16)}
_WIDENED = {f.name: dataclasses.replace(f, wide=True)
            for f in (BFLOAT16, FLOAT16)}

# The refactor invariant: FLOAT32's derived fields ARE the historical
# module constants (same np.int32 values the kernels close over).
assert FLOAT32.SIGN_MASK == SIGN_MASK and FLOAT32.MAG_MASK == MAG_MASK
assert FLOAT32.EXP_MASK == EXP_MASK and FLOAT32.MAN_MASK == MAN_MASK
assert FLOAT32.BIAS_SHIFTED == BIAS_SHIFTED and FLOAT32.MIN_NORM == MIN_NORM
assert FLOAT32.MAX_FINITE == MAX_FINITE
assert FLOAT32.MAX_EXP_FIELD == MAX_EXP_FIELD
assert FLOAT32.INF_BITS == INF_BITS and FLOAT32.exp_bias == EXP_BIAS
assert FLOAT32.ZERO_SENTINEL == PAM_ZERO_SENTINEL
assert FLOAT32.man_bits == MAN_BITS


def format_for_dtype(dtype) -> FloatFormat:
    """Resolve the FloatFormat of a float dtype; raises for unsupported."""
    dt = jnp.dtype(dtype)
    for f in (FLOAT32, BFLOAT16, FLOAT16):
        if jnp.dtype(f.dtype) == dt:
            return f
    raise ValueError(
        f"no PA FloatFormat for dtype {dt} (supported: f32, bf16, f16)")


def bits(x: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    """float -> carrier-int bit pattern (f32->int32 by default)."""
    i = jax.lax.bitcast_convert_type(x.astype(fmt.dtype), fmt.storage)
    return i.astype(fmt.carrier) if fmt.wide else i


def floats(i: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    """carrier-int bit pattern -> float (int32->f32 by default)."""
    return jax.lax.bitcast_convert_type(i.astype(fmt.storage), fmt.dtype)


def cmp_view(x: jax.Array, fmt: FloatFormat) -> jax.Array:
    """``x`` as float compares, floor and round should see it: itself, or
    its exact f32 embedding for a widened narrow format (the TPU vector
    unit compares and rounds only in f32)."""
    return x.astype(jnp.float32) if fmt.wide else x


def sign_bits(x: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    return bits(x, fmt) & fmt.SIGN_MASK


def magnitude_bits(x: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    return bits(x, fmt) & fmt.MAG_MASK


def exponent(x: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    """Unbiased exponent E (carrier int). Denormals/zero report -bias."""
    return (((bits(x, fmt) & fmt.EXP_MASK) >> fmt.man_bits)
            - fmt.np_carrier(fmt.exp_bias))


def mantissa_field(x: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    """Raw mantissa field as the carrier int."""
    return bits(x, fmt) & fmt.MAN_MASK


def mantissa_frac(x: jax.Array) -> jax.Array:
    """Mantissa fraction M in [0, 1) as float32 (exact: power-of-two scale)."""
    return mantissa_field(x).astype(jnp.float32) * np.float32(2.0**-MAN_BITS)


def compose(sign: jax.Array, unbiased_exp: jax.Array, man_field: jax.Array,
            fmt: FloatFormat = FLOAT32) -> jax.Array:
    """Assemble a float from sign bits (already in position), unbiased
    exponent and mantissa field (both carrier ints). Clamps exponent to the
    finite range; underflow flushes to zero (bf16-style, paper §2.2)."""
    e = unbiased_exp + fmt.exp_bias
    mag = (e << fmt.man_bits) | (man_field & fmt.MAN_MASK)
    mag = jnp.where(e <= 0, 0, jnp.minimum(mag, fmt.MAX_FINITE))
    return floats(sign | mag, fmt)


def pow2(k: jax.Array, fmt: FloatFormat = FLOAT32) -> jax.Array:
    """Exact 2**k as a float from an integer exponent, clamped to finite
    range."""
    e = jnp.clip(k + fmt.exp_bias, 1, (1 << fmt.exp_bits) - 2)
    return floats(e.astype(fmt.carrier) << fmt.man_bits, fmt)


def pow2_mul(x: jax.Array, k, fmt: FloatFormat | None = None) -> jax.Array:
    """Exact multiply of ``x`` by 2**k via exponent arithmetic (an int add on
    the bit pattern — multiplication-free and lossless unless it
    over/underflows). ``k`` may be a python int or an integer array
    broadcastable to ``x``. The format follows ``x``'s dtype (non-format
    dtypes coerce to f32, the historical behaviour)."""
    if fmt is None:
        dt = getattr(jnp.asarray(x), "dtype", None)
        fmt = FLOAT32
        if dt is not None and jnp.dtype(dt) in (jnp.bfloat16, jnp.float16):
            fmt = format_for_dtype(dt)
    x = jnp.asarray(x, fmt.dtype)
    i = bits(x, fmt)
    k = jnp.asarray(k, fmt.carrier)
    sign = i & fmt.SIGN_MASK
    mag = (i & fmt.MAG_MASK) + (k << fmt.np_carrier(fmt.man_bits))
    mag = jnp.where(mag < fmt.MIN_NORM, fmt.np_carrier(0),
                    jnp.minimum(mag, fmt.MAX_FINITE))
    out = floats(sign | mag, fmt)
    # preserve zeros / non-finite inputs
    xc = cmp_view(x, fmt)
    return jnp.where((xc == 0) | ~jnp.isfinite(xc), x, out)


def mantissa_round(x: jax.Array, keep_bits: int) -> jax.Array:
    """Round float32 to ``keep_bits`` mantissa bits (round-to-nearest-even).

    This simulates the narrow-mantissa formats of the paper's Appendix D
    (7 bits == bfloat16, 4 bits still trains, 3 bits degrades). Exponent
    range is unchanged (like bfloat16 vs float32). NaN/Inf pass through.
    """
    if keep_bits >= MAN_BITS:
        return jnp.asarray(x, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    s = MAN_BITS - keep_bits
    i = bits(x)
    mag = i & MAG_MASK
    half = np.int32((1 << (s - 1)) - 1)
    odd = (mag >> s) & 1
    mag = (mag + half + odd) & np.int32(~((1 << s) - 1))
    mag = jnp.minimum(mag, MAX_FINITE)
    out = floats((i & SIGN_MASK) | mag)
    return jnp.where(jnp.isfinite(x), out, x)


def is_pow2(x: jax.Array) -> jax.Array:
    """True where |x| is an exact power of two (zero mantissa, normal)."""
    return (mantissa_field(x) == 0) & jnp.isfinite(x) & (x != 0)
