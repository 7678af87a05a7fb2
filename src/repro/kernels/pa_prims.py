"""Shared PA bit-twiddling primitives for every Pallas kernel family.

This is the single kernel-side home of the float32 bit constants and the
piecewise-affine scalar helpers (``_pam`` / ``_padiv`` / ``_paexp2`` /
``_palog2``) that were previously duplicated across ``pa_softmax``,
``pam_eltwise`` and ``pam_matmul``; it also hosts the grouped PAM *tile*
product (``_pam_dot`` on the shared ``_contract`` loop, DESIGN.md §2.1) that
the matmul kernels and the fused PAM flash-attention kernel compose.

The constants are spelled as literal numpy int32 scalars — not imports from
``core.floatbits`` — so a kernel body closes over plain immediates; the
asserts below pin them to the canonical ``floatbits`` definitions, making a
drift impossible.

Scalar-helper semantics match the seed kernels exactly: zero operands force
a zero (0.0-signed) result, denormals compare equal to 0.0 under the
flush-to-zero backends we target, inf/nan inputs are OUT of contract for
``_pam``/``_padiv`` (use ``core.pam`` where full IEEE edges matter), and
``_paexp2`` overflows to +inf at a >= 128 exactly like ``paexp2_value``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import floatbits as _fb

# ---------------------------------------------------------------------------
# Bit-field constants (int32 domain). Literals; pinned to core/floatbits.py.
# ---------------------------------------------------------------------------
_SIGN = np.int32(-(2**31))
_MAG = np.int32(0x7FFFFFFF)
_EXP = np.int32(0x7F800000)
_MAN = np.int32(0x007FFFFF)
_BIAS = np.int32(127 << 23)
_MIN_NORM = np.int32(1 << 23)
_MAX_FINITE = np.int32(0x7F7FFFFF)
_MAX_EXPF = np.int32(254 << 23)
# A-side zero sentinel for the matmul-style tile product (see the derivation
# at floatbits.PAM_ZERO_SENTINEL / DESIGN.md §2.3).
_ZSENT = np.int32(-(1 << 30))

assert _SIGN == _fb.SIGN_MASK and _MAG == _fb.MAG_MASK
assert _EXP == _fb.EXP_MASK and _MAN == _fb.MAN_MASK
assert _BIAS == _fb.BIAS_SHIFTED and _MIN_NORM == _fb.MIN_NORM
assert _MAX_FINITE == _fb.MAX_FINITE and _MAX_EXPF == _fb.MAX_EXP_FIELD
assert _ZSENT == _fb.PAM_ZERO_SENTINEL

_LOG2E = np.float32(1.4426950408889634)
_LN2 = np.float32(0.6931471805599453)

# ---------------------------------------------------------------------------
# Transfer-function error bands (DESIGN.md §10). These are the analytic
# worst-case relative-error constants of the scalar helpers below, derived
# from the paper's piecewise-affine definitions; the abstract interpreter's
# error domain (analysis/domains.py) uses the same values as its per-op
# transfer functions, and tests/test_absint.py pins the two sets equal.
#
# Derivations (a = 2^ea (1+fa), b = 2^eb (1+fb), f in [0, 1)):
#   _pam:    pam(a,b)/(a*b) = (1+fa+fb+[fa+fb>=1]) / ((1+fa)(1+fb)); the
#            numerator is the mantissa-field add with carry into the
#            exponent, so the ratio lies in [8/9, 1] — worst at
#            fa = fb = 1/2 (ratio 2/(9/4)), exact when fa*fb = 0.
#   _padiv:  padiv(a,b)*(b/a) lies in [1, 9/8]: the mantissa subtract
#            drops the fa*fb cross term of the true quotient expansion,
#            worst again at fa = fb = 1/2.
#   _paexp2: Mitchell read-off 2^x ~ 2^floor(x) (1+frac(x)); relative
#            error (1+f)/2^f - 1 peaks at f = 1/ln2 - 1 with value
#            2^log2(e/(e... )) = 2^EPS_LOG2 - 1 ~ 0.061476.
#   _palog2: log2(1+f) ~ f; |f - log2(1+f)| peaks at the same critical
#            point f = 1/ln2 - 1 with value ~0.0860713 (ABSOLUTE error —
#            log2 output crosses zero, so no relative band exists).
PAM_REL_WORST = 1.0 / 9.0
PADIV_REL_WORST = 1.0 / 8.0
LOG2_ABS_WORST = 0.0860713320559342          # max_f |f - log2(1+f)|
EXP2_REL_WORST = 2.0 ** LOG2_ABS_WORST - 1.0  # ~0.061476
#   L-Mul (l = 4, every supported format): the mantissa-add product with
#   the +2^-l offset folded into the re-bias. No-carry ratio
#   (1+fa+fb+2^-l)/((1+fa)(1+fb)) peaks at +2^-l (fa = fb = 0); the
#   deficit side is worst on the carry boundary fa = fb = 15/32 where the
#   ratio is 2048/2209, so the band is [-161/2209, +1/16] ~ [-7.29%, +6.25%]
#   — tighter than PAM's [-1/9, 0] but two-sided.
LMUL_REL_WORST = 161.0 / 2209.0              # ~0.072885, fa = fb = 15/32
LMUL_REL_PLUS = 1.0 / 16.0                   # +2^-l at fa = fb = 0


# ---------------------------------------------------------------------------
# Elementwise PA helpers (VPU-friendly: pure int vector ops + one select).
# ---------------------------------------------------------------------------

def _pam(a, b):
    """Elementwise PAM a ·̂ b for finite/zero float32 (kernel contract).
    Operands broadcast first: a TPU kernel bitcasts vectors, never scalars."""
    a, b = jnp.broadcast_arrays(a, b)
    ai = jax.lax.bitcast_convert_type(a, jnp.int32)
    bi = jax.lax.bitcast_convert_type(b, jnp.int32)
    sign = (ai ^ bi) & _SIGN
    mag = (ai & _MAG) + (bi & _MAG) - _BIAS
    ovf = mag < -_BIAS      # disjoint-ranges int32 overflow test
    mag = jnp.where(mag < _MIN_NORM, 0, jnp.minimum(mag, _MAX_FINITE))
    mag = jnp.where(ovf, _MAX_FINITE, mag)
    out = jax.lax.bitcast_convert_type(sign | mag, jnp.float32)
    return jnp.where((a == 0.0) | (b == 0.0), 0.0, out)


def _padiv(a, b):
    """Elementwise PA division a ÷̂ b for finite/zero a, finite nonzero b."""
    a, b = jnp.broadcast_arrays(a, b)
    ai = jax.lax.bitcast_convert_type(a, jnp.int32)
    bi = jax.lax.bitcast_convert_type(b, jnp.int32)
    sign = (ai ^ bi) & _SIGN
    mag = (ai & _MAG) - (bi & _MAG) + _BIAS
    ovf = mag < -_BIAS      # disjoint-ranges int32 overflow test
    mag = jnp.where(mag < _MIN_NORM, 0, jnp.minimum(mag, _MAX_FINITE))
    mag = jnp.where(ovf, _MAX_FINITE, mag)
    out = jax.lax.bitcast_convert_type(sign | mag, jnp.float32)
    return jnp.where(a == 0.0, 0.0, out)


def _paexp2(a):
    """Elementwise paexp2 (paper Eq. 9); overflows to +inf at a >= 128."""
    ac = jnp.clip(a, -16384.0, 16384.0)
    n = jnp.floor(ac)
    man = jnp.round((ac - n) * np.float32(2.0**23)).astype(jnp.int32)
    e = n.astype(jnp.int32) + (man >> 23) + 127
    mag = (e << 23) | (man & _MAN)
    mag = jnp.where(e <= 0, 0, jnp.minimum(mag, _MAX_FINITE))
    out = jax.lax.bitcast_convert_type(mag, jnp.float32)
    return jnp.where(a >= 128.0, jnp.float32(jnp.inf), out)


def _palog2(a):
    """Elementwise palog2 (paper Eq. 10) for a > 0."""
    i = jax.lax.bitcast_convert_type(a, jnp.int32)
    return (i - _BIAS).astype(jnp.float32) * np.float32(2.0**-23)


# ---------------------------------------------------------------------------
# Grouped PAM tile product (DESIGN.md §2.1) — shared by the pam_matmul
# kernels and the fused PAM flash-attention kernel.
#
# A contraction is a sum of rank-1 "outer" steps: step c combines column c
# of the A-side arrays (M, C) with row c of the B-side arrays (C, N). On the
# TPU vector unit a column is a lane slice broadcast across lanes and a row
# is a sublane slice broadcast across sublanes; no gather is ever formed.
# Two-level reduction: ``g`` steps accumulate into a group partial, and the
# group partials add into the running sum in contraction order.
#
# The loop form follows from two static facts: the contraction length and
# the rows a step writes. Steps run in static chunks, where every column
# and row is a static slice and the scheduler overlaps the steps' lane
# broadcasts and adds. A contraction that is not a multiple of 128 lanes
# (the attention head dim, decode-sized tails, small test tiles) is one
# chunk. An aligned one takes as many whole groups per chunk as keep
# steps x vreg rows of a step's (M, N) partial within
# ``_CHUNK_VREG_STEPS``: a TPU tile's 128 steps are one chunk up to 64
# rows, and 128 rows take chunks of 64 steps, since longer static bodies
# outgrow the scoped VMEM stack in the attention backward. Chunks of a
# longer contraction run as a ``fori_loop``: the A side is rotated
# (``pltpu.roll``) so the chunk's first column sits in lane 0, and the B
# side waits in scoped VMEM so each chunk loads its rows with one aligned
# dynamic sublane slice. An iteration is a serial chain that costs about
# 20 single-vreg steps on a v5e, so short chunks are dear (DESIGN.md §2.1
# has the measurements). The loop carry starts at -0.0, the identity of
# IEEE addition, so every form adds the same values in the same order:
# ``g`` steps into a group partial, group partials into the running sum in
# contraction order. A tile product's bits do not depend on its form.
# A chunk is traced once per block shape (``_chunk``), from lax primitives.
# ---------------------------------------------------------------------------

# Largest static chunk: steps x vreg rows (8 sublanes) of a step's partial.
_CHUNK_VREG_STEPS = 1024


def _contract(a_side, b_side, product, g):
    """Sum over the contraction axis of ``product(cols, rows)``.

    a_side: tuple of (M, C) carrier arrays; b_side: tuple of (C, N) carrier
    arrays; ``product`` maps the step's (M, 1) columns and (1, N) rows to
    an (M, N) f32 partial. ``g`` must divide C.
    """
    m, c_len = a_side[0].shape
    ng = c_len // g
    u = ng if c_len % 128 else _largest_divisor(
        ng, _CHUNK_VREG_STEPS // (g * -(-m // 8)))      # groups per chunk
    s = u * g

    if s == c_len:
        return _chunk(product, u, g)(a_side, b_side)

    def run(*scratch):
        for ref, y in zip(scratch, b_side):
            ref[...] = y

        def body(c, acc):
            off = pl.multiple_of(c * s, s)
            cols = tuple(pltpu.roll(x, (c_len - off) % c_len, 1)
                         for x in a_side)
            rows = tuple(ref[pl.ds(off, s), :] for ref in scratch)
            return _chunk(product, u, g)(cols, rows, acc)

        neg0 = jnp.full((m, b_side[0].shape[1]), -0.0, jnp.float32)
        return jax.lax.fori_loop(0, c_len // s, body, neg0)

    return pl.run_scoped(run, *[pltpu.VMEM(y.shape, y.dtype)
                                for y in b_side])


@functools.lru_cache(maxsize=None)
def _chunk(product, u, g):
    """``u`` groups of ``g`` static steps over the first ``u * g`` columns
    of the A side and rows of the B side, added to ``acc`` when given.
    Jitted, so that every kernel and problem shape with the same block
    shapes shares one trace: the compilation cache keeps no traces, and a
    static chunk is thousands of operations."""
    def chunk(cols, rows, acc=None):
        for q in range(u):
            part = None
            for j in range(q * g, (q + 1) * g):
                p = product(
                    tuple(lax.slice_in_dim(x, j, j + 1, axis=1)
                          for x in cols),
                    tuple(lax.slice_in_dim(y, j, j + 1, axis=0)
                          for y in rows))
                part = p if part is None else lax.add(part, p)
            acc = part if acc is None else lax.add(acc, part)
        return acc

    return jax.jit(chunk)


def _largest_divisor(n, g):
    g_ = max(1, min(g, n))
    while n % g_:
        g_ -= 1
    return g_


def _make_pam_dot(fmt, fold):
    """(bm, bk) ·̂ (bk, bn) PAM tile product for one carrier format.

    Prep bitcasts each tile once: the A side keeps sign bits and
    zero-SENTINELED magnitudes, the B side sign bits, magnitudes with the
    re-bias ``fold`` subtracted and an explicit zero AND-mask (the sentinel
    only flushes against a bias-folded partner — floatbits.PAM_ZERO_SENTINEL
    has the derivation). Each product is one carrier add, the flush/clamp
    select, the mask, the sign xor/or — then its exact f32 embedding (a
    16-bit pattern shifted into the high half) joins the f32 sum.
    """
    SIGN, MAG = fmt.SIGN_MASK, fmt.MAG_MASK
    MINN, MAXF, ZSENT = fmt.MIN_NORM, fmt.MAX_FINITE, fmt.ZERO_SENTINEL
    to_f32 = np.int32(32 - fmt.width)
    ZERO, NEG1 = fmt.np_carrier(0), fmt.np_carrier(-1)

    def is_zero(x, xi):
        if fmt.width == 32:
            # Float compare: flush-to-zero backends make denormals == 0.0.
            return x == 0.0
        # Exponent-field test: explicit denormal flush in the carrier.
        return (xi & fmt.EXP_MASK) == ZERO

    def product(cols, rows):
        # lax ops, not jnp: each jnp op is a nested jit trace, and a static
        # chunk traces this once per step.
        sa, am = (lax.broadcast_in_dim(x, (x.shape[0], rows[0].shape[1]),
                                       (0, 1)) for x in cols)
        sb, bmg, bzm = (lax.broadcast_in_dim(y, sa.shape, (0, 1))
                        for y in rows)
        mag = lax.add(am, bmg)
        mag = lax.select(lax.lt(mag, MINN), lax.full_like(mag, ZERO),
                         lax.min(mag, MAXF))
        mag = lax.bitwise_and(mag, bzm)
        bits = lax.convert_element_type(
            lax.bitwise_or(lax.bitwise_xor(sa, sb), mag), jnp.int32)
        if to_f32:
            bits = lax.shift_left(bits, to_f32)
        return lax.bitcast_convert_type(bits, jnp.float32)

    def pam_dot(a, b, g):
        ai = _fb.bits(a, fmt)
        bi = _fb.bits(b, fmt)
        a_side = (ai & SIGN, jnp.where(is_zero(a, ai), ZSENT, ai & MAG))
        b_side = (bi & SIGN, (bi & MAG) - fold,
                  jnp.where(is_zero(b, bi), ZERO, NEG1))
        return _contract(a_side, b_side, product,
                         _largest_divisor(a.shape[-1], g))

    return pam_dot


# (bm, bk) ·̂ (bk, bn) f32 PAM tile product, ``g`` lowered to the largest
# divisor of the contraction axis.
_pam_dot = _make_pam_dot(_fb.FLOAT32, _BIAS)


# ---------------------------------------------------------------------------
# Per-format prims (FloatFormat engine family, DESIGN.md §11).
#
# ``get_prims(fmt_name, lmul)`` returns a namespace with the same five
# helpers as the module level, specialised to one FloatFormat and, when
# ``lmul`` is set, the L-Mul mantissa offset folded into the re-bias (one
# fused constant, zero extra adds per product).
#
# The ("f32", lmul=False) instance binds the module-level functions verbatim,
# so the historical f32 path is bit-identical by construction, not by test.
#
# Kernel carriers are int32 for every format: narrow formats run on their
# widened FloatFormat (16-bit patterns sign-extended into int32), because
# the TPU v5e vector unit has no 16-bit integer compares or shifts and no
# bf16 compares, floor or round. Float compares, floor and round of narrow
# values therefore run on their exact f32 embedding; float adds and the
# operands/results stay in the format's dtype. Against the int16-carrier
# jnp engine (core/pam.py, core/matmul.py) the narrow-format deltas are:
#   * zero test is the EXPONENT FIELD, not a float compare — the carrier
#     sees bf16 denormals explicitly, so the flush documented by the absint
#     domain (quantize-then-flush below 2^-126) is spelled out in bits;
#   * products below MIN_NORM flush to +0, magnitude sums saturate at
#     MAX_FINITE; a sum that wraps the int16 carrier lands on the same clamp
#     in int32 (the disjoint-ranges overflow test ``mag < -BIAS`` never
#     fires there, the ``minimum`` does) — bit-identical results;
#   * grouped tile products keep each PAM product's bits and ACCUMULATE IN
#     F32 (exact bf16->f32 embedding), matching the kernels' f32 VMEM
#     scratch posture. A tile product whose per-product magnitude reaches
#     2^128 is outside the contract: the int32 sum saturates where the int16
#     engine's wraps and flushes.
# ---------------------------------------------------------------------------


class Prims:
    """Bound PA primitives for one (FloatFormat, lmul) pair."""

    __slots__ = ("fmt", "lmul", "pam", "padiv", "paexp2", "palog2",
                 "pam_dot")

    def __init__(self, fmt, lmul, **fns):
        self.fmt = fmt
        self.lmul = lmul
        for k, v in fns.items():
            setattr(self, k, v)


def _build_prims(wf, lmul):
    """Prims on carrier format ``wf``: ``get_prims`` passes the widened
    (int32) format; the int16 instance exists only to pin the two equal."""
    fmt = _fb.FORMATS[wf.name]
    nc = wf.np_carrier
    C = wf.carrier
    dt = fmt.dtype
    SIGN, MAG, MAN = wf.SIGN_MASK, wf.MAG_MASK, wf.MAN_MASK
    BIAS, MINN, MAXF = wf.BIAS_SHIFTED, wf.MIN_NORM, wf.MAX_FINITE
    EXP = wf.EXP_MASK
    MB = fmt.man_bits
    # L-Mul folds its +2^-l mantissa offset into the re-bias constant. The
    # sentinel/overflow band proofs absorb the shift: it is <= 2^(MB-3),
    # tiny against the 2^MB-wide guard bands (checked for both carriers in
    # tests/test_format_dispatch.py).
    FOLD = nc(int(BIAS) - (int(wf.LMUL_OFFSET) if lmul else 0))
    ZERO = nc(0)
    shMB = nc(MB)

    if fmt.width == 32:
        def _is_zero(x, xi):
            # Float compare: flush-to-zero backends make denormals == 0.0.
            return x == 0.0
    else:
        def _is_zero(x, xi):
            # Exponent-field test: explicit denormal flush in the carrier.
            return (xi & EXP) == ZERO

    def pam(a, b):
        a, b = jnp.broadcast_arrays(a, b)
        ai = _fb.bits(a, wf)
        bi = _fb.bits(b, wf)
        sign = (ai ^ bi) & SIGN
        mag = (ai & MAG) + (bi & MAG) - FOLD
        ovf = mag < -BIAS       # disjoint-ranges carrier overflow test
        mag = jnp.where(mag < MINN, ZERO, jnp.minimum(mag, MAXF))
        mag = jnp.where(ovf, MAXF, mag)
        out = _fb.floats(sign | mag, wf)
        zero = _is_zero(a, ai) | _is_zero(b, bi)
        return jnp.where(zero, jnp.zeros((), dt), out)

    def padiv(a, b):
        # L-Mul is a product approximation only; division keeps plain PA.
        a, b = jnp.broadcast_arrays(a, b)
        ai = _fb.bits(a, wf)
        bi = _fb.bits(b, wf)
        sign = (ai ^ bi) & SIGN
        mag = (ai & MAG) - (bi & MAG) + BIAS
        ovf = mag < -BIAS
        mag = jnp.where(mag < MINN, ZERO, jnp.minimum(mag, MAXF))
        mag = jnp.where(ovf, MAXF, mag)
        out = _fb.floats(sign | mag, wf)
        return jnp.where(_is_zero(a, ai), jnp.zeros((), dt), out)

    def paexp2(a):
        # Clip bounds / overflow threshold are exact in every format
        # (powers of two); for a < 128 the biased exponent fits the carrier
        # un-wrapped, and a >= 128 is overridden to +inf below.
        ac = jnp.clip(a, -16384.0, 16384.0)
        n = jnp.floor(_fb.cmp_view(ac, wf)).astype(dt)
        man = jnp.round(_fb.cmp_view((ac - n) * jnp.asarray(2.0**MB, dt),
                                     wf)).astype(C)
        e = n.astype(C) + (man >> shMB) + nc(fmt.exp_bias)
        mag = (e << shMB) | (man & MAN)
        mag = jnp.where(e <= ZERO, ZERO, jnp.minimum(mag, MAXF))
        out = _fb.floats(mag, wf)
        return jnp.where(_fb.cmp_view(a, wf) >= 128.0,
                         jnp.asarray(jnp.inf, dt), out)

    def palog2(a):
        i = _fb.bits(a, wf)
        return (i - BIAS).astype(dt) * jnp.asarray(2.0**-MB, dt)

    return Prims(wf, lmul, pam=pam, padiv=padiv, paexp2=paexp2,
                 palog2=palog2, pam_dot=_make_pam_dot(wf, FOLD))


@functools.lru_cache(maxsize=None)
def get_prims(fmt_name: str = "f32", lmul: bool = False) -> Prims:
    """Primitives namespace for ``fmt_name`` ("f32" / "bf16" / "f16").

    The plain-f32 instance IS the module level: same function objects, so
    every existing kernel trace is untouched by the format refactor.
    """
    fmt = _fb.FORMATS[fmt_name]
    if fmt_name == "f32" and not lmul:
        return Prims(fmt, False, pam=_pam, padiv=_padiv, paexp2=_paexp2,
                     palog2=_palog2, pam_dot=_pam_dot)
    return _build_prims(fmt.widened, lmul)
