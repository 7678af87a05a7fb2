"""PA matrix multiplication (paper §3.2) — the framework's hot path.

``pa_matmul(a, b, pa=...)`` mirrors ``jnp.matmul`` semantics
(a: (..., M, K) @ b: (..., K, N), broadcastable batch dims) and routes by
``PAConfig``:

  * ``mode`` off        -> ``jnp.matmul`` (baseline)
  * ``impl`` "jnp"      -> bit-exact PAM contraction, grouped k-blocks with a
                           cost-model-sized ``lax.scan`` for large K
  * ``impl`` "pallas"   -> Pallas TPU kernels (kernels/pam_matmul), forward
                           AND backward
  * ``impl`` "hw"       -> ``jnp.matmul`` stand-in for a PAM-MXU (identical
                           dataflow/sharding; scalar semantics standard) —
                           used by the full-scale dry-run / roofline.

Backward pass implements the paper's Table 1 at matrix granularity:
approx: dA = g ·̂ Bᵀ, dB = Aᵀ ·̂ g (PAM matmuls); exact: the power-of-two
factor contraction, multiplication-free via PAM-by-pow2. Under
``impl="pallas"`` both variants run through the batched kernel entry points
instead of the jnp chunked scan.

The jnp path shares the engine's numeric contract (DESIGN.md §2.3):
bit-exact per product vs ``pam_value`` for zero or finite inputs with
per-product magnitude below 2^128 (clamping preserved up to 2^129); inf/nan
are outside the contract. Operands are bitcast and sign/magnitude-prepped
ONCE per matmul — never inside the contraction loop — and zero operands map
to a magnitude sentinel that flushes in the underflow select, so the inner
loop is 8 integer vector ops per scalar product.
"""
from __future__ import annotations

import functools
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from . import floatbits as fb
from .pam import (pam_value as _pam_value_op, ALPHA_MEAN as _ALPHA_MEAN,
                  _unbroadcast)
from .modes import PAConfig

_SIGN = fb.SIGN_MASK
_MAG = fb.MAG_MASK
_EXP = fb.EXP_MASK
_MAN = fb.MAN_MASK
_BIAS = fb.BIAS_SHIFTED
_MIN_NORM = fb.MIN_NORM
_MAX_EXPF = fb.MAX_EXP_FIELD
_MAX_FINITE = fb.MAX_FINITE
# A-side zero sentinel; B-side zeros use an explicit mask (derivation at
# floatbits.PAM_ZERO_SENTINEL, DESIGN.md §2.3).
_ZSENT = fb.PAM_ZERO_SENTINEL

# Group size for the two-level reduction (g products accumulate in
# registers before the cross-group vector reduce).
_GROUP = 16


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


def _zero_mask(x, xi, fmt):
    """Operand-is-zero test for the prep step. f32 keeps the float compare
    (bit-identical to the seed engine); narrow carriers test the exponent
    field, making the denormal flush explicit (DESIGN.md §11)."""
    if fmt.width == 32:
        return x == 0.0
    return (xi & fmt.EXP_MASK) == fmt.np_carrier(0)


def _fold_const(fmt, lmul: bool):
    """B-side re-bias fold: BIAS for PAM, BIAS - LMUL_OFFSET for L-Mul."""
    if not lmul:
        return fmt.BIAS_SHIFTED
    return fmt.np_carrier(int(fmt.BIAS_SHIFTED) - int(fmt.LMUL_OFFSET))


# ---------------------------------------------------------------------------
# Cost model for the scan chunk size.
#
# The grouped contraction materialises a (kc/g, M, N) partial-sums block per
# scan step. Too small wastes scan overhead; too large spills the cache
# hierarchy (the block is written by one fused loop and read back by the
# reduce). The default budget is a FIXED constant (measured optimum on the
# reference host; chunk boundaries move f32 accumulation order, so a
# load-dependent choice would make outputs vary run-to-run — accumulation
# order is non-contractual but determinism is worth keeping by default).
# Machine-specific tuning is explicit: REPRO_PAM_CHUNK_ELEMS pins the
# budget; REPRO_PAM_CHUNK_CALIBRATE=1 times a probe matmul at the candidate
# budgets once per process and keeps the winner. Problems that fit the
# smallest candidate never chunk, so test workloads are probe-free.
# ---------------------------------------------------------------------------

_BUDGET_CANDIDATES = (1 << 20, 1 << 22, 1 << 24)
_BUDGET_DEFAULT = 1 << 22
_budget_cache: list = []


def _chunk_budget() -> int:
    env = os.environ.get("REPRO_PAM_CHUNK_ELEMS")
    if env:
        return max(1 << 16, int(env))
    if not os.environ.get("REPRO_PAM_CHUNK_CALIBRATE"):
        return _BUDGET_DEFAULT
    if _budget_cache:
        return _budget_cache[0]
    best, best_us = _BUDGET_DEFAULT, None
    try:
        probe_a = jnp.ones((128, 4096), jnp.float32)
        probe_b = jnp.ones((4096, 128), jnp.float32)
        for cand in _BUDGET_CANDIDATES:
            fn = jax.jit(functools.partial(_pam_matmul_value, budget=cand))
            jax.block_until_ready(fn(probe_a, probe_b))      # compile
            t0 = time.perf_counter()
            for _ in range(3):
                out = fn(probe_a, probe_b)
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / 3 * 1e6
            if best_us is None or us < best_us:
                best, best_us = cand, us
    except Exception:        # pragma: no cover - calibration is best-effort
        pass
    _budget_cache.append(best)
    return best


def _chunk_k(m: int, k: int, n: int, g: int, budget: int | None) -> int:
    """Contraction chunk (multiple of g) whose partial block fits the
    budget. Problems under the smallest candidate never trigger the probe."""
    per_slice = max(1, m * n)
    if (k // g) * per_slice <= _BUDGET_CANDIDATES[0]:
        return k
    if budget is None:
        budget = _chunk_budget()
    kc = max(1, budget // per_slice) * g
    return min(k, max(g, kc))


# ---------------------------------------------------------------------------
# Grouped bit-level building blocks (shared by value and exact-grad paths).
# ---------------------------------------------------------------------------

def _prep_operands(a, b, fmt=fb.FLOAT32, lmul: bool = False):
    """Bitcast ONCE: (saT, amT) k-major for a (zero-sentineled magnitudes),
    (sb, bmg, bz) for b (bias-folded magnitudes + zero mask — the sentinel
    only flushes against a bias-folded partner, see
    floatbits.PAM_ZERO_SENTINEL). All reshaped to (..., K/g, g, dim) with K
    zero-padded to a multiple of g. Bit math runs in ``fmt``'s carrier
    (int32 for f32, int16 for bf16); ``lmul`` folds the L-Mul mantissa
    offset into the B-side re-bias."""
    a, b = jnp.asarray(a, fmt.dtype), jnp.asarray(b, fmt.dtype)
    k = a.shape[-1]
    g = max(1, min(_GROUP, k))
    kp = -(-k // g) * g
    if kp != k:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, kp - k)])
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, kp - k), (0, 0)])
    ai, bi = fb.bits(a, fmt), fb.bits(b, fmt)
    # f32 zero tests are FLOAT compares: under flush-to-zero arithmetic (CPU
    # and TPU) denormal inputs equal 0.0, matching pam_value's semantics.
    # (Narrow carriers use the exponent-field test — see _zero_mask.)
    # The B mask is an int AND-mask (0 where b==0, else ~0) — one vpand per
    # inner element instead of a bool select.
    az = _zero_mask(a, ai, fmt)
    bz = _zero_mask(b, bi, fmt)
    saT = _swap(ai & fmt.SIGN_MASK)                # (..., K, M)
    amT = _swap(jnp.where(az, fmt.ZERO_SENTINEL, ai & fmt.MAG_MASK))
    sb = bi & fmt.SIGN_MASK                        # (..., K, N)
    bmg = (bi & fmt.MAG_MASK) - _fold_const(fmt, lmul)
    bzM = jnp.where(bz, 0, -1).astype(fmt.carrier)

    def grp(x):
        return x.reshape(x.shape[:-2] + (kp // g, g) + x.shape[-1:])

    return grp(saT), grp(amT), grp(sb), grp(bmg), grp(bzM), g


def _grouped_pam_sum(saT, amT, sb, bmg, bzM, g, fmt=fb.FLOAT32):
    """sum_k pam(a, b) for prepped (..., C, g, M) / (..., C, g, N) chunks ->
    (..., M, N) float32. Two-level reduction: g in-register adds, then one
    vector reduce over the C group axis. Products stay in ``fmt``'s carrier;
    partial sums accumulate in f32 (exact embedding for bf16/f16, a no-op
    on the f32 path).

    NOTE: keep the per-product bit algorithm in sync with the kernels'
    tile product (kernels/pa_prims.py::_make_pam_dot); the kernels add
    their group partials in contraction order, this engine tree-reduces
    them, so sums agree to f32 rounding."""
    part = None
    for j in range(g):
        mag = amT[..., :, j, :, None] + bmg[..., :, j, None, :]
        mag = jnp.where(mag < fmt.MIN_NORM, 0, jnp.minimum(mag, fmt.MAX_FINITE))
        mag = mag & bzM[..., :, j, None, :]               # PAM(a, ±0) = ±0
        bits = (saT[..., :, j, :, None] ^ sb[..., :, j, None, :]) | mag
        p = fb.floats(bits, fmt).astype(jnp.float32)
        part = p if part is None else part + p
    return jnp.sum(part, axis=-3)


def _pam_matmul_value(a, b, *, budget: int | None = None, fmt=fb.FLOAT32,
                      lmul: bool = False):
    """Bit-exact PAM matmul on the jnp path; grouped k-blocks, cost-model
    chunked ``lax.scan`` over the contraction axis for large problems.
    Output dtype is ``fmt.dtype`` (accumulation stays f32 internally)."""
    a, b = jnp.asarray(a, fmt.dtype), jnp.asarray(b, fmt.dtype)
    m, k, n = a.shape[-2], a.shape[-1], b.shape[-1]
    saT, amT, sb, bmg, bzM, g = _prep_operands(a, b, fmt, lmul)
    ng = saT.shape[-3]                             # K(padded) / g groups
    kc = _chunk_k(m, ng * g, n, g, budget)
    nc = kc // g                                   # groups per scan chunk

    if ng <= nc:
        return _grouped_pam_sum(saT, amT, sb, bmg, bzM, g, fmt).astype(fmt.dtype)

    # Pad the GROUP axis so it splits into whole scan steps. Padded slices
    # look like zero operands (A sentinel / B AND-mask 0) and flush; no
    # float re-pad of the operands happens inside the scan.
    nsteps = -(-ng // nc)
    gpad = nsteps * nc - ng

    def split(x, padval=0):
        if gpad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(0, gpad), (0, 0), (0, 0)],
                        constant_values=padval)
        x = x.reshape(x.shape[:-3] + (nsteps, nc) + x.shape[-2:])
        return jnp.moveaxis(x, -4, 0)              # (nsteps, ..., nc, g, dim)

    xs = (split(saT), split(amT, fmt.ZERO_SENTINEL), split(sb), split(bmg),
          split(bzM))
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    acc0 = jnp.zeros(batch + (m, n), jnp.float32)

    def body(acc, chunk):
        return acc + _grouped_pam_sum(*chunk, g, fmt), ()

    acc, _ = jax.lax.scan(body, acc0, xs)
    return acc.astype(fmt.dtype)


def _exact_grad_a(a, b, g_, *, budget: int | None = None):
    """dA[..., m, k] = sum_n pam(dfactor(a[m,k], b[k,n]), g[m,n]) — the
    paper's Table 1 power-of-two factor contraction, fused at the bit level
    (no dfactor tensor) and chunked over n by the same cost model."""
    a, b, g_ = _f32(a), _f32(b), _f32(g_)
    m, k, n = a.shape[-2], a.shape[-1], b.shape[-1]
    grp = max(1, min(_GROUP, n))
    np_ = -(-n // grp) * grp
    if np_ != n:
        # padded G columns are zero -> masked out; padded B columns idem
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, np_ - n)])
        g_ = jnp.pad(g_, [(0, 0)] * (g_.ndim - 1) + [(0, np_ - n)])

    ai = jax.lax.bitcast_convert_type(a, jnp.int32)
    bi = jax.lax.bitcast_convert_type(b, jnp.int32)
    gi = jax.lax.bitcast_convert_type(g_, jnp.int32)
    maf_a = ai & _MAN                              # (..., M, K)
    bT, giT = _swap(b), _swap(gi)
    biT = _swap(bi)                                # (..., N, K)
    ebT = biT & _EXP
    sbT = biT & _SIGN
    mbT = biT & _MAN
    bzT = bT == 0.0
    sgT = giT & _SIGN                              # (..., N, M)
    gzT = _swap(g_) == 0.0
    gmgT = (giT & _MAG) - _BIAS

    def group(x):
        return x.reshape(x.shape[:-2] + (np_ // grp, grp) + x.shape[-1:])

    ebT, sbT, mbT, bzT = group(ebT), group(sbT), group(mbT), group(bzT)
    sgT, gzT, gmgT = group(sgT), group(gzT), group(gmgT)

    def chunk_sum(ebc, sbc, mbc, bzc, sgc, gzc, gmgc):
        part = None
        for j in range(grp):
            carry = (maf_a[..., None, :, :] + mbc[..., :, j, None, :]) & _MIN_NORM
            magf = jnp.clip(ebc[..., :, j, None, :] + carry, _MIN_NORM, _MAX_EXPF)
            mag = magf + gmgc[..., :, j, :, None]
            mag = jnp.where(mag < _MIN_NORM, 0, jnp.minimum(mag, _MAX_FINITE))
            bits = (sbc[..., :, j, None, :] ^ sgc[..., :, j, :, None]) | mag
            p = jax.lax.bitcast_convert_type(bits, jnp.float32)
            zero = bzc[..., :, j, None, :] | gzc[..., :, j, :, None]
            p = jnp.where(zero, 0.0, p)
            part = p if part is None else part + p
        return jnp.sum(part, axis=-3)

    ngp = np_ // grp
    nc = _chunk_k(m, np_, k, grp, budget) // grp
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])

    if ngp <= nc:
        return chunk_sum(ebT, sbT, mbT, bzT, sgT, gzT, gmgT)

    nsteps = -(-ngp // nc)
    gpad = nsteps * nc - ngp

    def split(x, pad_true=False):
        if gpad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(0, gpad), (0, 0), (0, 0)],
                        constant_values=(True if pad_true else 0))
        x = x.reshape(x.shape[:-3] + (nsteps, nc) + x.shape[-2:])
        return jnp.moveaxis(x, -4, 0)

    xs = (split(ebT), split(sbT), split(mbT), split(bzT, True),
          split(sgT), split(gzT, True), split(gmgT))
    acc0 = jnp.zeros(batch + (m, k), jnp.float32)

    def body(acc, c):
        return acc + chunk_sum(*c), ()

    acc, _ = jax.lax.scan(body, acc0, xs)
    return acc


def _exact_grad_b(a, b, g):
    """dB[..., k, n] = sum_m pam(dfactor(b[k,n], a[m,k]), g[m,n])."""
    # Reuse _exact_grad_a through transposition: dB = (dA of (Bᵀ, Aᵀ, gᵀ))ᵀ.
    return _swap(_exact_grad_a(_swap(b), _swap(a), _swap(g)))


def _round_inputs(a, b, mantissa_bits):
    if mantissa_bits is not None:
        a = fb.mantissa_round(a, mantissa_bits)
        b = fb.mantissa_round(b, mantissa_bits)
    return a, b


@functools.lru_cache(maxsize=None)
def _build(deriv: str, impl: str, mantissa_bits, compensate: bool,
           fmt_name: str = "f32"):
    """Build a custom_vjp PAM matmul for a static numeric configuration."""
    fmt = fb.FORMATS[fmt_name]
    lmul = impl == "lmul"
    if fmt_name != "f32" and mantissa_bits is not None:
        raise ValueError(
            "mantissa_bits simulation is an f32-path feature; "
            f"fmt={fmt_name!r} already has a narrow mantissa")

    if impl == "pallas":
        from repro.kernels.pam_matmul import ops as _kops

        def value(a, b):
            a, b = _round_inputs(jnp.asarray(a, fmt.dtype),
                                 jnp.asarray(b, fmt.dtype), mantissa_bits)
            return _kops.pam_matmul(a, b, fmt_name=fmt_name)

        def grad_exact(a, b, g):
            return (_kops.pam_exact_grad_a(a, b, g),
                    _kops.pam_exact_grad_b(a, b, g))
    else:
        def value(a, b):
            a, b = _round_inputs(jnp.asarray(a, fmt.dtype),
                                 jnp.asarray(b, fmt.dtype), mantissa_bits)
            return _pam_matmul_value(a, b, fmt=fmt, lmul=lmul)

        def grad_exact(a, b, g):
            return _exact_grad_a(a, b, g), _exact_grad_b(a, b, g)

    if fmt_name != "f32":
        # The exact power-of-two factor contraction is int32-fused; for
        # narrow formats run it on the (exact) f32 embedding and round the
        # cotangents back — the dfactors are powers of two either way.
        _ge = grad_exact

        def grad_exact(a, b, g):
            da, db = _ge(_f32(a), _f32(b), _f32(g))
            return da.astype(fmt.dtype), db.astype(fmt.dtype)

    def post(y):
        if compensate:
            return _pam_value_op(y, _ALPHA_MEAN)
        return y

    @jax.custom_vjp
    def mm(a, b):
        return post(value(a, b))

    def fwd(a, b):
        return post(value(a, b)), (a, b)

    def bwd(res, g):
        a, b = res
        if deriv == "exact" and impl != "hw":
            da, db = grad_exact(a, b, g)
        else:
            da = value(g, _swap(b))
            db = value(_swap(a), g)
        # The engines compute in fmt.dtype; cotangents must come back in
        # the PRIMAL dtypes or the surrounding transpose builds ill-typed
        # HLO (e.g. f32 operands under a bf16 config).
        da = jnp.asarray(da, jnp.result_type(a))
        db = jnp.asarray(db, jnp.result_type(b))
        return (_unbroadcast(da, jnp.shape(a)),
                _unbroadcast(db, jnp.shape(b)))

    mm.defvjp(fwd, bwd)
    return mm


def pa_matmul(a, b, pa: PAConfig):
    """Matrix multiply under the given numeric mode (mirrors jnp.matmul).

    The "hw" backend is the PAM-MXU dataflow stand-in (DESIGN.md §3): a
    native dot with standard AD — identical HLO structure, shardings and
    collectives to what PAM hardware would execute."""
    if not pa.matmul_is_pa or pa.impl == "hw":
        return jnp.matmul(a, b)
    return _build(pa.deriv, pa.impl, pa.mantissa_bits, pa.compensate,
                  pa.fmt)(a, b)


def pa_linear(x, w, bias, pa: PAConfig):
    """y = x @ w (+ bias). The bias add is a float add — free in PA terms."""
    y = pa_matmul(x, w, pa)
    if bias is not None:
        y = y + bias
    return y


def pa_elementwise_mul(a, b, pa: PAConfig, deriv: str | None = None):
    """Elementwise multiply under the numeric mode (used by gates, RoPE,
    scalar gains, optimizer-style updates inside models)."""
    if pa.mode == "off" or pa.impl == "hw" or not pa.nonlin_is_pa:
        return a * b
    if pa.fmt == "f32":
        a, b = _round_inputs(_f32(a), _f32(b), pa.mantissa_bits)
    from .pam import pam as _pam, lmul as _lmul
    op = _lmul if pa.impl == "lmul" else _pam
    return op(a, b, deriv or pa.deriv)
