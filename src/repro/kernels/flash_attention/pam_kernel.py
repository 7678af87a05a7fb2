"""Pallas kernels: fused PAM flash attention, forward + recompute backward.

One kernel streams KV blocks through VMEM computing all three stages of the
paper's attention in PA arithmetic — the PAM score products (the grouped
bit-level tile engine of DESIGN.md §2.1), the PA online-softmax (PAM by
log2(e) -> paexp2 -> running max/sum with PA rescaling, the streaming form
of the ``pa_softmax`` row kernel), and the PAM AV product — so in PAM mode
the quadratic S×T score tensor never exists in HBM (DESIGN.md §4).

GQA is shared through the grid, not through copies: Q batches over
``B*Hq`` heads while K/V stay at their true ``B*Hkv`` width, and every
sweep's K/V BlockSpec index map folds the query head onto its KV head
(``b -> b // rep``). The dK/dV sweep runs a ``(B*Hkv, nk, rep, nq)`` grid
whose two inner dims accumulate the whole query group into one Hkv-wide
output block — gradients come back at true Hkv width with no ``jnp.repeat``
materialisation anywhere (DESIGN.md §4.4).

Masking is positional via explicit per-token position arrays (``q_pos``,
``k_pos``) streamed alongside the operands — query positions as a column,
key positions as a row, so the (bq, bk) mask is a plain broadcast compare
on the TPU vector unit: ``k_pos < 0`` marks
padded/empty KV slots (rejected in EVERY mode), causal compares
``k_pos <= q_pos`` and a static ``window`` bounds ``q_pos - k_pos`` — the
same scheme the float flash kernel uses, generalised to arbitrary position
vectors so rolling KV caches work unchanged.

The backward is recompute-based (DESIGN.md §4.3) and takes TWO sweeps:
forward saves the output ``o`` plus the per-row streaming stats (m = running
max == true row max, l = streaming PA sum). The ``dsig`` row cotangent is
the PA form of FlashAttention's delta trick — ``Σ_j e·dP = l ·̂ (dO·O)``
exactly in PA exponent arithmetic, so ``dsig = -padiv(rowsum(pam(dO, O)),
l)`` needs no KV pass at all. Sweep 1 computes it once per query block and
streams KV tiles emitting both ``dsig`` and dQ; sweep 2 (KV-outer) emits
dK/dV. Each sweep recomputes its ``e``/``dP`` tiles exactly once. Grads
match the unfused `_sdpa` composition within the streaming-rescale
tolerance (DESIGN.md §4.2).

Block shapes follow the TPU rule that a block's last two dims are
multiples of (8, 128) or span the array: the per-row stats travel as
(B*H, S, 1) columns and the key positions as a (1, T) row. Validated in
interpret mode on CPU against the jnp engine, and compiled for TPU v5e in
the test tier (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import floatbits as _fb
from ..pa_prims import (_pam, _padiv, _paexp2, _pam_dot, _LOG2E, _LN2,
                        get_prims)

_NEG = np.float32(-1e30)
_L2E = np.float32(_LOG2E)
_LN2F = np.float32(_LN2)

# Mixed-precision posture for narrow formats (DESIGN.md §11): every
# O(S*T)-sized tile — scores, e, p, dS — lives in the format's carrier
# (int16 bit math, bf16 VMEM traffic), while the O(S)-sized streaming state
# (acc, m, l, dsig) stays f32 in VMEM and is rescaled by f32 PA ops whose
# narrow operands embed EXACTLY in f32 (bf16 -> f32 is lossless), so the
# f32 path below is the fmt="f32" instance of the same code, bit for bit.


def _masked_scores(q, k, qp, kp, *, g, scale, causal, window,
                   fmt_name: str = "f32"):
    """PAM score tile with positional masking.

    q: (bq, dh), k: (bk, dh), qp: (bq, 1) int32, kp: (1, bk) int32. Masked
    entries become exactly -1e30 — the same value the unfused path's
    ``where`` select uses, so paexp2 flushes them to an exact 0 (the
    bf16 rounding of -1e30 flushes identically).
    """
    pp = get_prims(fmt_name)
    dt = pp.fmt.dtype
    s = pp.pam_dot(q, k.T, g).astype(dt)           # (bq, bk)
    if scale is not None:
        s = pp.pam(s, jnp.asarray(np.float32(scale), dt))
    valid = kp >= 0
    if causal:
        valid &= kp <= qp
    if window is not None:
        valid &= (qp - kp) < window
    return jnp.where(valid, s, jnp.asarray(_NEG, dt))


def _delta_dsig(do, o, l, fmt_name: str = "f32"):
    """Row cotangent of the PA softmax sum via the delta trick:
    ``Σ_j padiv(pam(e, dP), pam(l, l)) == padiv(rowsum(pam(dO, O)), l)``
    in exact arithmetic (Σ_j e·dP = l·(dO·O)); both engines evaluate this
    identical PA expression (DESIGN.md §4.3). do/o: (bq, dh), l: (bq, 1).
    The dO·O products run in the carrier; the row sum and the padiv by the
    f32 ``l`` stat stay f32.
    """
    pp = get_prims(fmt_name)
    prod = pp.pam(do, o).astype(jnp.float32)
    return -_padiv(jnp.sum(prod, axis=-1, keepdims=True), l)


def _positions(q_pos, k_pos, sp, tp):
    """Padded position operands: queries as an (sp, 1) column, keys as a
    (1, tp) row; padding carries -1 (an empty slot, masked in every mode)."""
    qpos = jnp.pad(q_pos.astype(jnp.int32), (0, sp - q_pos.shape[0]),
                   constant_values=-1)[:, None]
    kpos = jnp.pad(k_pos.astype(jnp.int32), (0, tp - k_pos.shape[0]),
                   constant_values=-1)[None]
    return qpos, kpos


# ---------------------------------------------------------------------------
# Forward: streaming PA online-softmax. Outputs o plus the per-row stats
# (m, l) the recompute backward needs.
# ---------------------------------------------------------------------------

def _fwd_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref,
                l_out_ref, acc_ref, m_ref, l_ref,
                *, g, nk, causal, window, scale, fmt_name):
    pp = get_prims(fmt_name)
    dt = pp.fmt.dtype
    l2e = jnp.asarray(_L2E, dt)
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # (bq, dh)
    k = k_ref[0]                                   # (bk, dh)
    v = v_ref[0]                                   # (bk, dh)
    s = _masked_scores(q, k, qp_ref[...], kp_ref[...], g=g, scale=scale,
                       causal=causal, window=window, fmt_name=fmt_name)

    m_prev = m_ref[...]                            # (bq, 1) f32
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev,
                        jnp.max(s.astype(jnp.float32), axis=-1,
                                keepdims=True))
    # PA rescale: alpha == 1.0 exactly when the running max is unchanged
    # (PAM by 1.0 is the identity), so rescale error only accrues on steps
    # that raise the max (DESIGN.md §4.2). alpha/p run in the carrier; the
    # f32 streaming state is rescaled by the exactly-embedded alpha.
    alpha = pp.paexp2(pp.pam((m_prev - m_new).astype(dt), l2e))
    p = pp.paexp2(pp.pam(s - m_new.astype(dt), l2e))   # (bq, bk)
    l_ref[...] = (_pam(l_prev, alpha.astype(jnp.float32))
                  + jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True))
    acc_ref[...] = (_pam(acc_ref[...], alpha.astype(jnp.float32))
                    + pp.pam_dot(p, v, g))
    m_ref[...] = m_new

    @pl.when(kv == nk - 1)
    def _out():
        o_ref[0] = _padiv(acc_ref[...], l_ref[...]).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "g", "interpret",
                                             "fmt_name"))
def pam_flash_attention_fwd_bh(q, k, v, q_pos, k_pos, *, causal: bool,
                               window, scale, bq: int, bk: int, g: int,
                               interpret: bool, fmt_name: str = "f32"):
    """q: (B*Hq, S, Dh), k/v: (B*Hkv, T, Dh), q_pos: (S,), k_pos: (T,) int32.

    ``B*Hq`` must be a multiple of ``B*Hkv``; the query group shares its KV
    head through the K/V BlockSpec index maps (``b -> b // rep``), so K/V
    are never replicated in HBM. Returns (o, m, l) with m/l the (B*Hq, S)
    streaming row stats. Padding is positional: padded KV slots carry
    k_pos == -1 and are masked in every mode; padded query rows are cropped.
    ``fmt_name`` picks the FloatFormat: bf16 streams q/k/v/o tiles at half
    the HBM bytes while m/l and the accumulator stay f32.
    """
    dt = _fb.FORMATS[fmt_name].dtype
    bh, s_len, dh = q.shape
    t = k.shape[1]
    rep = bh // k.shape[0]
    bq_, bk_ = min(bq, s_len), min(bk, t)
    sp, tp = -(-s_len // bq_) * bq_, -(-t // bk_) * bk_
    qp = jnp.pad(q.astype(dt), ((0, 0), (0, sp - s_len), (0, 0)))
    kp = jnp.pad(k.astype(dt), ((0, 0), (0, tp - t), (0, 0)))
    vp = jnp.pad(v.astype(dt), ((0, 0), (0, tp - t), (0, 0)))
    qpos, kpos = _positions(q_pos, k_pos, sp, tp)
    nk = tp // bk_

    o, m, l = pl.pallas_call(
        functools.partial(_fwd_kernel, g=g, nk=nk, causal=causal,
                          window=window, scale=scale, fmt_name=fmt_name),
        grid=(bh, sp // bq_, nk),
        in_specs=[
            pl.BlockSpec((bq_, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((1, bk_), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, i, j: (b // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, dh), dt),
            jax.ShapeDtypeStruct((bh, sp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq_, dh), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
        ],
        interpret=interpret,
        name="pam_attention_fwd",
    )(qpos, kpos, qp, kp, vp)
    return o[:, :s_len], m[:, :s_len, 0], l[:, :s_len, 0]


# ---------------------------------------------------------------------------
# Backward sweep 1: dsig + dQ in ONE KV pass. dsig is the delta-trick row
# scalar (computed from o/do/l at the first KV step — no KV reduction
# needed); each KV tile then recomputes e/dP once and accumulates
#   d_e = padiv(dP, l) + dsig; d_u = pam(pam(e, ln2), d_e);
#   dS = pam(d_u, log2e) [·̂ scale];  dQ += dS ·̂ K.
# The completed dsig rows are emitted for sweep 2.
# ---------------------------------------------------------------------------

def _ds_tile(e, dp, l, dsig, *, scale, fmt_name="f32"):
    # The O(S)-sized stats (l, dsig) and the f32-accumulated dp tile feed an
    # f32 PA chain; the result rounds to the carrier ONCE for the dS·K /
    # dSᵀ·Q tile products (no-op round for f32).
    pp = get_prims(fmt_name)
    de = _padiv(dp, l) + dsig
    du = _pam(_pam(e.astype(jnp.float32), _LN2F), de)
    ds = _pam(du, _L2E)
    if scale is not None:
        ds = _pam(ds, np.float32(scale))
    return ds.astype(pp.fmt.dtype)


def _dq_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref, do_ref, m_ref,
               l_ref, dq_ref, dsig_ref, acc_ref, dsig_acc,
               *, g, nk, causal, window, scale, fmt_name):
    pp = get_prims(fmt_name)
    dt = pp.fmt.dtype
    l2e = jnp.asarray(_L2E, dt)
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dsig_acc[...] = _delta_dsig(do_ref[0], o_ref[0], l_ref[0], fmt_name)

    s = _masked_scores(q_ref[0], k_ref[0], qp_ref[...], kp_ref[...], g=g,
                       scale=scale, causal=causal, window=window,
                       fmt_name=fmt_name)
    m = m_ref[0]
    l = l_ref[0]
    e = pp.paexp2(pp.pam(s - m.astype(dt), l2e))   # masked entries: exact 0
    dp = pp.pam_dot(do_ref[0], v_ref[0].T, g)      # (bq, bk) f32
    ds = _ds_tile(e, dp, l, dsig_acc[...], scale=scale, fmt_name=fmt_name)
    acc_ref[...] += pp.pam_dot(ds, k_ref[0], g)    # (bq, dh)

    @pl.when(kv == nk - 1)
    def _out():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)
        dsig_ref[0] = dsig_acc[...]


# ---------------------------------------------------------------------------
# Backward sweep 2: dK/dV with a (B*Hkv, nk, rep, nq) grid — KV tiles
# outermost, then the query-head group, then query blocks, so each KV
# tile's accumulators live in VMEM across the WHOLE query group and dK/dV
# come back at true Hkv width.
#   dV += Pᵀ ·̂ dO  with P = padiv(e, l);   dK += dSᵀ ·̂ Q.
# ---------------------------------------------------------------------------

def _dkv_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
                dsig_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, g, rep, nq, causal, window, scale, fmt_name):
    pp = get_prims(fmt_name)
    dt = pp.fmt.dtype
    l2e = jnp.asarray(_L2E, dt)
    r = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(jnp.logical_and(r == 0, iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0]
    do = do_ref[0]
    s = _masked_scores(q, k_ref[0], qp_ref[...], kp_ref[...], g=g,
                       scale=scale, causal=causal, window=window,
                       fmt_name=fmt_name)
    m = m_ref[0]
    l = l_ref[0]
    dsig = dsig_ref[0]
    e = pp.paexp2(pp.pam(s - m.astype(dt), l2e))
    # p = e / l in f32 (l is an f32 stat), rounded once to the carrier for
    # the Pᵀ·dO tile product; masked rows stay an exact 0.
    p = _padiv(e.astype(jnp.float32), l).astype(dt)
    dv_acc[...] += pp.pam_dot(p.T, do, g)          # (bk, dh)
    dp = pp.pam_dot(do, v_ref[0].T, g)
    ds = _ds_tile(e, dp, l, dsig, scale=scale, fmt_name=fmt_name)
    dk_acc[...] += pp.pam_dot(ds.T, q, g)          # (bk, dh)

    @pl.when(jnp.logical_and(r == rep - 1, iq == nq - 1))
    def _out():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "g", "interpret",
                                             "fmt_name"))
def pam_flash_attention_bwd_bh(q, k, v, q_pos, k_pos, o, m, l, do, *,
                               causal: bool, window, scale, bq: int, bk: int,
                               g: int, interpret: bool,
                               fmt_name: str = "f32"):
    """Two-sweep recompute backward: (dq, dk, dv) from saved (o, m, l).

    q/o/do/m/l batch over B*Hq; k/v over B*Hkv. dk/dv are returned at true
    Hkv width — the group accumulation happens inside the KV-outer sweep.
    """
    dt = _fb.FORMATS[fmt_name].dtype
    bh, s_len, dh = q.shape
    bkv, t = k.shape[0], k.shape[1]
    rep = bh // bkv
    bq_, bk_ = min(bq, s_len), min(bk, t)
    sp, tp = -(-s_len // bq_) * bq_, -(-t // bk_) * bk_
    qp = jnp.pad(q.astype(dt), ((0, 0), (0, sp - s_len), (0, 0)))
    kp = jnp.pad(k.astype(dt), ((0, 0), (0, tp - t), (0, 0)))
    vp = jnp.pad(v.astype(dt), ((0, 0), (0, tp - t), (0, 0)))
    op = jnp.pad(o.astype(dt), ((0, 0), (0, sp - s_len), (0, 0)))
    dop = jnp.pad(do.astype(dt), ((0, 0), (0, sp - s_len), (0, 0)))
    mp = jnp.pad(m, ((0, 0), (0, sp - s_len)), constant_values=_NEG)[..., None]
    lp = jnp.pad(l, ((0, 0), (0, sp - s_len)), constant_values=1.0)[..., None]
    qpos, kpos = _positions(q_pos, k_pos, sp, tp)
    nk, nq = tp // bk_, sp // bq_

    pos_q_spec = pl.BlockSpec((bq_, 1), lambda b, i, j: (i, 0))
    pos_k_spec = pl.BlockSpec((1, bk_), lambda b, i, j: (0, j))
    q_spec = pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk_, dh), lambda b, i, j: (b // rep, j, 0))
    row_spec = pl.BlockSpec((1, bq_, 1), lambda b, i, j: (b, i, 0))

    dq, dsig = pl.pallas_call(
        functools.partial(_dq_kernel, g=g, nk=nk, causal=causal,
                          window=window, scale=scale, fmt_name=fmt_name),
        grid=(bh, nq, nk),
        in_specs=[pos_q_spec, pos_k_spec, q_spec, kv_spec, kv_spec, q_spec,
                  q_spec, row_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sp, dh), dt),
            jax.ShapeDtypeStruct((bh, sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq_, dh), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
        ],
        interpret=interpret,
        name="pam_attention_dq",
    )(qpos, kpos, qp, kp, vp, op, dop, mp, lp)

    # KV-outer grid for dK/dV: KV tiles are indexed by program_id(1), the
    # query group member by program_id(2), query blocks by program_id(3).
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g, rep=rep, nq=nq, causal=causal,
                          window=window, scale=scale, fmt_name=fmt_name),
        grid=(bkv, nk, rep, nq),
        in_specs=[
            pl.BlockSpec((bq_, 1), lambda b, j, r, i: (i, 0)),
            pl.BlockSpec((1, bk_), lambda b, j, r, i: (0, j)),
            pl.BlockSpec((1, bq_, dh), lambda b, j, r, i: (b * rep + r, i, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, j, r, i: (b, j, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, j, r, i: (b, j, 0)),
            pl.BlockSpec((1, bq_, dh), lambda b, j, r, i: (b * rep + r, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, j, r, i: (b * rep + r, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, j, r, i: (b * rep + r, i, 0)),
            pl.BlockSpec((1, bq_, 1), lambda b, j, r, i: (b * rep + r, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk_, dh), lambda b, j, r, i: (b, j, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, j, r, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, tp, dh), dt),
            jax.ShapeDtypeStruct((bkv, tp, dh), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_, dh), jnp.float32),
            pltpu.VMEM((bk_, dh), jnp.float32),
        ],
        interpret=interpret,
        name="pam_attention_dkv",
    )(qpos, kpos, qp, kp, vp, dop, mp, lp, dsig)

    return dq[:, :s_len], dk[:, :t], dv[:, :t]
