"""Public wrapper: fused PAM flash attention with a Pallas engine, a jnp
streaming fallback, and a recompute custom_vjp.

``pam_flash_attention`` mirrors the unfused `_sdpa` PAM composition
(scores -> PA softmax -> AV, ``models/attention.py``) but never
materialises the S×T score tensor: the Pallas engine streams KV blocks
through VMEM (``pam_kernel.py``); the jnp engine is the same streaming
algorithm as a ``lax.scan`` over KV blocks built on the core PAM matmul
engine — the portable fallback for non-Pallas backends, with the same
O(S·Dh) live-memory profile.

GQA never replicates K/V: the Pallas engine shares each KV head across its
query group through BlockSpec index maps (``b -> b // rep``); the jnp
engine folds the group into the query-row axis (``(B*Hkv, rep*S, Dh)``
with tiled positions — masking is purely positional, so the fold is free)
and its per-block dK/dV contractions group-accumulate naturally. Peak
fused-path K/V bytes are Hkv-sized on both engines.

Both engines share one custom_vjp: forward saves (q, k, v, positions, o,
row stats); the two-sweep backward recomputes score tiles once per sweep
and evaluates the approx-derivative PA chain of the unfused composition
with the delta-form ``dsig`` (DESIGN.md §4.3). Numeric contract vs the
unfused composition: DESIGN.md §4.2.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import floatbits as _fb
from repro.core.matmul import _pam_matmul_value
from repro.core.pam import pam_value, padiv_value, paexp2_value

from .. import autotune
from .._backend import per_device, use_interpret
from ..pa_prims import _LOG2E, _LN2
from . import pam_kernel as _pk

_NEG = np.float32(-1e30)


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


# ---------------------------------------------------------------------------
# jnp streaming engine: identical math to the Pallas kernels, as a scan over
# KV blocks. Carries (acc, m, l); the backward computes the delta-form dsig
# (no KV sweep) then one scan producing dq (accumulated) and dk/dv
# (per-block stacked outputs, contracted over the folded query group).
# ---------------------------------------------------------------------------

def _kv_blocks(k, v, k_pos, bc):
    t = k.shape[1]
    tp = -(-t // bc) * bc
    kb = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0)))
    vb = jnp.pad(v, ((0, 0), (0, tp - t), (0, 0)))
    kpos = jnp.pad(k_pos.astype(jnp.int32), (0, tp - t), constant_values=-1)
    nb = tp // bc
    kb = jnp.moveaxis(kb.reshape(kb.shape[0], nb, bc, -1), 1, 0)
    vb = jnp.moveaxis(vb.reshape(vb.shape[0], nb, bc, -1), 1, 0)
    return kb, vb, kpos.reshape(nb, bc), tp


def _block_scores(q, kblk, q_pos, kpblk, *, causal, window, scale,
                  fmt=_fb.FLOAT32):
    """(BH, S, bc) masked PAM scores for one KV block."""
    s = _pam_matmul_value(q, _swap(kblk), fmt=fmt)
    if scale is not None:
        s = pam_value(s, np.float32(scale))
    valid = (kpblk >= 0)[None, None, :]
    if causal:
        valid = valid & (kpblk[None, None, :] <= q_pos[None, :, None])
    if window is not None:
        valid = valid & ((q_pos[None, :, None] - kpblk[None, None, :])
                         < window)
    return jnp.where(valid, s, jnp.asarray(_NEG, s.dtype))


def _fold_group(x, bkv, rows):
    """(B*Hq, S, ...) -> (B*Hkv, rep*S, ...): query heads of one group
    become extra query rows of their shared KV head (batch-major layout
    makes this a pure reshape)."""
    return x.reshape((bkv, rows) + x.shape[2:])


def _jnp_fwd(q, k, v, q_pos, k_pos, *, causal, window, scale, bc,
             fmt_name="f32"):
    fmt = _fb.FORMATS[fmt_name]
    dt = fmt.dtype
    bhq, s_len, dh = q.shape
    bkv = k.shape[0]
    rep = bhq // bkv
    kb, vb, kpb, _ = _kv_blocks(k, v, k_pos, bc)
    qpos = q_pos.astype(jnp.int32)
    if rep > 1:
        q = _fold_group(q, bkv, rep * s_len)
        qpos = jnp.tile(qpos, rep)
    rows = q.shape[1]

    def step(carry, xs):
        acc, m_run, l_run = carry
        kblk, vblk, kpblk = xs
        s = _block_scores(q, kblk, qpos, kpblk, causal=causal, window=window,
                          scale=scale, fmt=fmt)
        m_new = jnp.maximum(m_run, jnp.max(s.astype(jnp.float32), axis=-1,
                                           keepdims=True))
        alpha = paexp2_value(pam_value((m_run - m_new).astype(dt), _LOG2E))
        p = paexp2_value(pam_value(s - m_new.astype(dt), _LOG2E))
        l_new = (pam_value(l_run, alpha.astype(jnp.float32))
                 + jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True))
        acc = (pam_value(acc, alpha.astype(jnp.float32))
               + _pam_matmul_value(p, vblk, fmt=fmt).astype(jnp.float32))
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((bkv, rows, dh), jnp.float32)
    m0 = jnp.full((bkv, rows, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bkv, rows, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, kpb))
    o = padiv_value(acc, l).astype(dt)
    return (o.reshape(bhq, s_len, dh), m.reshape(bhq, s_len),
            l.reshape(bhq, s_len))


def _jnp_bwd(q, k, v, q_pos, k_pos, o, m, l, do, *, causal, window, scale,
             bc, fmt_name="f32"):
    fmt = _fb.FORMATS[fmt_name]
    dt = fmt.dtype
    bhq, s_len, dh = q.shape
    bkv, t = k.shape[0], k.shape[1]
    rep = bhq // bkv
    kb, vb, kpb, tp = _kv_blocks(k, v, k_pos, bc)
    qpos = q_pos.astype(jnp.int32)
    if rep > 1:
        rows = rep * s_len
        q, o, do = (_fold_group(x, bkv, rows) for x in (q, o, do))
        m, l = (_fold_group(x, bkv, rows) for x in (m, l))
        qpos = jnp.tile(qpos, rep)
    m = m[..., None]
    l = l[..., None]
    # Delta-form dsig (DESIGN.md §4.3): the exact-arithmetic identity
    # Σ_j e·dP = l·(dO·O) collapses the old dsig KV sweep to one row op.
    # The dO·O products run in the format's carrier; the row sum and the
    # padiv by the f32 ``l`` stat stay f32.
    dsig = -padiv_value(jnp.sum(pam_value(do, o).astype(jnp.float32),
                                axis=-1, keepdims=True), l)

    def grad_step(dq_acc, xs):
        kblk, vblk, kpblk = xs
        s = _block_scores(q, kblk, qpos, kpblk, causal=causal, window=window,
                          scale=scale, fmt=fmt)
        e = paexp2_value(pam_value(s - m.astype(dt), _LOG2E))
        dp = _pam_matmul_value(do, _swap(vblk), fmt=fmt).astype(jnp.float32)
        p = padiv_value(e.astype(jnp.float32), l).astype(dt)
        dv_blk = _pam_matmul_value(_swap(p), do, fmt=fmt)  # (B*Hkv, bc, Dh)
        de = padiv_value(dp, l) + dsig
        du = pam_value(pam_value(e.astype(jnp.float32), _LN2), de)
        ds = pam_value(du, _LOG2E)
        if scale is not None:
            ds = pam_value(ds, np.float32(scale))
        ds = ds.astype(dt)
        dk_blk = _pam_matmul_value(_swap(ds), q, fmt=fmt)  # (B*Hkv, bc, Dh)
        return (dq_acc
                + _pam_matmul_value(ds, kblk, fmt=fmt).astype(jnp.float32),
                (dk_blk, dv_blk))

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(grad_step, dq0, (kb, vb, kpb))
    dk = jnp.moveaxis(dkb, 0, 1).reshape(bkv, tp, dh)[:, :t]
    dv = jnp.moveaxis(dvb, 0, 1).reshape(bkv, tp, dh)[:, :t]
    return dq.reshape(bhq, s_len, dh).astype(dt), dk, dv


# ---------------------------------------------------------------------------
# custom_vjp glue (per static numeric configuration). Forward and backward
# resolve their tile params independently (the two-sweep backward prefers
# different KV block sizes — autotune op "pam_attention_bwd").
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _build(causal: bool, window, scale, impl: str, bq: int, bk: int, g: int,
           bbq: int, bbk: int, bg: int, interpret: bool,
           fmt_name: str = "f32"):
    dt = _fb.FORMATS[fmt_name].dtype
    if impl == "pallas":
        # (B*H) rows split over a mesh's data axes: a contiguous block of
        # query heads keeps its KV heads (b -> b // rep) on the same device.
        fwd_k = functools.partial(
            _pk.pam_flash_attention_fwd_bh, causal=causal, window=window,
            scale=scale, bq=bq, bk=bk, g=g, interpret=interpret,
            fmt_name=fmt_name)
        bwd_k = functools.partial(
            _pk.pam_flash_attention_bwd_bh, causal=causal, window=window,
            scale=scale, bq=bbq, bk=bbk, g=bg, interpret=interpret,
            fmt_name=fmt_name)

        def fwd_fn(q, k, v, qpos, kpos):
            return per_device(fwd_k, q, k, v, qpos, kpos,
                              split=(True, True, True, False, False),
                              out_split=(True, True, True))

        def bwd_fn(q, k, v, qpos, kpos, o, m, l, do):
            return per_device(bwd_k, q, k, v, qpos, kpos, o, m, l, do,
                              split=(True,) * 3 + (False,) * 2 + (True,) * 4,
                              out_split=(True, True, True))
    else:
        fwd_jit = jax.jit(functools.partial(
            _jnp_fwd, causal=causal, window=window, scale=scale, bc=bk,
            fmt_name=fmt_name))
        bwd_jit = jax.jit(functools.partial(
            _jnp_bwd, causal=causal, window=window, scale=scale, bc=bbk,
            fmt_name=fmt_name))

        def fwd_fn(q, k, v, qpos, kpos):
            return fwd_jit(q, k, v, qpos, kpos)

        def bwd_fn(q, k, v, qpos, kpos, o, m, l, do):
            return bwd_jit(q, k, v, qpos, kpos, o, m, l, do)

    @jax.custom_vjp
    def att(q, k, v, qpos, kpos):
        return fwd_fn(q, k, v, qpos, kpos)[0]

    def fwd(q, k, v, qpos, kpos):
        o, m, l = fwd_fn(q, k, v, qpos, kpos)
        return o, (q, k, v, qpos, kpos, o, m, l)

    def bwd(res, do):
        q, k, v, qpos, kpos, o, m, l = res
        dq, dk, dv = bwd_fn(q, k, v, qpos, kpos, o, m, l,
                            jnp.asarray(do, dt))
        zero = lambda p: np.zeros(np.shape(p), jax.dtypes.float0)
        return dq, dk, dv, zero(qpos), zero(kpos)

    att.defvjp(fwd, bwd)
    return att


def pam_flash_attention(q, k, v, q_pos, k_pos, *, causal: bool = True,
                        window=None, scale=None, impl: str = "pallas",
                        bq=None, bk=None, g=None):
    """Fused PAM flash attention over (B, S, H, Dh) GQA layouts.

    q: (B, S, Hq, Dh), k/v: (B, T, Hkv, Dh) with Hq % Hkv == 0;
    q_pos: (S,), k_pos: (T,) absolute positions (k_pos < 0 = empty slot).
    K/V are flattened to their TRUE (B*Hkv, T, Dh) width — the query group
    shares its KV head through the engines' index maps, never via
    ``jnp.repeat``. ``scale``: None means the caller already folded the
    1/sqrt(dh) into q (attn_scale_in_q); a float is PAM-multiplied into the
    score tiles — matching ``scale_const`` on the unfused score tensor.
    ``impl``: "pallas" (kernels; interpret on CPU) or "jnp" (streaming
    scan). ``bq``/``bk``/``g`` override BOTH sweeps' tile params (tests);
    by default forward and backward resolve independently from
    ``kernels/autotune.py``.
    """
    b, s_len, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        # rep = bh // bkv truncates, so a non-divisible head count would
        # silently map late query heads onto a clamped KV block index.
        raise ValueError(f"GQA requires Hq % Hkv == 0, got Hq={hq} Hkv={hkv}")
    # bf16 q/k/v run the native int16-carrier engines end to end (half the
    # HBM bytes for tiles; f32 streaming stats); anything else takes the
    # historical f32 path.
    fmt_name = ("bf16" if all(jnp.asarray(x).dtype == jnp.bfloat16
                              for x in (q, k, v)) else "f32")
    dt = _fb.FORMATS[fmt_name].dtype
    qf = jnp.asarray(q, dt).transpose(0, 2, 1, 3).reshape(b * hq, s_len, dh)
    kf = jnp.asarray(k, dt).transpose(0, 2, 1, 3).reshape(b * hkv, t, dh)
    vf = jnp.asarray(v, dt).transpose(0, 2, 1, 3).reshape(b * hkv, t, dh)

    interpret = use_interpret()
    abq, abk, ag = autotune.tile_params("pam_attention", (s_len, t, dh),
                                        interpret, fmt_name)
    bbq, bbk, bg = autotune.tile_params("pam_attention_bwd", (s_len, t, dh),
                                        interpret, fmt_name)
    bq_, bk_, g_ = bq or abq, bk or abk, g or ag
    bbq_, bbk_, bg_ = bq or bbq, bk or bbk, g or bg
    scale_ = None if scale is None else float(np.float32(scale))
    window_ = None if window is None else int(window)

    att = _build(bool(causal), window_, scale_, impl, int(bq_), int(bk_),
                 int(g_), int(bbq_), int(bbk_), int(bg_), interpret,
                 fmt_name)
    o = att(qf, kf, vf, jnp.asarray(q_pos, jnp.int32),
            jnp.asarray(k_pos, jnp.int32))
    return o.reshape(b, hq, s_len, dh).transpose(0, 2, 1, 3)
