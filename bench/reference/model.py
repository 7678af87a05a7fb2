"""Plain reference of a LLaMA-style decoder (SmolLM's architecture) under
the three numeric modes the benchmark's configurations state.

  off     native float ops in the configuration's compute dtype
  matmul  every matrix product a PAM product (float32); the rest native
  full    every product, division, exp, log and sqrt piecewise affine;
          attention as the fused streaming PA softmax over KV blocks of
          ``kv_block`` keys (the configuration states the block)

Imports nothing of the program. Parameters are the benchmark's own
(``bench/weights.py``), a nested dict of the published layout.

The control of each configuration is this reference one precision lower:
``lower=True`` rounds every activation to bfloat16 (for the float32
modes), or rounds the operands of every forward matrix product to fp8
e4m3 with a per-tensor scale (for the bfloat16 ``off`` mode).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from . import pa

NEG = np.float32(-1e30)
F32 = jnp.float32


@jax.custom_vjp
def _fp8(x):
    """Round to fp8 e4m3 with a per-tensor scale (amax to 448); the
    backward passes the cotangent through unchanged."""
    amax = jnp.max(jnp.abs(x.astype(F32)))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = (x.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return y.astype(x.dtype)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@dataclasses.dataclass(frozen=True)
class Numerics:
    mode: str                      # off | matmul | full
    act: str = "float32"           # activation dtype
    lower: bool = False            # the control (see module docstring)
    kv_block: int = 128            # full mode: the fused attention's block
    deriv: str = "approx"
    loss_deriv: str = "exact"
    sqrt_grad: bool = True         # False: pasqrt passes no gradient

    @property
    def dt(self):
        if self.lower and self.mode != "off":
            return jnp.bfloat16
        return jnp.dtype(self.act)

    @property
    def pa(self):
        return self.mode == "full"

    def r(self, x):
        return x.astype(self.dt)

    # -- products ---------------------------------------------------------
    def mm(self, x, w):
        if self.mode == "off":
            x, w = x.astype(self.dt), w.astype(self.dt)
            if self.lower:
                x, w = _fp8(x), _fp8(w)
            return jnp.matmul(x, w)
        return self.r(pa.pam_matmul(x.astype(F32), w.astype(F32)))

    def mul(self, a, b, d=None):
        if self.pa:
            return self.r(pa.pam(a, b, d or self.deriv))
        return a * b.astype(a.dtype)

    # -- layers -----------------------------------------------------------
    def rmsnorm(self, x, gamma, eps):
        if not self.pa:
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return (x * jax.lax.rsqrt(var + eps)) * gamma.astype(x.dtype)
        d, r = self.deriv, self.r
        inv_n = np.float32(1.0 / x.shape[-1])
        var = r(pa.pam(r(jnp.sum(r(pa.pam(x, x, d)), -1, keepdims=True)),
                       inv_n, d))
        den = pa.pasqrt(r(var + np.float32(eps)), d)
        if not self.sqrt_grad:
            den = jax.lax.stop_gradient(den)
        y = r(pa.padiv(x, r(den), d))
        return r(pa.pam(y, gamma.astype(F32), d))

    def silu(self, x):
        if not self.pa:
            return jax.nn.silu(x)
        d, r = self.deriv, self.r
        e = r(pa.paexp2(r(pa.pam(-x, pa.LOG2E, d)), d))
        return r(pa.pam(x, r(pa.padiv(np.float32(1.0), r(1.0 + e), d)), d))

    def rope(self, x, positions, theta):
        """x (B, S, H, Dh); positions (1 or B, S). Rotates the two halves."""
        half = x.shape[-1] // 2
        freqs = (1.0 / theta) ** (np.arange(half, dtype=np.float32) / half)
        if self.pa:
            # positions * freqs without a product: the sum over the
            # position's set bits of freqs * 2^bit, in bit order
            pos = positions[..., None].astype(jnp.int32)
            ang = jnp.zeros(pos.shape[:-1] + freqs.shape, F32)
            for b in range(31):
                ang = ang + jnp.where((pos >> b) & 1 != 0,
                                      np.ldexp(freqs, b), np.float32(0))
        else:
            ang = positions[..., None].astype(F32) * freqs
        c = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
        s = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        r1 = self.mul(x1, c) - self.mul(x2, s)
        r2 = self.mul(x2, c) + self.mul(x1, s)
        return jnp.concatenate([r1, r2], axis=-1)

    def softmax(self, x, mask):
        x = jnp.where(mask, x, NEG)
        if not self.pa:
            return jax.nn.softmax(x, axis=-1)
        d, r = self.deriv, self.r
        m = jax.lax.stop_gradient(jnp.max(x, -1, keepdims=True))
        e = r(pa.paexp2(r(pa.pam(x - m, pa.LOG2E, d)), d))
        return r(pa.padiv(e, r(jnp.sum(e, -1, keepdims=True)), d))

    def cross_entropy(self, logits, labels, mask):
        """Mean over unmasked positions of logsumexp - target logit."""
        tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        w = mask.astype(logits.dtype)
        if not self.pa:
            nll = jax.scipy.special.logsumexp(logits, axis=-1) - tgt
            return jnp.sum(nll * w) / jnp.sum(w)
        d, r = self.loss_deriv, self.r
        m = jax.lax.stop_gradient(jnp.max(logits, -1, keepdims=True))
        s = jnp.sum(r(pa.paexp2(r(pa.pam(logits - m, pa.LOG2E, d)), d)), -1)
        lse = r(pa.pam(r(pa.palog2(r(s), d)), pa.LN2, d)) + m[..., 0]
        num = jnp.sum(r(pa.pam(r(lse - tgt), w, d)))
        return pa.padiv(num, jnp.sum(w), d)


# -- attention ----------------------------------------------------------------

def _heads(q, k, v):
    """(B, S, H, Dh) -> (B*H, S, Dh), query heads grouped over KV heads."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    f = lambda x, h: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], dh)
    return f(q, hq), f(k, hkv), f(v, hkv), hq // hkv


def _blocks(k, kpos, bk):
    t = k.shape[1]
    tp = -(-t // bk) * bk
    k = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0)))
    kpos = jnp.pad(kpos, (0, tp - t), constant_values=-1)
    return k, kpos, tp // bk


def _stream_scores(q, kb, qpos, kpb, scale):
    s = pa.pam_matmul_v(q, jnp.swapaxes(kb, -1, -2))
    s = pa.pam_v(s, scale)
    valid = (kpb[None, None, :] >= 0) & (kpb[None, None, :] <= qpos[None, :, None])
    return jnp.where(valid, s, NEG)


def _stream_fwd(q, k, v, qpos, kpos, bk, scale):
    """Streaming PA softmax attention: per KV block the running max, the
    PA rescale of the running sum and accumulator, then one PA division.
    q (R, S, Dh) with R = rep * (B*Hkv) query rows; k, v (B*Hkv, T, Dh)."""
    rep = q.shape[0] // k.shape[0]
    k = jnp.repeat(k, rep, axis=0)
    v = jnp.repeat(v, rep, axis=0)
    k, kp, nb = _blocks(k, kpos, bk)
    v, _, _ = _blocks(v, kpos, bk)
    r, s_len, dh = q.shape
    acc = jnp.zeros((r, s_len, dh), F32)
    m = jnp.full((r, s_len, 1), NEG, F32)
    l = jnp.zeros((r, s_len, 1), F32)
    for j in range(nb):
        sl = slice(j * bk, (j + 1) * bk)
        s = _stream_scores(q, k[:, sl], qpos, kp[sl], scale)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        alpha = pa.paexp2_v(pa.pam_v(m - m_new, pa.LOG2E))
        p = pa.paexp2_v(pa.pam_v(s - m_new, pa.LOG2E))
        l = pa.pam_v(l, alpha) + jnp.sum(p, -1, keepdims=True)
        acc = pa.pam_v(acc, alpha) + pa.pam_matmul_v(p, v[:, sl])
        m = m_new
    return pa.padiv_v(acc, l), m, l


def _stream_bwd(q, k, v, qpos, kpos, o, m, l, do, bk, scale):
    """The approx-derivative chain of the PA softmax, with the row sum's
    cotangent in delta form: dsig = -rowsum(dO ·̂ O) ÷̂ l."""
    rep = q.shape[0] // k.shape[0]
    t = k.shape[1]
    kr = jnp.repeat(k, rep, axis=0)
    vr = jnp.repeat(v, rep, axis=0)
    kr, kp, nb = _blocks(kr, kpos, bk)
    vr, _, _ = _blocks(vr, kpos, bk)
    dsig = -pa.padiv_v(jnp.sum(pa.pam_v(do, o), -1, keepdims=True), l)
    dq = jnp.zeros(q.shape, F32)
    dks, dvs = [], []
    for j in range(nb):
        sl = slice(j * bk, (j + 1) * bk)
        kb, vb = kr[:, sl], vr[:, sl]
        s = _stream_scores(q, kb, qpos, kp[sl], scale)
        e = pa.paexp2_v(pa.pam_v(s - m, pa.LOG2E))
        dp = pa.pam_matmul_v(do, jnp.swapaxes(vb, -1, -2))
        p = pa.padiv_v(e, l)
        dvs.append(pa.pam_matmul_v(jnp.swapaxes(p, -1, -2), do))
        de = pa.padiv_v(dp, l) + dsig
        ds = pa.pam_v(pa.pam_v(pa.pam_v(pa.pam_v(e, pa.LN2), de), pa.LOG2E),
                      scale)
        dks.append(pa.pam_matmul_v(jnp.swapaxes(ds, -1, -2), q))
        dq = dq + pa.pam_matmul_v(ds, kb)
    fold = lambda xs: jnp.concatenate(xs, 1)[:, :t].reshape(
        (k.shape[0], rep) + (t, q.shape[-1])).sum(1)
    return dq, fold(dks), fold(dvs)


def _make_stream(bk, scale):
    @jax.custom_vjp
    def att(q, k, v, qpos, kpos):
        return _stream_fwd(q, k, v, qpos, kpos, bk, scale)[0]

    def fwd(q, k, v, qpos, kpos):
        o, m, l = _stream_fwd(q, k, v, qpos, kpos, bk, scale)
        return o, (q, k, v, qpos, kpos, o, m, l)

    def bwd(res, do):
        q, k, v, qpos, kpos, o, m, l = res
        dq, dk, dv = _stream_bwd(q, k, v, qpos, kpos, o, m, l, do, bk, scale)
        z = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        return dq, dk, dv, z(qpos), z(kpos)

    att.defvjp(fwd, bwd)
    return att


def attention(nx: Numerics, q, k, v, qpos, kpos, stream_rows=None):
    """Causal GQA attention of q (B, S, Hq, Dh) over k, v (B, T, Hkv, Dh).

    qpos (1 or B, S) and kpos (1 or B, T) are absolute positions (-1: an
    empty key). ``stream_rows``, full mode only: "all", or a (B, S) bool
    of the rows computed as the fused streaming softmax, the others as
    the materialised PA softmax (a serving engine prefills with the fused
    kernel and decodes without it)."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = np.float32(1.0 / np.sqrt(dh))
    if stream_rows is not None and nx.pa:
        assert qpos.shape[0] == 1 and kpos.shape[0] == 1
        qf, kf, vf, _ = _heads(q.astype(F32), k.astype(F32), v.astype(F32))
        fused = _make_stream(nx.kv_block, scale)(qf, kf, vf, qpos[0], kpos[0])
        fused = nx.r(fused).reshape(b, hq, s, dh).transpose(0, 2, 1, 3)
        if isinstance(stream_rows, str):
            return fused
    mask = ((kpos[:, None, :] <= qpos[:, :, None])
            & (kpos[:, None, :] >= 0))[:, None, None]        # (., 1, 1, S, T)
    qh = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, s, dh)
    kh = k.transpose(0, 2, 3, 1)[:, :, None]
    vh = v.transpose(0, 2, 1, 3)[:, :, None]
    scores = nx.mm(qh, kh).astype(F32)
    scores = nx.r(pa.pam(scores, scale, nx.deriv)) if nx.pa else scores * scale
    probs = nx.softmax(scores, mask).astype(nx.dt)
    out = nx.mm(probs, vh).reshape(b, hq, s, dh).transpose(0, 2, 1, 3)
    if stream_rows is None or not nx.pa:
        return out
    return jnp.where(stream_rows[:, :, None, None], fused, out)


# -- the decoder --------------------------------------------------------------

def block(nx: Numerics, cfg, h, lp, positions, stream_rows):
    b, s, _ = h.shape
    hq, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    eps = cfg["rms_norm_eps"]
    x = nx.rmsnorm(h, lp["attn_norm"]["scale"], eps)
    q = nx.mm(x, lp["attn"]["wq"]).reshape(b, s, hq, dh)
    k = nx.mm(x, lp["attn"]["wk"]).reshape(b, s, hkv, dh)
    v = nx.mm(x, lp["attn"]["wv"]).reshape(b, s, hkv, dh)
    q = nx.rope(q, positions, cfg["rope_theta"])
    k = nx.rope(k, positions, cfg["rope_theta"])
    a = attention(nx, q, k, v, positions, positions, stream_rows)
    h = h + nx.mm(a.reshape(b, s, hq * dh), lp["attn"]["wo"])
    x = nx.rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    up = nx.mm(x, lp["mlp"]["w_up"])
    gate = nx.silu(nx.mm(x, lp["mlp"]["w_gate"]))
    return h + nx.mm(nx.mul(up, gate), lp["mlp"]["w_down"])


def logits(nx: Numerics, cfg, params, tokens, stream_rows=None):
    """All-position logits of tokens (B, S); positions 0..S-1."""
    s = tokens.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    h = jnp.take(params["embed"], tokens, axis=0).astype(nx.dt)

    @jax.checkpoint
    def body(h, lp):
        return block(nx, cfg, h, lp, positions, stream_rows), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = nx.rmsnorm(h, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return nx.mm(h, params["embed"].T)


def loss(nx: Numerics, cfg, params, batch):
    stream = "all" if nx.pa and cfg.get("attn_fused_pam") else None
    lg = logits(nx, cfg, params, batch["tokens"], stream).astype(F32)
    return nx.cross_entropy(lg, batch["labels"], batch["mask"])
