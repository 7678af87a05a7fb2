"""Jitted public wrappers: nd-batched PAM matmul + backward entry points,
all backed by single batched-grid Pallas launches (DESIGN.md §2).

Shape handling mirrors ``jnp.matmul``: a (..., M, K) @ b (..., K, N) with
broadcastable batch dims. Batch dims fold into the leading grid dimension
of ONE ``pallas_call`` (no vmap — one launch per matmul, not per batch
element). The common LM case (x @ W, W unbatched) collapses leading dims
into M instead: one big 2D kernel launch, the layout the TPU pipeline
likes best. An operand whose batch dims broadcast (all-1) is passed with
batch size 1 and replicated through the kernel's index map, never
materialised.

Tile parameters (bm, bn, bk, g) come from the shape-keyed autotune table in
``kernel.py`` unless overridden by keyword. Backend selection (compiled TPU
vs CPU interpret) is evaluated lazily per call via ``kernels._backend``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core import floatbits as _fb

from .._backend import per_device, use_interpret
from . import kernel as _k


def _resolve(m, n, k, bm, bn, bk, g, interpret, fmt_name="f32"):
    abm, abn, abk, ag = _k.tile_params(m, n, k, interpret, fmt_name)
    return (bm or abm, bn or abn, bk or abk, g or ag)


def _fold_batches(a, b):
    """Broadcast batch dims; return (a3, b3, batch_shape) with flat batches
    of size B or 1 (size-1 operands are replicated via the grid index map)."""
    batch = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    B = 1
    for d in batch:
        B *= d

    def flat(x):
        xb = x.shape[:-2]
        nb = 1
        for d in xb:
            nb *= d
        if nb == 1:
            return x.reshape((1,) + x.shape[-2:])
        if nb == B and all(d1 == d2 for d1, d2 in
                           zip(batch[len(batch) - len(xb):], xb)):
            return x.reshape((B,) + x.shape[-2:])
        # mixed per-dim broadcast (rare): materialise the broadcast
        full = jnp.broadcast_to(x, batch + x.shape[-2:])
        return full.reshape((B,) + x.shape[-2:])

    return flat(a), flat(b), batch


def _shares_rows(out_shape, *operands):
    """Split flags for ``per_device``: which (operand, is_row_operand) pairs
    share the output's leading dim, so that dividing it divides the call
    exactly. A row operand shares it whenever its rank and leading size
    are the output's; any other operand only as a batch dim (rank >= 3)."""
    return tuple(len(out_shape) >= 2 and x.ndim == len(out_shape)
                 and x.shape[0] == out_shape[0] and (rows or x.ndim >= 3)
                 for x, rows in operands)


def pam_matmul(a, b, *, bm: int | None = None, bn: int | None = None,
               bk: int | None = None, g: int | None = None,
               fmt_name: str | None = None, lmul: bool = False):
    """Bit-exact PAM matmul, jnp.matmul-shaped, one Pallas launch.

    ``fmt_name`` picks the operand FloatFormat ("f32"/"bf16"); when omitted
    it is inferred from the operand dtypes (bf16 operands run the native
    int16-carrier kernel, anything else takes the historical f32 path).
    """
    if fmt_name is None:
        fmt_name = ("bf16" if jnp.asarray(a).dtype == jnp.bfloat16
                    and jnp.asarray(b).dtype == jnp.bfloat16 else "f32")
    dt = _fb.FORMATS[fmt_name].dtype
    a = jnp.asarray(a, dt)
    b = jnp.asarray(b, dt)
    out_shape = (a.shape[:-1] + b.shape[-1:] if b.ndim == 2 else
                 jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
                 + (a.shape[-2], b.shape[-1]))
    split = _shares_rows(out_shape, (a, True), (b, False))
    return per_device(functools.partial(
        _pam_matmul_local, bm=bm, bn=bn, bk=bk, g=g, fmt_name=fmt_name,
        lmul=lmul), a, b, split=split, out_split=any(split))


def _pam_matmul_local(a, b, *, bm, bn, bk, g, fmt_name, lmul):
    interpret = use_interpret()
    if b.ndim == 2:
        # collapse leading dims into M (a 1D a collapses to M=1, matching
        # jnp.matmul's vector-matrix semantics): single 2D launch
        lead = a.shape[:-1]
        m = 1
        for d in lead:
            m *= d
        bm_, bn_, bk_, g_ = _resolve(m, b.shape[-1], a.shape[-1],
                                     bm, bn, bk, g, interpret, fmt_name)
        out = _k.pam_matmul_batched(
            a.reshape(1, m, a.shape[-1]), b[None],
            bm=bm_, bn=bn_, bk=bk_, g=g_, interpret=interpret,
            fmt_name=fmt_name, lmul=lmul)
        return out.reshape(*lead, b.shape[-1])

    a3, b3, batch = _fold_batches(a, b)
    m, k, n = a3.shape[-2], a3.shape[-1], b3.shape[-1]
    bm_, bn_, bk_, g_ = _resolve(m, n, k, bm, bn, bk, g, interpret, fmt_name)
    out = _k.pam_matmul_batched(a3, b3, bm=bm_, bn=bn_, bk=bk_, g=g_,
                                interpret=interpret, fmt_name=fmt_name,
                                lmul=lmul)
    return out.reshape(batch + (m, n))


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


def pam_matmul_grads_approx(a, b, g):
    """Approx-deriv backward (paper Table 1): dA = g ·̂ Bᵀ, dB = Aᵀ ·̂ g —
    two PAM matmuls routed through the kernel path."""
    return pam_matmul(g, _swap(b)), pam_matmul(_swap(a), g)


def pam_exact_grad_a(a, b, gr, *, bm: int | None = None,
                     bn: int | None = None, bk: int | None = None,
                     g: int | None = None):
    """Exact-deriv dA = sum_n pam(dfactor(A, B), G) via the fused kernel."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    gr = jnp.asarray(gr, jnp.float32)
    out_shape = (jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
                 + a.shape[-2:])
    split = _shares_rows(out_shape, (a, True), (b, False), (gr, True))
    return per_device(functools.partial(
        _exact_grad_a_local, bm=bm, bn=bn, bk=bk, g=g), a, b, gr,
        split=split, out_split=any(split))


def _exact_grad_a_local(a, b, gr, *, bm, bn, bk, g):
    interpret = use_interpret()
    a3, b3, batch = _fold_batches(a, b)
    m, k, n = a3.shape[-2], a3.shape[-1], b3.shape[-1]
    B = max(a3.shape[0], b3.shape[0])
    g3 = jnp.broadcast_to(gr, batch + (m, n)).reshape(B, m, n)
    bm_, bn_, bk_, g_ = _resolve(m, n, k, bm, bn, bk, g, interpret)
    out = _k.pam_exact_grad_a_batched(a3, b3, g3, bm=bm_, bn=bn_, bk=bk_,
                                      g=g_, interpret=interpret)
    return out.reshape(batch + (m, k))


def pam_exact_grad_b(a, b, gr, **kw):
    """Exact-deriv dB via the transposition identity
    dB = (dA of (Bᵀ, Aᵀ, gᵀ))ᵀ."""
    return _swap(pam_exact_grad_a(_swap(b), _swap(a), _swap(gr), **kw))
