"""Reference training: the loss and gradients of ``model.loss`` and an
AdamW step, native or piecewise affine (paper section 2.6), written
plainly; and the per-leaf readings the comparison takes."""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import model, pa

F32 = jnp.float32


def lr_at(step, o):
    """Linear warm-up, then cosine decay to min_lr_ratio of the peak."""
    step = jnp.asarray(step, F32)
    warm = jnp.minimum(1.0, (step + 1) / max(1, o["warmup_steps"]))
    t = jnp.clip((step - o["warmup_steps"])
                 / max(1, o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
    r = o["min_lr_ratio"]
    return o["peak_lr"] * warm * (r + (1 - r) * 0.5 * (1 + jnp.cos(jnp.pi * t)))


def adamw(params, grads, state, o):
    """Native AdamW with global-norm clipping and decoupled decay."""
    step = state["step"] + 1
    lr, t = lr_at(step, o), step.astype(F32)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                      for g in jax.tree.leaves(grads)))
    scale = o["grad_clip"] / jnp.maximum(gn, o["grad_clip"])
    b1, b2, eps, wd = o["b1"], o["b2"], o["eps"], o["weight_decay"]
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(p, g, m, v):
        g = g.astype(F32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        pf = p.astype(F32)
        return (pf - lr * u - lr * wd * pf).astype(p.dtype), m, v

    return _apply(upd, params, grads, state, step)


def pa_adamw(params, grads, state, o):
    """AdamW in PA arithmetic: PAM for every product, PADIV for every
    quotient, pasqrt; bias corrections 1 - paexp2(t ·̂ palog2 b)."""
    step = state["step"] + 1
    lr, t = lr_at(step, o), step.astype(F32)
    sq = sum(jnp.sum(pa.pam_v(g.astype(F32), g.astype(F32)))
             for g in jax.tree.leaves(grads))
    gn = pa.paexp2_v(pa.palog2_v(sq) * np.float32(0.5))
    clip = np.float32(o["grad_clip"])
    scale = pa.padiv_v(clip, jnp.maximum(gn, clip))
    b1, b2 = np.float32(o["b1"]), np.float32(o["b2"])
    bc1 = 1.0 - pa.paexp2_v(pa.pam_v(t, pa.palog2_v(b1)))
    bc2 = 1.0 - pa.paexp2_v(pa.pam_v(t, pa.palog2_v(b2)))
    lr_wd = pa.pam_v(lr, np.float32(o["weight_decay"]))
    eps = np.float32(o["eps"])

    def upd(p, g, m, v):
        g = pa.pam_v(g.astype(F32), scale)
        m = pa.pam_v(b1, m) + pa.pam_v(np.float32(1 - o["b1"]), g)
        v = pa.pam_v(b2, v) + pa.pam_v(np.float32(1 - o["b2"]), pa.pam_v(g, g))
        den = pa.paexp2_v(pa.palog2_v(pa.padiv_v(v, bc2)) * np.float32(0.5))
        u = pa.padiv_v(pa.padiv_v(m, bc1), den + eps)
        pf = p.astype(F32)
        return ((pf - pa.pam_v(lr, u)) - pa.pam_v(lr_wd, pf)).astype(p.dtype), m, v

    return _apply(upd, params, grads, state, step)


def _apply(upd, params, grads, state, step):
    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda x: x[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "step": step}


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))),
                        tree)


def delta_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32) - y.astype(F32)))), a, b)


@functools.lru_cache(maxsize=None)
def _step(nx: model.Numerics, cfg_items: tuple, opt_items: tuple):
    cfg, opt = dict(cfg_items), dict(opt_items)
    update = pa_adamw if nx.mode == "full" else adamw

    def step(p, s, b):
        loss, g = jax.value_and_grad(lambda q: model.loss(nx, cfg, q, b))(p)
        p, s = update(p, g, s, opt)
        return p, s, loss
    return jax.jit(step)


def run(nx: model.Numerics, cfg: dict, opt: dict, params, batches):
    """len(batches) steps from ``params``; returns (losses, first-step
    moment norms per leaf, norms of the parameter change per leaf)."""
    step = _step(nx, tuple(sorted(cfg.items())), tuple(sorted(opt.items())))

    state = {"m": jax.tree.map(lambda x: jnp.zeros(x.shape, F32), params),
             "v": jax.tree.map(lambda x: jnp.zeros(x.shape, F32), params),
             "step": jnp.zeros((), jnp.int32)}
    p, losses, m1 = params, [], None
    for i, b in enumerate(batches):
        p, state, loss = step(p, state, b)
        losses.append(float(loss))
        if i == 0:
            m1 = jax.device_get(leaf_norms(state["m"]))
    dn = jax.device_get(jax.jit(delta_norms)(p, params))
    return losses, m1, dn
