"""Public wrapper: fused PA AdamW update over parameter trees.

``pa_adamw_update`` is the optimizer-side entry ``optim/adamw.py``
dispatches to when the PA optimizer is active: ``impl="pallas"`` drives the
fused kernel leaf by leaf (flattened planes, donated buffers, tile params
from the shared autotune registry); any other impl runs the jnp engine —
the same ``pa_adamw_math`` mapped over leaves. The step's scalars (bias
corrections, lr, lr ·̂ wd, clip scale) are computed once here for both
engines; hyperparameters are static and baked into the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import autotune
from .._backend import per_device, use_interpret
from .kernel import pa_adamw_leaf_pallas
from .ref import pa_adamw_leaf_ref, pa_adamw_scalars


def tree_unzip3(out):
    """Split a tree of (a, b, c) leaf tuples into three trees (the shared
    unzip for per-leaf optimizer updates)."""
    leaves, treedef = jax.tree.flatten(out,
                                       is_leaf=lambda x: isinstance(x, tuple))
    return tuple(treedef.unflatten([l[i] for l in leaves]) for i in range(3))


def pa_adamw_update(params, grads, m, v, t, lr, scale, *, b1, b2, eps,
                    weight_decay, impl: str = "jnp", fmt: str = "f32"):
    """Fused PA AdamW step over pytrees. ``scale`` is the traced clip scale
    or None (grad_clip == 0: gradients enter the chain unscaled, matching
    the value-level seed bit for bit). ``fmt="bf16"`` runs the elementwise
    chain natively in bf16 (both engines). Returns
    (new_params, new_m, new_v)."""
    apply_scale = scale is not None
    hyp = dict(b1=float(b1), b2=float(b2), eps=float(eps),
               apply_scale=apply_scale)
    t = jnp.asarray(t, jnp.float32)
    lr = jnp.asarray(lr, jnp.float32)
    scale_ = jnp.float32(0) if scale is None else jnp.asarray(scale,
                                                              jnp.float32)
    bc1, bc2, lr_wd = pa_adamw_scalars(t, lr, b1=float(b1), b2=float(b2),
                                       wd=float(weight_decay))

    if impl == "pallas":
        interpret = use_interpret()
        scalars = jnp.stack([bc1, bc2, lr, lr_wd, scale_])

        def leaf(p, g, mm, vv, scalars):
            rows, cols = autotune.tile_params("pam_optim", (p.size,),
                                              interpret, fmt)
            return pa_adamw_leaf_pallas(p, g, mm, vv, scalars,
                                        rows=int(rows), cols=int(cols),
                                        interpret=interpret, fmt_name=fmt,
                                        **hyp)

        def upd(p, g, mm, vv):
            # Elementwise: on a mesh each device updates its slice of the
            # flattened leaf, whatever the leaf's own placement.
            flat = [x.reshape(-1) for x in (p, g, mm, vv)]
            out = per_device(leaf, *flat, scalars,
                             split=(True,) * 4 + (False,),
                             out_split=(True,) * 3, over="all")
            return tuple(o.reshape(p.shape) for o in out)
    else:
        def upd(p, g, mm, vv):
            return pa_adamw_leaf_ref(p, g, mm, vv, bc1, bc2, lr, lr_wd,
                                     scale_, fmt_name=fmt, **hyp)

    return tree_unzip3(jax.tree.map(upd, params, grads, m, v))
