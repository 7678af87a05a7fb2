"""Production mesh construction.

Axis semantics:
  pod   — DCN-connected pod index (crossed only by gradient/bat ch reduces)
  data  — intra-pod data parallelism (+ FSDP weight sharding)
  model — tensor/expert parallelism (+ KV-cache sequence parallelism)

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialisation.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic: any (pod, data, model) factorisation of the device count.
    Uses the first prod(shape) devices so a 512-device process can also build
    the 256-chip single-pod mesh."""
    import math
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(f"need {need} devices, have {len(devs)} "
                           "(dry-runs must set XLA_FLAGS first — see dryrun.py)")
    import numpy as np
    arr = np.asarray(devs[:need]).reshape(shape)
    return jax.sharding.Mesh(arr, tuple(axes),
                             axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def host_mesh():
    """Single-device mesh for local smoke runs."""
    return make_mesh((1, 1), ("data", "model"))
