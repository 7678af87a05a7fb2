"""Execution-backend selection and mesh placement shared by all kernel
packages.

Kernel wrappers must not freeze ``jax.default_backend()`` at import time:
the platform can change after import (tests spawning CPU subprocesses with
``XLA_FLAGS``, a host process that initialises TPU late). ``use_interpret()``
is therefore evaluated at *call* time; the result feeds the ``interpret=``
flag of ``pl.pallas_call`` and is a static jit argument, so each backend
gets its own compiled executable. The platform alone decides: compiled
Mosaic kernels on TPU, the Pallas interpreter everywhere else.

Mosaic kernels cannot be partitioned automatically, so every wrapper that
launches one goes through ``per_device``: under a multi-device mesh the
launch runs inside a ``shard_map`` on each device's rows, while the rest
of the program stays automatically partitioned (tensor, expert and FSDP
placements of the weights included).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import batch_axes


def use_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode (no TPU present)."""
    return jax.default_backend() != "tpu"


def per_device(fn, *args, split, out_split, over: str = "batch"):
    """``fn(*args)`` — a call that launches Pallas kernels — placed on the
    mesh the caller set with ``jax.sharding.use_abstract_mesh`` (the train
    step does, ``train.make_train_step``).

    Off a mesh, or on a one-device mesh, ``fn`` runs as is. Otherwise it
    runs under ``shard_map`` with every mesh axis manual. The leading dim
    of each arg flagged in ``split`` and of each output flagged in
    ``out_split`` (a bool, or a tuple of bools for a tuple of outputs) is
    divided over the mesh's batch axes (``over="batch"``: pod, data — the
    rows of a data-parallel step) or over all its axes (``over="all"``, for
    elementwise calls); the other args are whole on every device. When a
    flagged leading dim does not divide, nothing is divided and every
    device runs the whole call. Either way each output is what ``fn``
    gives on one device: flagged leading dims must be rows that ``fn``
    computes independently, so that any split of them is exact."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn(*args)
    axes = batch_axes(mesh) if over == "batch" else tuple(mesh.axis_names)
    n = math.prod(mesh.shape[a] for a in axes)
    rows = [x.shape[0] for x, s in zip(args, split) if s]
    spec = P(axes) if n > 1 and rows and all(r % n == 0 for r in rows) else P()
    pick = lambda s: spec if s else P()
    out_specs = (tuple(map(pick, out_split)) if isinstance(out_split, tuple)
                 else pick(out_split))
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(map(pick, split)),
                         out_specs=out_specs,
                         axis_names=set(mesh.axis_names),
                         check_vma=False)(*args)
