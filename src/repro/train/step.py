"""Train-step factory: loss -> grads -> (optionally compressed) -> AdamW.

Supports gradient accumulation over microbatches (a lax.scan, so the HLO
stays compact at any accumulation depth) and mantissa-truncation gradient
compression for the cross-pod (DCN) all-reduce — a PAM-native trick: the
paper's Appendix D shows >=4 mantissa bits suffice, so shaving gradient
mantissas before the slow inter-pod reduce is numerically in-distribution
for PA training.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import PAConfig
from repro.core import floatbits as fb
from repro.core.floatbits import mantissa_round
from repro.core.pam import pam_value
from repro.models.registry import Model
from repro.optim import OptConfig, adamw_update, init_opt_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    grad_compress_bits: Optional[int] = None    # e.g. 7 (bf16-equivalent)
    # Resilience (DESIGN.md §7). ``health=True`` adds a bit-level
    # non-finite scan over (loss, grad_norm, updated params) to the
    # metrics — integer exponent-field compares only, so the full-PA
    # multiplication audit still reports zero with guards enabled.
    # ``fault_arg=True`` (fault injection only — armed by a FaultPlan,
    # never in production) adds a scalar step argument that is added to
    # every gradient leaf: 0.0 is the identity, NaN/Inf poisons the step.
    health: bool = False
    fault_arg: bool = False
    # Flight recorder (DESIGN.md §8). ``record=True`` adds the bit-exact
    # flight metrics to the step output: loss/grad-norm BIT PATTERNS and a
    # per-leaf integer fingerprint of the updated param/opt tree
    # (bitcast -> position-mixed xor fold, resilience/recorder.py). All
    # integer ops, so the full-PA multiplication audit stays at zero with
    # the recorder armed.
    record: bool = False


def _split_micro(batch, n):
    def sp(x):
        b = x.shape[0]
        assert b % n == 0, f"batch {b} not divisible by microbatches {n}"
        return x.reshape((n, b // n) + x.shape[1:])
    return jax.tree.map(sp, batch)


def make_train_step(model: Model, opt_cfg: OptConfig,
                    train_cfg: TrainConfig = TrainConfig(), mesh=None):
    """The jit-able train step. With a ``mesh`` the step is traced under
    that mesh (``jax.sharding.use_abstract_mesh``) and stays automatically
    partitioned; the mesh only tells the kernel wrappers where to place
    their Pallas launches, which cannot be partitioned automatically
    (``kernels._backend.per_device``)."""
    pa: PAConfig = model.cfg.pa

    def train_step(params, opt_state, batch, fault=None):
        if train_cfg.microbatches > 1:
            micro = _split_micro(batch, train_cfg.microbatches)

            def acc(carry, mb):
                loss_sum, gsum = carry
                loss, g = jax.value_and_grad(model.loss)(params, mb)
                gsum = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gsum, g)
                return (loss_sum + loss, gsum), ()

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss_sum, gsum), _ = jax.lax.scan(acc, (jnp.float32(0), zeros), micro)
            n = train_cfg.microbatches
            # loss is a scalar metric: native mean (O(1) scalar, exempt from
            # the multiplication-free audit). The gradient average is
            # tensor-shaped and feeds the PA optimizer, so in PA mode it
            # must not emit native multiplies: a power-of-two microbatch
            # count is an exponent shift (bit-identical to * 1/n except
            # that subnormal results flush to zero), anything else is a
            # PAM by 1/n.
            loss = loss_sum * (1.0 / n)
            if pa.optimizer_is_pa and pa.impl != "hw":
                if n & (n - 1) == 0:
                    shift = 1 - n.bit_length()          # 2^-log2(n), exact
                    grads = jax.tree.map(lambda g: fb.pow2_mul(g, shift), gsum)
                else:
                    inv = np.float32(1.0 / n)
                    grads = jax.tree.map(lambda g: pam_value(g, inv), gsum)
            else:
                grads = jax.tree.map(lambda g: g * (1.0 / n), gsum)
        else:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)

        if train_cfg.grad_compress_bits is not None:
            grads = jax.tree.map(
                lambda g: mantissa_round(g.astype(jnp.float32),
                                         train_cfg.grad_compress_bits), grads)

        if train_cfg.fault_arg:
            # Fault injection (resilience chaos suite): add a host-supplied
            # scalar to every gradient leaf — 0.0 normally, NaN/Inf when the
            # plan fires — so the poison flows through the real update path.
            grads = jax.tree.map(
                lambda g: g + jnp.asarray(fault).astype(g.dtype), grads)

        params, opt_state, metrics = adamw_update(params, grads, opt_state,
                                                  opt_cfg, pa=pa)
        metrics["loss"] = loss
        if train_cfg.health:
            # Bit-level non-finite sentinel (resilience/detectors.py):
            # integer exponent-field compares only — enabling guards keeps
            # the full-PA step's multiplication audit at zero.
            from repro.resilience.detectors import nonfinite_count
            metrics["nonfinite"] = nonfinite_count(
                (loss, metrics["grad_norm"], params))
        if train_cfg.record:
            # Flight recorder (resilience/recorder.py): bit patterns +
            # integer tree fingerprint of the POST-update state — exactly
            # what a checkpoint at this step would contain, which is what
            # lets replay verify its anchor before re-running a window.
            from repro.resilience.recorder import float_bits, tree_leaf_digests
            metrics["loss_bits"] = float_bits(loss)
            metrics["grad_norm_bits"] = float_bits(metrics["grad_norm"])
            metrics["leaf_digests"] = tree_leaf_digests(
                {"params": params, "opt": opt_state})
        return params, opt_state, metrics

    step = train_step
    if not train_cfg.fault_arg:
        # production signature unchanged when no fault plan is armed
        step = lambda params, opt_state, batch: train_step(params, opt_state,
                                                           batch)
    if mesh is None:
        return step

    def step_on_mesh(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(*args)
    return step_on_mesh


def make_eval_step(model: Model):
    def eval_step(params, batch):
        return model.loss(params, batch)
    return eval_step
