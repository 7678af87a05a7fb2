"""Multi-device audit checks (layer 4 support, DESIGN.md §9): prove the
zero-tensor-multiply invariant survives ``shard_map`` collectives —
gradient psum and the FSDP-style norm all-reduce (ROADMAP item 1).

This module FORCES a 4-device host platform at import time (the flag must
be set before the first jax initialisation), so it must run in its own
process::

    PYTHONPATH=src python -m repro.analysis.shard_check [--execute]

It prints a JSON report to stdout and exits nonzero if any check finds a
tensor-shaped multiply. The audit gates in tests/ and benchmarks/ invoke
it as a subprocess; ``launch.audit`` (which forces the same flag) imports
``run_checks`` directly.

Checks (all on the tiny full-PA decoder used by the train-step audit
gates):

  ``train_dp``        — data-parallel train step under ``shard_map``
      over a 4-way mesh: per-shard value_and_grad, gradient psum, exact
      pow2 mean over shards (4 devices = exponent shift), a PA
      partial-norm all-reduce (per-shard PAM sum-of-squares -> scalar
      psum -> O(1) scalar sqrt), then the fused PA-AdamW update.
  ``train_dp_health`` — same step with the bit-level non-finite sentinel
      folded in (integer exponent-field compares must stay exempt under
      collectives too).
  ``decode_dp``       — the continuous engine's fused decode+sample step
      (temperature > 0: PA Gumbel-argmax) shard_mapped over the slot
      pool, cache leaves sharded on their per-leaf slot dimension
      (``cache_batch_dims``).

Each check reports ``psum_count`` alongside the audit so the gate can
assert the collectives are actually present (a vacuously-collective-free
program proves nothing).
"""
from __future__ import annotations

import os

# A CPU-only tool: pin the platform before jax initialises, so it never
# takes an accelerator from the process that owns it.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           ).strip()

import argparse
import json
import sys
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .audit import jaxpr_mul_stats
from .contract import _iter_eqns
from .jaxpr_types import open_jaxpr

N_DEVICES = 4
COLLECTIVE_PRIMS = ("psum", "all_gather", "psum_scatter", "all_to_all",
                    "ppermute")


def _tiny_cfg(deriv: str = "approx"):
    from repro.core import PAConfig
    from repro.models.common import ModelConfig
    return ModelConfig(name="tiny", family="decoder", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                       vocab_size=64, max_seq_len=64, param_dtype="float32",
                       compute_dtype="float32", remat="none",
                       pa=PAConfig(mode="full", deriv=deriv,
                                   loss_deriv="exact"))


def _mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((N_DEVICES,), ("data",))


def collective_count(jaxpr) -> int:
    return sum(1 for eqn, _ in _iter_eqns(open_jaxpr(jaxpr))
               if eqn.primitive.name in COLLECTIVE_PRIMS)


def _train_dp(health: bool):
    """(jaxpr, run_thunk) for the shard_map data-parallel train step."""
    from repro.core import floatbits as fb
    from repro.core.pam import pam_value
    from repro.data import DataConfig, SyntheticLM
    from repro.models import build_model
    from repro.optim import OptConfig, adamw_update, init_opt_state

    model = build_model(_tiny_cfg())
    opt_cfg = OptConfig(peak_lr=3e-3, warmup_steps=5, total_steps=30)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = init_opt_state(params, opt_cfg)
    data = SyntheticLM(DataConfig(vocab_size=64, seq_len=32, global_batch=8,
                                  seed=1))
    batch = jax.tree.map(jnp.asarray, data.batch(0))

    def dp_step(params, opt_state, batch):
        loss, g_local = jax.value_and_grad(model.loss)(params, batch)
        # FSDP-style norm all-reduce: per-shard PAM partial sum of squares,
        # ONE scalar psum, sqrt on the O(1) scalar (audit-exempt).
        local_sq = sum(jnp.sum(pam_value(x, x))
                       for x in jax.tree.leaves(g_local))
        dp_norm = jnp.sqrt(jax.lax.psum(local_sq, "data"))
        # Gradient all-reduce, then mean over 4 shards = exact exponent
        # shift (pow2_mul, the paper's "pow2 scales are exact" rule).
        g = jax.tree.map(lambda x: jax.lax.psum(x, "data"), g_local)
        g = jax.tree.map(lambda x: fb.pow2_mul(x, -2), g)
        loss = fb.pow2_mul(jax.lax.psum(loss, "data"), -2)
        params, opt_state, metrics = adamw_update(params, g, opt_state,
                                                  opt_cfg, pa=model.cfg.pa)
        metrics["loss"] = loss
        metrics["dp_grad_norm"] = dp_norm
        if health:
            from repro.resilience.detectors import nonfinite_count
            metrics["nonfinite"] = nonfinite_count(
                (loss, metrics["grad_norm"], params))
        return params, opt_state, metrics

    step = jax.shard_map(dp_step, mesh=_mesh(),
                         in_specs=(P(), P(), P("data")),
                         out_specs=(P(), P(), P()), check_vma=False)
    jaxpr = jax.make_jaxpr(step)(params, opt_state, batch)
    run = lambda: jax.block_until_ready(jax.jit(step)(params, opt_state,
                                                      batch))
    return jaxpr, run


def _decode_dp():
    """(jaxpr, run_thunk) for the engine decode+sample step shard_mapped
    over the slot pool (2 slots per device)."""
    from repro.models import build_model
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.engine import ServeConfig

    model = build_model(_tiny_cfg())
    params = model.init(jax.random.PRNGKey(0))
    n_slots = 2 * N_DEVICES
    eng = ContinuousEngine(model, params,
                           ServeConfig(n_slots=n_slots, max_len=32,
                                       temperature=1.0))
    dims = model.cache_batch_dims()
    cache_specs = jax.tree.map(
        lambda d: P(*([None] * d + ["data"])), dims)
    n_extras = int(eng.cfg.guard_nonfinite) + int(eng.cfg.record)
    step = jax.shard_map(
        eng._step_impl, mesh=_mesh(),
        in_specs=(P(), cache_specs, P("data"), P("data"), P("data"),
                  P("data")),
        out_specs=(P("data"),) + (P("data"),) * n_extras + (cache_specs,),
        check_vma=False)
    args = (params, eng.cache, jnp.zeros((n_slots, 1), jnp.int32),
            jnp.zeros((n_slots,), jnp.int32),
            jnp.arange(n_slots, dtype=jnp.int32),
            jnp.zeros((n_slots,), jnp.int32))
    jaxpr = jax.make_jaxpr(step)(*args)
    run = lambda: jax.block_until_ready(jax.jit(step)(*args))
    return jaxpr, run


def run_checks(execute: bool = False) -> Dict:
    """Run all shard_map audit checks; returns the JSON-able report."""
    checks = {}
    # decode_dp is shard_map-without-collectives by design (slot rows are
    # independent); only the train checks must prove psums are present.
    builders = {
        "train_dp": (lambda: _train_dp(health=False), True),
        "train_dp_health": (lambda: _train_dp(health=True), True),
        "decode_dp": (_decode_dp, False),
    }
    for name, (build, need_collectives) in builders.items():
        jaxpr, run = build()
        stats = jaxpr_mul_stats(jaxpr)
        entry = {
            "tensor_total": stats["tensor_total"],
            "tensor": stats["tensor"],
            "tensor_sites": stats["tensor_sites"],
            "pow2": stats["pow2"],
            "integer": stats["integer"],
            "by_family": stats["by_family"],
            "collective_count": collective_count(jaxpr),
            "require_collectives": need_collectives,
            "executed": False,
        }
        if stats["tensor_total"]:
            entry["violations"] = stats["violations"]
        if execute:
            run()
            entry["executed"] = True
        checks[name] = entry
    return {
        "kind": "shard_check",
        "device_count": jax.device_count(),
        "backend": jax.default_backend(),
        "checks": checks,
        "ok": all(c["tensor_total"] == 0
                  and (c["collective_count"] > 0
                       or not c["require_collectives"])
                  for c in checks.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--execute", action="store_true",
                    help="also run each step on the forced 4-device mesh "
                         "(compiles; slower)")
    ns = ap.parse_args(argv)
    if jax.device_count() < N_DEVICES:
        print(json.dumps({"kind": "shard_check", "ok": False,
                          "error": f"only {jax.device_count()} devices — "
                                   "XLA_FLAGS was set after jax init?"}))
        return 2
    report = run_checks(execute=ns.execute)
    print(json.dumps(report, indent=2, sort_keys=True))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
