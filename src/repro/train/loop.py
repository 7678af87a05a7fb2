"""Fault-tolerant, self-healing training loop.

Production posture implemented and testable on one host:
  * periodic async checkpoints (atomic + integrity-checked, see checkpoint/),
  * automatic resume-from-latest on start (params, optimizer state, step),
    walking past integrity-failed checkpoints to the newest GOOD one,
  * deterministic stateless data -> restart replays the exact stream,
  * graceful-preemption hook: if ``<workdir>/PREEMPT`` appears, the loop
    checkpoints synchronously, CONSUMES the file, and exits 0 (the
    SLURM/BORG SIGTERM analogue; tests exercise it). Consuming matters: a
    restarted job that still sees the stale file would immediately
    re-checkpoint and exit after one step, forever,
  * telemetry ``history`` (loss, step times, straggler alerts, recovery
    counters) is persisted alongside every checkpoint — a resumed run
    APPENDS to the run-so-far record instead of starting a fresh dict,
  * straggler telemetry: EWMA of step time + alert when a step exceeds
    ``straggler_factor`` x EWMA — on a real fleet this feeds the scheduler;
    here it is logged and surfaced in the returned history,
  * self-healing (DESIGN.md §7): arming a ``RecoveryPolicy`` enables the
    bit-level non-finite sentinel + median-window loss-spike detector; an
    unhealthy step rolls params/opt back to the last good checkpoint,
    permanently skips the offending batch in the deterministic data
    stream, and bounded consecutive rollbacks escalate to
    ``UnrecoverableTrainingError``. Checkpoint IO is retry-wrapped with
    exponential backoff,
  * deterministic fault injection (``resilience/faults.py``): an armed
    ``FaultPlan`` can poison gradients, fail checkpoint writes, delay
    steps, or drop the PREEMPT file at exact step/data-index clocks — the
    chaos suite drives all of them through this loop. No plan armed ->
    every hook is None and the hot path is unchanged.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.data import DataConfig, SyntheticLM
from repro.models.registry import Model
from repro.optim import OptConfig, init_opt_state
from .step import TrainConfig, make_train_step


@dataclasses.dataclass
class LoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    straggler_factor: float = 3.0
    keep_ckpts: int = 3


def straggler_check(ewma, dt: float, factor: float):
    """Compare ``dt`` against the PRE-update EWMA, then fold it in.

    Returns ``(is_straggler, new_ewma)``. Order matters: updating the EWMA
    first dilutes the threshold by ``0.1 * factor * dt`` — a step had to be
    ~(factor + 0.1*factor)/(1 - 0.09*factor)… slower than the trailing
    average before it tripped (for factor=3: ~4.1x instead of 3x), so real
    stragglers near the threshold were silently absorbed into the average
    they were being judged by.
    """
    alert = ewma is not None and dt > factor * ewma
    new_ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
    return alert, new_ewma


def _fresh_history():
    return {"loss": [], "step_time": [], "straggler_alerts": 0,
            "rollbacks": 0, "io_retries": 0, "skipped_batches": [],
            "restore_skipped": []}


def _note_restore_skipped(ckpt, history, log):
    """Surface checkpoints that ``restore_latest`` walked past because they
    failed integrity: the operator must see that corruption happened, and
    replay must anchor to the step that was ACTUALLY restored, not the
    newest step on disk."""
    skipped = getattr(ckpt, "last_restore_skipped", [])
    if skipped:
        history["restore_skipped"] = sorted(
            set(history.get("restore_skipped", [])) | set(skipped))
        log(f"[loop] restore skipped corrupted checkpoint step(s) "
            f"{skipped} — integrity failures recorded in history")


def jit_train_step(model: Model, opt_cfg: OptConfig,
                   train_cfg: TrainConfig = TrainConfig(), mesh=None):
    """The jitted step ``train()`` runs and the placements of its
    arguments: ``(step_fn, shardings, batch_sharding)``. Without a mesh
    both placements are None (the default device). On a mesh the params
    and optimizer state keep their model placements (tensor, expert and
    FSDP shardings from the model's axis rules) from step to step, and
    the batch is split over the data axes."""
    step_fn = make_train_step(model, opt_cfg, train_cfg, mesh=mesh)
    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0, 1)), None, None
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.parallel.sharding import batch_axes, tree_shardings
    from repro.optim import opt_state_meta
    shardings = {"params": model.shardings(mesh),
                 "opt": tree_shardings(opt_state_meta(model.meta(), opt_cfg),
                                       mesh, model.cfg.rules)}
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1),
                      out_shardings=(shardings["params"], shardings["opt"],
                                     NamedSharding(mesh, PartitionSpec())))
    return (step_fn, shardings,
            NamedSharding(mesh, PartitionSpec(batch_axes(mesh))))


def train(model: Model, opt_cfg: OptConfig, data_cfg: DataConfig,
          workdir: str, loop_cfg: LoopConfig = LoopConfig(),
          train_cfg: TrainConfig = TrainConfig(),
          mesh=None, log: Callable[[str], None] = print,
          fault_plan=None, recovery=None, recorder=None):
    """Run (or resume) a training job. Returns (params, history).

    ``history`` is CUMULATIVE across preempt/restart cycles: it is
    persisted with every checkpoint and reloaded on resume, so
    ``history['loss'][k]`` is always the loss of global step ``k``.

    ``recovery`` (``resilience.RecoveryPolicy``) arms self-healing;
    ``fault_plan`` (``resilience.FaultPlan``) arms chaos injection;
    ``recorder`` (``resilience.FlightRecorder``) arms the bit-exact
    flight journal (DESIGN.md §8): per-step loss/grad-norm bits + an
    integer fingerprint of the updated param/opt tree, truncated on
    rollback exactly like ``history`` and flushed atomically with every
    checkpoint — ``resilience.replay`` verifies it from any anchor.
    """
    from repro.resilience.detectors import LossSpikeDetector
    from repro.resilience.recovery import (UnrecoverableTrainingError,
                                           data_index, retry_io)

    os.makedirs(workdir, exist_ok=True)
    io_fault = fault_plan.io_fault if fault_plan is not None else None
    ckpt = Checkpointer(os.path.join(workdir, "ckpts"),
                        keep=loop_cfg.keep_ckpts, io_fault=io_fault)
    data = SyntheticLM(data_cfg)

    use_fault_arg = fault_plan is not None and fault_plan.armed("nan_grad")
    if recovery is not None or use_fault_arg or recorder is not None:
        train_cfg = dataclasses.replace(train_cfg,
                                        health=recovery is not None,
                                        fault_arg=use_fault_arg,
                                        record=recorder is not None)
    step_fn, shardings, batch_sharding = jit_train_step(model, opt_cfg,
                                                        train_cfg, mesh)

    params = model.init(jax.random.PRNGKey(data_cfg.seed))
    opt_state = init_opt_state(params, opt_cfg)
    if recorder is not None:
        # The journal header pins the step configuration: replay rebuilds a
        # bit-identical program from it (health/fault_arg change the traced
        # graph, and even `g + 0.0` is not a bit-level identity on -0.0).
        recorder.load_existing()
        recorder.attach({"params": params, "opt": opt_state},
                        step_cfg=dataclasses.asdict(train_cfg))

    start_step = 0
    state_like = {"params": params, "opt": opt_state}
    place_batch = jnp.asarray
    if mesh is not None:
        place_batch = lambda x: jax.device_put(x, batch_sharding)
        params = jax.tree.map(jax.device_put, params, shardings["params"])
        opt_state = jax.tree.map(jax.device_put, opt_state, shardings["opt"])

    history = _fresh_history()
    latest = ckpt.latest_step()
    if latest is not None:
        # shardings flow into restore itself: one device_put onto the target
        # sharding, instead of a default-device restore followed by a second
        # full-tree transfer. restore_latest walks past integrity-failed
        # checkpoints to the newest good one.
        restored_step, restored = ckpt.restore_latest(state_like, shardings,
                                                      log=log)
        params, opt_state = restored["params"], restored["opt"]
        start_step = restored_step
        saved = ckpt.load_extra(restored_step)
        if saved and "history" in saved:
            history.update(saved["history"])
        _note_restore_skipped(ckpt, history, log)
        log(f"[loop] resumed from checkpoint step {restored_step}")
    if recorder is not None:
        # Journal records past the restored step belong to a trajectory
        # this run will re-execute (and re-record bit-identically) — or,
        # after a fallback past corruption, to one it never will. Either
        # way the journal must anchor to the step actually restored.
        recorder.truncate(start_step)

    def save_ckpt(step, blocking):
        def do():
            extra = {"history": history}
            if recorder is not None:
                # journal first: the on-disk journal must cover at least as
                # far as any checkpoint that might anchor a replay, and the
                # ring tail rides in the extra.json sidecar
                recorder.flush()
                extra["flight"] = recorder.sidecar()
            ckpt.save(step, {"params": params, "opt": opt_state},
                      blocking=blocking, extra=extra)
        if recovery is not None:
            attempts = {"n": 0}

            def counted():
                attempts["n"] += 1
                do()
            retry_io(counted, retries=recovery.io_retries,
                     backoff_s=recovery.io_backoff_s, log=log)
            history["io_retries"] += attempts["n"] - 1
        else:
            do()

    # A rollback needs an anchor: with recovery armed, make sure a "last
    # good" checkpoint exists before the first step runs.
    if recovery is not None and ckpt.latest_step() is None:
        save_ckpt(start_step, blocking=True)

    spike = (LossSpikeDetector(recovery.spike_window, recovery.spike_factor,
                               recovery.spike_min_history)
             if recovery is not None else None)
    skipped = set(history.get("skipped_batches", []))
    consecutive_rollbacks = 0
    ewma = None
    preempt_file = os.path.join(workdir, "PREEMPT")

    step = start_step
    while step < loop_cfg.steps:
        t0 = time.perf_counter()
        if fault_plan is not None:
            spec = fault_plan.pop("straggler", step)
            if spec is not None:
                # inside the timed window — the EWMA straggler alert must
                # see the injected delay, exactly like a real slow step
                time.sleep(spec.delay_s)
            if fault_plan.pop("preempt", step) is not None:
                open(preempt_file, "w").close()
        d = data_index(step, skipped) if skipped else step
        batch = jax.tree.map(place_batch, data.batch(d))
        if use_fault_arg:
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, fault_plan.grad_fault(d))
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0

        # -- health sentinels + rollback (DESIGN.md §7) ---------------------
        if recovery is not None:
            reason = None
            if int(metrics["nonfinite"]) > 0 or not np.isfinite(loss):
                reason = (f"non-finite state "
                          f"({int(metrics['nonfinite'])} leaves flagged, "
                          f"loss={loss})")
            elif spike.check(loss):
                reason = (f"loss spike ({loss:.4f} > "
                          f"{recovery.spike_factor}x trailing median)")
            if reason is not None:
                history["rollbacks"] += 1
                consecutive_rollbacks += 1
                if consecutive_rollbacks > recovery.max_rollbacks:
                    raise UnrecoverableTrainingError(
                        f"step {step}: {reason}; {consecutive_rollbacks} "
                        f"consecutive rollbacks without progress — "
                        f"escalating to abort")
                skipped.add(d)
                history["skipped_batches"] = sorted(skipped)
                good_step, restored = retry_io(
                    lambda: ckpt.restore_latest(state_like, shardings,
                                                log=log),
                    retries=recovery.io_retries,
                    backoff_s=recovery.io_backoff_s, log=log)
                params, opt_state = restored["params"], restored["opt"]
                _note_restore_skipped(ckpt, history, log)
                log(f"[loop] UNHEALTHY step {step}: {reason} — rolled back "
                    f"to checkpoint step {good_step}, skipping batch {d} "
                    f"(retry {consecutive_rollbacks}/{recovery.max_rollbacks})")
                history["loss"] = history["loss"][:good_step]
                history["step_time"] = history["step_time"][:good_step]
                if recorder is not None:
                    # the journal mirrors history: the rolled-back steps
                    # never ran, and their replay re-records bit-identically
                    recorder.truncate(good_step)
                spike.reset()
                ewma = None
                step = good_step
                continue

        prev_ewma = ewma                    # the threshold the alert uses
        alert, ewma = straggler_check(ewma, dt, loop_cfg.straggler_factor)
        if alert and step > start_step + 3:
            history["straggler_alerts"] += 1
            log(f"[loop] STRAGGLER step {step}: {dt:.3f}s vs EWMA "
                f"{prev_ewma:.3f}s")
        history["loss"].append(loss)
        history["step_time"].append(dt)
        if recorder is not None:
            recorder.record_step(step, d, metrics)

        if step % loop_cfg.log_every == 0:
            log(f"[loop] step {step:5d} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")

        done = step + 1
        if os.path.exists(preempt_file):
            save_ckpt(done, blocking=True)
            # consume the signal: a restarted job must not see the stale
            # file and re-checkpoint+exit after one step forever
            try:
                os.remove(preempt_file)
            except OSError:
                pass
            log(f"[loop] preemption requested — checkpointed at step {done}, "
                f"exiting")
            return params, history
        if done % loop_cfg.ckpt_every == 0 or done == loop_cfg.steps:
            save_ckpt(done, blocking=(done == loop_cfg.steps))
            consecutive_rollbacks = 0       # a new good anchor exists
        step += 1
    if recovery is not None:
        try:
            ckpt.wait()
        except OSError as e:
            history["io_retries"] += 1
            log(f"[loop] final async checkpoint failed after retries: {e}")
    else:
        ckpt.wait()
    if recorder is not None:
        recorder.flush()
    return params, history
