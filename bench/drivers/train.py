"""Training cells: the program's jitted train step, driven back to back.

Set-up builds one compiled step and its state, makes a pool of distinct
batches on the device, and drives the step through its first
``checked_steps`` steps, reading what the comparison needs. The window
then dispatches the same compiled step on the pool, cycled, keeping at
most ``in_flight`` steps queued: the host neither builds a batch nor
waits on the step it has just dispatched. Losses are fetched after the
window.
"""
from __future__ import annotations

import collections
import gc

import numpy as np
import jax
import jax.numpy as jnp

from bench import arch, correct, harness, program, trace, weights
from bench.reference import model as ref_model, train as ref_train


def batches(seed, traffic, vocab):
    """``pool`` batches of distinct rows, from the seed, on the host."""
    n, b, s = traffic["pool"], traffic["batch"], traffic["seq"]
    toks = weights.markov_tokens(seed, "train", n * b, s, vocab)
    return [{"tokens": toks[i * b:(i + 1) * b, :-1],
             "labels": toks[i * b:(i + 1) * b, 1:],
             "mask": np.ones((b, s), bool)} for i in range(n)]


def opt_config(traffic):
    from repro.optim import OptConfig
    return OptConfig(**traffic["optimizer"])


class Program:
    """The program's compiled train step and its state for one seed."""

    def __init__(self, run, step_fault=None):
        from repro.optim import init_opt_state
        from repro.train import TrainConfig, jit_train_step
        self.run, conf, tr = run, run.config, run.traffic
        self.sizes, self.ref = program.sizes(conf), arch.of(conf)
        self.model = program.build(conf)
        program.check_kv_block(conf, tr["seq"], tr["seq"])
        opt = opt_config(tr)
        self._init_opt = jax.jit(lambda p: init_opt_state(p, opt))
        step, _, _ = jit_train_step(self.model, opt, TrainConfig())
        self.step_fn = step if step_fault is None else step_fault(step)
        self._norms = jax.jit(ref_train.leaf_norms)
        self._delta = jax.jit(ref_train.delta_norms)
        self.compiled = None

    def start(self, seed):
        """Weights, state and batch pool for a seed; compiles once."""
        tr = self.run.traffic
        self.params = weights.init_params(self.ref, self.sizes, seed)
        program.check_params(self.model, self.params)
        self.opt_state = self._init_opt(self.params)
        self.pool = [jax.tree.map(jnp.asarray, b)
                     for b in batches(seed, tr, self.sizes["vocab_size"])]
        if self.compiled is None:
            args = (self.params, self.opt_state, self.pool[0])
            if self.sizes["mode"] == "full":
                n = program.mul_audit(jax.make_jaxpr(self.step_fn)(*args))
                harness.log(audit="train step", tensor_multiplies=n)
                if n:
                    raise RuntimeError(f"the full-PA train step has {n} "
                                       "tensor multiplies")
            self.compiled = self.step_fn.lower(*args).compile()
        self.i = 0

    def step(self):
        self.params, self.opt_state, met = self.compiled(
            self.params, self.opt_state, self.pool[self.i % len(self.pool)])
        self.i += 1
        return met["loss"]

    def checked(self, seed, n):
        """The first n steps, with the readings the comparison takes."""
        losses, m1 = [], None
        for k in range(n):
            losses.append(self.step())
            if k == 0:
                m1 = jax.device_get(self._norms(self.opt_state["m"]))
        p0 = weights.init_params(self.ref, self.sizes, seed)
        dn = jax.device_get(self._delta(self.params, p0))
        del p0
        return [float(x) for x in losses], m1, dn

    def free(self):
        for k in ("params", "opt_state", "pool"):
            self.__dict__.pop(k, None)
        gc.collect()


def window(prog, seconds, in_flight, counter):
    """Back-to-back steps for ``seconds``; returns (steps, elapsed s,
    losses, compilations in the window)."""
    queue, losses = collections.deque(), []
    c0 = counter.n
    t0 = harness.now()
    with trace.span("bench.window"):
        while True:
            with trace.span("bench.dispatch"):
                loss = prog.step()
            losses.append(loss)
            queue.append(loss)
            while len(queue) >= in_flight:
                with trace.span("bench.wait_oldest"):
                    queue.popleft().block_until_ready()
            if harness.now() - t0 >= seconds:
                break
        with trace.span("bench.drain"):
            jax.block_until_ready((prog.params, prog.opt_state, losses))
    return len(losses), harness.now() - t0, losses, counter.n - c0


def reference(run, seed, lower=False, **numerics):
    """The reference's readings for a seed: the same weights from the
    seed, the same first batches."""
    s, tr, ref = program.sizes(run.config), run.traffic, arch.of(run.config)
    nx = ref_model.Numerics(mode=s["mode"], act=s["compute_dtype"],
                            lower=lower, kv_block=s["kv_block"] or 128,
                            **numerics)
    pool = batches(seed, tr, s["vocab_size"])
    bs = [jax.tree.map(jnp.asarray, pool[i % len(pool)])
          for i in range(tr["checked_steps"])]
    params = weights.init_params(ref, s, seed)
    return ref_train.run(ref, nx, s, tr["optimizer"], params, bs)


def run_cell(run):
    tr = run.traffic
    prog = Program(run, getattr(run, "step_fault", None))
    prog.start(run.seed)
    got = prog.checked(run.seed, tr["checked_steps"])
    secs = run.seconds
    if run.trace:
        secs = min(secs, tr.get("trace_seconds", secs))
    tracer = trace.Tracer() if run.trace else None
    run.setup_s = harness.now() - run.t_start
    if tracer:
        tracer.start()
    steps, elapsed, losses, compiles = window(prog, secs, tr["in_flight"],
                                              run.counter)
    if tracer:
        run.trace_data = tracer.stop()
    losses = [float(x) for x in jax.device_get(losses)]
    harness.log(window_steps=steps, window_s=elapsed,
                window_compilations=compiles, checked_losses=got[0],
                window_losses={"first": losses[:3], "last": losses[-3:],
                               "min": min(losses), "max": max(losses)})
    run.memory_peak = harness.peak_bytes(run.devices)
    harness.log(peak_bytes_in_use=run.memory_peak)
    prog.free()
    del prog
    want = reference(run, run.seed)
    tokens = steps * tr["batch"] * tr["seq"]
    run.e2e = {"train_tokens_per_s": tokens / elapsed}
    run.counts = {"tokens_per_step": tr["batch"] * tr["seq"],
                  "steps": steps, "window_s": elapsed}
    run.attempted = steps
    run.failed = int(sum(not np.isfinite(x) for x in losses))
    run.checks = correct.train_checks(got, want, run.limits)
    run.checks["window_compilations"] = {"value": compiles, "limit": 0}
    run.checks["nonfinite_window_losses"] = {"value": run.failed, "limit": 0}
