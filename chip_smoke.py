#!/usr/bin/env python3
"""On-chip smoke test of the main path: smollm-135m at its published widths
(30 layers, d=576, 9 heads / 3 KV heads, d_ff 1536, vocab 49152), random
weights from a seed and the repo's synthetic data, trained and served on a
TPU through the library calls the launchers make (``repro.launch.train``,
``repro.launch.serve``), all in this one process.

  python chip_smoke.py            one chip: 3 training steps each in modes
                                  off, matmul, full (f32) and full (bf16)
                                  on the Pallas kernels, then full on the
                                  jnp engine as the reference; PA-AdamW on
                                  both engines in both formats against a
                                  numpy reference; then greedy
                                  continuous-batching serving in full mode
                                  against the one-shot engine
  python chip_smoke.py --chips 4  full-mode Pallas training on a
                                  (data=4, model=1) mesh against the same
                                  global batch on one chip

Each phase prints one JSON line; the last line of standard output is
``{"ok": true, "device": {...}}``. Any failed check, and a machine whose
JAX finds no TPU, exits non-zero without that line. A smoke run, not a
benchmark: step times are host-clock walls of untuned kernels.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import PAConfig  # noqa: E402
from repro.data import DataConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.kernels.pam_optim.ops import pa_adamw_update  # noqa: E402
from repro.kernels.pam_optim.ref import pa_adamw_numpy  # noqa: E402
from repro.optim import OptConfig, init_opt_state  # noqa: E402
from repro.serve import ContinuousEngine, Engine, Request, ServeConfig  # noqa: E402
from repro.train import LoopConfig, TrainConfig, jit_train_step, train  # noqa: E402

ARCH = "smollm-135m"
SEQ, BATCH, STEPS = 1024, 4, 3
LOSS_RTOL = 1e-3
# Kernels each Pallas training mode must show in its compiled step.
TRAIN_KERNELS = {
    "matmul": {"pam_matmul"},
    "full": {"pam_matmul", "pam_attention_fwd", "pam_attention_dq",
             "pam_attention_dkv", "pa_adamw"},
}
SERVE = dict(requests=8, slots=4, prompt=64, new_tokens=16, max_len=256)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(record):
    print(json.dumps(record), flush=True)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def custom_calls(compiled):
    """(kernel name, output dims) of every ``tpu_custom_call`` in a compiled
    program — a Pallas kernel that was compiled, not interpreted."""
    out = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
        result = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) custom-call\(", line)
        dims = [tuple(int(d) for d in s.split(",") if d)
                for s in re.findall(r"\w+\[([\d,]*)\]",
                                    result.group(1) if result else "")]
        out.append((name.group(1) if name else "?", dims))
    return out


def attention_engine(kernels, pa, fused):
    """Which attention ran, read off the compiled program: the fused PAM
    kernels, the fused jnp engine, or the unfused composition (which also
    covers per-slot decode, whose per-row masks the fused path cannot
    take)."""
    if "pam_attention_fwd" in kernels:
        return "fused PAM attention, Pallas kernels"
    if fused and pa.mode == "full" and pa.impl != "pallas":
        return f"fused PAM attention, {pa.impl} engine"
    return "unfused composition"


def peak_bytes(devices):
    """Each device's peak bytes in use since the process started: the
    phases run in one process, so a phase's number includes every phase
    before it."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def step_args(model, opt, data, shardings, batch_sharding):
    """Shapes of the arguments ``train()`` hands its jitted step, placed as
    ``jit_train_step`` places them (None: the default device)."""
    def placed(tree, sh):
        if sh is None:
            return tree
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, sh)

    params = model.abstract()
    opt_state = jax.eval_shape(lambda p: init_opt_state(p, opt), params)
    batch = {k: jax.ShapeDtypeStruct((data.global_batch, data.seq_len), dt,
                                     sharding=batch_sharding)
             for k, dt in (("tokens", np.int32), ("labels", np.int32),
                           ("mask", np.bool_))}
    return (placed(params, shardings and shardings["params"]),
            placed(opt_state, shardings and shardings["opt"]), batch)


def train_phase(pa, *, fused, mesh=None, label=None):
    """STEPS steps of ``train()`` at full width; returns the phase record."""
    cfg = get_config(ARCH, pa=pa, attn_fused_pam=fused)
    model = build_model(cfg)
    opt = OptConfig(peak_lr=3e-3, warmup_steps=1, total_steps=STEPS)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                      global_batch=BATCH)
    train_cfg = TrainConfig()
    # The step train() jits (the same jit_train_step), compiled ahead of
    # time on the same shapes and placements: its compile time and the
    # kernels in its HLO. train() then loads it from the persistent
    # compilation cache.
    t0 = time.perf_counter()
    step, shardings, batch_sharding = jit_train_step(model, opt, train_cfg,
                                                     mesh)
    compiled = step.lower(*step_args(model, opt, data, shardings,
                                     batch_sharding)).compile()
    compile_s = time.perf_counter() - t0
    calls = custom_calls(compiled)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")   # train() resumes
    try:
        t0 = time.perf_counter()
        _, hist = train(model, opt, data, workdir,
                        LoopConfig(steps=STEPS, ckpt_every=STEPS, log_every=1),
                        train_cfg, mesh=mesh, log=log)
        wall_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    devices = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    kernels = dict(collections.Counter(n for n, _ in calls))
    rec = {
        "phase": "train", "label": label or pa.mode, "mode": pa.mode,
        "fmt": pa.fmt, "impl": pa.impl,
        "attention": attention_engine(kernels, pa, fused),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "seq": SEQ, "batch": BATCH, "compile_s": round(compile_s, 2),
        "wall_s": round(wall_s, 2),
        "step_s": [round(t, 4) for t in hist["step_time"]],
        "loss": hist["loss"],
        "tpu_custom_call": len(calls), "kernels": kernels,
        "process_peak_bytes_in_use": peak_bytes(devices),
    }
    check(len(hist["loss"]) == STEPS, f"{rec['label']}: ran "
          f"{len(hist['loss'])} of {STEPS} steps")
    check(all(np.isfinite(hist["loss"])),
          f"{rec['label']}: non-finite loss {hist['loss']}")
    if pa.impl == "pallas" and pa.mode != "off":
        missing = TRAIN_KERNELS[pa.mode] - set(rec["kernels"])
        check(rec["tpu_custom_call"] > 0 and not missing,
              f"{rec['label']}: compiled step lacks kernels {missing} "
              f"({rec['kernels']})")
    return rec, calls


def rel(a, b):
    return abs(a - b) / abs(b)


def one_chip():
    recs = {}
    phases = [
        ("off", PAConfig(mode="off", impl="pallas"), False),
        ("matmul", PAConfig(mode="matmul", impl="pallas"), False),
        ("full", PAConfig(mode="full", impl="pallas"), True),
        ("full_bf16", PAConfig(mode="full", impl="pallas", fmt="bf16"), True),
        ("full_jnp", PAConfig(mode="full", impl="jnp"), True),
    ]
    for label, pa, fused in phases:
        log(f"[chip_smoke] training phase {label}")
        rec, _ = train_phase(pa, fused=fused, label=label)
        emit(rec)
        recs[label] = rec
    diffs = [rel(a, b) for a, b in zip(recs["full"]["loss"],
                                       recs["full_jnp"]["loss"])]
    emit({"phase": "check", "what": "full pallas vs full jnp, same init and "
          "batches", "loss_rel_diff": diffs, "first_step_limit": LOSS_RTOL})
    check(diffs[0] <= LOSS_RTOL, f"first-step loss full/pallas "
          f"{recs['full']['loss'][0]} vs full/jnp "
          f"{recs['full_jnp']['loss'][0]}: rel diff {diffs[0]:.3g}")
    optim_phase()
    serve_phase()


def optim_phase():
    """PA-AdamW on one smollm-135m MLP leaf (576 x 1536: bf16 params and
    gradients, f32 moments, as training holds them), in both formats, on
    the compiled kernel and on the jnp engine: each must match the
    independent numpy reference ``pa_adamw_numpy`` bit for bit."""
    rng = np.random.default_rng(0)
    shape = (576, 1536)
    bf16 = jax.numpy.bfloat16
    p = (rng.standard_normal(shape) * 0.05).astype(bf16)
    g = (rng.standard_normal(shape) * 1e-3).astype(bf16)
    m = (rng.standard_normal(shape) * 1e-4).astype(np.float32)
    v = (rng.random(shape) * 1e-6 + 1e-9).astype(np.float32)
    hyp = dict(b1=0.9, b2=0.98, eps=1e-8, weight_decay=1e-4)
    t, lr, scale = 3.0, 1e-3, 0.75
    for fmt in ("f32", "bf16"):
        ref = pa_adamw_numpy(p, g, m, v, t, lr, scale, fmt_name=fmt, **hyp)
        rec = {"phase": "optim", "fmt": fmt, "leaf": list(shape),
               "what": "elements whose bits differ from the numpy reference"}
        for impl in ("pallas", "jnp"):
            out = jax.jit(lambda *a: pa_adamw_update(
                *({"w": x} for x in a), t, lr, scale, impl=impl, fmt=fmt,
                **hyp))(p, g, m, v)
            rec[impl] = {n: differing_bits(o["w"], r) for n, o, r in
                         zip(("p", "m", "v"), out, ref)}
        emit(rec)
        check(not any(rec["pallas"].values()) and not any(
            rec["jnp"].values()), f"PA-AdamW ({fmt}) departs from the "
            f"numpy reference: {rec}")


def differing_bits(a, b):
    """How many elements of ``a`` and ``b`` differ in their bit patterns."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype)
    u = {2: np.uint16, 4: np.uint32}[a.dtype.itemsize]
    return int(np.count_nonzero(a.view(u) != b.view(u)))


def serve_phase():
    log("[chip_smoke] serving phase")
    pa = PAConfig(mode="full", impl="pallas")
    cfg = get_config(ARCH, pa=pa)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    s = SERVE
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (s["requests"], s["prompt"])).astype(np.int32)
    engine = ContinuousEngine(model, params, ServeConfig(
        max_len=s["max_len"], n_slots=s["slots"]))
    t0 = time.perf_counter()
    out = engine.run([Request(rid=i, prompt=prompts[i],
                              max_new_tokens=s["new_tokens"])
                      for i in range(s["requests"])])
    wall_s = time.perf_counter() - t0
    n = s["slots"]
    # the engine's jitted decode+sample step, as it ran
    compiled = engine._step_fn.lower(
        params, engine.cache, np.zeros((n, 1), np.int32),
        np.zeros((n,), np.int32)).compile()
    calls = custom_calls(compiled)
    t0 = time.perf_counter()
    ref = Engine(model, params, ServeConfig(max_len=s["max_len"])).generate(
        prompts, max_new_tokens=s["new_tokens"])
    oneshot_s = time.perf_counter() - t0
    lat = engine.latency_summary()
    statuses = dict(engine.scheduler.status)
    mismatched = [rid for rid in range(s["requests"])
                  if rid not in out
                  or not np.array_equal(out[rid], ref[rid])]
    kernels = dict(collections.Counter(n for n, _ in calls))
    emit({
        "phase": "serve", "mode": pa.mode, "fmt": pa.fmt, "impl": pa.impl,
        "attention": attention_engine(kernels, pa, cfg.attn_fused_pam),
        **s, "wall_s": round(wall_s, 2), "oneshot_wall_s": round(oneshot_s, 2),
        "ttft_p50_s": lat["ttft_p50_s"], "per_token_p50_s":
            lat["per_token_p50_s"], "ticks": lat["ticks"],
        "tokens": {str(k): len(v) for k, v in out.items()},
        "statuses": sorted(set(statuses.values())),
        "token_mismatch_vs_oneshot": mismatched,
        "tpu_custom_call": len(calls), "kernels": kernels,
        "process_peak_bytes_in_use": peak_bytes(jax.devices()[:1]),
    })
    check(len(out) == s["requests"] and all(
        len(v) == s["new_tokens"] for v in out.values()),
        f"requests did not all finish with {s['new_tokens']} tokens")
    check(set(statuses.values()) == {"ok"}, f"statuses {statuses}")
    check(not mismatched, f"continuous vs one-shot tokens differ for "
          f"requests {mismatched}")
    check("pam_matmul" in kernels,
          "decode step has no compiled PAM kernels")


def four_chips():
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(devices)}")
    pa = PAConfig(mode="full", impl="pallas")
    mesh = make_mesh((4, 1), ("data", "model"))
    log("[chip_smoke] training on the (data=4, model=1) mesh")
    mesh_rec, mesh_calls = train_phase(pa, fused=True, mesh=mesh,
                                       label="full_mesh4")
    rows = lambda calls: sorted({d[0][1] for nm, d in calls
                                 if nm == "pam_matmul" and d and len(d[0]) == 3})
    mesh_rec["pam_matmul_rows"] = rows(mesh_calls)
    emit(mesh_rec)
    log("[chip_smoke] training on one chip, same global batch")
    one_rec, one_calls = train_phase(pa, fused=True, label="full_one_chip")
    one_rec["pam_matmul_rows"] = rows(one_calls)
    emit(one_rec)
    diffs = [rel(a, b) for a, b in zip(mesh_rec["loss"], one_rec["loss"])]
    emit({"phase": "check", "what": "mesh (data=4) vs one chip",
          "loss_rel_diff": diffs, "limit": LOSS_RTOL,
          "mesh_process_peak_bytes_in_use":
              mesh_rec["process_peak_bytes_in_use"]})
    check(max(diffs) <= LOSS_RTOL, f"mesh vs one-chip loss rel diffs {diffs}")
    check(all(b > 0 for b in mesh_rec["process_peak_bytes_in_use"]),
          f"a mesh device held no memory: "
          f"{mesh_rec['process_peak_bytes_in_use']}")
    # Per-device kernels: under shard_map no PAM matmul sees the global
    # token count, which the one-chip program does.
    check(BATCH * SEQ in one_rec["pam_matmul_rows"]
          and BATCH * SEQ not in mesh_rec["pam_matmul_rows"],
          f"mesh kernels see global shapes: rows {mesh_rec['pam_matmul_rows']}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})")
        return 2
    enable_compile_cache()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
