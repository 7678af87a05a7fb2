"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --pa full --steps 100 --workdir /tmp/run

Any assigned architecture is selectable via --arch; --smoke selects the
reduced config (CPU-runnable), otherwise the full config is used (sized for
the production mesh; on real hardware pass --mesh-shape/--mesh-axes).
"""
from __future__ import annotations

import argparse
import os

from repro.core import PAConfig
from repro.configs import ARCHS, SHAPES, get_config, get_smoke_config
from repro.data import DataConfig
from repro.models import build_model
from repro.optim import OptConfig
from repro.train import LoopConfig, TrainConfig, train
from .compile_cache import enable_compile_cache


def build_pa(args) -> PAConfig:
    return PAConfig(mode=args.pa, deriv=args.deriv, loss_deriv=args.loss_deriv,
                    impl=args.impl, mantissa_bits=args.mantissa_bits,
                    compensate=args.compensate)


def add_pa_args(ap):
    ap.add_argument("--pa", choices=["off", "matmul", "full"], default="off")
    ap.add_argument("--deriv", choices=["exact", "approx"], default="approx")
    ap.add_argument("--loss-deriv", choices=["exact", "approx"], default="exact")
    ap.add_argument("--impl", choices=["jnp", "pallas", "hw"], default="jnp")
    ap.add_argument("--mantissa-bits", type=int, default=None)
    ap.add_argument("--compensate", action="store_true")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress-bits", type=int, default=None)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--record", action="store_true",
                    help="arm the bit-exact flight recorder: per-step "
                         "journal at <workdir>/journal.jsonl, verifiable "
                         "with repro.launch.replay (DESIGN.md §8)")
    ap.add_argument("--mesh-shape", default=None, help="e.g. 2,16,16")
    ap.add_argument("--mesh-axes", default="pod,data,model")
    add_pa_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    pa = build_pa(args)
    cfg = (get_smoke_config(args.arch, pa=pa) if args.smoke
           else get_config(args.arch, pa=pa))
    model = build_model(cfg)

    mesh = None
    if args.mesh_shape:
        from .mesh import make_mesh
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
        mesh = make_mesh(shape, tuple(args.mesh_axes.split(","))[:len(shape)])

    opt = OptConfig(peak_lr=args.lr, warmup_steps=max(1, args.steps // 10),
                    total_steps=args.steps)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.batch)
    recorder = None
    if args.record:
        from repro.resilience import FlightRecorder, journal_path
        recorder = FlightRecorder(journal_path(args.workdir))
    params, hist = train(
        model, opt, data, args.workdir,
        LoopConfig(steps=args.steps, ckpt_every=args.ckpt_every),
        TrainConfig(microbatches=args.microbatches,
                    grad_compress_bits=args.grad_compress_bits),
        mesh=mesh, recorder=recorder)
    print(f"final loss {hist['loss'][-1]:.4f} "
          f"(first {hist['loss'][0]:.4f}); "
          f"median step {sorted(hist['step_time'])[len(hist['step_time'])//2]*1e3:.0f} ms")


if __name__ == "__main__":
    main()
