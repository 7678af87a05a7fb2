"""Serving launcher: one-shot batched generation OR the continuous-batching
engine driven by a Poisson request trace.

One-shot (fixed batch, run-to-completion — the legacy mode):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 4 --new-tokens 16

Continuous batching (slot pool + request queue, DESIGN.md §6):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --continuous --slots 4 --requests 16 --rate 0.5 --new-tokens-max 32

``--rate`` is the Poisson arrival rate in requests per decode tick;
inter-arrival gaps are drawn from Exp(rate) and cumulated into integer
arrival ticks, so a trace is reproducible from ``--trace-seed``.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import build_model
from repro.serve import ContinuousEngine, Engine, Request, ServeConfig
from .compile_cache import enable_compile_cache
from .train import add_pa_args, build_pa


def poisson_trace(n_requests: int, rate: float, prompt_len: int,
                  new_tokens_min: int, new_tokens_max: int,
                  vocab_size: int, seed: int = 0):
    """A reproducible request trace: Poisson arrivals (in scheduler ticks),
    uniform random generation budgets, random prompts."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), n_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(int) if rate > 0 else \
        np.zeros(n_requests, int)
    return [
        Request(rid=i,
                prompt=rng.integers(0, vocab_size, (prompt_len,)).astype(np.int32),
                max_new_tokens=int(rng.integers(new_tokens_min,
                                                new_tokens_max + 1)),
                arrival=int(arrivals[i]))
        for i in range(n_requests)
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    # continuous-batching trace driver
    ap.add_argument("--continuous", action="store_true",
                    help="slot-pool engine driven by a Poisson request trace")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per decode tick)")
    ap.add_argument("--new-tokens-min", type=int, default=4)
    ap.add_argument("--new-tokens-max", type=int, default=0,
                    help="0 -> use --new-tokens as the fixed budget")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are produced")
    add_pa_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    pa = build_pa(args)
    cfg = (get_smoke_config(args.arch, pa=pa) if args.smoke
           else get_config(args.arch, pa=pa))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if not args.continuous:
        engine = Engine(model, params,
                        ServeConfig(max_len=args.max_len,
                                    temperature=args.temperature))
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.batch, args.prompt_len)).astype(np.int32)
        t0 = time.perf_counter()
        out = engine.generate(prompts, max_new_tokens=args.new_tokens)
        dt = time.perf_counter() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
        print("sample:", out[0].tolist())
        return

    hi = args.new_tokens_max or args.new_tokens
    lo = min(args.new_tokens_min, hi)
    trace = poisson_trace(args.requests, args.rate, args.prompt_len,
                          lo, hi, cfg.vocab_size, seed=args.trace_seed)
    engine = ContinuousEngine(
        model, params,
        ServeConfig(max_len=args.max_len, temperature=args.temperature,
                    n_slots=args.slots, eos_id=args.eos_id))
    on_token = ((lambda rid, tok: print(f"  [req {rid}] {tok}"))
                if args.stream else None)
    t0 = time.perf_counter()
    out = engine.run(trace, on_token=on_token)
    dt = time.perf_counter() - t0
    total = sum(len(t) for t in out.values())
    lat = engine.latency_summary()
    print(f"served {len(out)} requests / {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s) on {args.slots} slots")
    print(f"ttft p50/p99: {lat['ttft_p50_s']*1e3:.1f}/"
          f"{lat['ttft_p99_s']*1e3:.1f} ms  "
          f"per-token p50/p99: {lat['per_token_p50_s']*1e3:.1f}/"
          f"{lat['per_token_p99_s']*1e3:.1f} ms  "
          f"occupancy {lat['slot_occupancy_mean']:.2f}  "
          f"ticks {int(lat['ticks'])}")
    first = trace[0]
    print(f"sample [req {first.rid}]:", out[first.rid].tolist())


if __name__ == "__main__":
    main()
