#!/usr/bin/env python3
"""Readings that set the benchmark's correctness limits and serving rate,
run on the chip, each cell in one process (one compilation):

  python bench/calibrate.py train <cell> --seeds 12 --control 3
      per seed, the program's checked steps against the reference; on
      the first ``--control`` seeds also the control (the reference one
      precision lower) and the half-batch fault (the second half of the
      batch masked out of the loss), each against the reference
      ``--witness``: also the reference with pasqrt passing no gradient,
      the program's RMSNorm backward (a full-PA cell)
  python bench/calibrate.py serve <cell> --seeds 12 --control 3 --seconds 15
      per seed a short window at the cell's rate and the served-token
      logit gap; the control's gap on the first ``--control`` seeds
  python bench/calibrate.py sweep <cell> --rates 1,2,3 --seconds 30
      the serving window at each offered rate: throughput, tails, and
      whether the queue grew

The benchmark's own runs never run this. Each reading is one JSON line;
the last line sums them up: the largest program reading (lower) and the
smallest control or fault reading (upper) of each number.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import correct, faults, harness  # noqa: E402


class Cell:
    def __init__(self, name, seconds):
        spec, wl, conf, traffic = harness.find_cell(name)
        self.workload, self.config, self.traffic = wl, conf, traffic
        self.seconds, self.trace, self.trace_data = seconds, False, None
        self.devices, _ = harness.check_devices(wl["chips"])
        harness.enable_compile_cache()
        self.counter = harness.CompileCounter()


def train(cell, seeds, n_control, witness=False):
    from bench.drivers import train as drv
    out = {"program": [], "control": [], "half_batch": []}
    prog = drv.Program(cell)
    bad = drv.Program(cell, step_fault=faults.half_batch)
    n = cell.traffic["checked_steps"]
    for k, seed in enumerate(seeds):
        prog.start(seed)
        got = prog.checked(seed, n)
        prog.free()
        want = drv.reference(cell, seed)
        rd, where = correct.train_readings(got, want)
        out["program"].append(rd)
        harness.log(seed=seed, who="program", readings=rd, where=where,
                    losses=got[0], reference_losses=want[0])
        if witness:
            alt = drv.reference(cell, seed, sqrt_grad=False)
            rd, where = correct.train_readings(got, alt)
            harness.log(seed=seed, who="program vs reference without the "
                        "pasqrt gradient", readings=rd, where=where)
        if k < n_control:
            ctl = drv.reference(cell, seed, lower=True)
            rd = correct.train_readings(ctl, want)[0]
            out["control"].append(rd)
            harness.log(seed=seed, who="control", readings=rd)
            bad.start(seed)
            got = bad.checked(seed, n)
            bad.free()
            rd = correct.train_readings(got, want)[0]
            out["half_batch"].append(rd)
            harness.log(seed=seed, who="half_batch", readings=rd)
    return out


def serve(cell, seeds, n_control):
    from bench.drivers import serve as drv
    out = {"program": [], "control": []}
    tr = cell.traffic
    prog = drv.Program(cell, seeds[0])
    for k, seed in enumerate(seeds):
        prog.reseed(seed)
        sched = drv.schedule(seed, tr, cell.seconds)
        toks = drv.prompts(seed, sched, prog.sizes["vocab_size"])
        rec = drv.serve(prog, sched, toks, cell.seconds, cell.counter)
        e2e, counts = drv.summarize(rec, sched, cell.seconds)
        reqs = drv.sample(seed, rec, sched, tr["check_requests"])
        gap, n = drv.reference_gaps(cell, seed, reqs, toks, rec["tokens"])
        out["program"].append({"served_logit_gap": gap})
        harness.log(seed=seed, who="program", served_logit_gap=gap,
                    tokens=n, e2e=e2e, counts=counts)
        if k < n_control:
            cg, _ = drv.reference_gaps(cell, seed, reqs, toks, rec["tokens"],
                                       lower=True)
            out["control"].append({"served_logit_gap": cg})
            harness.log(seed=seed, who="control", served_logit_gap=cg)
    return out


def sweep(cell, rates, seed):
    from bench.drivers import serve as drv
    tr = dict(cell.traffic)
    prog = drv.Program(cell, seed)
    for rate in rates:
        prog.reseed(seed)
        tr["rate_rps"] = rate
        sched = drv.schedule(seed, tr, cell.seconds)
        toks = drv.prompts(seed, sched, prog.sizes["vocab_size"])
        rec = drv.serve(prog, sched, toks, cell.seconds, cell.counter)
        e2e, counts = drv.summarize(rec, sched, cell.seconds)
        t0 = rec["t0"]
        ttft = [(sched[r][0], rec["emits"][r][0] - (t0 + sched[r][0]))
                for r in rec["in_window"]]
        half = cell.seconds / 2
        first = [t for d, t in ttft if d < half]
        second = [t for d, t in ttft if d >= half]
        harness.log(rate_rps=rate, e2e=e2e, counts=counts,
                    ttft_median_first_half=float(np.median(first)),
                    ttft_median_second_half=float(np.median(second)),
                    drain_s=max(e[-1] for e in rec["emits"].values()) - (t0 + cell.seconds))


def summary(out):
    lower, upper = {}, {}
    for k in out["program"][0]:
        lower[k] = max(r[k] for r in out["program"])
        ups = [r[k] for who in out if who != "program" for r in out[who]]
        upper[k] = min(ups) if ups else None
    return {"lower": lower, "upper": upper}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train", "serve", "sweep"))
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--rates", default="1,2,3")
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args()
    cell = Cell(args.cell, args.seconds)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if args.what == "sweep":
        sweep(cell, [float(x) for x in args.rates.split(",")], seeds[0])
        return
    if args.what == "train":
        out = train(cell, seeds, args.control, args.witness)
    else:
        out = serve(cell, seeds, args.control)
    harness.log(summary=summary(out))


if __name__ == "__main__":
    main()
