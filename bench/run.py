#!/usr/bin/env python3
"""Benchmark entry point.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration and traffic files by
name, runs the traffic's driver (``bench/drivers/<kind>.py``) on the
chips of this machine, checks the timed path's output against the plain
reference, and prints one JSON result as the last line of standard
output. With ``--trace 0`` the metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py`` from the run and its profiler trace.
Without the program, without a TPU, with too few chips, or with a chip
missing from ``bench/peaks.json`` it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


class Run:
    """One run of one cell: its inputs, and what the driver records."""

    def __init__(self, args, spec, wl, conf, traffic):
        self.args, self.spec, self.workload, self.config = args, spec, wl, conf
        self.traffic = traffic
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.t_start = T_START
        self.trace_data = None
        self.e2e, self.counts, self.checks = {}, {}, {}
        self.attempted = self.failed = 0
        self.memory_peak = 0
        self.setup_s = None


def read_metric(name, run):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def applies(metric, wl):
    return "workloads" not in metric or wl["name"] in metric["workloads"]


def result(run):
    from bench import correct
    spec, wl = run.spec, run.workload
    metrics = {}
    if not run.trace:
        vals = dict(run.e2e, setup_s=run.setup_s)
        for m in spec["end_to_end"]:
            if applies(m, wl):
                metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            if applies(m, wl):
                v = read_metric(m["name"], run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d = run.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(run.all_devices), "memory_peak_bytes": run.memory_peak}
    out = {"correct": correct.all_within(run.checks), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace and run.trace_data:
        device["busy_s"] = run.trace_data["busy_s"]
        device["window_s"] = run.trace_data["window_s"]
        out["breakdown"] = {"device_ops": run.trace_data["device_ops"],
                            "idle_gaps": run.trace_data["idle_gaps"]}
    out["checks"] = run.checks
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import correct, harness
    spec, wl, conf, traffic = harness.find_cell(args.workload)
    run = Run(args, spec, wl, conf, traffic)
    run.limits = correct.limits_for(wl["name"])
    if run.limits is None:
        raise RuntimeError(f"no limits for {wl['name']} in bench/limits/")
    if importlib.util.find_spec("repro") is None:
        harness.eprint("bench: the program under test (src/repro) is not here")
        return 2
    try:
        run.devices, run.peaks = harness.check_devices(wl["chips"])
    except harness.NoChip as e:
        harness.eprint(f"bench: {e}")
        return 2
    import jax
    run.all_devices = jax.devices()
    harness.log(device={"platform": run.devices[0].platform,
                        "kind": run.devices[0].device_kind,
                        "count": len(run.all_devices)},
                compile_cache=harness.enable_compile_cache())
    run.counter = harness.CompileCounter()
    driver = importlib.import_module("bench.drivers." + traffic["kind"])
    driver.run_cell(run)
    out = result(run)
    harness.log(attempted=run.attempted, failed=run.failed, counts=run.counts)
    if run.trace_data:
        harness.log(trace={k: run.trace_data[k] for k in (
            "window_s", "busy_s", "kernels", "trace_bytes") if k in run.trace_data})
    for k, c in run.checks.items():
        harness.eprint(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
