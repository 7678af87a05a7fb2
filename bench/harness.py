"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, counting compilations, and the run's record."""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip missing from the peaks."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_cell(name: str):
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    conf = load_json(os.path.join(ROOT, conf_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", wl["traffic"] + ".json"))
    return spec, wl, conf, traffic


def log(**rec):
    """An earlier line of standard output: one JSON object."""
    print(json.dumps(rec, default=float), flush=True)


def check_devices(chips: int):
    """The devices a cell runs on; raises NoChip rather than fall back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks[kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (whatever the environment names), keeping every program, so
    that only a cell's first run in a checkout compiles."""
    import jax
    d = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


class CompileCounter:
    """Counts traces, compilations and compile-cache loads from JAX's own
    monitoring events, so a window can show that it compiled nothing."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def now() -> float:
    return time.perf_counter()


def eprint(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
