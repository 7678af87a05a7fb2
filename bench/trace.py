"""Profiler traces: the harness's host spans, and the reduction of a
``.xplane.pb`` to what the per-layer metrics read.

The reduction takes the device planes' operation events ("XLA Ops"),
clipped to the harness's ``bench.window`` span: their union is the busy
time, the rest of the window is idle. An operation event is named by
its HLO instruction, ``%pam_matmul.348 = f32[1,1024,49152]{...}
custom-call(f32[...] %a, ...)``: the name before the number is the
operation's kind (a Pallas kernel's name, ``fusion``, ``while``...), and
the shapes up to ``custom_call_target`` are its result and operands.
Kernel time is the sum of a kind's events, and its bytes those of its
events' shapes, each in the share of its time that lies in the window;
program time is the sum of a jitted program's module events ("XLA
Modules"). Each idle gap is attributed to the innermost ``bench.*`` host
span that covers its middle.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import tempfile

from bench import work

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def span(name: str):
    """A host span in the profiler's trace (free when it is off)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    def __init__(self):
        self.dir = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # the harness's spans suffice
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            out = reduce_file(path)
            if out is not None:
                out["trace_bytes"] = os.path.getsize(path)
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load(path):
    """(device planes' events, host spans) of an xplane file. Device
    events: (line, name, start_ns, end_ns); spans: (name, start,
    end)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, spans = collections.defaultdict(list), []
    for pl in pd.planes:
        if DEVICE_PLANE.match(pl.name):
            for ln in pl.lines:
                if ln.name in (OPS_LINE, MODULES_LINE):
                    for e in ln.events:
                        s = float(e.start_ns)
                        dev[pl.name].append((ln.name, e.name, s,
                                             s + float(e.duration_ns)))
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        spans.append((e.name, s, s + float(e.duration_ns)))
    return dev, spans


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


CONTROL_FLOW = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """``%pam_matmul.348 = ...`` -> ``pam_matmul``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_shapes(name: str) -> str:
    """The result and operand types of an operation event's name."""
    return name.split(", custom_call_target", 1)[0]


def reduce_events(dev, spans):
    """The numbers the metrics read, from loaded events (see ``load``).
    ``device_ops`` sums time by operation kind, leaving out control flow
    (a ``while`` event spans the operations of its body)."""
    wins = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not wins or not dev:
        return None
    w0, w1 = wins[0]
    per_chip, gaps = [], []
    op_time = collections.Counter()
    module_time = collections.Counter()
    kernels = collections.defaultdict(lambda: [0.0, 0])
    for plane, evs in sorted(dev.items()):
        ops = []
        for line, name, s0, e0 in evs:
            s, e = max(s0, w0), min(e0, w1)
            if e <= s:
                continue
            if line == MODULES_LINE:
                module_time[name] += (e - s) * 1e-9
                continue
            ops.append((s, e))
            kind = op_kind(name)
            if kind not in CONTROL_FLOW:
                op_time[kind] += (e - s) * 1e-9
            if kind.startswith(("pam_", "pa_")):
                k = kernels[kind]
                k[0] += (e - s) * 1e-9
                # an event cut by the window's edge keeps its share of bytes
                k[1] += work.shape_bytes(op_shapes(name)) * (e - s) / (e0 - s0)
        busy = _union(ops)
        per_chip.append(sum(e - s for s, e in busy) * 1e-9)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    inner = sorted(spans, key=lambda x: x[2] - x[1])

    def where(t):
        for n, s, e in inner:
            if n != "bench.window" and s <= t <= e:
                return n
        return "bench.window"

    gaps.sort(reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(per_chip) / len(per_chip),
        "op_time": dict(op_time),
        "module_time": dict(module_time),
        "kernels": {k: {"seconds": v[0], "bytes": v[1]} for k, v in kernels.items()},
        "device_ops": [[n, t] for n, t in op_time.most_common(10)],
        "idle_gaps": [[where(m), g * 1e-9] for g, m in gaps[:10]],
    }


def reduce_file(path):
    return reduce_events(*load(path))
