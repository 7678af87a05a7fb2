"""The program's own spans in a profiler trace, beside the harness's.

The serving engine marks the host phases of its tick with ``serve.*``
spans (``serve/continuous.py``), on the same clock as the harness's
``bench.*`` spans and the device planes. ``bench/trace.py`` keeps only the
``bench.*`` spans; ``reduce_file`` here keeps both, so an idle gap inside
the engine's tick is named by the ``serve.*`` span it falls in, and adds
``span_time``: for each span name, the union of its spans clipped to the
window, and the part of that union in which the device is idle (the
complement of the union of operation events, the busy set that
``device_idle.*`` reads), by exact interval intersection.

``engine_split`` turns that into three shares of the window: time inside
``serve.admit``, device-idle time inside it, and device-idle time inside
``serve.tick`` but outside ``serve.admit``.

  python3 bench/spans.py --workload smollm-135m-full.serve --seed <n> --seconds <s>

runs a cell traced, as ``bench/run.py --trace 1`` does, with this
reduction, and prints the engine split and ``span_time`` after the
result line.
"""
from __future__ import annotations

import collections

PREFIXES = ("bench.", "serve.")


def host_spans(path):
    """(name, start_ns, end_ns) of the host events named ``bench.*`` or
    ``serve.*``."""
    from jax.profiler import ProfileData
    out = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIXES):
                        s = float(e.start_ns)
                        out.append((e.name, s, s + float(e.duration_ns)))
    return out


def _overlap(a, b):
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def span_time(dev, spans):
    """{span name: {"seconds", "idle_s"}} over the first ``bench.window``:
    the union of the name's spans clipped to the window, and the part of
    it in which no operation runs on the device (averaged over the device
    planes, as ``busy_s`` is). None without a window or a device."""
    from bench.trace import MODULES_LINE, _union
    wins = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not wins or not dev:
        return None
    w0, w1 = wins[0]
    busy = []
    for _, evs in sorted(dev.items()):
        ops = [(max(s, w0), min(e, w1)) for line, _, s, e in evs
               if line != MODULES_LINE]
        busy.append(_union([(s, e) for s, e in ops if e > s]))
    by_name = collections.defaultdict(list)
    for n, s, e in spans:
        s, e = max(s, w0), min(e, w1)
        if n != "bench.window" and e > s:
            by_name[n].append((s, e))
    out = {}
    for n, iv in sorted(by_name.items()):
        u = _union(iv)
        tot = sum(e - s for s, e in u)
        idle = sum(tot - _overlap(u, b) for b in busy) / len(busy)
        out[n] = {"seconds": tot * 1e-9, "idle_s": idle * 1e-9}
    return out


def reduce_events(dev, spans):
    """``bench.trace.reduce_events`` over every span given, plus
    ``span_time``."""
    from bench import trace
    red = trace.reduce_events(dev, spans)
    if red is not None:
        red["span_time"] = span_time(dev, spans)
    return red


def reduce_file(path):
    from bench import trace
    dev, _ = trace.load(path)
    return reduce_events(dev, host_spans(path))


def engine_split(red):
    """Shares (%) of the traced window: ``admission_share.serve`` (inside
    ``serve.admit``), ``admission_idle.serve`` (device idle inside
    ``serve.admit``) and ``tick_host_idle.serve`` (device idle inside
    ``serve.tick``, outside ``serve.admit``). None where the trace holds
    no ``serve.tick``, as a training cell's does."""
    st = (red or {}).get("span_time") or {}
    if "serve.tick" not in st or red["window_s"] <= 0:
        return None
    none = {"seconds": 0.0, "idle_s": 0.0}
    adm, tick, w = st.get("serve.admit", none), st["serve.tick"], red["window_s"]
    return {"admission_share.serve": 100.0 * adm["seconds"] / w,
            "admission_idle.serve": 100.0 * adm["idle_s"] / w,
            "tick_host_idle.serve": 100.0 * (tick["idle_s"] - adm["idle_s"]) / w}


def main(argv):
    import json
    from bench import run, trace
    kept = {}

    def reduce_and_keep(path):
        kept.update(reduce_file(path) or {})
        return kept or None

    # the harness's Tracer reduces through this module-level name
    trace.reduce_file = reduce_and_keep
    rc = run.main(argv + ["--trace", "1"])
    print(json.dumps({"engine_split": engine_split(kept),
                      "span_time": kept.get("span_time")}), flush=True)
    return rc


if __name__ == "__main__":
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    sys.exit(main(sys.argv[1:]))
