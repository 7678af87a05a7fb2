"""The serving engine's spans and the device planes on one clock, checked
on a small trace recorded on a TPU v5e: the two-layer full-PA model of
``tiny.py`` in a ``ContinuousEngine`` of 2 slots, 2 requests (prompts of
16 and 32 tokens), 8 ticks, each tick in a ``bench.tick`` span inside one
``bench.window``. The trace was cut to its span lines and the device
planes' "XLA Ops" and "XLA Modules" lines, with the stats of device
events and their metadata dropped; source paths do not remain."""
import os

import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "serve_tiny.xplane.pb")
SLACK_NS = 50e3


@pytest.fixture(scope="module")
def events():
    dev, _ = trace.load(DATA)
    sp = spans.host_spans(DATA)
    mods = sorted((s, e, n) for line, n, s, e in dev["/device:TPU:0"]
                  if line == trace.MODULES_LINE)
    return dev, sp, mods


def _named(sp, name):
    return sorted((s, e) for n, s, e in sp if n == name)


def _waited_on(mods, prefix, hosts):
    """For each (start, end) of a host phase that dispatches one program
    named ``prefix``, that program's module event, in order."""
    progs = [m for m in mods if m[2].startswith(prefix)]
    progs = [m for m in progs if m[0] >= hosts[0][0] - 5e6]
    assert len(progs) >= len(hosts)
    return progs[:len(hosts)]


def test_the_trace_holds_the_engine_run(events):
    _, sp, _ = events
    assert len(_named(sp, "serve.tick")) == 8
    assert len(_named(sp, "serve.admit")) == 2
    assert os.path.getsize(DATA) < 300_000


def test_host_waits_end_after_the_device_work_they_wait_on(events):
    _, sp, mods = events
    prefill = _named(sp, "serve.admit.prefill")
    first = _named(sp, "serve.admit.first_token")
    for (s, e, _), (_, wait_end) in zip(_waited_on(mods, "jit_prefill", prefill), first):
        assert wait_end >= e - SLACK_NS
    launch = _named(sp, "serve.decode.launch")
    fetch = _named(sp, "serve.decode.fetch")
    assert len(launch) == len(fetch) == 8
    for (s, e, _), (_, wait_end) in zip(_waited_on(mods, "jit_step", launch), fetch):
        assert wait_end >= e - SLACK_NS


def test_one_constant_offset_fits_every_dispatch_and_fetch(events):
    """A program starts after its dispatch began and ends before the
    host's fetch of its output ends. Read on the trace's clocks, each
    pair bounds the offset of the device timeline against the host's;
    one offset fits all of them. In this trace the device events sit
    0.8-2.3 ms early, which is what exact span intersection is good to."""
    _, sp, mods = events
    lo, hi = [], []
    for host, wait, prefix in (("serve.admit.prefill", "serve.admit.first_token", "jit_prefill"),
                               ("serve.decode.launch", "serve.decode.fetch", "jit_step")):
        starts, waits = _named(sp, host), _named(sp, wait)
        for (s, e, _), (h0, _), (_, w1) in zip(_waited_on(mods, prefix, starts), starts, waits):
            lo.append(h0 - s)
            hi.append(w1 - e)
    assert max(lo) <= min(hi)
    assert 0.0 < max(lo) < 1e6 < 2e6 < min(hi) < 3e6


def test_idle_gaps_inside_the_tick_are_named_by_engine_spans(events):
    red = spans.reduce_file(DATA)
    where = [n for n, _ in red["idle_gaps"]]
    assert any(n.startswith("serve.") for n in where)
    assert "bench.tick" not in where
    split = spans.engine_split(red)
    idle = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    assert 0 < split["admission_idle.serve"] <= split["admission_share.serve"]
    assert split["admission_idle.serve"] + split["tick_host_idle.serve"] <= idle
