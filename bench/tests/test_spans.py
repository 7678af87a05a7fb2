"""The reduction that keeps the program's ``serve.*`` spans: ``span_time``
on synthetic events, and the engine split's readers on traces with and
without the engine's spans."""
import os

import pytest

from bench import spans, trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def _ops(*iv):
    return {"/device:TPU:0": [("XLA Ops", f"%fusion.{i} = f32[4]{{0}} fusion()", s, e)
                              for i, (s, e) in enumerate(iv)]}


def test_nested_spans_split_idle_time_exactly():
    # device busy 0-20, 30-60, 90-100; idle 20-30, 60-90
    dev = _ops((0, 20), (30, 60), (90, 100))
    sp = [("bench.window", 0, 100), ("bench.tick", 10, 95),
          ("serve.tick", 12, 94), ("serve.admit", 25, 70)]
    st = spans.span_time(dev, sp)
    assert st["bench.tick"] == {"seconds": pytest.approx(85e-9),
                                "idle_s": pytest.approx(40e-9)}
    assert st["serve.tick"] == {"seconds": pytest.approx(82e-9),
                                "idle_s": pytest.approx(40e-9)}
    # 25-30 and 60-70 idle inside the admission
    assert st["serve.admit"] == {"seconds": pytest.approx(45e-9),
                                 "idle_s": pytest.approx(15e-9)}
    red = spans.reduce_events(dev, sp)
    split = spans.engine_split(red)
    assert split["admission_share.serve"] == pytest.approx(45.0)
    assert split["admission_idle.serve"] == pytest.approx(15.0)
    assert split["tick_host_idle.serve"] == pytest.approx(25.0)
    idle = 100.0 * (1 - red["busy_s"] / red["window_s"])
    assert split["admission_idle.serve"] + split["tick_host_idle.serve"] <= idle + 1e-9
    # gaps are named by the innermost span over their middle
    assert red["idle_gaps"] == [["serve.tick", pytest.approx(30e-9)],
                                ["serve.admit", pytest.approx(10e-9)]]


def test_a_gap_straddling_two_spans_is_split_between_them():
    dev = _ops((0, 40), (80, 100))                  # idle 40-80
    sp = [("bench.window", 0, 100), ("serve.admit.prefill", 30, 50),
          ("serve.admit.first_token", 50, 90), ("serve.admit.prefill", 95, 99)]
    st = spans.span_time(dev, sp)
    assert st["serve.admit.prefill"]["seconds"] == pytest.approx(24e-9)
    assert st["serve.admit.prefill"]["idle_s"] == pytest.approx(10e-9)
    assert st["serve.admit.first_token"]["idle_s"] == pytest.approx(30e-9)
    # the midpoint rule gives the whole gap to one span; span_time does not
    red = spans.reduce_events(dev, sp)
    assert red["idle_gaps"] == [["serve.admit.first_token", pytest.approx(40e-9)]]


def test_spans_and_gaps_are_clipped_at_the_window_edges():
    dev = _ops((20, 50), (70, 130))                 # idle in window: 10-20, 50-70
    sp = [("bench.window", 10, 110), ("serve.tick", 0, 30),
          ("serve.tick", 60, 200), ("serve.emit", 200, 210)]
    st = spans.span_time(dev, sp)
    assert st["serve.tick"] == {"seconds": pytest.approx(70e-9),
                                "idle_s": pytest.approx(20e-9)}
    assert "serve.emit" not in st                   # wholly outside the window


def test_overlapping_spans_of_one_name_count_once_and_planes_average():
    dev = {"/device:TPU:0": [("XLA Ops", "%a.1 = f32[] a()", 0, 50),
                             ("XLA Modules", "jit_step(1)", 0, 100)],
           "/device:TPU:1": [("XLA Ops", "%a.1 = f32[] a()", 0, 100)]}
    sp = [("bench.window", 0, 100), ("serve.tick", 20, 80), ("serve.tick", 40, 90)]
    st = spans.span_time(dev, sp)
    # union 20-90; idle 50-90 on chip 0 (module events are not ops), none on chip 1
    assert st["serve.tick"] == {"seconds": pytest.approx(70e-9),
                                "idle_s": pytest.approx(20e-9)}


def test_without_a_window_or_device_there_is_nothing_to_read():
    assert spans.span_time(_ops((0, 1)), [("serve.tick", 0, 1)]) is None
    assert spans.span_time({}, [("bench.window", 0, 1)]) is None
    assert spans.engine_split(None) is None


def test_the_engine_split_reads_nothing_from_a_trace_without_serve_spans():
    # a training cell's trace: bench.* spans only
    red = spans.reduce_file(TINY)
    assert not any(n.startswith("serve.") for n in red["span_time"])
    assert spans.engine_split(red) is None
    dev = _ops((0, 20))
    assert spans.engine_split(spans.reduce_events(
        dev, [("bench.window", 0, 100), ("bench.tick", 10, 90)])) is None


def test_with_bench_spans_only_the_harness_keys_are_unchanged():
    base = trace.reduce_file(TINY)
    red = spans.reduce_file(TINY)
    assert set(red) == set(base) | {"span_time"}
    for k, v in base.items():
        assert red[k] == v, k
    st = red["span_time"]
    assert st["bench.dispatch"]["seconds"] > 0
    # the sleeps lie in no inner span: idle inside the spans is less than all idle
    assert sum(v["idle_s"] for v in st.values()) < red["window_s"] - red["busy_s"]
