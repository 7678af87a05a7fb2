"""The trace reduction on a small trace recorded on a TPU v5e: a PAM
matmul kernel and a fusion dispatched three times under the harness's
spans, with host sleeps between them. The source paths in its op
metadata were rewritten (same lengths) to a neutral prefix."""
import os

import pytest

from bench import trace, work

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace.reduce_file(DATA)


def test_busy_is_the_union_of_device_ops(red):
    assert red["window_s"] == pytest.approx(0.01929591)
    ops = sum(red["op_time"].values())
    assert red["busy_s"] == pytest.approx(ops)           # no overlap here
    assert 0 < red["busy_s"] < 0.01 * red["window_s"]


def test_kernel_time_and_bytes_by_name(red):
    k = red["kernels"]["pam_matmul"]
    # three calls of (1,256,256) @ (1,256,128) -> (1,256,128), f32
    assert k["bytes"] == 3 * 4 * (256 * 256 + 2 * 256 * 128)
    assert k["seconds"] == pytest.approx(3.5e-5 * 3, rel=0.02)
    assert [n for n, _ in red["device_ops"]] == ["pam_matmul", "tanh_add_fusion"]


def test_idle_gaps_are_attributed_to_host_spans(red):
    where = [n for n, _ in red["idle_gaps"]]
    # the sleeps lie in no inner span; the dispatches are spans
    assert where[:3] == ["bench.window"] * 3
    assert "bench.dispatch" in where
    gaps = sum(g for _, g in red["idle_gaps"])
    assert gaps <= red["window_s"] - red["busy_s"] + 1e-9


def test_op_kind_and_shapes():
    n = ('%pam_matmul.348 = f32[1,8,16]{2,1,0} custom-call(f32[1,8,4]{2,1,0} '
         '%a, f32[1,4,16]{2,1,0} %b), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={f32[1,8,4]{2,1,0}}')
    assert trace.op_kind(n) == "pam_matmul"
    assert work.shape_bytes(trace.op_shapes(n)) == 4 * (128 + 32 + 64)


def test_overlapping_events_and_window_clipping():
    dev = {"/device:TPU:0": [
        ("XLA Ops", "%while.1 = (s32[]) while(s32[] %x)", 0.0, 100.0),
        ("XLA Ops", "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %y)", 10.0, 30.0),
        ("XLA Ops", "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %y)", 150.0, 250.0),
        ("XLA Modules", "jit_prefill(1)", 150.0, 250.0)]}
    spans = [("bench.window", 50.0, 200.0), ("bench.tick", 90.0, 160.0)]
    red = trace.reduce_events(dev, spans)
    assert red["busy_s"] == pytest.approx(100e-9)        # 50-100 and 150-200
    assert red["op_time"] == {"fusion": pytest.approx(50e-9)}
    assert red["module_time"]["jit_prefill(1)"] == pytest.approx(50e-9)
    assert red["idle_gaps"] == [["bench.tick", pytest.approx(50e-9)]]


def test_a_kernel_cut_by_the_window_keeps_its_share_of_bytes():
    name = ('%pam_matmul.7 = f32[1,8,16]{2,1,0} custom-call(f32[1,8,4]{2,1,0} '
            '%a, f32[1,4,16]{2,1,0} %b), custom_call_target="tpu_custom_call"')
    dev = {"/device:TPU:0": [("XLA Ops", name, 0.0, 100.0),
                             ("XLA Ops", name, 100.0, 140.0),
                             ("XLA Ops", name, 170.0, 230.0)]}
    red = trace.reduce_events(dev, [("bench.window", 75.0, 200.0)])
    k = red["kernels"]["pam_matmul"]
    assert k["seconds"] == pytest.approx(95e-9)          # 25 + 40 + 30
    assert k["bytes"] == pytest.approx(4 * 224 * (0.25 + 1.0 + 0.5))
