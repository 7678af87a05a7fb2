"""A run with the timed path broken underneath must come out not correct:
each fault a cell can have, driven through the rest of a run (the chip
check skipped, a cut-down model on the CPU), against the cell's limits.
The PA configurations run the program's jnp engine here, which computes
what the Pallas kernels compute; interpret-mode kernels are too slow for
the test tier."""
import pytest

from bench import correct, faults
from bench.drivers import serve, train
from bench.tests import tiny

TRAIN_CELLS = [("smollm-135m-off", "smollm-135m-off.train", "pallas"),
               ("smollm-135m-matmul", "smollm-135m-matmul.train", "jnp")]


def _train(conf_name, cell, impl, fault):
    conf = tiny.config(conf_name, impl=impl)
    tr = tiny.traffic("train_s1024_b8", seq=64, batch=2, pool=4)
    r = tiny.run(conf, tr, seconds=0.3, limits=correct.limits_for(cell))
    r.step_fault = fault
    train.run_cell(r)
    return r


@pytest.mark.parametrize("conf_name,cell,impl", TRAIN_CELLS)
@pytest.mark.parametrize("fault", [None, faults.unchanged_state, faults.half_batch],
                         ids=["sound", "unchanged_state", "half_batch"])
def test_train_faults(conf_name, cell, impl, fault):
    r = _train(conf_name, cell, impl, fault)
    assert correct.all_within(r.checks) == (fault is None), r.checks


def _serve(fault):
    conf = tiny.config("smollm-135m-full", impl="jnp")
    conf["precision"]["attention_kv_block"] = 256     # the CPU's block
    tr = tiny.traffic("serve_poisson_p64-512_o16-64_s8",
                      prompt_lengths=[8, 16, 32, 64], max_len=96,
                      output_min=4, output_max=8, rate_rps=4.0, warmup_s=1,
                      tail_s=5, check_requests=4, slots=4)
    r = tiny.run(conf, tr, seconds=3.0,
                 limits=correct.limits_for("smollm-135m-full.serve"))
    r.token_fault = fault
    serve.run_cell(r)
    return r


@pytest.mark.parametrize("fault", [None, faults.altered_token],
                         ids=["sound", "altered_token"])
def test_serve_faults(fault):
    r = _serve(fault)
    assert correct.all_within(r.checks) == (fault is None), r.checks
