"""The control of each configuration, the reference one precision lower
put in the program's place, must fail the cell's comparison, at a size a
test run holds: for the float32 PA modes every activation rounded to
bfloat16, for the bfloat16 native mode the forward products' operands
rounded to fp8 e4m3. The sound reference against itself passes."""
import numpy as np
import pytest

from bench import correct
from bench.drivers import serve, train
from bench.tests import tiny


@pytest.mark.parametrize("conf_name,cell", [
    ("smollm-135m-off", "smollm-135m-off.train"),
    ("smollm-135m-matmul", "smollm-135m-matmul.train")])
def test_train_control_fails(conf_name, cell):
    conf = tiny.config(conf_name)
    tr = tiny.traffic("train_s1024_b8", seq=64, batch=2, pool=4)
    r = tiny.run(conf, tr)
    limits = correct.limits_for(cell)
    want = train.reference(r, 11)
    ctl = train.reference(r, 11, lower=True)
    same = correct.train_readings(want, want)[0]
    got = correct.train_readings(ctl, want)[0]
    assert all(v <= limits[k] for k, v in same.items())
    assert any(v > limits[k] for k, v in got.items()), (got, limits)


def test_serve_control_fails():
    # a 4096-token vocabulary, so that near-ties among the logits occur
    conf = tiny.config("smollm-135m-full")
    conf["vocab_size"] = conf["program"]["overrides"]["vocab_size"] = 4096
    conf["precision"]["attention_kv_block"] = 32
    tr = tiny.traffic("serve_poisson_p64-512_o16-64_s8", max_len=96)
    r = tiny.run(conf, tr)
    rng = np.random.default_rng(5)
    toks = [rng.integers(0, 4096, n).astype(np.int32) for n in (40, 64, 20)]
    served = {i: list(rng.integers(0, 4096, 32)) for i in range(3)}
    lim = correct.limits_for("smollm-135m-full.serve")["served_logit_gap"]
    gap, n = serve.reference_gaps(r, 3, [0, 1, 2], toks, served, lower=True)
    assert n == 96 and gap > lim
