"""The seam between the harness and an architecture (``bench/arch.py``).

The LLaMA reference keeps the numbers it gave before it moved behind the
seam: the weights, the logits, the losses and the first training steps'
readings, bit for bit, in each numeric mode, and the FLOP count. A new
architecture reaches both drivers' reference paths and the FLOP count
by adding one file, and an unknown one names the file to add."""
import hashlib
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import bench.reference
from bench import arch, harness, program, weights
from bench.drivers import serve, train
from bench.reference import llama, model, train as ref_train
from bench.tests import tiny

CONFIGS = ["smollm-135m-off", "smollm-135m-matmul", "smollm-135m-full"]
SEED = 2**31 + 12345
STUB_DIR = os.path.join(os.path.dirname(__file__), "arch_stub")
STUB = "bench.reference.llamastub"

# Recorded from the reference before it moved into bench/reference/llama.py.
TINY_WEIGHTS = "0ebb4f4ee164d6255f6ec9f641ca800e892dc9b2eeed00bca1dd51abdde80f56"
PUBLISHED_WEIGHTS = "bb5078f8309b5cae891c29aa85c6f447c48492cca1617d5e92d8c6bb61964e13"
REFERENCE = {
    "smollm-135m-off": {
        "logits": "f1219ac0c6cb6a7174d339aec110b89a262d8f3485e454e95504a6b047f1f7af",
        "losses": [5.549915313720703, 5.554941177368164, 5.495363712310791],
        "m1": "a57a24fb46ce37b87012289cf73f6123da3e3780edc4b9f94d0752aabf98e8f6",
        "dn": "6f712fb0a030f47ce2014e5b6c2e19b828a917f90602cf11db556540f0e9334c"},
    "smollm-135m-matmul": {
        "logits": "3f77e5a9d6aa9ebb5fed38d6471fa1d70319a5b77d239bae99c7d86b5e54cfcf",
        "losses": [5.548519134521484, 5.554925918579102, 5.503654479980469],
        "m1": "e622f4428dbf9c64f7f2661591c79fbaac52d752790fc493bde63eddc06c05f8",
        "dn": "a8aa6df2fa0784911bc8f7c74ef3589267f73b869a5ee43418a9c25e0568b92c"},
    "smollm-135m-full": {
        "logits": "b49b3e45dc690537beae03e3065507054b1dc103876b535073c5a8fb2f83488b",
        "stream_logits": "72f7719b4b8d7d0c5803ea304ec00fe8338b7d7f30b59674e55f4c2350ddd7fc",
        "losses": [5.3758416175842285, 5.380032539367676, 5.32251501083374],
        "m1": "440f35258c304915cceb7ac3cafaa08ee32bf5daeb8ddfb8a17f45b3bfbb057b",
        "dn": "d30c9b76e7c1ff2cb89393d7b948af6d8817e5e9c8f9a94f10c6caa48a09a25c"},
}
FLOPS_AT_1024 = 913151232.0


def digest(tree) -> str:
    """sha256 over each leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)}|{x.dtype}|{x.shape}|".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def published(name):
    return harness.load_json(f"{harness.BENCH}/configs/{name}.json")


@pytest.mark.parametrize("size", ["tiny", "published"])
@pytest.mark.parametrize("name", CONFIGS)
def test_weights_keep_their_bits(name, size):
    conf = tiny.config(name) if size == "tiny" else published(name)
    s = program.sizes(conf)
    params = weights.init_params(arch.of(conf), s, SEED)
    assert digest(params) == (TINY_WEIGHTS if size == "tiny" else PUBLISHED_WEIGHTS)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_keeps_its_numbers(name):
    conf = tiny.config(name)
    conf["precision"]["attention_kv_block"] = 32        # two blocks at seq 64
    s, ref = program.sizes(conf), arch.of(conf)
    assert ref is llama
    want = REFERENCE[name]
    nx = model.Numerics(mode=s["mode"], act=s["compute_dtype"],
                        kv_block=s["kv_block"] or 128)
    params = weights.init_params(ref, s, SEED)
    toks = weights.markov_tokens(SEED, "arch-test", 6, 64, s["vocab_size"])
    rows = jnp.asarray(toks[:2, :-1])
    lg = jax.jit(lambda p, t: ref.logits(nx, s, p, t))(params, rows)
    assert digest(lg) == want["logits"]
    if s["mode"] == "full":                # prefill rows streamed, as served
        st = jnp.asarray(np.arange(64)[None] < np.array([[40], [64]]))
        lg = jax.jit(lambda p, t, st: ref.logits(nx, s, p, t, st))(params, rows, st)
        assert digest(lg) == want["stream_logits"]
    batches = [{"tokens": jnp.asarray(toks[2 * i:2 * i + 2, :-1]),
                "labels": jnp.asarray(toks[2 * i:2 * i + 2, 1:]),
                "mask": jnp.ones((2, 64), bool)} for i in range(3)]
    loss = jax.jit(lambda p, b: ref.loss(nx, s, p, b))(params, batches[0])
    assert float(loss) == want["losses"][0]
    opt = tiny.traffic("train_s1024_b8")["optimizer"]
    losses, m1, dn = ref_train.run(ref, nx, s, opt, params, batches)
    assert losses == want["losses"]
    assert digest(m1) == want["m1"] and digest(dn) == want["dn"]


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_per_token_keeps_its_count(name):
    conf = published(name)
    assert arch.of(conf).flops_per_token(program.sizes(conf), 1024) == FLOPS_AT_1024


@pytest.fixture
def stub(monkeypatch):
    """``LlamaStubForCausalLM``, found by the rule in a directory added to
    ``bench.reference``'s path."""
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, STUB_DIR])
    sys.modules.pop(STUB, None)
    mod = arch.of({"architecture": "LlamaStubForCausalLM"})
    assert mod.__file__ == os.path.join(STUB_DIR, "llamastub.py")
    mod.CALLS.clear()
    yield mod
    sys.modules.pop(STUB, None)
    vars(bench.reference).pop("llamastub", None)


def _as(conf, architecture):
    return dict(conf, architecture=architecture)


def test_a_new_architecture_reaches_the_train_driver(stub):
    tr = tiny.traffic("train_s1024_b8", seq=64, batch=2, pool=4)
    conf = tiny.config("smollm-135m-matmul")
    got = train.reference(tiny.run(_as(conf, "LlamaStubForCausalLM"), tr), 11)
    assert stub.CALLS["sizes"] and stub.CALLS["layout"] and stub.CALLS["loss"]
    assert json.dumps(got[0]) == json.dumps(train.reference(tiny.run(conf, tr), 11)[0])
    program.build(_as(conf, "LlamaStubForCausalLM"))
    assert stub.CALLS["check"] == 1
    path = os.path.join(harness.BENCH, "metrics", "train_mfu.py")
    spec = importlib.util.spec_from_file_location("bench_metric_train_mfu", path)
    mfu = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mfu)
    run = types.SimpleNamespace(
        config=_as(published("smollm-135m-matmul"), "LlamaStubForCausalLM"),
        traffic=tiny.traffic("train_s1024_b1"), e2e={"train_tokens_per_s": 430.0},
        peaks={"bf16_flops": 197e12}, devices=[None])
    assert mfu.read(run) == 100.0 * FLOPS_AT_1024 * 430.0 / 197e12
    assert stub.CALLS["flops_per_token"] == 1


def test_a_new_architecture_reaches_the_serve_driver(stub):
    conf = tiny.config("smollm-135m-full")
    conf["precision"]["attention_kv_block"] = 32
    tr = tiny.traffic("serve_poisson_p64-512_o16-64_s8", max_len=64)
    rng = np.random.default_rng(5)
    toks = [rng.integers(0, 256, n).astype(np.int32) for n in (40, 24)]
    served = {i: list(rng.integers(0, 256, 16)) for i in range(2)}
    got = serve.reference_gaps(tiny.run(_as(conf, "LlamaStubForCausalLM"), tr),
                               3, [0, 1], toks, served)
    assert stub.CALLS["sizes"] and stub.CALLS["layout"] and stub.CALLS["logits"]
    assert got == serve.reference_gaps(tiny.run(conf, tr), 3, [0, 1], toks, served)


@pytest.mark.parametrize("architecture,file", [
    ("DeepseekV3ForCausalLM", "bench/reference/deepseekv3.py"),
    ("Qwen2MoeForCausalLM", "bench/reference/qwen2moe.py"),
    ("ModelForCausalLM", "bench/reference/model.py")])         # shared, no sizes
def test_an_unknown_architecture_names_the_file_to_add(architecture, file):
    conf = dict(tiny.config("smollm-135m-off"), architecture=architecture)
    with pytest.raises(LookupError, match=file):
        program.sizes(conf)


def test_the_rule_takes_only_class_names():
    assert arch.module_name("LlamaForCausalLM") == "bench.reference.llama"
    with pytest.raises(ValueError):
        arch.module_name("../llama")
