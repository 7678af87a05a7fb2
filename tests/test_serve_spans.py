"""The serving engine's profiler spans: one ``serve.tick`` per tick, one
``serve.admit`` per admission with its phases inside it, the decode step's
launch before its fetch, and greedy tokens that do not depend on whether
a trace is being recorded."""
import glob

import numpy as np
import jax
import pytest

from repro.models import build_model
from repro.models.common import ModelConfig
from repro.serve import ContinuousEngine, Request, ServeConfig

CFG = ModelConfig(name="spans", family="decoder", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_head=16, d_ff=64,
                  vocab_size=32, max_seq_len=64,
                  param_dtype="float32", compute_dtype="float32",
                  remat="none")
ADMIT_PHASES = ("serve.admit.prepare", "serve.admit.prefill",
                "serve.admit.first_token", "serve.admit.insert")


def _requests():
    rng = np.random.default_rng(3)
    # three prompt lengths; the third arrives after the first two finish,
    # so some ticks decode nothing
    return [Request(rid=10 + i, prompt=rng.integers(0, 32, (n,)).astype(np.int32),
                    max_new_tokens=m, arrival=a)
            for i, (n, m, a) in enumerate([(4, 3, 0), (7, 5, 0), (10, 4, 9)])]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(engine, tokens untraced, tokens traced, spans) with spans as
    (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    model = build_model(CFG)
    eng = ContinuousEngine(model, model.init(jax.random.PRNGKey(0)),
                           ServeConfig(max_len=32, n_slots=2))
    plain = eng.run(_requests())
    eng.reset()
    d = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(d)
    try:
        out = eng.run(_requests())
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    spans = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    if e.name.startswith("serve."):
                        s = float(e.start_ns)
                        spans.append((e.name, s, s + float(e.duration_ns),
                                      dict(e.stats)))
    return eng, plain, out, sorted(spans, key=lambda x: x[1])


def _named(spans, name):
    return [x for x in spans if x[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_one_tick_span_per_tick(traced):
    eng, _, _, spans = traced
    ticks = _named(spans, "serve.tick")
    assert len(ticks) == eng.metrics["ticks"] > 9
    assert [t[3]["tick"] for t in ticks] == list(range(len(ticks)))
    assert sum(t[3]["admitted"] for t in ticks) == eng.metrics["prefills"] == 3
    assert any(t[3]["active"] == 0 for t in ticks)
    assert max(t[3]["active"] for t in ticks) == 2


def test_one_admit_span_per_prefill_with_its_phases_inside(traced):
    eng, _, _, spans = traced
    admits = _named(spans, "serve.admit")
    assert len(admits) == eng.metrics["prefills"]
    assert sorted((a[3]["rid"], a[3]["prompt_len"]) for a in admits) == [
        (10, 4), (11, 7), (12, 10)]
    ticks = _named(spans, "serve.tick")
    for a in admits:
        assert sum(_inside(a, t) for t in ticks) == 1
        phases = [x for x in spans if x[0] in ADMIT_PHASES and _inside(x, a)]
        assert [p[0] for p in phases] == list(ADMIT_PHASES)   # once each, in order
        assert all(p[2] <= q[1] for p, q in zip(phases, phases[1:]))
    for name in ADMIT_PHASES:
        assert len(_named(spans, name)) == len(admits)


def test_decode_launch_precedes_fetch_in_every_active_tick(traced):
    _, _, _, spans = traced
    for t in _named(spans, "serve.tick"):
        inner = {n: [x for x in _named(spans, n) if _inside(x, t)]
                 for n in ("serve.decode.launch", "serve.decode.fetch", "serve.emit")}
        if t[3]["active"] == 0:
            assert not any(inner.values())
            continue
        (launch,), (fetch,), (emit,) = inner.values()
        assert launch[2] <= fetch[1] and fetch[2] <= emit[1]


def test_greedy_tokens_do_not_depend_on_tracing(traced):
    eng, plain, out, _ = traced
    assert sorted(plain) == sorted(out) == [10, 11, 12]
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], out[rid])
    assert "decode_wall" not in eng.metrics
