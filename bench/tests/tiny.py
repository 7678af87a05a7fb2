"""A cut-down cell for tests on the CPU: the same files' shape, a two-layer
model of narrow widths, short sequences, and a driver run without the
chip check."""
from __future__ import annotations

import copy
import types

from bench import harness

SIZES = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 256}
OVERRIDES = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "d_head": 16, "d_ff": 128, "vocab_size": 256}


def config(name: str, **program) -> dict:
    conf = harness.load_json(f"{harness.BENCH}/configs/{name}.json")
    conf.update(SIZES)
    conf["program"] = dict(conf["program"], overrides=dict(OVERRIDES), **program)
    return conf


def traffic(name: str, **kw) -> dict:
    tr = copy.deepcopy(harness.load_json(f"{harness.BENCH}/traffic/{name}.json"))
    tr.update(kw)
    return tr


def run(conf, tr, *, seed=7, seconds=0.5, limits=None, workload="tiny"):
    import jax
    r = types.SimpleNamespace(
        config=conf, traffic=tr, seed=seed, seconds=seconds, trace=False,
        t_start=harness.now(), trace_data=None, e2e={}, counts={},
        checks={}, attempted=0, failed=0, memory_peak=0, setup_s=None,
        limits=limits or {}, devices=jax.devices()[:1],
        all_devices=jax.devices(), workload={"name": workload},
        counter=harness.CompileCounter())
    return r
