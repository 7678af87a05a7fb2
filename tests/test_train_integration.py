"""Integration: training convergence (baseline vs PA modes), fault tolerance,
serving consistency — the paper's central claims at reduced scale."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import PAConfig
from repro.models.common import ModelConfig
from repro.models import build_model
from repro.optim import OptConfig
from repro.data import DataConfig, SyntheticLM
from repro.train import LoopConfig, TrainConfig, train, make_train_step
from repro.serve import Engine, ServeConfig

TINY = ModelConfig(name="tiny", family="decoder", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                   vocab_size=64, max_seq_len=64, param_dtype="float32",
                   compute_dtype="float32", remat="none")
OPT = OptConfig(peak_lr=3e-3, warmup_steps=5, total_steps=30,
                weight_decay=1e-4)
DATA = DataConfig(vocab_size=64, seq_len=32, global_batch=8, seed=1)


def _run(tmp, cfg, steps=30, **kw):
    model = build_model(cfg)
    return train(model, OPT, DATA, str(tmp),
                 LoopConfig(steps=steps, ckpt_every=10, log_every=100),
                 log=lambda *_: None, **kw)


@pytest.mark.parametrize("pa", [
    PAConfig(mode="off"),
    PAConfig(mode="matmul", deriv="approx"),
    PAConfig(mode="full", deriv="approx", loss_deriv="exact"),
])
def test_training_converges(tmp_path, pa):
    """The paper's claim: PA training tracks the baseline with the same
    hyperparameters."""
    _, hist = _run(tmp_path / pa.mode, TINY.replace(pa=pa))
    assert hist["loss"][-1] < hist["loss"][0] * 0.75


def test_resume_continues_from_checkpoint(tmp_path):
    _, h1 = _run(tmp_path, TINY, steps=20)
    _, h2 = _run(tmp_path, TINY, steps=30)
    # history is persisted with checkpoints: the resumed run APPENDS its 10
    # new steps to the 20 restored ones instead of starting a fresh dict
    assert len(h2["loss"]) == 30
    assert h2["loss"][:20] == h1["loss"]


def test_preemption_checkpoint_and_restart(tmp_path):
    preempt = os.path.join(str(tmp_path), "PREEMPT")
    _run(tmp_path, TINY, steps=10)
    open(preempt, "w").close()
    _, h = _run(tmp_path, TINY, steps=30)
    assert len(h["loss"]) == 11      # checkpointed + exited after one step
    # the loop CONSUMES the preemption file — a restart in the same workdir
    # must continue training, not re-checkpoint and exit after one step
    assert not os.path.exists(preempt)
    _, h3 = _run(tmp_path, TINY, steps=30)
    assert len(h3["loss"]) == 30     # resumed at 11, ran to completion


def test_microbatch_equivalence(rng, tmp_path):
    """Gradient accumulation must match the monolithic step for mean loss."""
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    from repro.optim import init_opt_state
    batch = jax.tree.map(jnp.asarray, SyntheticLM(DATA).batch(0))
    s1 = make_train_step(model, OPT, TrainConfig(microbatches=1))
    s4 = make_train_step(model, OPT, TrainConfig(microbatches=4))
    st = init_opt_state(params, OPT)
    p1, _, m1 = jax.jit(s1)(params, st, batch)
    st = init_opt_state(params, OPT)
    p4, _, m4 = jax.jit(s4)(params, st, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=2e-2)
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    assert d < 1e-2


def test_grad_compression_trains(tmp_path):
    _, hist = _run(tmp_path, TINY,
                   train_cfg=TrainConfig(grad_compress_bits=4))
    assert hist["loss"][-1] < hist["loss"][0] * 0.8


def test_serve_greedy_consistent_with_forward(tmp_path):
    """Engine decode must agree with teacher-forced forward argmax."""
    model = build_model(TINY)
    params, _ = _run(tmp_path, TINY)
    eng = Engine(model, params, ServeConfig(max_len=64))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 64, (2, 8)).astype(np.int32)
    out = eng.generate(prompts, max_new_tokens=4)
    # teacher-forced check of the first generated token
    full, _ = model.logits(params, {"tokens": jnp.asarray(prompts)})
    first = np.asarray(jnp.argmax(full[:, -1], -1))
    np.testing.assert_array_equal(out[:, 0], first)


@pytest.mark.parametrize("pa", [
    PAConfig(mode="off"),
    PAConfig(mode="full", deriv="approx", loss_deriv="exact"),
])
def test_mesh_training_matches_single_device(tmp_path, pa):
    """``train(mesh=...)`` places params, optimizer state and batches on
    the mesh and traces the step under it. On this process's one device
    the mesh step must give the unmeshed step's losses."""
    from repro.launch.mesh import host_mesh
    cfg = TINY.replace(pa=pa)
    _, plain = _run(tmp_path / "plain", cfg, steps=3)
    _, meshed = _run(tmp_path / "mesh", cfg, steps=3, mesh=host_mesh())
    np.testing.assert_allclose(meshed["loss"], plain["loss"], rtol=1e-6)
