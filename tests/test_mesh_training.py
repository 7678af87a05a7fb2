"""Training on a (data=2, model=2) mesh of four host devices.

``train(mesh=...)`` keeps the step automatically partitioned: the params
and optimizer state must keep the tensor-parallel placements the model's
axis rules give them, and the losses must match the same run without a
mesh. Under ``impl="pallas"`` every kernel launch must run inside a
``shard_map`` (Mosaic kernels cannot be partitioned automatically) on its
device's rows.

The device count must be set before jax initialises, so the runs happen in
one subprocess that executes this file as a script; the tests read its
JSON report.
"""
import json
import os
import subprocess
import sys

import pytest

IMPLS = ("jnp", "pallas")
STEPS = 3


def _report():
    """Run both engines with and without the mesh; print one JSON line."""
    import tempfile

    import jax
    from repro.analysis.contract import _iter_eqns
    from repro.analysis.jaxpr_types import open_jaxpr
    from repro.core import PAConfig
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.models.common import ModelConfig
    from repro.optim import OptConfig, init_opt_state
    from repro.train import LoopConfig, TrainConfig, make_train_step, train

    mesh = make_mesh((2, 2), ("data", "model"))
    opt = OptConfig(peak_lr=3e-3, warmup_steps=1, total_steps=STEPS)
    data = DataConfig(vocab_size=64, seq_len=32, global_batch=8, seed=1)
    out = {}
    for impl in IMPLS:
        cfg = ModelConfig(name="tiny", family="decoder", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                          d_ff=128, vocab_size=64, max_seq_len=64,
                          param_dtype="float32", compute_dtype="float32",
                          remat="none", attn_fused_pam=True,
                          pa=PAConfig(mode="full", impl=impl))
        model = build_model(cfg)
        rec = {}
        for name, m in (("plain", None), ("mesh", mesh)):
            params, hist = train(model, opt, data, tempfile.mkdtemp(),
                                 LoopConfig(steps=STEPS, ckpt_every=STEPS),
                                 mesh=m, log=lambda *_: None)
            rec[name] = hist["loss"]
        want = model.shardings(mesh)
        rec["kept"] = all(jax.tree.leaves(jax.tree.map(
            lambda p, s: p.sharding.is_equivalent_to(s, p.ndim),
            params, want)))
        rec["model_sharded"] = sum("model" in str(p.sharding.spec)
                                   for p in jax.tree.leaves(params))
        # every kernel launch of the step, with and without the mesh:
        # (enclosing calls, kernel name, operand shapes)
        p0 = model.init(jax.random.PRNGKey(0))
        args = (p0, init_opt_state(p0, opt), jax.tree.map(
            jax.numpy.asarray, SyntheticLM(data).batch(0)))
        for name, m in (("plain", None), ("mesh", mesh)):
            jaxpr = jax.make_jaxpr(make_train_step(
                model, opt, TrainConfig(), mesh=m))(*args)
            rec[name + "_kernels"] = [
                (ctx, str(getattr(eqn.params["name"], "name",
                                  eqn.params["name"])),
                 [v.aval.shape for v in eqn.invars])
                for eqn, ctx in _iter_eqns(open_jaxpr(jaxpr))
                if eqn.primitive.name == "pallas_call"]
        out[impl] = rec
    print(json.dumps(out))


@pytest.fixture(scope="module")
def mesh_report():
    proc = subprocess.run(
        [sys.executable, __file__], capture_output=True, text=True,
        timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..",
                                        "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("impl", IMPLS)
def test_mesh_keeps_param_shardings(mesh_report, impl):
    rec = mesh_report[impl]
    assert rec["kept"]
    assert rec["model_sharded"] > 0      # tensor parallelism is in use


@pytest.mark.parametrize("impl", IMPLS)
def test_mesh_losses_match_unmeshed(mesh_report, impl):
    rec = mesh_report[impl]
    assert len(rec["mesh"]) == STEPS
    for a, b in zip(rec["mesh"], rec["plain"]):
        assert abs(a - b) <= 1e-5 * abs(b)


def _rows(kernels, name, dim):
    """Largest ``dim`` of the 3-D operands of the kernels called ``name``."""
    return max(s[dim] for _, n, shapes in kernels if n == name
               for s in shapes if len(s) == 3)


def test_mesh_kernels_run_per_device(mesh_report):
    """Every Pallas launch of the meshed step sits in a shard_map, on its
    data shard's rows: the 8 x 32 batch is 256 token rows for the PAM
    matmul (128 per data shard), and 32 B*H rows for attention (16)."""
    assert mesh_report["jnp"]["mesh_kernels"] == []
    rec = mesh_report["pallas"]
    plain, mesh = rec["plain_kernels"], rec["mesh_kernels"]
    assert len(mesh) == len(plain) > 0
    assert all("shard_map" in ctx for ctx, _, _ in mesh)
    assert not any("shard_map" in ctx for ctx, _, _ in plain)
    assert (_rows(plain, "pam_matmul", 1), _rows(mesh, "pam_matmul", 1)) \
        == (256, 128)
    assert (_rows(plain, "pam_attention_fwd", 0),
            _rows(mesh, "pam_attention_fwd", 0)) == (32, 16)


if __name__ == "__main__":
    _report()
