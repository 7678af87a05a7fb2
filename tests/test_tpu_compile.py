"""Compile the main-path Pallas kernels for a TPU v5e chip, without the chip.

The TPU compiler is installed with jax; it compiles for a described,
unattached ``v5e:2x2`` topology. Each test lowers one kernel — the main
path's PAM matmul, exact grad, fused attention and PA-AdamW, plus the PA
softmax and elementwise kernels — at smollm-135m widths (d=576,
d_ff=1536, vocab 49152; 9 query / 3 KV heads of 64) in f32 and bf16 with
``interpret=False`` — what the chip runs — and checks that
Mosaic accepted it: block shapes, vector layouts, the v5e op set (no 16-bit
compares) and the VMEM limit. Interpret-mode tests cannot see any of these.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file. Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.flash_attention import pam_kernel
from repro.kernels.pam_matmul import kernel as mm_kernel
from repro.kernels.pam_optim import kernel as optim_kernel

FMTS = {"f32": jnp.float32, "bf16": jnp.bfloat16}
BH, BKV, S, DH = 18, 6, 1024, 64          # B=2 x (9 query, 3 KV) heads


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out of any cache the process has on.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("batch,m,k,n", [(1, 4096, 576, 1536),
                                          (1, 4, 576, 49152),
                                          (1, 8, 576, 576),
                                          (72, 1, 576, 64)],
                         ids=["train_mlp_up", "decode_lm_head",
                              "decode_proj_8_slots", "decode_slot_av"])
def test_pam_matmul_fwd(one_chip, fmt, batch, m, k, n):
    bm, bn, bk, g = mm_kernel.tile_params(m, n, k, False, fmt)
    _compile(one_chip, lambda a, b: mm_kernel.pam_matmul_batched(
        a, b, bm=bm, bn=bn, bk=bk, g=g, interpret=False, fmt_name=fmt),
        ((batch, m, k), FMTS[fmt]), ((batch, k, n), FMTS[fmt]))


@pytest.mark.parametrize("fmt", FMTS)
def test_pam_exact_grad(one_chip, fmt):
    # Narrow formats run the exact-derivative contraction on their exact
    # f32 embedding (core/matmul.py), so both formats reach the f32 kernel.
    m, k, n = 4096, 576, 1536
    bm, bn, bk, g = mm_kernel.tile_params(m, n, k, False)
    f32 = lambda x: x.astype(jnp.float32)
    _compile(one_chip, lambda a, b, gr: mm_kernel.pam_exact_grad_a_batched(
        f32(a), f32(b), f32(gr), bm=bm, bn=bn, bk=bk, g=g, interpret=False),
        ((1, m, k), FMTS[fmt]), ((1, k, n), FMTS[fmt]),
        ((1, m, n), FMTS[fmt]))


def _attn_tiles(op, fmt):
    return autotune.tile_params(op, (S, S, DH), False, fmt)


@pytest.mark.parametrize("fmt", FMTS)
def test_pam_attention_fwd(one_chip, fmt):
    bq, bk, g = _attn_tiles("pam_attention", fmt)
    dt = FMTS[fmt]
    _compile(one_chip, lambda q, k, v, qp, kp:
             pam_kernel.pam_flash_attention_fwd_bh(
                 q, k, v, qp, kp, causal=True, window=None, scale=None,
                 bq=bq, bk=bk, g=g, interpret=False, fmt_name=fmt),
             ((BH, S, DH), dt), ((BKV, S, DH), dt), ((BKV, S, DH), dt),
             ((S,), jnp.int32), ((S,), jnp.int32))


@pytest.mark.parametrize("fmt", FMTS)
def test_pam_attention_bwd(one_chip, fmt):
    bq, bk, g = _attn_tiles("pam_attention_bwd", fmt)
    dt = FMTS[fmt]
    compiled = _compile(
        one_chip, lambda q, k, v, qp, kp, o, m, l, do:
        pam_kernel.pam_flash_attention_bwd_bh(
            q, k, v, qp, kp, o, m, l, do, causal=True, window=None,
            scale=None, bq=bq, bk=bk, g=g, interpret=False, fmt_name=fmt),
        ((BH, S, DH), dt), ((BKV, S, DH), dt), ((BKV, S, DH), dt),
        ((S,), jnp.int32), ((S,), jnp.int32), ((BH, S, DH), dt),
        ((BH, S), jnp.float32), ((BH, S), jnp.float32), ((BH, S, DH), dt))
    # two sweeps: dsig + dQ, then dK/dV
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("fmt", FMTS)
def test_pa_adamw(one_chip, fmt):
    rows, cols = autotune.tile_params("pam_optim", (576 * 1536,), False, fmt)
    leaf = (576, 1536)
    _compile(one_chip, lambda p, g, m, v, s: optim_kernel.pa_adamw_leaf_pallas(
        p, g, m, v, s, b1=0.9, b2=0.95, eps=1e-8, apply_scale=True,
        rows=rows, cols=cols, interpret=False, fmt_name=fmt),
        (leaf, jnp.bfloat16), (leaf, FMTS[fmt]), (leaf, jnp.float32),
        (leaf, jnp.float32), ((5,), jnp.float32))


@pytest.mark.parametrize("fmt", FMTS)
def test_pa_softmax(one_chip, fmt):
    from repro.kernels.pa_softmax.kernel import pa_softmax_rows
    r, c = 4 * 9 * 1024, 1024                 # attention-sized score rows
    (rows,) = autotune.tile_params("pa_softmax", (r, c), False, fmt)
    _compile(one_chip, lambda x: pa_softmax_rows(
        x, rows=rows, interpret=False, fmt_name=fmt), ((r, c), FMTS[fmt]))


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("op", ["pam", "lmul", "padiv", "paexp2", "palog2"])
def test_pam_eltwise(one_chip, fmt, op):
    from repro.kernels.pam_eltwise.kernel import eltwise_binary, eltwise_unary
    shape = ((4096, 1536), FMTS[fmt])
    if op in ("paexp2", "palog2"):
        _compile(one_chip, lambda a: eltwise_unary(
            a, op=op, interpret=False, fmt_name=fmt), shape)
    else:
        _compile(one_chip, lambda a, b: eltwise_binary(
            a, b, op=op, interpret=False, fmt_name=fmt), shape, shape)


def test_mesh_train_step(v5e_2x2, monkeypatch):
    """The full-PA Pallas train step of smollm-135m (depth cut to 2 layers)
    at seq 1024 x batch 4 on a (data=2, model=2) mesh of the four chips.
    Every kernel must compile inside its shard_map, on its data shard's
    rows — 2048 tokens per PAM matmul, 2 x 9 heads of attention — while the
    tensor-parallel weights stay sharded over ``model``."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core import PAConfig
    from repro.kernels.flash_attention import pam_ops
    from repro.kernels.pam_matmul import ops as mm_ops
    from repro.kernels.pam_optim import ops as optim_ops
    from repro.models import build_model
    from repro.optim import OptConfig, init_opt_state
    from repro.train import TrainConfig, jit_train_step

    for mod in (mm_ops, pam_ops, optim_ops):       # what the chip runs
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    mesh = Mesh(np.array(v5e_2x2).reshape(2, 2), ("data", "model"))
    cfg = dataclasses.replace(
        get_config("smollm-135m", pa=PAConfig(mode="full", impl="pallas"),
                   attn_fused_pam=True), n_layers=2)
    model = build_model(cfg)
    opt = OptConfig(peak_lr=3e-3, warmup_steps=1, total_steps=3)
    step, shardings, batch_sharding = jit_train_step(model, opt,
                                                     TrainConfig(), mesh)
    placed = lambda tree, sh: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh)
    params = model.abstract()
    opt_state = jax.eval_shape(lambda p: init_opt_state(p, opt), params)
    batch = {k: jax.ShapeDtypeStruct((4, S), dt, sharding=batch_sharding)
             for k, dt in (("tokens", jnp.int32), ("labels", jnp.int32),
                           ("mask", jnp.bool_))}
    assert any("model" in str(s.spec)
               for s in jax.tree.leaves(shardings["params"]))
    text = step.lower(placed(params, shardings["params"]),
                      placed(opt_state, shardings["opt"]),
                      batch).compile().as_text()
    shapes = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
            out = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]", line)
            shapes.setdefault(name.group(1), set()).add(
                tuple(int(d) for d in out.group(1).split(",")))
    assert {"pam_matmul", "pam_attention_fwd", "pam_attention_dq",
            "pam_attention_dkv", "pa_adamw"} <= set(shapes)
    assert max(s[1] for s in shapes["pam_matmul"]) == 2048
    assert {s[0] for s in shapes["pam_attention_fwd"]} == {BH}
