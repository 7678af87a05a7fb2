"""Fused PA-AdamW optimizer (kernels/pam_optim, DESIGN.md §5): engine/seed
bit parity, checkpoint-resume parity, and the train-step multiplication
audit — the paper's §2.6 claim that forward + backward + optimizer run
multiplication-free, checked on the jaxpr."""
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import PAConfig
from repro.core import floatbits as fb
from repro.analysis import jaxpr_mul_stats
from repro.optim import OptConfig, adamw_update, init_opt_state

from benchmarks.seed_reference import seed_pa_adamw_update

PA_JNP = PAConfig(mode="full", impl="jnp")
PA_PALLAS = PAConfig(mode="full", impl="pallas")


def small_tree(rng, scale=1.0):
    mk = lambda s: jnp.asarray(rng.standard_normal(s) * scale, jnp.float32)
    return {"w": mk((24, 40)), "b": mk((7,)), "e": mk((130, 8))}


def assert_tree_bits_equal(a, b, what=""):
    for i, (x, y) in enumerate(zip(jax.tree.leaves(a), jax.tree.leaves(b))):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), (
            f"{what}: leaf {i} differs bitwise "
            f"(max |d| = {np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32)).max()})")


# ---------------------------------------------------------------------------
# Engine / seed bit parity.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["f32", "bf16"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_engines_match_numpy_reference(fmt, impl):
    """Both engines, jitted, equal the independent numpy reference bit for
    bit: bf16 params and gradients, f32 moments (a bf16 chain rounds its
    sums to bf16 even where XLA would keep them in f32)."""
    from repro.kernels.pam_optim.ops import pa_adamw_update
    from repro.kernels.pam_optim.ref import pa_adamw_numpy
    rng = np.random.default_rng(3)
    shape = (48, 256)
    p = (rng.standard_normal(shape) * 0.05).astype(jnp.bfloat16)
    g = (rng.standard_normal(shape) * 1e-3).astype(jnp.bfloat16)
    m = (rng.standard_normal(shape) * 1e-4).astype(np.float32)
    v = (rng.random(shape) * 1e-6 + 1e-9).astype(np.float32)
    hyp = dict(b1=0.9, b2=0.98, eps=1e-8, weight_decay=1e-4)
    out = jax.jit(lambda *a: pa_adamw_update(
        *({"w": x} for x in a), 3.0, 1e-3, 0.75, impl=impl, fmt=fmt,
        **hyp))(p, g, m, v)
    ref = pa_adamw_numpy(p, g, m, v, 3.0, 1e-3, 0.75, fmt_name=fmt, **hyp)
    assert_tree_bits_equal([o["w"] for o in out], list(ref), f"{impl}/{fmt}")


@pytest.mark.parametrize("moment_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("grad_clip", [1.0, 0.0])
def test_fused_engines_and_seed_bit_parity(rng, moment_dtype, grad_clip):
    """Pallas kernel == jnp engine == frozen value-level seed chain, bit for
    bit, for f32 and bf16 moment storage and both clip branches."""
    cfg = OptConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10,
                    grad_clip=grad_clip, moment_dtype=moment_dtype)
    p = small_tree(rng)
    g = small_tree(np.random.default_rng(1))
    st = init_opt_state(p, cfg)
    st = {**st, "step": jnp.asarray(5, jnp.int32)}   # mid-run bias correction
    out = {impl: adamw_update(p, g, st, cfg, pa=pa)
           for impl, pa in (("jnp", PA_JNP), ("pallas", PA_PALLAS))}
    seed_p, seed_st, _ = seed_pa_adamw_update(p, g, st, cfg)
    for impl in ("jnp", "pallas"):
        p2, st2, m = out[impl]
        assert st2["m"]["w"].dtype == jnp.dtype(moment_dtype)
        assert_tree_bits_equal(p2, seed_p, f"{impl} params")
        assert_tree_bits_equal(st2["m"], seed_st["m"], f"{impl} m")
        assert_tree_bits_equal(st2["v"], seed_st["v"], f"{impl} v")


@pytest.mark.parametrize("moment_dtype", ["float32", "bfloat16"])
def test_extreme_gradients_finite_and_parity(rng, moment_dtype):
    """±1e20 gradients: v = pam(g, g) rides the PAM overflow clamp; both
    engines must stay finite and keep seed parity."""
    cfg = OptConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10,
                    moment_dtype=moment_dtype)
    p = small_tree(rng)
    g = jax.tree.map(lambda x: jnp.where(x > 0, 1e20, -1e20).astype(jnp.float32), p)
    st = init_opt_state(p, cfg)
    seed_p, seed_st, _ = seed_pa_adamw_update(p, g, st, cfg)
    for pa in (PA_JNP, PA_PALLAS):
        p2, st2, _ = adamw_update(p, g, st, cfg, pa=pa)
        for leaf in jax.tree.leaves(p2):
            assert bool(jnp.isfinite(leaf).all())
        assert_tree_bits_equal(p2, seed_p, f"{pa.impl} extreme params")
        assert_tree_bits_equal(st2["v"], seed_st["v"], f"{pa.impl} extreme v")


def test_resume_from_checkpoint_opt_state(rng, tmp_path):
    """Optimizer state that went through a checkpoint save/restore cycle
    (device -> npz -> device) must keep fused/seed bit parity on the next
    step — moments and the step counter survive the roundtrip exactly."""
    from repro.checkpoint import Checkpointer
    cfg = OptConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10,
                    moment_dtype="bfloat16")
    p = small_tree(rng)
    st = init_opt_state(p, cfg)
    for i in range(3):
        g = small_tree(np.random.default_rng(i))
        p, st, _ = adamw_update(p, g, st, cfg, pa=PA_JNP)
    ck = Checkpointer(str(tmp_path))
    ck.save(3, {"params": p, "opt": st}, blocking=True)
    ck.wait()
    restored = ck.restore(ck.latest_step(), {"params": p, "opt": st})
    assert int(restored["opt"]["step"]) == 3
    g = small_tree(np.random.default_rng(9))
    seed_p, seed_st, _ = seed_pa_adamw_update(restored["params"], g,
                                              restored["opt"], cfg)
    for pa in (PA_JNP, PA_PALLAS):
        p2, st2, _ = adamw_update(restored["params"], g, restored["opt"],
                                  cfg, pa=pa)
        assert_tree_bits_equal(p2, seed_p, f"{pa.impl} resumed params")
        assert_tree_bits_equal(st2["m"], seed_st["m"], f"{pa.impl} resumed m")


# ---------------------------------------------------------------------------
# Bugfix regressions: the two native-multiply leaks in the PA train path.
# ---------------------------------------------------------------------------

def _tiny_model_cfg():
    from repro.models.common import ModelConfig
    return ModelConfig(name="tiny", family="decoder", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                       vocab_size=64, max_seq_len=64, param_dtype="float32",
                       compute_dtype="float32", remat="none",
                       pa=PAConfig(mode="full", deriv="approx",
                                   loss_deriv="exact"))


def _train_step_jaxpr(opt_cfg, train_cfg):
    from repro.models import build_model
    from repro.data import DataConfig, SyntheticLM
    from repro.train import make_train_step
    cfg = _tiny_model_cfg()
    model = build_model(cfg)
    step = make_train_step(model, opt_cfg, train_cfg)
    params = model.init(jax.random.PRNGKey(0))
    st = init_opt_state(params, opt_cfg)
    data = SyntheticLM(DataConfig(vocab_size=64, seq_len=32, global_batch=12,
                                  seed=1))
    batch = jax.tree.map(jnp.asarray, data.batch(0))
    return jax.make_jaxpr(step)(params, st, batch)


def test_pa_microbatch_averaging_emits_no_tensor_multiplies():
    """Regression for the grad-averaging leak (train/step.py): in PA mode a
    non-power-of-two microbatch count used to average gradients with a
    native `g * inv` per tensor. The PA train step's jaxpr must now be free
    of tensor-shaped mul-family ops at any accumulation depth."""
    from repro.train import TrainConfig
    opt = OptConfig(peak_lr=3e-3, warmup_steps=5, total_steps=30)
    stats = jaxpr_mul_stats(_train_step_jaxpr(opt, TrainConfig(microbatches=3)))
    assert stats["tensor_total"] == 0, stats["tensor_sites"]


def test_pa_pow2_microbatch_averaging_is_exact_shift():
    """Power-of-two accumulation depth divides by an exponent shift:
    bit-identical to the native mean for normal results (subnormals flush —
    PA semantics), and still multiplication-free."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((33, 9)) * 1e3, jnp.float32)
    got = fb.pow2_mul(g, -2)
    want = g * np.float32(0.25)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # subnormal boundary: the shift flushes to zero by construction (a
    # native mul may gradually underflow on non-FTZ backends; XLA CPU
    # flushes too, so both agree here)
    tiny = jnp.float32(2e-38)
    assert float(fb.pow2_mul(tiny, -2)) == 0.0


def test_pa_grad_clip0_norm_is_multiplication_free(rng):
    """Regression for the `grad_clip == 0` leak (optim/adamw.py): the norm
    used to fall through to jnp.square. The PA update's jaxpr must audit
    clean with clipping disabled, and the PA norm must track the native
    norm within the PAM error band."""
    cfg = OptConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10,
                    grad_clip=0.0)
    p = small_tree(rng)
    g = small_tree(np.random.default_rng(2))
    st = init_opt_state(p, cfg)
    jx = jax.make_jaxpr(
        lambda pp, gg, ss: adamw_update(pp, gg, ss, cfg, pa=PA_JNP))(p, g, st)
    stats = jaxpr_mul_stats(jx)
    assert stats["tensor_total"] == 0, stats["tensor_sites"]
    _, _, m = adamw_update(p, g, st, cfg, pa=PA_JNP)
    _, _, m_native = adamw_update(p, g, st, cfg)
    np.testing.assert_allclose(float(m["grad_norm"]),
                               float(m_native["grad_norm"]), rtol=0.15)


# ---------------------------------------------------------------------------
# The multiplication audit: paper §2.6, Table 3 last row — the ENTIRE
# train step (forward, backward, grad averaging, optimizer) multiplication-
# free at the jaxpr level.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grad_clip,microbatches", [(1.0, 3), (0.0, 4),
                                                    (1.0, 1)])
def test_full_pa_train_step_multiplication_audit(grad_clip, microbatches):
    """Zero tensor-shaped mul/div/pow/sqrt/square ops anywhere in the
    full-PA train step jaxpr (recursing through scan/pjit/custom-vjp
    sub-jaxprs). Exempt, as documented in repro/analysis/audit.py: the O(1)
    scalar schedule, power-of-two literal scales (exact exponent adds), and
    integer addressing arithmetic."""
    from repro.train import TrainConfig
    opt = OptConfig(peak_lr=3e-3, warmup_steps=5, total_steps=30,
                    grad_clip=grad_clip)
    stats = jaxpr_mul_stats(_train_step_jaxpr(
        opt, TrainConfig(microbatches=microbatches)))
    assert stats["tensor_total"] == 0, stats["tensor_sites"]
    # sanity: the walker saw real work — PA ops lean on pow2 literal scales
    # (paexp2/palog2), and the scalar schedule is allowed to multiply
    assert stats["pow2"] > 0
    assert stats["scalar"].get("mul", 0) > 0


def test_audit_catches_native_multiplies(rng):
    """The auditor itself must flag tensor muls/squares/divs — guard against
    a silently-vacuous audit."""
    x = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)

    def leaky(a):
        return jnp.sum(a * 0.3 + jnp.square(a) + a / (a + 2.0))

    stats = jaxpr_mul_stats(jax.make_jaxpr(leaky)(x))
    assert stats["tensor"].get("mul") == 1
    assert stats["tensor"].get("square") == 1
    assert stats["tensor"].get("div") == 1
    assert stats["tensor_total"] == 3
    # contractions are multiplication work even with a scalar output, and a
    # pow2 NUMERATOR is still a real per-element reciprocal
    s_dot = jaxpr_mul_stats(jax.make_jaxpr(lambda a: a @ a)(x))
    assert s_dot["tensor"].get("dot_general") == 1
    s_vdot = jaxpr_mul_stats(jax.make_jaxpr(
        lambda a: jnp.dot(a[0], a[0]))(x))
    assert s_vdot["tensor_total"] == 1          # scalar-shaped, still counted
    s_rcp = jaxpr_mul_stats(jax.make_jaxpr(lambda a: 2.0 / a)(x))
    assert s_rcp["tensor"].get("div") == 1
    # pow2 literal scaling (mul either side, div by pow2) and scalar math
    # stay exempt
    ok = jax.make_jaxpr(lambda a: jnp.sum(a * 0.5 + a / 4.0) * 3.0)(x)
    s2 = jaxpr_mul_stats(ok)
    assert s2["tensor_total"] == 0 and s2["pow2"] == 2
    assert s2["scalar"].get("mul") == 1


def test_shard_map_dp_train_step_audit_zero(shard_audit_report):
    """The audit invariant survives shard_map data parallelism: the 4-way
    DP train step (per-shard grads, gradient psum, pow2 shard mean, PA
    partial-norm all-reduce, fused PA-AdamW) stays at zero tensor-shaped
    multiplies — and actually contains the collectives (a psum-free program
    would prove nothing). Runs in a subprocess with a forced 4-device host
    platform (see conftest.shard_audit_report)."""
    rep = shard_audit_report
    assert rep["device_count"] >= 4, rep
    check = rep["checks"]["train_dp"]
    assert check["tensor_total"] == 0, check.get("violations")
    assert check["collective_count"] > 0
    assert check["pow2"] > 0          # pow2 shard mean + PA kernel scales
    assert rep["ok"], rep["checks"].keys()
