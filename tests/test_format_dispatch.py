"""FloatFormat engine-family tests (DESIGN.md §11).

Four pillars of the format refactor:

  1. Frozen bit layouts — every derived constant of FLOAT32 / BFLOAT16 /
     FLOAT16 pinned to hand-computed literals, so a change to the generic
     derivation in ``core/floatbits.py`` cannot silently move a mask.
  2. f32 bit-identity pre/post — ``get_prims("f32")`` must BE the seed
     module functions, and the generic ``_build_prims`` machinery must
     reproduce those seed bits exactly on adversarial operands (including
     the int32-wrap overflow edge), per kernel family via the K=1 /
     per-product routes that eliminate accumulation order.
  3. bf16-native semantics — denormal flush, saturation clamp (the int16
     analogue of the f32 2^129 wrap), signed zeros, and the measured
     error of the live int16-carrier engines sitting inside the static
     absint certificate (ISSUE acceptance, also re-checked by `make audit`).
  4. Format discipline — mixed f32/bf16 operands are a TypeError, never a
     silent promotion; the L-Mul engine stays inside its analytic
     [-161/2209, +1/16] band in both carriers.
"""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import PAConfig, floatbits as fb
from repro.core.matmul import pa_matmul
from repro.kernels import pa_prims as pp
from repro.kernels.pa_prims import _build_prims, get_prims

pam = importlib.import_module("repro.core.pam")


def _bits(x):
    fmt = fb.format_for_dtype(jnp.asarray(x).dtype)
    return np.asarray(jax.lax.bitcast_convert_type(jnp.asarray(x), fmt.carrier))


def _log_uniform(rng, n, e_lo, e_hi, dtype):
    mag = np.exp2(rng.uniform(e_lo, e_hi, n)).astype(np.float32)
    sgn = rng.choice([-1.0, 1.0], n).astype(np.float32)
    x = (sgn * mag).astype(np.float32)
    x[rng.random(n) < 0.05] = 0.0
    return jnp.asarray(x).astype(dtype)


# ---------------------------------------------------------------------------
# 1. Frozen layouts.
# ---------------------------------------------------------------------------

class TestFrozenLayouts:
    def test_f32(self):
        f = fb.FLOAT32
        assert (f.dtype, f.carrier) == (jnp.float32, jnp.int32)
        assert int(f.SIGN_MASK) == -(1 << 31)
        assert int(f.MAG_MASK) == 0x7FFFFFFF
        assert int(f.EXP_MASK) == 0x7F800000
        assert int(f.MAN_MASK) == 0x007FFFFF
        assert int(f.BIAS_SHIFTED) == 127 << 23
        assert int(f.MIN_NORM) == 1 << 23
        assert int(f.MAX_EXP_FIELD) == 254 << 23
        assert int(f.MAX_FINITE) == 0x7F7FFFFF
        assert int(f.INF_BITS) == 0x7F800000
        assert int(f.ZERO_SENTINEL) == -(1 << 30)
        assert (f.exp_bias, f.man_bits) == (127, 23)

    def test_bf16(self):
        f = fb.BFLOAT16
        assert (f.dtype, f.carrier) == (jnp.bfloat16, jnp.int16)
        assert int(f.SIGN_MASK) == -32768
        assert int(f.MAG_MASK) == 32767
        assert int(f.EXP_MASK) == 32640          # 0x7F80
        assert int(f.MAN_MASK) == 127
        assert int(f.BIAS_SHIFTED) == 16256      # 127 << 7
        assert int(f.MIN_NORM) == 128
        assert int(f.MAX_FINITE) == 32639        # 0x7F7F
        assert int(f.INF_BITS) == 32640
        assert int(f.ZERO_SENTINEL) == -16384
        assert (f.exp_bias, f.man_bits) == (127, 7)

    def test_f16(self):
        f = fb.FLOAT16
        assert (f.dtype, f.carrier) == (jnp.float16, jnp.int16)
        assert int(f.BIAS_SHIFTED) == 15 << 10
        assert int(f.MIN_NORM) == 1 << 10
        assert int(f.MAX_FINITE) == 0x7BFF
        assert int(f.EXP_MASK) == 0x7C00
        assert int(f.ZERO_SENTINEL) == -16384
        assert (f.exp_bias, f.man_bits) == (15, 10)

    def test_lmul_offsets(self):
        # l(m) = 4 for every supported format (m = 23, 7, 10 all > 4).
        assert fb.FLOAT32.LMUL_L == 4 and int(fb.FLOAT32.LMUL_OFFSET) == 1 << 19
        assert fb.BFLOAT16.LMUL_L == 4 and int(fb.BFLOAT16.LMUL_OFFSET) == 8
        assert fb.FLOAT16.LMUL_L == 4 and int(fb.FLOAT16.LMUL_OFFSET) == 1 << 6

    def test_sentinel_band_absorbs_lmul_fold(self):
        # The L-Mul fold shifts the re-bias by 2^(m-4); the zero-sentinel /
        # overflow-band disjointness proofs need that shift to stay far
        # below the 2^m-wide guard bands in BOTH carriers (the comment in
        # pa_prims._build_prims points here).
        for f in (fb.FLOAT32, fb.BFLOAT16, fb.FLOAT16):
            fold = int(f.BIAS_SHIFTED) - int(f.LMUL_OFFSET)
            assert 0 < fold < int(f.BIAS_SHIFTED)
            # sentinel + (mag - fold) always lands in the flush band
            # [carrier_min, MIN_NORM) — flushed, never wrapped — for any
            # in-range partner magnitude, with either fold constant.
            assert int(f.ZERO_SENTINEL) + int(f.MAX_FINITE) - fold \
                < int(f.MIN_NORM)
            assert int(f.ZERO_SENTINEL) - fold >= -(1 << (f.width - 1))


# ---------------------------------------------------------------------------
# 2. f32 bit-identity pre/post refactor.
# ---------------------------------------------------------------------------

class TestF32BitIdentity:
    def test_f32_prims_are_the_seed_functions(self):
        p = get_prims("f32", lmul=False)
        assert p.pam is pp._pam
        assert p.padiv is pp._padiv
        assert p.paexp2 is pp._paexp2
        assert p.palog2 is pp._palog2
        assert p.pam_dot is pp._pam_dot

    def test_generic_builder_reproduces_seed_bits(self, rng):
        """_build_prims(FLOAT32) — the formula the bf16/f16/L-Mul engines
        come from — must match the seed's literal-constant helpers bit for
        bit, including underflow-flush and the int32-wrap overflow edge."""
        gen = _build_prims(fb.FLOAT32, lmul=False)
        a = _log_uniform(rng, 4096, -140.0, 130.0, jnp.float32)
        b = _log_uniform(rng, 4096, -140.0, 130.0, jnp.float32)
        np.testing.assert_array_equal(_bits(gen.pam(a, b)),
                                      _bits(pp._pam(a, b)))
        bnz = jnp.where(b == 0.0, jnp.float32(1.0), b)
        np.testing.assert_array_equal(_bits(gen.padiv(a, bnz)),
                                      _bits(pp._padiv(a, bnz)))
        e = jnp.asarray(rng.uniform(-160.0, 160.0, 4096).astype(np.float32))
        np.testing.assert_array_equal(_bits(gen.paexp2(e)),
                                      _bits(pp._paexp2(e)))
        pos = jnp.abs(jnp.where(a == 0.0, jnp.float32(1.0), a))
        np.testing.assert_array_equal(_bits(gen.palog2(pos)),
                                      _bits(pp._palog2(pos)))

    def test_generic_tile_product_reproduces_seed_bits(self, rng):
        gen = _build_prims(fb.FLOAT32, lmul=False)
        a = _log_uniform(rng, 16 * 24, -10.0, 10.0, jnp.float32).reshape(16, 24)
        b = _log_uniform(rng, 24 * 8, -10.0, 10.0, jnp.float32).reshape(24, 8)
        np.testing.assert_array_equal(_bits(gen.pam_dot(a, b, 4)),
                                      _bits(pp._pam_dot(a, b, 4)))

    def test_matmul_family_k1_products_bit_exact(self, rng):
        """K=1 eliminates accumulation: every pam_matmul product must be
        bit-identical to the seed value-level PAM forward."""
        from repro.kernels.pam_matmul import pam_matmul
        a = _log_uniform(rng, 16, -6.0, 6.0, jnp.float32).reshape(16, 1)
        b = _log_uniform(rng, 8, -6.0, 6.0, jnp.float32).reshape(1, 8)
        got = pam_matmul(a, b, bm=8, bn=8, bk=1)
        want = pam.pam_value(a, b)
        np.testing.assert_array_equal(_bits(got), _bits(want))

    def test_attention_family_k1_scores_bit_exact(self, rng):
        """The attention family's score core IS ``pam_dot`` (pam_kernel
        resolves it through get_prims); at contraction length 1 every f32
        score must be bit-identical to the seed PAM forward. Engine-level,
        pallas and jnp agree to f32 sum order on the fused output."""
        from repro.kernels.flash_attention import pam_flash_attention
        a = _log_uniform(rng, 17, -4.0, 4.0, jnp.float32).reshape(17, 1)
        b = _log_uniform(rng, 13, -4.0, 4.0, jnp.float32).reshape(1, 13)
        np.testing.assert_array_equal(_bits(pp._pam_dot(a, b, 16)),
                                      _bits(pam.pam_value(a, b)))
        B, S, H, Dh = 1, 4, 2, 4
        q = _log_uniform(rng, B * S * H * Dh, -2.0, 2.0,
                         jnp.float32).reshape(B, S, H, Dh)
        k = _log_uniform(rng, B * S * H * Dh, -2.0, 2.0,
                         jnp.float32).reshape(B, S, H, Dh)
        v = _log_uniform(rng, B * S * H * Dh, -2.0, 2.0,
                         jnp.float32).reshape(B, S, H, Dh)
        pos = jnp.arange(S)
        o_pl = pam_flash_attention(q, k, v, pos, pos, impl="pallas",
                                   bq=4, bk=4, g=2)
        o_jn = pam_flash_attention(q, k, v, pos, pos, impl="jnp",
                                   bq=4, bk=4, g=2)
        assert o_pl.dtype == o_jn.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_jn),
                                   rtol=1e-5, atol=1e-6)

    def test_optim_family_engines_bit_equal(self, rng):
        from repro.kernels.pam_optim.ops import pa_adamw_update
        p = {"w": _log_uniform(rng, 64, -4.0, 2.0, jnp.float32)}
        g = {"w": _log_uniform(rng, 64, -6.0, 0.0, jnp.float32)}
        m = {"w": jnp.zeros(64, jnp.float32)}
        v = {"w": jnp.zeros(64, jnp.float32)}
        kw = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
        outs = {}
        for impl in ("jnp", "pallas"):
            outs[impl] = pa_adamw_update(p, g, m, v, 1, 1e-3, None,
                                         impl=impl, fmt="f32", **kw)
        for a, b in zip(jax.tree_util.tree_leaves(outs["jnp"]),
                        jax.tree_util.tree_leaves(outs["pallas"])):
            np.testing.assert_array_equal(_bits(a), _bits(b))

    def test_softmax_family_f32_path_unchanged(self, rng):
        """f32 softmax inputs must produce f32 outputs through the seed
        (int32-carrier) route — and the generic-builder f32 prims compose
        to the same bits as the kernel's helpers."""
        from repro.kernels.pa_softmax import pa_softmax
        x = _log_uniform(rng, 4 * 32, -3.0, 3.0, jnp.float32).reshape(4, 32)
        y = pa_softmax(x)
        assert y.dtype == jnp.float32
        rows = np.asarray(jnp.sum(y, axis=-1))
        np.testing.assert_allclose(rows, np.ones_like(rows), rtol=0.2)


# ---------------------------------------------------------------------------
# 3. bf16-native semantics (absint agreement).
# ---------------------------------------------------------------------------

class TestBf16Semantics:
    def test_underflow_flushes_to_signed_zero(self):
        a = jnp.asarray(2.0 ** -100, jnp.bfloat16)
        b = jnp.asarray(-(2.0 ** -60), jnp.bfloat16)
        out = pam.pam_value(a, b)
        assert out.dtype == jnp.bfloat16
        assert float(out) == 0.0
        assert int(_bits(out)) == int(fb.BFLOAT16.SIGN_MASK)  # -0.0

    def test_denormal_input_is_zero_for_the_engines(self):
        # Exponent-field zero test (int16 carrier): a bf16 denormal operand
        # behaves as exact zero, matching the flush-to-zero absint domain.
        denorm = fb.floats(jnp.asarray(64, jnp.int16), fb.BFLOAT16)  # 2^-127
        assert float(denorm) != 0.0                 # it IS a denormal value
        p = get_prims("bf16").pam(denorm, jnp.asarray(3.0, jnp.bfloat16))
        assert float(p) == 0.0

    def test_overflow_saturates_to_max_finite(self):
        # exponent sum 240 > 254-field ceiling: clamp, not inf, not wrap.
        a = jnp.asarray(2.0 ** 120, jnp.bfloat16)
        out = pam.pam_value(a, a)
        assert int(_bits(out)) == int(fb.BFLOAT16.MAX_FINITE)
        neg = pam.pam_value(-a, a)
        assert int(_bits(neg)) == np.int16(
            fb.BFLOAT16.SIGN_MASK | fb.BFLOAT16.MAX_FINITE)

    def test_int16_wrap_edge_saturates(self):
        """The int16 analogue of the f32 2^129 wrap (DESIGN.md §11): two
        max-finite magnitudes overflow the carrier add; the disjoint
        negative-range test must classify it as overflow -> MAX_FINITE."""
        top = fb.floats(jnp.asarray(int(fb.BFLOAT16.MAX_FINITE), jnp.int16),
                        fb.BFLOAT16)
        out = get_prims("bf16").pam(top, top)
        assert int(_bits(out)) == int(fb.BFLOAT16.MAX_FINITE)

    def test_bf16_relative_error_inside_certificate_band(self, rng):
        from repro.analysis.domains import EPS_PAM_WORST, quant_eps
        a = _log_uniform(rng, 8192, -20.0, 20.0, jnp.bfloat16)
        b = _log_uniform(rng, 8192, -20.0, 20.0, jnp.bfloat16)
        got = np.asarray(pam.pam_value(a, b), np.float64)
        true = np.asarray(a, np.float64) * np.asarray(b, np.float64)
        nz = true != 0.0
        rel = got[nz] / true[nz] - 1.0
        qe = quant_eps(fb.BFLOAT16.man_bits)
        assert rel.max() <= qe + 1e-9
        assert rel.min() >= -EPS_PAM_WORST - qe - 1e-9

    def test_measured_bf16_error_within_static_certificate(self):
        # ISSUE acceptance, pinned in tier-1 (the audit re-checks the same
        # block when it regenerates AUDIT.json).
        from repro.launch.audit import bf16_measured_block
        block = bf16_measured_block()
        assert block["within_certificate"] is True
        for op, rec in block["ops"].items():
            assert rec["measured_rel_worst"] <= rec["static_rel_worst"], op

    def test_bf16_matmul_reduced_operand_bytes(self, rng):
        # The bf16 kernels see half-width operands end to end: output dtype
        # stays bf16 (no silent f32 upcast of the result).
        from repro.kernels.pam_matmul import pam_matmul
        a = _log_uniform(rng, 16 * 32, -3.0, 3.0, jnp.bfloat16).reshape(16, 32)
        b = _log_uniform(rng, 32 * 8, -3.0, 3.0, jnp.bfloat16).reshape(32, 8)
        out = pam_matmul(a, b, bm=8, bn=8, bk=16)
        assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# 4. Format discipline + the L-Mul band.
# ---------------------------------------------------------------------------

class TestFormatDiscipline:
    def test_mixed_formats_raise(self, rng):
        a32 = jnp.asarray(rng.standard_normal(8).astype(np.float32))
        a16 = a32.astype(jnp.bfloat16)
        with pytest.raises(TypeError, match="one float format"):
            pam.pam_value(a32, a16)
        with pytest.raises(TypeError, match="one float format"):
            pam.pam(a16, a32)

    def test_scalars_follow_the_array_operand(self):
        # np.float32 constants (core/nn.py style) carry no format vote.
        x = jnp.asarray([1.5, 2.5], jnp.bfloat16)
        out = pam.pam_value(x, np.float32(2.0))
        assert out.dtype == jnp.bfloat16

    @pytest.mark.parametrize("fmt_name", ["f32", "bf16"])
    def test_lmul_error_band(self, rng, fmt_name):
        from repro.analysis.domains import quant_eps
        fmt = fb.FORMATS[fmt_name]
        a = _log_uniform(rng, 8192, -12.0, 12.0, fmt.dtype)
        b = _log_uniform(rng, 8192, -12.0, 12.0, fmt.dtype)
        got = np.asarray(pam.lmul_value(a, b), np.float64)
        true = np.asarray(a, np.float64) * np.asarray(b, np.float64)
        nz = true != 0.0
        rel = got[nz] / true[nz] - 1.0
        qe = quant_eps(fmt.man_bits)
        assert rel.max() <= pp.LMUL_REL_PLUS + qe + 1e-9
        assert rel.min() >= -pp.LMUL_REL_WORST - qe - 1e-9

    def test_lmul_engine_through_matmul(self, rng):
        cfg = PAConfig(mode="full", impl="lmul", deriv="approx",
                       loss_deriv="approx")
        a = _log_uniform(rng, 8 * 16, -4.0, 4.0, jnp.float32).reshape(8, 16)
        b = _log_uniform(rng, 16 * 4, -4.0, 4.0, jnp.float32).reshape(16, 4)
        got = np.asarray(pa_matmul(a, b, cfg), np.float64)
        a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
        true = a64 @ b64
        # Per-product relative error is banded, so the accumulated error is
        # bounded by band * sum(|products|) — NOT by band * |sum| (signed
        # cancellation can make the naive relative error arbitrarily large).
        band = max(pp.LMUL_REL_WORST, pp.LMUL_REL_PLUS) + 2.0 ** -22
        bound = band * (np.abs(a64) @ np.abs(b64))
        assert np.all(np.abs(got - true) <= bound + 1e-9)


# ---------------------------------------------------------------------------
# 5. Widened carriers: what the TPU kernels run (no 16-bit compares on v5e).
# ---------------------------------------------------------------------------

def _all_bf16():
    """Every bf16 bit pattern: zeros, denormals, normals, infs, NaNs."""
    return fb.floats(jnp.arange(-32768, 32768, dtype=jnp.int32),
                     fb.BFLOAT16.widened)


def _u16(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))


def _bf16_pairs(rng, n=1 << 17):
    """Random bit-pattern pairs plus every pair of a grid of edge values."""
    edges = np.array([0x0000, 0x8000, 0x0001, 0x807F, 0x0080, 0x8080,
                      0x3F80, 0xBF80, 0x3FC0, 0x7F7F, 0xFF7F, 0x7F00,
                      0x0100, 0x7F80, 0xFF80, 0x7FC0, 0x4300, 0xC300],
                     np.uint16)
    ea, eb = np.meshgrid(edges, edges)
    ra = rng.integers(0, 1 << 16, n).astype(np.uint16)
    rb = rng.integers(0, 1 << 16, n).astype(np.uint16)
    to = lambda u: jax.lax.bitcast_convert_type(jnp.asarray(u), jnp.bfloat16)
    return (to(np.concatenate([ra, ea.ravel()])),
            to(np.concatenate([rb, eb.ravel()])))


class TestWidenedCarrier:
    def test_widened_layout_round_trips_every_pattern(self):
        w = fb.BFLOAT16.widened
        assert (w.dtype, w.storage, w.carrier) == (jnp.bfloat16, jnp.int16,
                                                   jnp.int32)
        assert fb.FLOAT32.widened is fb.FLOAT32 and w.widened is w
        for name in ("SIGN_MASK", "MAG_MASK", "EXP_MASK", "MAN_MASK",
                     "BIAS_SHIFTED", "MIN_NORM", "MAX_FINITE", "INF_BITS",
                     "ZERO_SENTINEL", "LMUL_OFFSET"):
            assert int(getattr(w, name)) == int(getattr(fb.BFLOAT16, name))
        x = _all_bf16()
        np.testing.assert_array_equal(
            np.asarray(fb.bits(x, w)), np.arange(-32768, 32768))
        np.testing.assert_array_equal(
            np.asarray(fb.bits(x, w)),
            np.asarray(fb.bits(x, fb.BFLOAT16)).astype(np.int32))

    @pytest.mark.parametrize("op", ["paexp2", "palog2", "pasqrt"])
    def test_value_ops_unary_exhaustive(self, op):
        """The IEEE-complete value ops on every bf16 input: int32 carrier
        == int16 carrier, bit for bit (the fused PA-AdamW kernel's ops)."""
        x = _all_bf16()
        narrow = jax.jit(getattr(pam.ValueOps(fb.BFLOAT16), op))(x)
        wide = jax.jit(getattr(pam.ValueOps(fb.BFLOAT16.widened), op))(x)
        assert wide.dtype == narrow.dtype == jnp.bfloat16
        np.testing.assert_array_equal(_u16(wide), _u16(narrow))

    @pytest.mark.parametrize("op", ["pam", "padiv"])
    def test_value_ops_binary(self, rng, op):
        a, b = _bf16_pairs(rng)
        narrow = jax.jit(getattr(pam.ValueOps(fb.BFLOAT16), op))(a, b)
        wide = jax.jit(getattr(pam.ValueOps(fb.BFLOAT16.widened), op))(a, b)
        np.testing.assert_array_equal(_u16(wide), _u16(narrow))

    @pytest.mark.parametrize("lmul", [False, True])
    def test_kernel_prims_match_int16_carrier(self, rng, lmul):
        """The kernels' prims (widened) against the same formulas on the
        int16 carrier, over their contract: finite inputs, palog2 of
        positives, and the grouped tile product."""
        w = get_prims("bf16", lmul)
        n16 = _build_prims(fb.BFLOAT16, lmul)
        assert w.fmt.carrier == jnp.int32 and n16.fmt.carrier == jnp.int16
        a, b = _bf16_pairs(rng)
        fin = np.isfinite(np.asarray(a, np.float32)) & np.isfinite(
            np.asarray(b, np.float32))
        a, b = a[fin], b[fin]
        for name in ("pam", "padiv"):
            np.testing.assert_array_equal(
                _u16(jax.jit(getattr(w, name))(a, b)),
                _u16(jax.jit(getattr(n16, name))(a, b)), err_msg=name)
        np.testing.assert_array_equal(_u16(jax.jit(w.paexp2)(a)),
                                      _u16(jax.jit(n16.paexp2)(a)))
        pos = jnp.abs(a)
        np.testing.assert_array_equal(_u16(jax.jit(w.palog2)(pos)),
                                      _u16(jax.jit(n16.palog2)(pos)))
        x = _log_uniform(rng, 24 * 96, -8.0, 8.0,
                         jnp.bfloat16).reshape(24, 96)
        y = _log_uniform(rng, 96 * 40, -8.0, 8.0,
                         jnp.bfloat16).reshape(96, 40)
        dot = lambda p: jax.jit(lambda u, v: p.pam_dot(u, v, 8))(x, y)
        np.testing.assert_array_equal(np.asarray(dot(w)),
                                      np.asarray(dot(n16)))

    def test_optim_family_engines_bit_equal_bf16(self, rng):
        """bf16 compute: the kernel's widened chain equals the jnp engine's
        int16 chain under jit (eager dispatch rounds bf16 intermediates
        that a compiled program may keep at f32 precision)."""
        from repro.kernels.pam_optim.ops import pa_adamw_update
        p = {"w": _log_uniform(rng, 4096, -4.0, 2.0, jnp.bfloat16)}
        g = {"w": _log_uniform(rng, 4096, -6.0, 0.0, jnp.float32)}
        m = {"w": _log_uniform(rng, 4096, -8.0, -2.0, jnp.float32)}
        v = {"w": jnp.abs(_log_uniform(rng, 4096, -12.0, -4.0, jnp.float32))}
        outs = {impl: jax.jit(lambda p, g, m, v, impl=impl: pa_adamw_update(
                    p, g, m, v, 3, 1e-3, jnp.float32(0.5), b1=0.9, b2=0.95,
                    eps=1e-8, weight_decay=0.1, impl=impl, fmt="bf16"))(
                    p, g, m, v)
                for impl in ("jnp", "pallas")}
        for a, b in zip(jax.tree_util.tree_leaves(outs["jnp"]),
                        jax.tree_util.tree_leaves(outs["pallas"])):
            np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                          np.asarray(b).view(np.uint8))
