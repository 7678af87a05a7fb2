"""Vectorized PAM matmul engine: batched/broadcast paths, Pallas backward
parity, per-product bit-exactness, tunables and the chunked jnp fallback."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import PAConfig, pa_matmul
from repro.core.matmul import (_pam_matmul_value, _exact_grad_a,
                               _exact_grad_b, _swap)
from repro.core.pam import pam_value
from repro.kernels.pam_matmul import (pam_matmul, pam_matmul_ref,
                                      pam_matmul_grads_approx,
                                      pam_exact_grad_a, pam_exact_grad_b,
                                      tile_params)
from repro.kernels import _backend


def bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.int32))


class TestBatchedBroadcast:
    """Parity of the single-launch batched grid vs the jnp path."""

    def test_batched_shared_b(self, rng):
        a = rng.standard_normal((3, 16, 24)).astype(np.float32)
        b = rng.standard_normal((24, 8)).astype(np.float32)
        got = np.asarray(pam_matmul(jnp.asarray(a), jnp.asarray(b),
                                    bm=8, bn=8, bk=8))
        want = np.asarray(_pam_matmul_value(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_batched_both(self, rng):
        a = rng.standard_normal((4, 12, 20)).astype(np.float32)
        b = rng.standard_normal((4, 20, 6)).astype(np.float32)
        got = np.asarray(pam_matmul(jnp.asarray(a), jnp.asarray(b),
                                    bm=8, bn=8, bk=8))
        want = np.asarray(_pam_matmul_value(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_broadcast_a_over_batched_b(self, rng):
        a = rng.standard_normal((12, 20)).astype(np.float32)
        b = rng.standard_normal((3, 20, 6)).astype(np.float32)
        got = np.asarray(pam_matmul(jnp.asarray(a), jnp.asarray(b),
                                    bm=8, bn=8, bk=8))
        want = np.asarray(_pam_matmul_value(jnp.asarray(a), jnp.asarray(b)))
        assert got.shape == (3, 12, 6)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_mixed_broadcast_batch_dims(self, rng):
        a = rng.standard_normal((2, 1, 4, 6)).astype(np.float32)
        b = rng.standard_normal((2, 5, 6, 3)).astype(np.float32)
        got = np.asarray(pam_matmul(jnp.asarray(a), jnp.asarray(b),
                                    bm=4, bn=4, bk=4))
        want = np.asarray(_pam_matmul_value(jnp.asarray(a), jnp.asarray(b)))
        assert got.shape == (2, 5, 4, 3)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_jnp_batched_vs_oracle(self, rng):
        a = rng.standard_normal((2, 3, 9, 17)).astype(np.float32)
        b = rng.standard_normal((17, 7)).astype(np.float32)
        got = np.asarray(_pam_matmul_value(jnp.asarray(a), jnp.asarray(b)))
        for i in range(2):
            for j in range(3):
                want = np.asarray(pam_matmul_ref(a[i, j], b))
                np.testing.assert_allclose(got[i, j], want,
                                           rtol=2e-5, atol=2e-5)


class TestBitExactProducts:
    """K=1 eliminates accumulation: products must be bit-identical to
    pam_value, including zeros, denormal flushes and the clamp band."""

    def _check(self, a, b):
        got = pam_matmul(a, b, bm=64, bn=64, bk=1)
        want = jnp.broadcast_to(pam_value(a, b), got.shape)
        np.testing.assert_array_equal(bits(got), bits(want))
        got_j = _pam_matmul_value(a, b)
        np.testing.assert_array_equal(bits(got_j), bits(want))

    def test_normals_and_zeros(self, rng):
        a = jnp.asarray(rng.standard_normal((32, 1)), jnp.float32)
        a = a.at[3, 0].set(0.0).at[5, 0].set(-0.0)
        b = jnp.asarray(rng.standard_normal((1, 32)), jnp.float32)
        b = b.at[0, 7].set(0.0)
        self._check(a, b)

    def test_underflow_flush(self, rng):
        a = jnp.asarray(rng.standard_normal((16, 1)) * 1e-30, jnp.float32)
        b = jnp.asarray(rng.standard_normal((1, 16)) * 1e-15, jnp.float32)
        self._check(a, b)

    def test_zeros_against_large_magnitudes(self):
        """Regression: PAM(a, 0) must be exactly ±0 for ANY finite a — the
        A-side sentinel alone cannot flush b==0 against |a| >= 2 (raw
        magnitudes), which needs the explicit B-zero mask."""
        big = jnp.float32([[3.4e38], [8.0], [4.0], [-2.0], [1e-38], [0.0]])
        zeros = jnp.float32([[0.0, -0.0, 1.0, -2.0]])
        self._check(big, zeros)
        self._check(jnp.float32([[0.0]]),
                    jnp.float32([[3.4e38, -8.0, 0.0, 1e-40]]))

    def test_zero_cotangent_backward_large_activations(self):
        """Regression: dB = Aᵀ ·̂ g with g == 0 rows and |A| >= 4 must give
        exactly zero gradient columns (routine with masked losses)."""
        a = jnp.float32([[8.0, -16.0], [3.4e38, 4.0]])
        b = jnp.float32([[1.0, 2.0], [3.0, 4.0]])
        for impl in ("jnp", "pallas"):
            pa = PAConfig(mode="matmul", impl=impl, deriv="approx")
            da, db = jax.grad(
                lambda x, y: jnp.sum(pa_matmul(x, y, pa) *
                                     jnp.float32([[0.0, 1.0], [0.0, 1.0]])),
                argnums=(0, 1))(a, b)
            assert np.asarray(db)[:, 0].tolist() == [0.0, 0.0], (impl, db)

    def test_overflow_clamp_band(self):
        # |a*b| in [2^128, 2^129): pam clamps to MAX_FINITE; preserved
        a = jnp.full((4, 1), 2.0**80, jnp.float32)
        b = jnp.full((1, 4), -(2.0**48.5), jnp.float32)
        self._check(a, b)


class TestPallasBackward:
    """Kernel-path backward vs jnp-path backward, both deriv variants."""

    @pytest.mark.parametrize("deriv", ["approx", "exact"])
    def test_grad_parity_2d(self, rng, deriv):
        a = jnp.asarray(rng.standard_normal((6, 33)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((33, 5)), jnp.float32)

        def loss(pa):
            return jax.grad(lambda x, y: jnp.sum(pa_matmul(x, y, pa)),
                            argnums=(0, 1))(a, b)

        da_j, db_j = loss(PAConfig(mode="matmul", impl="jnp", deriv=deriv))
        da_p, db_p = loss(PAConfig(mode="matmul", impl="pallas", deriv=deriv))
        np.testing.assert_allclose(np.asarray(da_p), np.asarray(da_j),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(db_p), np.asarray(db_j),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("deriv", ["approx", "exact"])
    def test_grad_parity_batched(self, rng, deriv):
        a = jnp.asarray(rng.standard_normal((2, 6, 12)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((2, 12, 5)), jnp.float32)

        def loss(pa):
            return jax.grad(lambda x, y: jnp.sum(pa_matmul(x, y, pa)),
                            argnums=(0, 1))(a, b)

        da_j, db_j = loss(PAConfig(mode="matmul", impl="jnp", deriv=deriv))
        da_p, db_p = loss(PAConfig(mode="matmul", impl="pallas", deriv=deriv))
        np.testing.assert_allclose(np.asarray(da_p), np.asarray(da_j),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(db_p), np.asarray(db_j),
                                   rtol=2e-5, atol=2e-5)

    def test_approx_grads_entry_point(self, rng):
        a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
        g = jnp.ones((8, 4), jnp.float32)
        da, db = pam_matmul_grads_approx(a, b, g)
        np.testing.assert_allclose(
            np.asarray(da), np.asarray(_pam_matmul_value(g, _swap(b))),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(db), np.asarray(_pam_matmul_value(_swap(a), g)),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("impl", ["jnp", "pallas"])
    def test_exact_grads_vs_independent_oracle(self, rng, impl):
        """Both exact-grad engines vs the retained scalar oracle
        (pam_exact_dfactor + pam_value) — catches a bug shared by the two
        fused bit-level implementations, which only cross-check each other
        otherwise."""
        from repro.core.pam import pam_exact_dfactor

        a = jnp.asarray(rng.standard_normal((5, 9)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((9, 4)), jnp.float32)
        b = b.at[:, 1].set(0.0)
        g = jnp.asarray(rng.standard_normal((5, 4)), jnp.float32)
        g = g.at[2, :].set(0.0)

        # oracle: dA[m,k] = sum_n pam(dfactor(a[m,k], b[k,n]), g[m,n])
        f = pam_exact_dfactor(a[:, :, None], b[None, :, :])     # (M, K, N)
        da_oracle = jnp.sum(pam_value(f, g[:, None, :]), axis=-1)
        fb_ = pam_exact_dfactor(b.T[:, :, None], a.T[None, :, :])
        db_oracle = jnp.sum(pam_value(fb_, g.T[:, None, :]), axis=-1).T

        if impl == "pallas":
            da = pam_exact_grad_a(a, b, g, bm=8, bn=8, bk=8)
            db = pam_exact_grad_b(a, b, g, bm=8, bn=8, bk=8)
        else:
            da, db = _exact_grad_a(a, b, g), _exact_grad_b(a, b, g)
        np.testing.assert_allclose(np.asarray(da), np.asarray(da_oracle),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(db), np.asarray(db_oracle),
                                   rtol=2e-5, atol=2e-5)

    def test_1d_left_operand(self, rng):
        """jnp.matmul-style vector @ matrix (regression: the collapse path
        must accept a.ndim == 1)."""
        a = jnp.asarray(rng.standard_normal(8), jnp.float32)
        b = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
        got = pam_matmul(a, b, bm=8, bn=8, bk=8)
        assert got.shape == (4,)
        want = _pam_matmul_value(a[None], b)[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_exact_grad_kernel_vs_jnp_with_zeros(self, rng):
        a = jnp.asarray(rng.standard_normal((6, 33)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((33, 5)), jnp.float32)
        b = b.at[:, 2].set(0.0)
        g = jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)
        g = g.at[0, :].set(0.0)
        da = pam_exact_grad_a(a, b, g, bm=8, bn=8, bk=8)
        np.testing.assert_allclose(np.asarray(da),
                                   np.asarray(_exact_grad_a(a, b, g)),
                                   rtol=2e-5, atol=2e-5)
        db = pam_exact_grad_b(a, b, g, bm=8, bn=8, bk=8)
        np.testing.assert_allclose(np.asarray(db),
                                   np.asarray(_exact_grad_b(a, b, g)),
                                   rtol=2e-5, atol=2e-5)


class TestTunablesAndFallback:
    def test_autotune_table_resolves(self):
        bm, bn, bk, g = tile_params(256, 256, 256, True)
        assert bk % g == 0 and bm > 0 and bn > 0

    def test_prime_tile_sizes(self, rng):
        # bk=7 forces the g-divisor adjustment (7 is prime)
        a = rng.standard_normal((5, 7)).astype(np.float32)
        b = rng.standard_normal((7, 3)).astype(np.float32)
        got = np.asarray(pam_matmul(jnp.asarray(a), jnp.asarray(b),
                                    bm=8, bn=8, bk=7, g=16))
        want = np.asarray(pam_matmul_ref(a, b))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_chunked_scan_matches_single_shot(self, rng):
        a = jnp.asarray(rng.standard_normal((8, 640)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((640, 4)), jnp.float32)
        single = _pam_matmul_value(a, b, budget=1 << 24)
        chunked = _pam_matmul_value(a, b, budget=64)
        # identical group-level products; only the scan carries differ
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(single),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_reference_shape_parity(self, rng):
        """The benchmark's 256^3 reference shape, autotuned tiles."""
        a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        got = np.asarray(pam_matmul(a, b))
        want = np.asarray(_pam_matmul_value(a, b))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_large_batched_grid(self, rng):
        a = jnp.asarray(rng.standard_normal((4, 128, 128)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((4, 128, 128)), jnp.float32)
        got = np.asarray(pam_matmul(a, b))
        want = np.asarray(_pam_matmul_value(a, b))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_interpret_backend_helper(self, monkeypatch):
        """The platform alone decides: interpret off the TPU, compiled on
        it, re-read at every call (no import-time freeze, no override)."""
        assert _backend.use_interpret() == (jax.default_backend() != "tpu")
        monkeypatch.setattr(_backend.jax, "default_backend", lambda: "tpu")
        assert _backend.use_interpret() is False
        monkeypatch.setattr(_backend.jax, "default_backend", lambda: "cpu")
        assert _backend.use_interpret() is True
