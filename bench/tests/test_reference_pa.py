"""The reference's piecewise-affine arithmetic against the paper's
definitions, worked by hand."""
import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import pa


def test_pam_adds_exponents_and_mantissas():
    # 1.5 ·̂ 1.5: mantissas 0.5 + 0.5 carry into the exponent -> 2.0
    assert float(pa.pam_v(1.5, 1.5)) == 2.0
    assert float(pa.pam_v(1.25, 1.5)) == 1.75        # 1 + 0.25 + 0.5
    assert float(pa.pam_v(-2.0, 3.0)) == -6.0        # powers of two exact
    assert float(pa.pam_v(0.0, 3.0)) == 0.0


def test_padiv_exp2_log2():
    assert float(pa.padiv_v(3.0, 1.5)) == 2.0         # 2^(1-0) (1 + .5 - .5)
    assert float(pa.padiv_v(1.0, 1.5)) == 0.75        # borrow: 2^-1 (1 + .5)
    assert float(pa.paexp2_v(2.5)) == 6.0             # 2^2 (1 + 0.5)
    assert float(pa.palog2_v(6.0)) == 2.5             # 2 + 0.5
    assert float(pa.paexp2_v(-200.0)) == 0.0


def test_pasqrt_has_the_chain_rule_derivative():
    # approx derivative of paexp2(palog2(a) / 2) at 4: 2 ·̂ ln2 ·̂ 1/2 ÷̂ (4 ·̂ ln2)
    g = float(jax.grad(lambda x: pa.pasqrt(x))(jnp.float32(4.0)))
    assert 0.2 < g < 0.3


def test_pam_matmul_is_sum_of_pam_products():
    r = np.random.default_rng(0)
    a = r.standard_normal((5, 7)).astype(np.float32)
    b = r.standard_normal((7, 3)).astype(np.float32)
    want = np.asarray(pa.pam_v(a[:, :, None], b[None])).sum(1)
    got = np.asarray(pa.pam_matmul_v(a, b))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    batched = np.asarray(pa.pam_matmul_v(np.stack([a, 2 * a]), b))
    np.testing.assert_allclose(batched[0], got, rtol=1e-6, atol=1e-6)


def test_exact_derivative_of_pam_is_a_power_of_two():
    da = jax.grad(lambda x: pa.pam(x, jnp.float32(1.5), "exact"))(jnp.float32(1.25))
    assert float(da) == 1.0          # exponent of 1.5, no carry at 0.25+0.5
    da = jax.grad(lambda x: pa.pam(x, jnp.float32(1.5), "exact"))(jnp.float32(1.75))
    assert float(da) == 2.0          # 0.75 + 0.5 carries
