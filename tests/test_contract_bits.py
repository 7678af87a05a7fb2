"""The PAM tile product's bits do not depend on its loop form.

``pa_prims._contract`` runs a contraction as static chunks, looped over
when the contraction is 128-lane aligned and a step's rows are tall
(DESIGN.md §2.1). Every form must add what the plain sequential sum adds:
the products of ``g`` consecutive steps into a group partial, the group
partials into the running sum in contraction order. These cases pin that
bit for bit on the CPU (interpret mode) for the three kernels that share
the tile product: against numpy for the matmul forward, and against the
same kernel with the sequential sum in place of ``_contract`` for the
exact gradient and the fused attention forward.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pam import pam_value
from repro.kernels import pa_prims
from repro.kernels.flash_attention import pam_kernel
from repro.kernels.pam_matmul import kernel as mm_kernel

FMTS = {"f32": jnp.float32, "bf16": jnp.bfloat16}
LENGTHS = (64, 128, 256, 640)
ROWS = (1, 2, 8, 64, 128)
GROUPS = (1, 8, 16)

# Every (length, g, format) up to 256 lanes, rows cycling so that each
# length meets every row count and both loop forms (one chunk, looped
# chunks); 640 lanes (five 128-step chunks, the slowest to interpret)
# once per g.
MATMUL_CASES = [(c, ROWS[i % len(ROWS)], g, f) for i, (c, g, f) in
                enumerate(itertools.product(LENGTHS[:-1], GROUPS, FMTS))]
MATMUL_CASES += [(LENGTHS[-1], 2, 1, "bf16"), (LENGTHS[-1], 8, 8, "f32"),
                 (LENGTHS[-1], 128, 16, "bf16")]


def _operand(rng, shape, dt):
    """Signed log-uniform magnitudes in [2^-6, 2^6] with a few +-0."""
    x = np.exp2(rng.uniform(-6.0, 6.0, shape)) * rng.choice([-1.0, 1.0],
                                                            shape)
    flat = x.reshape(-1)
    flat[rng.choice(flat.size, max(1, flat.size // 50), replace=False)] = 0.0
    flat[rng.choice(flat.size, max(1, flat.size // 50), replace=False)] = -0.0
    return jnp.asarray(x.astype(np.float32)).astype(dt)


def _grouped_sum(p, g):
    """Sequential f32 sum over axis 1 of (M, C, N) products in g-groups."""
    acc = None
    for q in range(0, p.shape[1], g):
        part = p[:, q]
        for j in range(q + 1, q + g):
            part = part + p[:, j]
        acc = part if acc is None else acc + part
    return acc


def _sequential_contract(a_side, b_side, product, g):
    """The reference loop form: one step at a time, groups in order."""
    def step(j):
        return product(
            tuple(jax.lax.dynamic_slice_in_dim(x, j, 1, 1) for x in a_side),
            tuple(jax.lax.dynamic_slice_in_dim(y, j, 1, 0) for y in b_side))

    def group(q):
        return jax.lax.fori_loop(1, g, lambda i, part: part + step(q * g + i),
                                 step(q * g))

    return jax.lax.fori_loop(1, a_side[0].shape[1] // g,
                             lambda q, acc: acc + group(q), group(0))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


def _with_sequential_contract(monkeypatch, fn):
    """``fn()`` with the sequential sum in place of ``_contract`` (the
    kernels are jitted: their caches are cleared on both sides)."""
    jax.clear_caches()
    with monkeypatch.context() as m:
        m.setattr(pa_prims, "_contract", _sequential_contract)
        m.setattr(mm_kernel, "_contract", _sequential_contract)
        out = np.asarray(fn())
    jax.clear_caches()
    return out


@pytest.mark.parametrize("c_len,rows,g,fmt", MATMUL_CASES)
def test_matmul_tile_product_is_the_grouped_sequential_sum(c_len, rows, g,
                                                           fmt):
    rng = np.random.default_rng(c_len * 1000 + rows * 10 + g)
    n = 128
    a = _operand(rng, (1, rows, c_len), FMTS[fmt])
    b = _operand(rng, (1, c_len, n), FMTS[fmt])
    if rows > 1:
        # A row of zeros signed against B's first column: every product of
        # that output is -0.0, and so is their sum in any correct form.
        a = a.at[0, -1].set(jnp.copysign(jnp.zeros((), a.dtype), -b[0, :, 0]))
    got = mm_kernel.pam_matmul_batched(a, b, bm=rows, bn=n, bk=c_len, g=g,
                                       interpret=True, fmt_name=fmt)[0]
    prods = jax.jit(pam_value)(a[0][:, :, None], b[0][None, :, :])
    want = _grouped_sum(np.asarray(prods.astype(jnp.float32)), g)
    want = jnp.asarray(want).astype(FMTS[fmt])
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n_len,rows,g", [(64, 2, 8), (128, 8, 16),
                                          (128, 128, 8), (256, 64, 1),
                                          (640, 128, 16)])
def test_exact_grad_tile_product_is_the_grouped_sequential_sum(
        monkeypatch, n_len, rows, g):
    rng = np.random.default_rng(n_len + rows + g)
    k = 128
    a = _operand(rng, (1, rows, k), jnp.float32)
    b = _operand(rng, (1, k, n_len), jnp.float32)
    gr = _operand(rng, (1, rows, n_len), jnp.float32)

    def run():
        return mm_kernel.pam_exact_grad_a_batched(
            a, b, gr, bm=rows, bn=n_len, bk=k, g=g, interpret=True)

    np.testing.assert_array_equal(
        _bits(run()), _bits(_with_sequential_contract(monkeypatch, run)))


@pytest.mark.parametrize("bk,bq,g,fmt", [(64, 8, 8, "f32"),
                                         (128, 8, 8, "bf16"),
                                         (128, 128, 16, "f32"),
                                         (256, 64, 8, "f32"),
                                         (256, 128, 16, "bf16")])
def test_attention_value_product_is_the_grouped_sequential_sum(
        monkeypatch, bk, bq, g, fmt):
    rng = np.random.default_rng(bk + bq + g)
    s, t, dh = bq, bk, 64
    q = _operand(rng, (1, s, dh), FMTS[fmt])
    k = _operand(rng, (1, t, dh), FMTS[fmt])
    v = _operand(rng, (1, t, dh), FMTS[fmt])
    pos = jnp.arange(t, dtype=jnp.int32)

    def run():
        return pam_kernel.pam_flash_attention_fwd_bh(
            q, k, v, pos[t - s:], pos, causal=True, window=None,
            scale=0.125, bq=bq, bk=bk, g=g, interpret=True, fmt_name=fmt)[0]

    np.testing.assert_array_equal(
        _bits(run()), _bits(_with_sequential_contract(monkeypatch, run)))
