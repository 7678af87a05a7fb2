"""Property-based tests (hypothesis) for the system's core invariants."""
import math

import numpy as np
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core import pam_value, padiv_value, paexp2_value, palog2_value
from repro.core import floatbits as fb

# bounds must be exactly float32-representable for width=32 strategies
_LO = float(np.float32(1e-30))
_HI = float(np.float32(1e30))
finite = st.floats(min_value=_LO, max_value=_HI, allow_nan=False,
                   allow_infinity=False, width=32)
signed = st.floats(min_value=-_HI, max_value=_HI, allow_nan=False,
                   allow_infinity=False, width=32).filter(lambda x: abs(x) > _LO)


def f32(x):
    return jnp.asarray(np.float32(x))


@settings(max_examples=300, deadline=None)
@given(a=signed, b=signed)
def test_pam_relative_error_band(a, b):
    """PAM error is always in [-1/9, 0] relative to the true product."""
    p = float(pam_value(f32(a), f32(b)))
    true = float(np.float32(a)) * float(np.float32(b))
    fmax = float(np.finfo(np.float32).max)
    if not np.isfinite(true) or true == 0 or p == 0.0 or abs(true) > fmax:
        return  # over/underflow clamp region: the band only holds in-range
    rel = (p - true) / true
    assert -1 / 9 - 1e-6 <= rel <= 1e-6


@settings(max_examples=300, deadline=None)
@given(a=signed, b=signed)
def test_pam_commutative(a, b):
    assert float(pam_value(f32(a), f32(b))) == float(pam_value(f32(b), f32(a)))


@settings(max_examples=300, deadline=None)
@given(a=signed, b=signed)
def test_pam_sign_correct(a, b):
    p = float(pam_value(f32(a), f32(b)))
    if p != 0.0:
        assert math.copysign(1, p) == math.copysign(1, a) * math.copysign(1, b)


@settings(max_examples=300, deadline=None)
@given(a=signed, k=st.integers(min_value=-30, max_value=30))
def test_pam_by_pow2_exact(a, k):
    """Multiplication by a power of two is exact under PAM (Table 1 relies
    on this for multiplication-free exact derivatives)."""
    b = float(2.0 ** k)
    p = float(pam_value(f32(a), f32(b)))
    true = float(np.float32(np.float32(a) * np.float32(b)))
    if p == 0.0 or not np.isfinite(true):
        return
    assert p == true


@settings(max_examples=300, deadline=None)
@given(a=finite)
def test_log2_exp2_roundtrip(a):
    x = float(paexp2_value(palog2_value(f32(a))))
    # the f32 log-domain value E+M carries |E| into the integer part, losing
    # ~(2+|E|)*2^-24 of mantissa precision -> tolerance scales with |log2 a|
    tol = (4.0 + abs(math.log2(abs(a)))) * 2.0 ** -24
    assert abs(x - float(np.float32(a))) <= tol * abs(a)


@settings(max_examples=300, deadline=None)
@given(a=finite, b=finite)
def test_padiv_inverts_pam(a, b):
    p = float(pam_value(f32(a), f32(b)))
    fmax = float(np.finfo(np.float32).max)
    if p == 0.0 or not np.isfinite(p) or abs(p) >= fmax:
        return  # clamped products are not invertible
    back = float(padiv_value(f32(p), f32(b)))
    assert abs(back - float(np.float32(a))) <= 2e-6 * abs(a)


@settings(max_examples=200, deadline=None)
@given(a=signed, bits=st.integers(min_value=1, max_value=23))
def test_mantissa_round_properties(a, bits):
    r = float(fb.mantissa_round(f32(a), bits))
    # idempotent
    assert float(fb.mantissa_round(f32(r), bits)) == r
    # relative error bounded by half an ulp at `bits`
    if a != 0:
        assert abs(r - float(np.float32(a))) / abs(a) <= 2.0 ** (-bits) + 1e-9


@settings(max_examples=200, deadline=None)
@given(a=signed)
def test_palog2_is_monotone_in_magnitude(a):
    x = abs(float(np.float32(a)))
    l1 = float(palog2_value(f32(x)))
    l2 = float(palog2_value(f32(x * 2)))
    if np.isfinite(l2):
        assert l2 >= l1


@settings(max_examples=200, deadline=None)
@given(data=st.data())
def test_pam_monotone_for_positive(data):
    """For positive fixed b, pam(., b) is non-decreasing (piecewise affine
    with positive slopes)."""
    b = data.draw(finite)
    a1 = data.draw(finite)
    a2 = data.draw(finite)
    lo, hi = sorted([a1, a2])
    p_lo = float(pam_value(f32(lo), f32(b)))
    p_hi = float(pam_value(f32(hi), f32(b)))
    assert p_hi >= p_lo


# ---------------------------------------------------------------------------
# Flight-recorder tree fingerprint (resilience/recorder.py, DESIGN.md §8).
# ---------------------------------------------------------------------------

import jax  # noqa: E402
from repro.resilience.recorder import (combine_digests, leaf_digest,  # noqa: E402
                                       tree_digest, tree_leaf_digests)

_SHAPES = [(3,), (2, 4), (5,)]


def _tree_from(vals, dtypes):
    """A small {a, b/{c,d}, e} tree over fixed shapes with chosen dtypes."""
    a, c, d = [np.full(s, v, dt)
               for v, dt, s in zip(vals, dtypes, _SHAPES)]
    return {"a": jnp.asarray(a), "b": {"c": jnp.asarray(c),
                                       "d": jnp.asarray(d)}}


_leaf_floats = st.lists(st.floats(min_value=-1e6, max_value=1e6,
                                  allow_nan=False, width=32),
                        min_size=1, max_size=1)


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_tree_digest_order_independent(data):
    """The combined digest is a function of {path: leaf bits}, not of dict
    insertion order: rebuilding the same tree with keys inserted in a
    different order must not change it (leaves are salted by PATH crc32)."""
    vals = [data.draw(st.floats(min_value=-1e6, max_value=1e6,
                                allow_nan=False, width=32))
            for _ in range(3)]
    fwd = {"a": jnp.full(_SHAPES[0], np.float32(vals[0])),
           "b": {"c": jnp.full(_SHAPES[1], np.float32(vals[1])),
                 "d": jnp.full(_SHAPES[2], np.float32(vals[2]))}}
    rev = {}
    rev["b"] = {}
    rev["b"]["d"] = jnp.full(_SHAPES[2], np.float32(vals[2]))
    rev["b"]["c"] = jnp.full(_SHAPES[1], np.float32(vals[1]))
    rev["a"] = jnp.full(_SHAPES[0], np.float32(vals[0]))
    assert int(tree_digest(fwd)) == int(tree_digest(rev))
    # and the host-side combine mirrors the in-jit one
    assert int(tree_digest(fwd)) == combine_digests(
        [int(v) for v in np.asarray(tree_leaf_digests(fwd))])


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_tree_digest_mixed_dtypes_deterministic(data):
    """f32/bf16 mixed trees digest deterministically (same values+dtypes ->
    same digest; bf16 and f32 encodings of a value differ)."""
    v = data.draw(st.floats(min_value=-1e4, max_value=1e4, allow_nan=False,
                            width=32))
    mixed = {"a": jnp.full(_SHAPES[0], v, jnp.float32),
             "b": {"c": jnp.full(_SHAPES[1], v, jnp.bfloat16),
                   "d": jnp.full(_SHAPES[2], v, jnp.float32)}}
    again = {"a": jnp.full(_SHAPES[0], v, jnp.float32),
             "b": {"c": jnp.full(_SHAPES[1], v, jnp.bfloat16),
                   "d": jnp.full(_SHAPES[2], v, jnp.float32)}}
    assert int(tree_digest(mixed)) == int(tree_digest(again))
    all_f32 = {"a": mixed["a"],
               "b": {"c": mixed["b"]["c"].astype(jnp.float32),
                     "d": mixed["b"]["d"]}}
    if v != 0.0:   # 0.0 has identical (zero) bits in both encodings' words
        assert int(tree_digest(mixed)) != int(tree_digest(all_f32))


@settings(max_examples=50, deadline=None)
@given(data=st.data())
def test_single_bit_flip_changes_digest(data):
    """Acceptance property: ANY single bit flip in ANY leaf changes both
    that leaf's digest and the combined tree digest (fmix32 is a bijection,
    so this is structural, not probabilistic)."""
    vals = [data.draw(st.floats(min_value=-1e6, max_value=1e6,
                                allow_nan=False, width=32))
            for _ in range(3)]
    dtypes = data.draw(st.tuples(*[st.sampled_from([np.float32, "bfloat16"])
                                   for _ in range(3)]))
    import ml_dtypes
    dtypes = [np.dtype(ml_dtypes.bfloat16) if d == "bfloat16" else np.dtype(d)
              for d in dtypes]
    tree = _tree_from(vals, dtypes)
    leaf_i = data.draw(st.integers(min_value=0, max_value=2))
    base = np.asarray(tree_leaf_digests(tree))
    base_combined = int(tree_digest(tree))

    flat, treedef = jax.tree_util.tree_flatten(tree)
    arr = np.array(flat[leaf_i])
    bits = arr.reshape(-1).view(np.uint8)
    bit = data.draw(st.integers(min_value=0, max_value=bits.size * 8 - 1))
    bits[bit // 8] ^= np.uint8(1 << (bit % 8))
    flat[leaf_i] = jnp.asarray(arr)
    flipped = jax.tree_util.tree_unflatten(treedef, flat)

    got = np.asarray(tree_leaf_digests(flipped))
    assert int(got[leaf_i]) != int(base[leaf_i])
    others = [i for i in range(3) if i != leaf_i]
    assert all(int(got[i]) == int(base[i]) for i in others)
    assert int(tree_digest(flipped)) != base_combined


@settings(max_examples=50, deadline=None)
@given(salt=st.integers(min_value=0, max_value=0xFFFFFFFF),
       data=st.data())
def test_leaf_digest_position_sensitive(salt, data):
    """Swapping two distinct elements changes the digest (words are mixed
    with their index before the XOR fold — a plain XOR would be blind to
    transpositions)."""
    a = data.draw(st.floats(min_value=0.5, max_value=1e3, width=32))
    b = data.draw(st.floats(min_value=-1e3, max_value=-0.5, width=32))
    x = jnp.asarray(np.array([a, b, 0.25], np.float32))
    y = jnp.asarray(np.array([b, a, 0.25], np.float32))
    assert int(leaf_digest(x, salt)) != int(leaf_digest(y, salt))
