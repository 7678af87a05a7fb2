"""Randomised soundness properties for the abstract domains (DESIGN.md §10).

``tests/test_absint.py::test_interval_containment_seeded`` additionally
keeps a deterministic slice of the containment property in tier-1.

The property: for any concrete inputs drawn INSIDE the declared contract
(magnitudes in ``2^[E_LO, E_HI]``, either sign, exact zeros allowed), the
concrete PA result never escapes the output interval the interpreter
computed for that contract — interval transfer functions over-approximate,
never under-approximate.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.analysis import analyze_jaxpr  # noqa: E402
from repro.analysis import domains as D  # noqa: E402

pam = importlib.import_module("repro.core.pam")

E_LO, E_HI = -10, 3
RANGE = (-(2.0 ** E_HI), 2.0 ** E_HI)
MLO = 2.0 ** E_LO

# One value inside the declared contract: sign * 2^e * (1+f), or zero.
_contract_nonzero = st.builds(
    lambda s, e, f: s * float(np.float32(2.0 ** e * (1.0 + f))),
    st.sampled_from((-1.0, 1.0)),
    st.integers(min_value=E_LO, max_value=E_HI - 1),
    st.floats(min_value=0.0, max_value=0.999, allow_nan=False),
)
_contract_floats = st.one_of(st.just(0.0), _contract_nonzero)


def _out_interval(fn, n_args):
    args = [jnp.zeros((2,), jnp.float32)] * n_args
    rep = analyze_jaxpr(jax.make_jaxpr(fn)(*args),
                        float_range=RANGE, float_mlo=MLO)
    v = rep.out_vals[0]
    return float(v.lo), float(v.hi)


_PAM_IV = None
_PADIV_IV = None
_EXP2_IV = None


def _ivs():
    # Analyze once per process, not once per Hypothesis example.
    global _PAM_IV, _PADIV_IV, _EXP2_IV
    if _PAM_IV is None:
        _PAM_IV = _out_interval(lambda a, b: pam.pam_value(a, b), 2)
        _PADIV_IV = _out_interval(lambda a, b: pam.padiv_value(a, b), 2)
        _EXP2_IV = _out_interval(lambda a, b: pam.paexp2_value(a), 2)
    return _PAM_IV, _PADIV_IV, _EXP2_IV


@settings(max_examples=200, deadline=None)
@given(a=_contract_floats, b=_contract_floats)
def test_pam_value_never_escapes_interval(a, b):
    lo, hi = _ivs()[0]
    got = float(pam.pam_value(jnp.float32(a), jnp.float32(b)))
    assert lo - 1e-9 <= got <= hi + 1e-9, (a, b, got, lo, hi)


@settings(max_examples=200, deadline=None)
@given(a=_contract_floats, b=_contract_nonzero)
def test_padiv_value_never_escapes_interval(a, b):
    lo, hi = _ivs()[1]
    got = float(pam.padiv_value(jnp.float32(a), jnp.float32(b)))
    assert lo - 1e-9 <= got <= hi + 1e-9, (a, b, got, lo, hi)


@settings(max_examples=200, deadline=None)
@given(a=_contract_floats)
def test_paexp2_value_never_escapes_interval(a):
    lo, hi = _ivs()[2]
    got = float(pam.paexp2_value(jnp.float32(a)))
    assert lo - 1e-9 <= got <= hi + 1e-9, (a, got, lo, hi)


@settings(max_examples=200, deadline=None)
@given(a=_contract_floats, b=_contract_floats)
def test_measured_pam_error_inside_declared_band(a, b):
    # The analytic [-1/9, 0] relative band holds pointwise for any
    # in-contract operands (the certificate's base constant is sound).
    if a == 0.0 or b == 0.0:
        return
    got = float(pam.pam_value(jnp.float32(a), jnp.float32(b)))
    true = float(np.float64(a) * np.float64(b))
    rel = got / true - 1.0
    assert -D.EPS_PAM_WORST - 1e-6 <= rel <= 1e-6, (a, b, rel)
