"""Abstract interpreter over jaxprs for PA range safety and error
certificates (layer 3 of the analysis subsystem, DESIGN.md §10).

Two questions, one pass:

1. **Range safety** — given declared input ranges, can any PAM/PADIV
   magnitude add/sub reach the int32 failure exponents? Sites are
   recognised *semantically* in the bit domain: an int tagged as a
   float's bit pattern, masked with MAG_MASK, becomes a :class:`MagExpr`
   linear form; when two magnitude terms merge in a single add/sub whose
   exact constant offset matches the PAM (``-BIAS``) or PADIV
   (``+BIAS``) fold, that equation IS a PA site, wherever it was inlined
   from (``core/pam.py`` values under grad, ``kernels/pa_prims.py``
   scalar helpers, the bias-folded grouped tile product). Each site gets
   f32-exponent bounds of its decoded result and a verdict: ``overflow``
   (e >= 128, guarded ops saturate to MAX_FINITE), ``wrap`` (e >= 129 on
   an UNGUARDED site — only the grouped tile product lacks the
   ``mag < -BIAS`` rescue — silently flushing the product to zero), and
   ``denormal`` (e <= -127, nonzero x nonzero flushed to zero). This
   upgrades ``contract.py``'s literal-only ``pam_wrap_risk_literal`` into
   a reachability proof with the same frame-chain provenance.

2. **Error certificates** — worst-case and expected (signed mean)
   relative error of every float output versus the exact-multiplication
   program, priced per mantissa width (f32/f16/bf16 in one pass).
   PAM/PADIV error composes at the recognised site from its operands'
   certificates plus the op band (constants in ``analysis/domains.py``,
   mirrored in ``kernels/pa_prims.py``); PAEXP2/PALOG2 are inlined bit
   dances, so their error is *injected* at the instance entry equation,
   located by ``source_info`` frame anchors (``paexp2_value``/
   ``_paexp2``/``palog2_value``/``_palog2``) — pasqrt composes from the
   two. Additions use the documented no-cancellation assumption; scanned
   bodies extrapolate linearly over the trip count.

What a certificate does NOT promise: anything about inf/nan inputs
(out of contract, DESIGN.md §2.3), cancellation-heavy sums, or inputs
outside the declared ranges. Loop-carried values are widened to the
activation-ceiling contract (``+-2^32``, runtime-enforced by the
resilience sentinels) rather than to infinity — assume-guarantee, not
unsoundness: a certificate is conditional on that contract holding.

Unknown primitives never abort the pass: their float outputs fall to the
contract hull with joined input error and are counted in ``opaque``
(set ``ABSINT_STRICT=1`` to re-raise while developing new handlers).
"""
from __future__ import annotations

import math
import os
from collections import Counter
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import floatbits as fb
from .audit import _eqn_frames
from .jaxpr_types import ClosedJaxpr, is_drop_var, is_literal
from .domains import (
    AbsVal, BIG, DEFAULT_WIDTHS, EPS_EXP2_MEAN, EPS_EXP2_WORST,
    EPS_LOG2_ABS_MEAN, EPS_LOG2_ABS_WORST, EPS_PADIV_MEAN, EPS_PADIV_WORST,
    EPS_PAM_MEAN, EPS_PAM_WORST, Err, FLUSH_MIN, IntVal, LN2, MagExpr,
    PaFlow, PamSite, Witness, _EXP_CAP, bool_int, const_val, decode_mag,
    encode_mag, err_zero, int_const, mag_bounds_of, make_val, quant_eps,
    top_float, top_int,
)

__all__ = ["AnalysisReport", "analyze_jaxpr", "default_inputs",
           "ACTIVATION_CEIL"]

# Loop-widening / opaque-fallback hull: the activation-ceiling contract.
ACTIVATION_CEIL = 2.0 ** 32
# Error-extrapolation trip count assumed for while loops (no static length).
WHILE_ERR_ITERS = 4096
# Conservative device-count bound for shard_map collectives.
NDEV_BOUND = 64
_FIXPOINT_ITERS = 4

_SIGN_I = int(fb.SIGN_MASK)          # -2^31
_MAG_I = int(fb.MAG_MASK)
_MAN_I = int(fb.MAN_MASK)
_BIAS_I = int(fb.BIAS_SHIFTED)
_MINNORM_I = int(fb.MIN_NORM)
_MAXFIN_I = int(fb.MAX_FINITE)
_ZSENT_I = int(fb.PAM_ZERO_SENTINEL)
_I32_LO, _I32_HI = -(1 << 31), (1 << 31) - 1

_EXP2_ANCHORS = frozenset({"paexp2_value", "_paexp2"})
_LOG2_ANCHORS = frozenset({"palog2_value", "_palog2"})

# Prims _resolve walks through when chasing a var to its defining event.
_RESOLVE_PASS = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "copy",
    "convert_element_type", "stop_gradient", "device_put"})


def _isnan(x: float) -> bool:
    return x != x


def _flo(x: float) -> float:
    return -math.inf if _isnan(x) else x


def _fhi(x: float) -> float:
    return math.inf if _isnan(x) else x


def _clampm(x: float) -> float:
    if _isnan(x):
        return BIG
    return max(-BIG, min(x, BIG))


def _cap(x: float) -> float:
    if _isnan(x):
        return BIG
    return min(x, BIG)


def _prod_bounds(a: AbsVal, b: AbsVal) -> Tuple[float, float]:
    cands = []
    for xa in (a.lo, a.hi):
        for xb in (b.lo, b.hi):
            p = xa * xb
            if _isnan(p):           # 0 * inf
                return -math.inf, math.inf
            cands.append(p)
    return min(cands), max(cands)


def _shape_n(shape, axes) -> int:
    n = 1
    for i in axes:
        n *= int(shape[i])
    return max(n, 1)


def _srl32(a: int, s: int) -> int:
    """int32 logical right shift on a python int."""
    return (int(a) & 0xFFFFFFFF) >> int(s)


# ---------------------------------------------------------------------------
# Witness concrete-evaluation table (numpy semantics per primitive).
# ---------------------------------------------------------------------------

def _np_of(aval, v):
    return np.dtype(aval.dtype).type(v)


_WIT_EVAL = {
    "add": lambda a, b: a + b, "add_any": lambda a, b: a + b,
    "sub": lambda a, b: a - b, "mul": lambda a, b: a * b,
    "div": lambda a, b: a / b, "neg": lambda a: -a,
    "abs": lambda a: abs(a), "sign": np.sign,
    "max": np.maximum, "min": np.minimum,
    "floor": np.floor, "ceil": np.ceil, "round": np.round,
    "and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor,
    "not": np.bitwise_not,
    "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
    "is_finite": np.isfinite,
    "shift_left": lambda a, b: a << b,
    "shift_right_arithmetic": lambda a, b: a >> b,
    "shift_right_logical": _srl32,
    "clamp": lambda lo, x, hi: np.minimum(np.maximum(x, lo), hi),
    "exp2": np.exp2, "exp": np.exp, "sqrt": np.sqrt,
    "stop_gradient": lambda a: a, "copy": lambda a: a,
}


# ---------------------------------------------------------------------------
# Concrete array -> abstract value.
# ---------------------------------------------------------------------------

def _is_float_dtype(dtype) -> bool:
    try:
        import jax.numpy as jnp
        return jnp.issubdtype(np.dtype(dtype), np.floating)
    except TypeError:
        return False


def _is_int_dtype(dtype) -> bool:
    try:
        import jax.numpy as jnp
        d = np.dtype(dtype)
        return jnp.issubdtype(d, np.integer) or d == np.bool_
    except TypeError:
        return False


def val_of_array(x, nw: int):
    """Exact abstract value of a concrete array (trace constants)."""
    try:
        arr = np.asarray(x)
    except Exception:
        return top_int(nw)
    if arr.size == 0:
        return int_const(0, nw) if not _is_float_dtype(arr.dtype) \
            else const_val(0.0, nw)
    if _is_float_dtype(arr.dtype):
        a64 = arr.astype(np.float64)
        if np.isnan(a64).any():
            return top_float(nw)
        lo, hi = float(a64.min()), float(a64.max())
        nz = np.abs(a64[a64 != 0.0])
        mlo = float(nz.min()) if nz.size else math.inf
        wit = Witness(lo, None) if lo == hi else None
        return AbsVal(lo, hi, mlo, bool((a64 == 0.0).any()),
                      err_zero(nw), wit)
    if _is_int_dtype(arr.dtype):
        a64 = arr.astype(np.int64)
        lo, hi = int(a64.min()), int(a64.max())
        pos = a64[a64 > 0]
        wit = Witness(float(lo), None) if lo == hi else None
        return IntVal(lo, hi, err_zero(nw),
                      mlo=int(pos.min()) if pos.size else None, wit=wit)
    return top_int(nw)


# ---------------------------------------------------------------------------
# The interpreter.
# ---------------------------------------------------------------------------

class Interp:
    def __init__(self, widths=DEFAULT_WIDTHS):
        self.widths = tuple(widths)
        self.nw = len(self.widths)
        ms = [m for _, m in self.widths]
        self.eps_pam = tuple(EPS_PAM_WORST + quant_eps(m) for m in ms)
        self.eps_padiv = tuple(EPS_PADIV_WORST + quant_eps(m) for m in ms)
        self.eps_exp2 = tuple(EPS_EXP2_WORST + quant_eps(m) for m in ms)
        self.eps_log2 = tuple(EPS_LOG2_ABS_WORST + quant_eps(m) for m in ms)
        self.env: Dict = {}
        self.defs: Dict = {}
        self.alias: Dict = {}
        self.sites: Dict[int, PamSite] = {}
        self.opaque: Counter = Counter()
        self.notes: set = set()
        self.n_eqns = 0
        self.ctx: List[str] = []
        self._worigin = 1
        self._injected: set = set()
        self._anchor_in: Dict = {}
        self._strict = bool(os.environ.get("ABSINT_STRICT"))

    # -- env --------------------------------------------------------------
    def read(self, atom):
        if is_literal(atom):
            return val_of_array(atom.val, self.nw)
        v = self.env.get(atom)
        if v is None:
            v = self._top_for(getattr(atom, "aval", None))
            self.env[atom] = v
        return v

    def _top_for(self, aval):
        dt = getattr(aval, "dtype", None)
        if dt is not None and _is_float_dtype(dt):
            return top_float(self.nw)
        return top_int(self.nw)

    def _out_float(self, eqn, i=0) -> bool:
        aval = getattr(eqn.outvars[i], "aval", None)
        dt = getattr(aval, "dtype", None)
        return dt is not None and _is_float_dtype(dt)

    def _hull(self, err: Err) -> AbsVal:
        return make_val(-ACTIVATION_CEIL, ACTIVATION_CEIL, mlo=FLUSH_MIN,
                        zero=True, err=err, nw=self.nw)

    def _join_errs(self, vals) -> Err:
        e = err_zero(self.nw)
        for v in vals:
            e = e.join(v.err)
        return e

    # -- run --------------------------------------------------------------
    def run_closed(self, closed, in_vals):
        jaxpr = closed.jaxpr
        consts = [val_of_array(c, self.nw) for c in closed.consts]
        return self.run(jaxpr, in_vals, consts)

    def run(self, jaxpr, in_vals, const_vals=()):
        for v, a in zip(jaxpr.constvars, const_vals):
            self.env[v] = a
        for v, a in zip(jaxpr.invars, in_vals):
            self.env[v] = a
        for eqn in jaxpr.eqns:
            self.n_eqns += 1
            for ov in eqn.outvars:
                if not is_drop_var(ov):
                    self.defs[ov] = eqn
            self._eqn(eqn)
        return [self.read(v) for v in jaxpr.outvars]

    def _bind_outs(self, eqn, outs):
        for ov, val in zip(eqn.outvars, outs):
            if not is_drop_var(ov):
                self.env[ov] = self._ceil_contract(val)

    def _ceil_contract(self, val):
        """Activation-ceiling contract (DESIGN.md §10): every value a
        program PRODUCES is assumed within ±2^32 — the same ceiling the
        runtime exponent sentinels (resilience/detectors.py) enforce and
        the widening hull uses. Without it, interval composition through
        stacked matmul layers inflates exponents past any threshold and
        every deep target reports vacuous wrap. Declared INPUTS are bound
        directly in ``run`` and stay unclamped, so seeded-violation
        ranges still reach the PA sites un-narrowed."""
        if not isinstance(val, AbsVal):
            return val
        if val.lo >= -ACTIVATION_CEIL and val.hi <= ACTIVATION_CEIL:
            return val
        self.notes.add("activation_ceil_applied")
        lo = max(min(val.lo, ACTIVATION_CEIL), -ACTIVATION_CEIL)
        hi = min(max(val.hi, -ACTIVATION_CEIL), ACTIVATION_CEIL)
        wit = val.wit
        if wit is not None and not (lo <= wit.val <= hi):
            wit = None
        return replace(val, lo=lo, hi=hi, mlo=min(val.mlo, ACTIVATION_CEIL),
                       wit=wit)

    def _eqn(self, eqn):
        name = eqn.primitive.name
        handler = _HANDLERS.get(name)
        if handler is None:
            self._opaque(eqn, note=True)
        else:
            try:
                outs = handler(self, eqn)
            except Exception:
                if self._strict:
                    raise
                self._opaque(eqn, note=True)
            else:
                self._bind_outs(eqn, outs)
                self._witness(eqn, name)
                ak = self._anchor(eqn)
                if ak is not None:
                    # Inside a paexp2/palog2 dance the instance-entry
                    # injection already prices the WHOLE op; per-eqn
                    # transfer functions would double-count, so errors
                    # pass through join-only until the dance exits.
                    je = self._join_errs([self.read(v) for v in eqn.invars])
                    for ov in eqn.outvars:
                        if is_drop_var(ov):
                            continue
                        v = self.env.get(ov)
                        if v is not None:
                            self.env[ov] = replace(v, err=je)
        self._maybe_inject(eqn)

    def _opaque(self, eqn, note=False):
        self.opaque[eqn.primitive.name] += 1
        if note:
            self.notes.add(f"opaque:{eqn.primitive.name}")
        err = self._join_errs([self.read(v) for v in eqn.invars])
        outs = []
        for i in range(len(eqn.outvars)):
            outs.append(self._hull(err) if self._out_float(eqn, i)
                        else replace(top_int(self.nw), err=err))
        self._bind_outs(eqn, outs)

    # -- central witness evaluation ---------------------------------------
    def _witness(self, eqn, name):
        if len(eqn.outvars) != 1 or is_drop_var(eqn.outvars[0]):
            return
        cur = self.env.get(eqn.outvars[0])
        if cur is None or cur.wit is not None:
            return
        if name == "select_n":
            self._wit_select(eqn, cur)
            return
        fn = _WIT_EVAL.get(name)
        if fn is None:
            return
        vals = [self.read(v) for v in eqn.invars]
        if not all(v.wit is not None for v in vals):
            return
        axes, origin = None, 0
        for v in vals:
            w = v.wit
            if w.axes is not None:
                if axes is not None and (axes != w.axes
                                         or origin != w.origin):
                    return
                axes, origin = w.axes, w.origin
        try:
            with np.errstate(all="ignore"):
                args = [_np_of(iv.aval, v.wit.val)
                        for iv, v in zip(eqn.invars, vals)]
                if name == "shift_right_logical":
                    out = _srl32(int(args[0]), int(args[1]))
                    if out > _I32_HI:
                        out -= 1 << 32
                else:
                    out = fn(*args)
                oval = float(np.asarray(out).item())
        except Exception:
            return
        if _isnan(oval):
            return
        self.env[eqn.outvars[0]] = replace(cur,
                                           wit=Witness(oval, axes, origin))

    def _wit_select(self, eqn, cur):
        vals = [self.read(v) for v in eqn.invars]
        pred = vals[0]
        if pred.wit is None:
            return
        idx = int(pred.wit.val)
        if not (0 <= idx < len(vals) - 1):
            return
        case = vals[1 + idx]
        if case.wit is None or not pred.wit.compatible(case.wit):
            return
        axes, origin = pred.wit.merge_meta(case.wit)
        self.env[eqn.outvars[0]] = replace(
            cur, wit=Witness(case.wit.val, axes, origin))

    # -- def-chain resolution ---------------------------------------------
    def _resolve(self, atom):
        if is_literal(atom):
            return atom, None
        v = atom
        for _ in range(64):
            while v in self.alias:
                v = self.alias[v]
            eqn = self.defs.get(v)
            if eqn is None:
                return v, None
            name = eqn.primitive.name
            if name in _RESOLVE_PASS:
                iv = eqn.invars[0]
                if is_literal(iv):
                    return v, eqn
                v = iv
                continue
            if name in ("jit", "pjit"):
                try:
                    idx = list(eqn.outvars).index(v)
                    v = eqn.params["jaxpr"].jaxpr.outvars[idx]
                    continue
                except Exception:
                    return v, eqn
            return v, eqn
        return v, None

    # -- frame anchors + exp2/log2 error injection -------------------------
    def _anchor(self, eqn):
        try:
            tb = eqn.source_info.traceback
            frames = tb.frames if tb is not None else ()
        except Exception:
            return None
        for i, f in enumerate(frames):
            fn = f.function_name
            if fn in _EXP2_ANCHORS or fn in _LOG2_ANCHORS:
                kind = "exp2" if fn in _EXP2_ANCHORS else "log2"
                chain = tuple((g.file_name, g.line_num)
                              for g in frames[i + 1:i + 9])
                return kind, (fn, chain, tuple(self.ctx))
        return None

    def _maybe_inject(self, eqn):
        ak = self._anchor(eqn)
        if ak is None:
            return
        kind, key = ak
        if key in self._injected:
            return
        fin = None
        for iv in eqn.invars:
            if is_literal(iv):
                continue            # clip bounds etc. are not the input
            aval = getattr(iv, "aval", None)
            if aval is not None and getattr(aval, "dtype", None) is not None \
                    and _is_float_dtype(aval.dtype):
                fin = self.read(iv)
                break
        if fin is None or not isinstance(fin, AbsVal):
            return
        self._anchor_in[key] = fin
        inj = self._inj_exp2(fin) if kind == "exp2" else self._inj_log2(fin)
        self._injected.add(key)
        for ov in eqn.outvars:
            if is_drop_var(ov):
                continue
            v = self.env.get(ov)
            if v is not None:
                self.env[ov] = replace(v, err=v.err.join(inj))

    def _inj_exp2(self, a: AbsVal) -> Err:
        amax = min(a.mhi, 16384.0)
        rel, mrel = [], []
        for i in range(self.nw):
            d = min(_EXP_CAP, amax * a.err.rel[i] + a.err.abs_[i])
            rel.append(_cap((1.0 + self.eps_exp2[i]) * 2.0 ** d - 1.0))
            dm = max(-_EXP_CAP, min(_EXP_CAP,
                                    amax * a.err.mrel[i] + a.err.mabs[i]))
            mrel.append(_clampm((1.0 + EPS_EXP2_MEAN + quant_eps(
                self.widths[i][1]) * 0.5) * 2.0 ** dm - 1.0))
        z = (0.0,) * self.nw
        return Err(tuple(rel), z, tuple(mrel), z)

    def _inj_log2(self, a: AbsVal) -> Err:
        mlo = max(a.mlo, FLUSH_MIN) if not math.isinf(a.mlo) else 1.0
        ab, mab = [], []
        for i in range(self.nw):
            ab.append(_cap(self.eps_log2[i] + a.err.rel[i] / LN2
                           + a.err.abs_[i] / (mlo * LN2)))
            mab.append(_clampm(EPS_LOG2_ABS_MEAN + a.err.mrel[i] / LN2
                               + a.err.mabs[i] / (mlo * LN2)))
        z = (0.0,) * self.nw
        return Err(z, tuple(ab), z, tuple(mab))

    # -- PA site emission --------------------------------------------------
    def _emit_site(self, eqn, expr: MagExpr, base_err: Err) -> IntVal:
        ilo = sum((0 if p.zero else mag_bounds_of(p)[0]) for p in expr.pos) \
            - sum(mag_bounds_of(n)[1] for n in expr.neg) + expr.off_lo
        ihi = sum(mag_bounds_of(p)[1] for p in expr.pos) \
            - sum((0 if n.zero else mag_bounds_of(n)[0])
                  for n in expr.neg) + expr.off_hi
        out = IntVal(int(ilo), int(ihi), base_err, mag=expr)
        P, N = len(expr.pos), len(expr.neg)
        if expr.nterms != 2 or expr.off_lo != expr.off_hi:
            return out
        want = (1 - P + N) * _BIAS_I
        if expr.off_lo != want:
            return out
        if P == 2:
            kind, a, b = "pam", expr.pos[0], expr.pos[1]
        elif P == 1 and N == 1:
            kind, a, b = "padiv", expr.pos[0], expr.neg[0]
        else:
            return out
        e_lo, e_hi = expr.e_bounds()
        site = self.sites.get(id(eqn))
        if site is None:
            frames = _eqn_frames(eqn)
            site = PamSite(kind=kind, site=frames[0] if frames else "?",
                           frames=tuple(frames), context=tuple(self.ctx),
                           e_lo=e_lo, e_hi=e_hi)
            self.sites[id(eqn)] = site
        else:
            site.e_lo = min(site.e_lo, e_lo)
            site.e_hi = max(site.e_hi, e_hi)
        err = self._pam_err(a, b) if kind == "pam" else self._padiv_err(a, b)
        flow = PaFlow(kind=kind, err=err, site=site,
                      mhi_prod=_cap(a.mhi * b.mhi))
        return replace(out, err=err, pa=flow)

    def _pam_err(self, a: AbsVal, b: AbsVal) -> Err:
        rel, ab, mrel, mab = [], [], [], []
        for i in range(self.nw):
            rel.append(_cap((1 + a.err.rel[i]) * (1 + b.err.rel[i])
                            * (1 + self.eps_pam[i]) - 1))
            ab.append(_cap(a.err.abs_[i] * b.mhi * 1.2
                           + b.err.abs_[i] * a.mhi * 1.2))
            mrel.append(_clampm((1 + a.err.mrel[i]) * (1 + b.err.mrel[i])
                                * (1 + EPS_PAM_MEAN) - 1))
            mab.append(_clampm(a.err.mabs[i] * b.mhi * 1.2
                               + b.err.mabs[i] * a.mhi * 1.2))
        return Err(tuple(rel), tuple(ab), tuple(mrel), tuple(mab))

    def _padiv_err(self, a: AbsVal, b: AbsVal) -> Err:
        bmlo = max(b.mlo, FLUSH_MIN) if not math.isinf(b.mlo) else 1.0
        rel, ab, mrel, mab = [], [], [], []
        for i in range(self.nw):
            rb = min(b.err.rel[i], 0.5)
            rel.append(_cap((1 + a.err.rel[i]) / (1 - rb)
                            * (1 + self.eps_padiv[i]) - 1))
            ab.append(_cap(a.err.abs_[i] / bmlo * 1.2
                           + b.err.abs_[i] * a.mhi / (bmlo * bmlo) * 1.2))
            mrel.append(_clampm((1 + a.err.mrel[i]) * (1 + EPS_PADIV_MEAN)
                                - 1))
            mab.append(_clampm(a.err.mabs[i] / bmlo * 1.2))
        return Err(tuple(rel), tuple(ab), tuple(mrel), tuple(mab))


# ---------------------------------------------------------------------------
# Handlers. Each takes (interp, eqn) and returns a list of abstract outputs.
# ---------------------------------------------------------------------------

def _as_float(v, nw):
    if isinstance(v, AbsVal):
        return v
    return make_val(float(v.lo), float(v.hi), err=v.err, nw=nw)


def _as_int(v, nw):
    if isinstance(v, IntVal):
        return v
    lo = int(max(min(v.lo, 2 ** 62), -(2 ** 62))) if not _isnan(v.lo) \
        else -(2 ** 62)
    hi = int(max(min(v.hi, 2 ** 62), -(2 ** 62))) if not _isnan(v.hi) \
        else 2 ** 62
    return IntVal(lo, hi, v.err)


def _rd(it, eqn):
    return [it.read(v) for v in eqn.invars]


def _bits_of_float(v: float) -> int:
    return int(np.float32(v).view(np.int32))


def _relmax_rule(it, eqn, xa):
    """sub(x, broadcast(reduce_max(x, axes))) -> [lo-hi, 0] with an
    attained-zero witness (the softmax shift)."""
    xatom, matom = eqn.invars
    if is_literal(xatom) or is_literal(matom):
        return None
    mv, md = it._resolve(matom)
    if md is None or md.primitive.name != "reduce_max":
        return None
    op = md.invars[0]
    if is_literal(op):
        return None
    ov, _ = it._resolve(op)
    xv, _ = it._resolve(xatom)
    if xv is not ov:
        return None
    axes = tuple(int(a) for a in md.params.get("axes", ()))
    if not axes:
        return None
    lo = _flo(xa.lo - xa.hi)
    origin = it._worigin
    it._worigin += 1
    merr = it.read(matom).err
    return make_val(min(lo, 0.0), 0.0, mlo=FLUSH_MIN, zero=True,
                    err=xa.err.through_add(merr),
                    wit=Witness(0.0, axes, origin), nw=it.nw)


def _int_addsub(it, eqn, x, y, sub):
    err = x.err.join(y.err)
    ex = x.mag
    ey = y.mag.negate() if (sub and y.mag is not None) else y.mag
    expr = None
    if ex is not None and ey is not None:
        expr = MagExpr(ex.pos + ey.pos, ex.neg + ey.neg,
                       ex.off_lo + ey.off_lo, ex.off_hi + ey.off_hi)
    elif ex is not None:
        d_lo, d_hi = (-y.hi, -y.lo) if sub else (y.lo, y.hi)
        expr = MagExpr(ex.pos, ex.neg, ex.off_lo + d_lo, ex.off_hi + d_hi)
    elif ey is not None:
        expr = MagExpr(ey.pos, ey.neg, ey.off_lo + x.lo, ey.off_hi + x.hi)
    elif y.mag is not None and not sub:
        expr = MagExpr(y.mag.pos, y.mag.neg,
                       y.mag.off_lo + x.lo, y.mag.off_hi + x.hi)
    if expr is not None:
        return it._emit_site(eqn, expr, err)
    if sub:
        lo, hi = x.lo - y.hi, x.hi - y.lo
    else:
        lo, hi = x.lo + y.lo, x.hi + y.hi
    return IntVal(lo, hi, err, pa=x.pa or y.pa)


def _h_addsub(it, eqn):
    name = eqn.primitive.name
    x, y = _rd(it, eqn)
    if not it._out_float(eqn):
        return [_int_addsub(it, eqn, _as_int(x, it.nw), _as_int(y, it.nw),
                            name == "sub")]
    xa, ya = _as_float(x, it.nw), _as_float(y, it.nw)
    if name == "sub":
        rel = _relmax_rule(it, eqn, xa)
        if rel is not None:
            return [rel]
        lo, hi = _flo(xa.lo - ya.hi), _fhi(xa.hi - ya.lo)
    else:
        lo, hi = _flo(xa.lo + ya.lo), _fhi(xa.hi + ya.hi)
    return [make_val(lo, hi, err=xa.err.through_add(ya.err), nw=it.nw)]


def _mul_err(it, x, y):
    rel, ab, mrel, mab = [], [], [], []
    for i in range(it.nw):
        rel.append(_cap((1 + x.err.rel[i]) * (1 + y.err.rel[i]) - 1))
        ab.append(_cap(x.err.abs_[i] * y.mhi + y.err.abs_[i] * x.mhi
                       + x.err.abs_[i] * y.err.abs_[i]))
        mrel.append(_clampm((1 + x.err.mrel[i]) * (1 + y.err.mrel[i]) - 1))
        mab.append(_clampm(x.err.mabs[i] * y.mhi + y.err.mabs[i] * x.mhi))
    return Err(tuple(rel), tuple(ab), tuple(mrel), tuple(mab))


def _h_mul(it, eqn):
    x, y = _rd(it, eqn)
    if not it._out_float(eqn):
        xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
        cands = [xi.lo * yi.lo, xi.lo * yi.hi, xi.hi * yi.lo, xi.hi * yi.hi]
        return [IntVal(min(cands), max(cands), xi.err.join(yi.err))]
    xa, ya = _as_float(x, it.nw), _as_float(y, it.nw)
    lo, hi = _prod_bounds(xa, ya)
    if math.isinf(xa.mlo) or math.isinf(ya.mlo):
        mlo = math.inf
    else:
        mlo = max(xa.mlo * ya.mlo, 5e-324)
    zero = xa.zero or ya.zero
    return [AbsVal(lo, hi, mlo, zero, _mul_err(it, xa, ya), None)]


def _h_div(it, eqn):
    x, y = _rd(it, eqn)
    if not it._out_float(eqn):
        xi = _as_int(x, it.nw)
        return [IntVal(min(xi.lo, -abs(xi.lo)), max(xi.hi, abs(xi.hi)),
                       xi.err.join(_as_int(y, it.nw).err))]
    xa, ya = _as_float(x, it.nw), _as_float(y, it.nw)
    ymlo = max(ya.mlo, 5e-324) if not math.isinf(ya.mlo) else 1.0
    rel, ab, mrel, mab = [], [], [], []
    for i in range(it.nw):
        ry = min(ya.err.rel[i], 0.5)
        rel.append(_cap((1 + xa.err.rel[i]) / (1 - ry) - 1))
        ab.append(_cap(xa.err.abs_[i] / ymlo
                       + ya.err.abs_[i] * xa.mhi / (ymlo * ymlo)))
        mrel.append(_clampm((1 + xa.err.mrel[i]) / (1 - min(max(
            ya.err.mrel[i], -0.5), 0.5)) - 1))
        mab.append(_clampm(xa.err.mabs[i] / ymlo))
    err = Err(tuple(rel), tuple(ab), tuple(mrel), tuple(mab))
    mlo = max(xa.mlo / max(ya.mhi, 5e-324), 5e-324) \
        if not math.isinf(xa.mlo) else math.inf
    if ya.zero or (ya.lo <= 0.0 <= ya.hi):
        m = xa.mhi / ymlo
        return [AbsVal(-max(m, abs(xa.lo) / ymlo), max(m, abs(xa.hi) / ymlo)
                       if not math.isinf(m) else math.inf,
                       mlo, True, err, None)]
    cands = []
    for xv in (xa.lo, xa.hi):
        for yv in (ya.lo, ya.hi):
            q = xv / yv
            if _isnan(q):
                return [AbsVal(-math.inf, math.inf, mlo, xa.zero, err, None)]
            cands.append(q)
    return [AbsVal(min(cands), max(cands), mlo, xa.zero, err, None)]


def _h_maxmin(it, eqn):
    name = eqn.primitive.name
    x, y = _rd(it, eqn)
    err = x.err.join(y.err)
    if not it._out_float(eqn):
        xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
        if name == "max":
            lo, hi = max(xi.lo, yi.lo), max(xi.hi, yi.hi)
        else:
            lo, hi = min(xi.lo, yi.lo), min(xi.hi, yi.hi)
        # Min positive value of max/min(a, b): only claimable when known
        # for BOTH operands (the extremum lands on either one).
        mlo = min(xi.mlo, yi.mlo) \
            if xi.mlo is not None and yi.mlo is not None else None
        return [IntVal(lo, hi, err, mlo=mlo, pa=xi.pa or yi.pa)]
    xa, ya = _as_float(x, it.nw), _as_float(y, it.nw)
    if name == "max":
        lo, hi = max(xa.lo, ya.lo), max(xa.hi, ya.hi)
    else:
        lo, hi = min(xa.lo, ya.lo), min(xa.hi, ya.hi)
    return [make_val(lo, hi, mlo=min(xa.mlo, ya.mlo),
                     zero=xa.zero or ya.zero, err=err, nw=it.nw)]


def _h_clamp(it, eqn):
    lo_v, x, hi_v = _rd(it, eqn)
    if not it._out_float(eqn):
        xi = _as_int(x, it.nw)
        l, h = _as_int(lo_v, it.nw), _as_int(hi_v, it.nw)
        return [IntVal(max(xi.lo, l.lo), min(xi.hi, h.hi),
                       xi.err, mlo=xi.mlo, pa=xi.pa)]
    xa = _as_float(x, it.nw)
    l, h = _as_float(lo_v, it.nw), _as_float(hi_v, it.nw)
    lo = min(max(xa.lo, l.lo), h.hi)
    hi = min(max(xa.hi, l.lo), h.hi)
    return [make_val(lo, hi, zero=xa.zero or (lo <= 0.0 <= hi),
                     err=xa.err.join(l.err).join(h.err), nw=it.nw)]


def _h_unary_float(it, eqn):
    name = eqn.primitive.name
    x = _as_float(it.read(eqn.invars[0]), it.nw)
    nw = it.nw
    if name == "neg":
        return [AbsVal(-x.hi, -x.lo, x.mlo, x.zero, x.err, None)]
    if name == "abs":
        return [AbsVal(0.0 if x.zero or x.lo <= 0 <= x.hi
                       else x.mlo, x.mhi, x.mlo, x.zero, x.err, None)]
    if name == "sign":
        return [make_val(-1.0, 1.0, err=err_zero(nw), nw=nw)]
    if name in ("floor", "ceil", "round"):
        f = math.floor if name == "floor" else (
            math.ceil if name == "ceil" else round)
        lo = f(x.lo) if not math.isinf(x.lo) else x.lo
        hi = f(x.hi) if not math.isinf(x.hi) else x.hi
        ab = tuple(_cap(a + x.mhi * r + 1.0)
                   for a, r in zip(x.err.abs_, x.err.rel))
        err = Err((0.0,) * nw, ab, (0.0,) * nw,
                  tuple(_clampm(m) for m in x.err.mabs))
        return [make_val(lo, hi, err=err, nw=nw)]
    if name in ("exp", "exp2"):
        base = math.e if name == "exp" else 2.0
        lg = (1.0 / LN2) if name == "exp" else 1.0
        lo = base ** max(min(x.lo, 256.0), -256.0) if x.lo > -math.inf else 0.0
        hi = math.inf if x.hi > 128.0 * (1 if name == "exp2" else LN2) * 2 \
            else base ** min(x.hi, 700.0)
        rel = tuple(_cap(base ** min(_EXP_CAP, x.mhi * r + a) - 1)
                    for r, a in zip(x.err.rel, x.err.abs_))
        mrel = tuple(_clampm(base ** max(-_EXP_CAP, min(
            _EXP_CAP, x.mhi * m + ma)) - 1)
            for m, ma in zip(x.err.mrel, x.err.mabs))
        err = Err(rel, (0.0,) * nw, mrel, (0.0,) * nw)
        return [make_val(lo, hi, zero=False, err=err, nw=nw)]
    if name in ("log", "log2"):
        if x.lo <= 0 or x.zero:
            return [it._hull(x.err)]
        f = math.log if name == "log" else math.log2
        k = 1.0 if name == "log" else 1.0 / LN2
        ab = tuple(_cap(a0 + k * (r + a / max(x.mlo, 5e-324)))
                   for a0, (r, a) in zip((0.0,) * nw,
                                         zip(x.err.rel, x.err.abs_)))
        err = Err((0.0,) * nw, ab, (0.0,) * nw, (0.0,) * nw)
        return [make_val(f(x.lo), f(x.hi), err=err, nw=nw)]
    if name in ("sqrt", "rsqrt"):
        slo, shi = math.sqrt(max(x.lo, 0.0)), math.sqrt(max(x.hi, 0.0)) \
            if not math.isinf(x.hi) else math.inf
        rel = tuple(_cap((1 + min(r, BIG / 2)) ** 0.5 - 1 + a)
                    for r, a in zip(x.err.rel, x.err.abs_))
        err = Err(rel, (0.0,) * nw,
                  tuple(m * 0.5 for m in x.err.mrel), (0.0,) * nw)
        if name == "sqrt":
            return [make_val(slo, shi, zero=x.zero, err=err, nw=nw)]
        if slo <= 0.0:
            return [it._hull(err)]
        return [make_val(1.0 / shi if shi > 0 else math.inf, 1.0 / slo,
                         err=err, nw=nw)]
    if name in ("sin", "cos"):
        ab = tuple(_cap(a + x.mhi * r)
                   for r, a in zip(x.err.rel, x.err.abs_))
        err = Err((0.0,) * nw, ab, (0.0,) * nw, (0.0,) * nw)
        return [make_val(-1.0, 1.0, err=err, nw=nw)]
    if name == "tanh":
        return [make_val(-1.0, 1.0, err=x.err, nw=nw)]
    if name == "logistic":
        return [make_val(0.0, 1.0, zero=False, err=x.err, nw=nw)]
    if name == "integer_pow":
        y = int(eqn.params.get("y", 2))
        cands = [x.lo ** y, x.hi ** y] + ([0.0] if x.zero
                                          or x.lo <= 0 <= x.hi else [])
        cands = [c for c in cands if not _isnan(c)] or [-math.inf, math.inf]
        rel = tuple(_cap((1 + r) ** abs(y) - 1) for r in x.err.rel)
        err = Err(rel, tuple(_cap(a * abs(y) * x.mhi ** max(abs(y) - 1, 0))
                             for a in x.err.abs_),
                  tuple(_clampm((1 + m) ** abs(y) - 1) for m in x.err.mrel),
                  (0.0,) * nw)
        return [make_val(min(cands), max(cands), err=err, nw=nw)]
    raise NotImplementedError(name)


def _h_identity(it, eqn):
    return [it.read(eqn.invars[0])]


def _h_convert(it, eqn):
    x = it.read(eqn.invars[0])
    new = np.dtype(eqn.params["new_dtype"])
    wit = None
    if x.wit is not None:
        try:
            with np.errstate(all="ignore"):
                wv = float(np.asarray(x.wit.val).astype(new).item())
            if not _isnan(wv):
                wit = Witness(wv, x.wit.axes, x.wit.origin)
        except Exception:
            wit = None
    if _is_float_dtype(new):
        xa = _as_float(x, it.nw)
        return [replace(xa, wit=wit)]
    xi = _as_int(_as_float(x, it.nw) if isinstance(x, AbsVal) else x, it.nw)
    if isinstance(x, AbsVal):
        lo = int(math.trunc(max(min(x.lo, 2.0 ** 62), -(2.0 ** 62))))
        hi = int(math.trunc(max(min(x.hi, 2.0 ** 62), -(2.0 ** 62))))
        return [IntVal(lo, hi, x.err, wit=wit)]
    return [replace(xi, wit=wit)]


def _exp2_range_cap(it, eqn, out):
    """Tighten the decoded paexp2 result to 2^ceil(a_hi): the interval
    domain cannot couple ``n`` and the mantissa carry inside the bit
    compose, so the raw decode balloons to MAX_FINITE even for a <= 0."""
    if not isinstance(out, AbsVal):
        return out
    ak = it._anchor(eqn)
    if ak is None or ak[0] != "exp2":
        return out
    ent = it._anchor_in.get(ak[1])
    if ent is None or ent.hi >= 127.0 or math.isinf(ent.hi):
        return out
    cap = 2.0 ** (math.floor(ent.hi) + 1)
    if out.hi <= cap and out.lo >= 0.0:
        return out
    return AbsVal(max(out.lo, 0.0), min(out.hi, cap),
                  min(out.mlo, cap), out.zero, out.err, out.wit)


def _h_bitcast(it, eqn):
    x = it.read(eqn.invars[0])
    wit = None
    if x.wit is not None:
        try:
            src = np.dtype(eqn.invars[0].aval.dtype)
            dst = np.dtype(eqn.params["new_dtype"])
            with np.errstate(all="ignore"):
                wv = float(np.asarray(src.type(x.wit.val)).view(dst).item())
            if not _isnan(wv):
                wit = Witness(wv, x.wit.axes, x.wit.origin)
        except Exception:
            wit = None
    if it._out_float(eqn):
        if not isinstance(x, IntVal):
            return [replace(_as_float(x, it.nw), wit=wit)]
        err = x.err
        if x.smag is not None:
            m = x.smag
            maghi = math.inf if m.hi > _MAXFIN_I else decode_mag(m.hi)
            mlo_f = decode_mag(m.mlo) if m.mlo else 0.0
            out = AbsVal(-maghi, maghi,
                         mlo_f if mlo_f > 0 else FLUSH_MIN,
                         m.lo < _MINNORM_I, err, wit)
        elif x.bits_of is not None:
            f = x.bits_of
            out = replace(f, err=f.err.join(err), wit=wit)
        elif x.sign_only:
            out = AbsVal(0.0, 0.0, math.inf, True, err, wit)
        elif x.lo >= 0 and x.hi <= _I32_HI:
            hi_f = math.inf if x.hi > _MAXFIN_I else decode_mag(x.hi)
            lo_f = decode_mag(max(x.lo, 0))
            mlo_f = decode_mag(x.mlo) if x.mlo else 0.0
            out = AbsVal(lo_f, hi_f,
                         mlo_f if mlo_f > 0 else FLUSH_MIN,
                         x.lo < _MINNORM_I, err, wit)
        else:
            out = replace(it._hull(err), wit=wit)
        return [_exp2_range_cap(it, eqn, out)]
    if isinstance(x, AbsVal):
        if x.lo >= 0 and not math.isinf(x.hi) and not x.zero or \
                (x.lo >= 0 and not math.isinf(x.hi)):
            return [IntVal(_bits_of_float(x.lo), _bits_of_float(x.hi),
                           x.err, bits_of=x, wit=wit)]
        return [IntVal(_I32_LO, _I32_HI, x.err, bits_of=x, wit=wit)]
    return [replace(_as_int(x, it.nw), wit=wit)]


def _h_and(it, eqn):
    x, y = _rd(it, eqn)
    if it._out_float(eqn):
        return [it._hull(x.err.join(y.err))]
    xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
    err = xi.err.join(yi.err)
    aval = getattr(eqn.outvars[0], "aval", None)
    if aval is not None and np.dtype(aval.dtype) == np.bool_:
        # {0,1} interval conjunction (dual of `or`).
        lo = max(min(min(xi.lo, yi.lo), 1), 0)
        hi = max(min(min(xi.hi, yi.hi), 1), 0)
        return [replace(IntVal(lo, hi, err), err=err)]
    for a, b in ((xi, yi), (yi, xi)):
        if b.lo == b.hi:
            L = b.lo
            if L == 0:
                return [replace(int_const(0, it.nw), err=err)]
            if L == _MAG_I and a.bits_of is not None:
                f = a.bits_of
                lo, hi, mlo = mag_bounds_of(f)
                return [IntVal(lo, hi, err, mlo=mlo,
                               mag=MagExpr((f,), (), 0, 0))]
            if L == _SIGN_I:
                return [IntVal(_SIGN_I, 0, err, sign_only=True)]
            if L == _MAG_I:
                return [IntVal(0, _MAG_I, err)]
            if L == _MAN_I:
                return [IntVal(0, _MAN_I, err)]
        if -1 <= b.lo <= 0 and b.hi == 0 and b.lo < 0 and a.lo >= 0:
            return [IntVal(0, a.hi, err, mlo=a.mlo, pa=a.pa)]
        if b.lo == -1 and b.hi == 0:
            return [IntVal(min(a.lo, 0), max(a.hi, 0), err,
                           mlo=a.mlo, pa=a.pa)]
    if xi.lo >= 0 and yi.lo >= 0:
        return [IntVal(0, min(xi.hi, yi.hi), err,
                       pa=xi.pa or yi.pa)]
    if xi.lo >= 0:
        return [IntVal(0, xi.hi, err, pa=xi.pa)]
    if yi.lo >= 0:
        return [IntVal(0, yi.hi, err, pa=yi.pa)]
    return [IntVal(_I32_LO, _I32_HI, err)]


def _h_or(it, eqn):
    x, y = _rd(it, eqn)
    xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
    err = xi.err.join(yi.err)
    aval = getattr(eqn.outvars[0], "aval", None)
    if aval is not None and np.dtype(aval.dtype) == np.bool_:
        # {0,1} interval disjunction: surely-1 if either operand is,
        # surely-0 only if both are — keeps decided inf/nan predicates
        # decided through `isinf(a) | isinf(b)` chains.
        lo = max(min(xi.lo, 1), min(yi.lo, 1), 0)
        hi = max(min(xi.hi, 1), min(yi.hi, 1), 0)
        return [replace(IntVal(lo, hi, err), err=err)]
    for a, b in ((xi, yi), (yi, xi)):
        if a.sign_only and 0 <= b.lo and b.hi <= _MAG_I:
            return [IntVal(_SIGN_I + b.lo, b.hi, err, smag=b, pa=b.pa)]
        if b.lo == b.hi == 0:
            return [replace(a, err=err)]
    if xi.sign_only and yi.sign_only:
        return [IntVal(_SIGN_I, 0, err, sign_only=True)]
    if xi.lo >= 0 and yi.lo >= 0:
        top = max(xi.hi, yi.hi, 1)
        hi = min((1 << int(top).bit_length()) - 1, _I32_HI)
        return [IntVal(max(xi.lo, yi.lo), hi, err, pa=xi.pa or yi.pa)]
    return [IntVal(_I32_LO, _I32_HI, err)]


def _h_xor(it, eqn):
    x, y = _rd(it, eqn)
    xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
    err = xi.err.join(yi.err)
    aval = getattr(eqn.outvars[0], "aval", None)
    if aval is not None and np.dtype(aval.dtype) == np.bool_:
        if xi.lo == xi.hi and yi.lo == yi.hi:
            v = (int(xi.lo) ^ int(yi.lo)) & 1
            return [replace(int_const(v, it.nw), err=err)]
        return [replace(bool_int(it.nw), err=err)]
    if xi.sign_only and yi.sign_only:
        return [IntVal(_SIGN_I, 0, err, sign_only=True)]
    if 0 <= xi.lo and xi.hi <= 1 and 0 <= yi.lo and yi.hi <= 1:
        return [IntVal(0, 1, err)]
    return [IntVal(_I32_LO, _I32_HI, err)]


def _h_not(it, eqn):
    x = _as_int(it.read(eqn.invars[0]), it.nw)
    aval = getattr(eqn.outvars[0], "aval", None)
    if aval is not None and np.dtype(aval.dtype) == np.bool_:
        lo = max(min(1 - x.hi, 1), 0)
        hi = max(min(1 - x.lo, 1), 0)
        return [replace(IntVal(lo, hi, x.err), err=x.err)]
    return [IntVal(-x.hi - 1, -x.lo - 1, x.err)]


def _h_shift(it, eqn):
    name = eqn.primitive.name
    x, y = _rd(it, eqn)
    xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
    err = xi.err.join(yi.err)
    if yi.lo == yi.hi and 0 <= yi.lo < 64:
        s = yi.lo
        if name == "shift_left":
            lo, hi = xi.lo << s, xi.hi << s
        elif name == "shift_right_arithmetic":
            lo, hi = xi.lo >> s, xi.hi >> s
        else:
            if xi.lo >= 0:
                lo, hi = xi.lo >> s, xi.hi >> s
            else:
                lo, hi = 0, 0xFFFFFFFF >> s
        return [IntVal(lo, hi, err, pa=xi.pa)]
    return [IntVal(_I32_LO, _I32_HI, err)]


def _h_cmp(it, eqn):
    name = eqn.primitive.name
    x, y = _rd(it, eqn)
    if name in ("lt", "le") and isinstance(x, IntVal) and x.pa is not None \
            and isinstance(y, IntVal) and y.lo == y.hi == -_BIAS_I:
        x.pa.site.guarded = True
    if name in ("gt", "ge") and isinstance(y, IntVal) and y.pa is not None \
            and isinstance(x, IntVal) and x.lo == x.hi == -_BIAS_I:
        y.pa.site.guarded = True
    err = x.err.join(y.err)
    # Decide statically when the intervals allow it — this is what prunes
    # the inf/nan edge selects for finite declared inputs.
    dec = None
    same = (len(eqn.invars) == 2
            and not is_literal(eqn.invars[0])
            and eqn.invars[0] is eqn.invars[1])
    if same:
        # x == x: abstractly true — declared inputs carry no NaN and NaN
        # producers fall to the hull (DESIGN.md §10 contract).
        dec = {"eq": 1, "le": 1, "ge": 1, "ne": 0, "lt": 0, "gt": 0}[name]
    else:
        xl, xh, yl, yh = x.lo, x.hi, y.lo, y.hi
        if name == "lt":
            dec = 1 if xh < yl else (0 if xl >= yh else None)
        elif name == "le":
            dec = 1 if xh <= yl else (0 if xl > yh else None)
        elif name == "gt":
            dec = 1 if xl > yh else (0 if xh <= yl else None)
        elif name == "ge":
            dec = 1 if xl >= yh else (0 if xh < yl else None)
        elif name == "eq":
            dec = 0 if (xh < yl or yh < xl) else (
                1 if xl == xh == yl == yh else None)
        elif name == "ne":
            dec = 1 if (xh < yl or yh < xl) else (
                0 if xl == xh == yl == yh else None)
    if dec is not None:
        return [replace(int_const(dec, it.nw), err=err)]
    return [replace(bool_int(it.nw), err=err)]


def _sel_false_lo(it, eqn):
    """Relational lo-refinement for the PA flush idiom
    ``select_n(lt(u, K), f(u), 0)``: on the false branch ``u >= K``, so
    when the false case resolves to ``u`` itself (or ``min/max(u, L)``)
    its lower bound lifts to ``K`` (resp. ``min(K, L)``).  This is what
    keeps the denormal-flush select in pam/padiv from dragging the
    magnitude interval below 0 and killing the smag tag."""
    try:
        pv, pe = it._resolve(eqn.invars[0])
        if pe is None or pe.primitive.name != "lt":
            return None
        u_atom, k_atom = pe.invars
        if not is_literal(k_atom):
            return None
        karr = np.asarray(k_atom.val)
        if not np.issubdtype(karr.dtype, np.integer) or karr.size != 1:
            return None
        K = int(karr.reshape(()))
        uv = it._resolve(u_atom)[0]
        fv, fe = it._resolve(eqn.invars[1])
        if fv is uv:
            return K
        if fe is not None and fe.primitive.name in ("min", "max"):
            lit, other = None, None
            for a in fe.invars:
                if is_literal(a):
                    la = np.asarray(a.val)
                    if np.issubdtype(la.dtype, np.integer) and la.size == 1:
                        lit = int(la.reshape(()))
                else:
                    other = a
            if lit is not None and other is not None \
                    and it._resolve(other)[0] is uv:
                return min(K, lit) if fe.primitive.name == "min" else K
    except Exception:
        pass
    return None


def _h_select(it, eqn):
    vals = _rd(it, eqn)
    pred, cases = vals[0], vals[1:]
    if pred.lo == pred.hi and 0 <= pred.lo < len(cases):
        chosen = cases[int(pred.lo)]
        out = _as_float(chosen, it.nw) if it._out_float(eqn) \
            else _as_int(chosen, it.nw)
        return [replace(out, err=out.err.join(pred.err))]
    err = it._join_errs(vals)
    if it._out_float(eqn):
        out = _as_float(cases[0], it.nw)
        for c in cases[1:]:
            out = out.join(_as_float(c, it.nw))
        return [replace(out, err=err, wit=None)]
    ints = [_as_int(c, it.nw) for c in cases]
    if len(ints) == 2:
        flo = _sel_false_lo(it, eqn)
        if flo is not None and flo > ints[0].lo:
            ints[0] = replace(ints[0], lo=min(flo, ints[0].hi))
    tagged = [c for c in ints
              if c.mag is not None or c.smag is not None
              or c.pa is not None or c.mlo is not None]
    consts = [c for c in ints if c.lo == c.hi]
    if len(tagged) == 1 and len(consts) == len(ints) - 1 \
            and tagged[0].lo != tagged[0].hi:
        t = tagged[0]
        lo = min(c.lo for c in ints)
        hi = max(c.hi for c in ints)
        return [replace(t, lo=lo, hi=hi, err=err, bits_of=None, wit=None)]
    out = ints[0]
    for c in ints[1:]:
        out = out.join(c)
    return [replace(out, err=err, wit=None)]


# ---------------------------------------------------------------------------
# Shape / gather / reduction handlers.
# ---------------------------------------------------------------------------

def _h_broadcast(it, eqn):
    x = it.read(eqn.invars[0])
    bd = tuple(int(d) for d in eqn.params.get("broadcast_dimensions", ()))
    wit = x.wit
    if wit is not None and wit.axes is not None:
        try:
            wit = Witness(wit.val, tuple(sorted(bd[a] for a in wit.axes)),
                          wit.origin)
        except Exception:
            wit = None
    return [replace(x, wit=wit)]


def _h_transpose(it, eqn):
    x = it.read(eqn.invars[0])
    perm = tuple(int(p) for p in eqn.params.get("permutation", ()))
    wit = x.wit
    if wit is not None and wit.axes is not None:
        try:
            wit = Witness(wit.val, tuple(sorted(
                j for j, p in enumerate(perm) if p in wit.axes)), wit.origin)
        except Exception:
            wit = None
    return [replace(x, wit=wit)]


def _h_shapepass(it, eqn):
    x = it.read(eqn.invars[0])
    wit = x.wit if (x.wit is not None and x.wit.axes is None) else None
    return [replace(x, wit=wit)]


def _h_joinall(it, eqn):
    vals = _rd(it, eqn)
    if it._out_float(eqn):
        out = _as_float(vals[0], it.nw)
        for v in vals[1:]:
            out = out.join(_as_float(v, it.nw))
    else:
        out = _as_int(vals[0], it.nw)
        for v in vals[1:]:
            out = out.join(_as_int(v, it.nw))
    return [replace(out, wit=None)]


def _h_pad(it, eqn):
    x, pv = _rd(it, eqn)
    if it._out_float(eqn):
        return [replace(_as_float(x, it.nw).join(_as_float(pv, it.nw)),
                        wit=None)]
    return [replace(_as_int(x, it.nw).join(_as_int(pv, it.nw)), wit=None)]


def _h_iota(it, eqn):
    dim = int(eqn.params.get("dimension", 0))
    shape = eqn.params.get("shape") or getattr(
        eqn.outvars[0].aval, "shape", (1,))
    n = int(shape[dim]) if shape else 1
    if it._out_float(eqn):
        return [make_val(0.0, float(max(n - 1, 0)), nw=it.nw)]
    return [IntVal(0, max(n - 1, 0), err_zero(it.nw))]


def _h_argminmax(it, eqn):
    shape = getattr(eqn.invars[0].aval, "shape", (1,))
    axes = eqn.params.get("axes", (0,))
    n = _shape_n(shape, axes)
    return [IntVal(0, max(n - 1, 0), it.read(eqn.invars[0]).err)]


def _h_reduce_sum(it, eqn):
    x = it.read(eqn.invars[0])
    axes = tuple(int(a) for a in eqn.params.get("axes", ()))
    shape = getattr(eqn.invars[0].aval, "shape", ())
    n = _shape_n(shape, axes)
    if not it._out_float(eqn):
        xi = _as_int(x, it.nw)
        return [IntVal(min(n * xi.lo, xi.lo), max(n * xi.hi, xi.hi),
                       xi.err)]
    xa = _as_float(x, it.nw)
    err = replace(xa.err,
                  abs_=tuple(_cap(a * n) for a in xa.err.abs_),
                  mabs=tuple(_clampm(a * n) for a in xa.err.mabs))
    if xa.is_const and xa.wit is not None and xa.wit.axes is None:
        return [const_val(xa.lo * n, it.nw).with_err(err)]
    lo = min(n * xa.lo, xa.lo)
    hi = max(n * xa.hi, xa.hi)
    w = xa.wit
    if w is not None and xa.lo >= 0.0 and w.val > 0.0 \
            and (w.axes is None or set(w.axes) <= set(axes)):
        return [AbsVal(max(lo, w.val), _fhi(hi), max(w.val, xa.mlo)
                       if not math.isinf(xa.mlo) else w.val,
                       False, err, None)]
    return [make_val(_flo(lo), _fhi(hi), err=err, nw=it.nw)]


def _h_reduce_minmax(it, eqn):
    x = it.read(eqn.invars[0])
    if not it._out_float(eqn):
        xi = _as_int(x, it.nw)
        return [replace(xi, wit=None)]
    xa = _as_float(x, it.nw)
    wit = xa.wit if (xa.wit is not None and xa.wit.axes is None) else None
    return [replace(xa, wit=wit)]


def _h_reduce_bool(it, eqn):
    return [replace(bool_int(it.nw), err=it.read(eqn.invars[0]).err)]


def _h_rem(it, eqn):
    x, y = _rd(it, eqn)
    xi, yi = _as_int(x, it.nw), _as_int(y, it.nw)
    err = xi.err.join(yi.err)
    if it._out_float(eqn):
        ya = _as_float(y, it.nw)
        m = ya.mhi if not math.isinf(ya.mhi) else ACTIVATION_CEIL
        return [make_val(-m, m, err=err, nw=it.nw)]
    if yi.lo == yi.hi and yi.lo > 0 and xi.lo >= 0:
        return [IntVal(0, min(xi.hi, yi.lo - 1), err)]
    m = max(abs(yi.lo), abs(yi.hi), 1)
    return [IntVal(-m + 1, m - 1, err)]


def _h_scatter(it, eqn):
    vals = _rd(it, eqn)
    op, upd = vals[0], vals[-1]
    name = eqn.primitive.name
    if it._out_float(eqn):
        oa, ua = _as_float(op, it.nw), _as_float(upd, it.nw)
        if name in ("scatter-add", "scatter_add"):
            shape = getattr(eqn.invars[-1].aval, "shape", ())
            n = _shape_n(shape, range(len(shape)))
            lo = oa.lo + min(0.0, n * ua.lo)
            hi = oa.hi + max(0.0, n * ua.hi)
            return [make_val(_flo(lo), _fhi(hi),
                             err=oa.err.through_add(ua.err), nw=it.nw)]
        return [replace(oa.join(ua), wit=None)]
    oi, ui = _as_int(op, it.nw), _as_int(upd, it.nw)
    return [replace(oi.join(ui), wit=None)]


def _h_dus(it, eqn):
    op = it.read(eqn.invars[0])
    upd = it.read(eqn.invars[1])
    if it._out_float(eqn):
        return [replace(_as_float(op, it.nw).join(_as_float(upd, it.nw)),
                        wit=None)]
    return [replace(_as_int(op, it.nw).join(_as_int(upd, it.nw)), wit=None)]


def _h_gather(it, eqn):
    x = it.read(eqn.invars[0])
    idx_err = it.read(eqn.invars[1]).err if len(eqn.invars) > 1 \
        else err_zero(it.nw)
    return [replace(x, err=x.err.join(idx_err), wit=None)]


def _h_is_finite(it, eqn):
    x = it.read(eqn.invars[0])
    if isinstance(x, AbsVal) and math.isfinite(x.lo) and math.isfinite(x.hi):
        return [replace(int_const(1, it.nw), err=x.err)]
    return [replace(bool_int(it.nw), err=x.err)]


def _h_random(it, eqn):
    outs = []
    for i, ov in enumerate(eqn.outvars):
        if it._out_float(eqn, i):
            outs.append(make_val(0.0, 1.0, nw=it.nw))
        else:
            outs.append(IntVal(0, (1 << 32) - 1, err_zero(it.nw)))
    return outs


def _h_psum(it, eqn):
    outs = []
    for i, v in enumerate(eqn.invars):
        x = it.read(v)
        if isinstance(x, AbsVal):
            lo = min(x.lo, NDEV_BOUND * x.lo)
            hi = max(x.hi, NDEV_BOUND * x.hi)
            outs.append(make_val(_flo(lo), _fhi(hi),
                                 err=x.err.scaled_n(NDEV_BOUND), nw=it.nw))
        else:
            outs.append(IntVal(min(x.lo, NDEV_BOUND * x.lo),
                               max(x.hi, NDEV_BOUND * x.hi), x.err))
    return outs


def _h_axis_index(it, eqn):
    return [IntVal(0, NDEV_BOUND - 1, err_zero(it.nw))]


# ---------------------------------------------------------------------------
# Control flow.
# ---------------------------------------------------------------------------

def _same_bounds(a, b) -> bool:
    if type(a) is not type(b):
        return False
    if isinstance(a, AbsVal):
        return (a.lo, a.hi, a.mlo, a.zero) == (b.lo, b.hi, b.mlo, b.zero)
    return (a.lo, a.hi, a.sign_only) == (b.lo, b.hi, b.sign_only)


def _widen(it, v):
    if isinstance(v, AbsVal):
        return AbsVal(min(v.lo, -ACTIVATION_CEIL),
                      max(v.hi, ACTIVATION_CEIL),
                      FLUSH_MIN, True, v.err, None)
    return replace(top_int(it.nw), err=v.err)


def _extrap_err(e_out: Err, e_in: Err, L: float, nw: int) -> Err:
    rel = tuple(_cap(e_in.rel[i] + L * max(0.0, e_out.rel[i] - e_in.rel[i]))
                for i in range(nw))
    ab = tuple(_cap(e_in.abs_[i] + L * max(0.0, e_out.abs_[i] - e_in.abs_[i]))
               for i in range(nw))
    mrel = tuple(_clampm(e_in.mrel[i] + L * (e_out.mrel[i] - e_in.mrel[i]))
                 for i in range(nw))
    mab = tuple(_clampm(e_in.mabs[i] + L * (e_out.mabs[i] - e_in.mabs[i]))
                for i in range(nw))
    return Err(rel, ab, mrel, mab)


def _alias_call(it, body, eqn_invars):
    for bv, atom in zip(body.invars, eqn_invars):
        if not is_literal(atom):
            it.alias[bv] = atom


def _run_fixpoint(it, body, consts, carry, xs, const_vals, L, note=None):
    """Range fixpoint over a loop body; error extrapolated over L trips."""
    nk = len(carry)
    carry_in = list(carry)
    outs = None
    for step in range(_FIXPOINT_ITERS):
        carry_in = list(carry)
        outs = it.run(body, consts + carry + xs, const_vals)
        new_carry = outs[:nk]
        joined = [c.join(n) for c, n in zip(carry, new_carry)]
        if all(_same_bounds(c, j) for c, j in zip(carry, joined)):
            carry = joined
            break
        carry = joined
        if step == _FIXPOINT_ITERS - 2:
            carry = [_widen(it, c) for c in carry]
    new_carry, ys = outs[:nk], outs[nk:]
    deltas = []
    final_carry = []
    for c_in, c_out, c_rng in zip(carry_in, new_carry, carry):
        e = _extrap_err(c_out.err, c_in.err, L, it.nw)
        final_carry.append(replace(c_rng, err=e, wit=None))
        deltas.append(Err(
            tuple(max(0.0, o - i) for o, i in zip(c_out.err.rel,
                                                  c_in.err.rel)),
            tuple(max(0.0, o - i) for o, i in zip(c_out.err.abs_,
                                                  c_in.err.abs_)),
            tuple(o - i for o, i in zip(c_out.err.mrel, c_in.err.mrel)),
            tuple(o - i for o, i in zip(c_out.err.mabs, c_in.err.mabs))))
    maxd = err_zero(it.nw)
    for d in deltas:
        maxd = maxd.join(d)
    ys_out = []
    for y in ys:
        e = Err(tuple(_cap(y.err.rel[i] + L * maxd.rel[i])
                      for i in range(it.nw)),
                tuple(_cap(y.err.abs_[i] + L * maxd.abs_[i])
                      for i in range(it.nw)),
                tuple(_clampm(y.err.mrel[i] + L * maxd.mrel[i])
                      for i in range(it.nw)),
                tuple(_clampm(y.err.mabs[i] + L * maxd.mabs[i])
                      for i in range(it.nw)))
        ys_out.append(replace(y, err=e, wit=None))
    if note:
        it.notes.add(note)
    return final_carry + ys_out


def _h_scan(it, eqn):
    p = eqn.params
    closed = p["jaxpr"]
    nc, nk = int(p["num_consts"]), int(p["num_carry"])
    vals = _rd(it, eqn)
    consts, carry, xs = vals[:nc], vals[nc:nc + nk], vals[nc + nk:]
    L = max(int(p.get("length", 1) or 1), 1)
    _alias_call(it, closed.jaxpr, eqn.invars)
    it.ctx.append("scan")
    try:
        const_vals = [val_of_array(c, it.nw) for c in closed.consts]
        return _run_fixpoint(it, closed.jaxpr, consts, carry, xs,
                             const_vals, L)
    finally:
        it.ctx.pop()


def _h_while(it, eqn):
    p = eqn.params
    cn, bn = int(p["cond_nconsts"]), int(p["body_nconsts"])
    cjx, bjx = p["cond_jaxpr"], p["body_jaxpr"]
    vals = _rd(it, eqn)
    b_consts = vals[cn:cn + bn]
    carry = vals[cn + bn:]
    _alias_call(it, bjx.jaxpr, eqn.invars[cn:])
    it.ctx.append("while")
    try:
        it.run(cjx.jaxpr, vals[:cn] + carry,
               [val_of_array(c, it.nw) for c in cjx.consts])
        return _run_fixpoint(it, bjx.jaxpr, b_consts, carry, [],
                             [val_of_array(c, it.nw) for c in bjx.consts],
                             WHILE_ERR_ITERS, note="while_err_extrapolated")
    finally:
        it.ctx.pop()


def _h_cond(it, eqn):
    branches = eqn.params["branches"]
    vals = _rd(it, eqn)
    ops = vals[1:]
    it.ctx.append("cond")
    try:
        outs = None
        for br in branches:
            _alias_call(it, br.jaxpr, eqn.invars[1:])
            res = it.run(br.jaxpr, ops,
                         [val_of_array(c, it.nw) for c in br.consts])
            if outs is None:
                outs = res
            else:
                outs = [a.join(b) if type(a) is type(b)
                        else it._hull(a.err.join(b.err))
                        for a, b in zip(outs, res)]
        return [replace(o, wit=None) for o in outs]
    finally:
        it.ctx.pop()


def _h_pjit(it, eqn):
    closed = eqn.params["jaxpr"]
    vals = _rd(it, eqn)
    _alias_call(it, closed.jaxpr, eqn.invars)
    it.ctx.append(eqn.primitive.name)
    try:
        return it.run(closed.jaxpr, vals,
                      [val_of_array(c, it.nw) for c in closed.consts])
    finally:
        it.ctx.pop()


def _h_custom_vjp(it, eqn):
    closed = eqn.params.get("fun_jaxpr") or eqn.params.get("call_jaxpr")
    vals = _rd(it, eqn)
    _alias_call(it, closed.jaxpr, eqn.invars)
    it.ctx.append(eqn.primitive.name)
    try:
        return it.run(closed.jaxpr, vals,
                      [val_of_array(c, it.nw) for c in closed.consts])
    finally:
        it.ctx.pop()


def _h_remat(it, eqn):
    body = eqn.params["jaxpr"]
    vals = _rd(it, eqn)
    if isinstance(body, ClosedJaxpr):
        consts = [val_of_array(c, it.nw) for c in body.consts]
        body = body.jaxpr
    else:
        consts = []
    _alias_call(it, body, eqn.invars)
    it.ctx.append("remat")
    try:
        return it.run(body, vals, consts)
    finally:
        it.ctx.pop()


def _h_shard_map(it, eqn):
    body = eqn.params["jaxpr"]
    vals = _rd(it, eqn)
    if isinstance(body, ClosedJaxpr):
        consts = [val_of_array(c, it.nw) for c in body.consts]
        body = body.jaxpr
    else:
        consts = []
    _alias_call(it, body, eqn.invars)
    it.ctx.append("shard_map")
    try:
        return it.run(body, vals, consts)
    finally:
        it.ctx.pop()


def _h_pallas(it, eqn):
    it.notes.add("pallas_opaque")
    it.opaque["pallas_call"] += 1
    err = it._join_errs(_rd(it, eqn))
    outs = []
    for i in range(len(eqn.outvars)):
        outs.append(it._hull(err) if it._out_float(eqn, i)
                    else replace(top_int(it.nw), err=err))
    return outs


_HANDLERS = {
    "add": _h_addsub, "add_any": _h_addsub, "sub": _h_addsub,
    "mul": _h_mul, "div": _h_div,
    "max": _h_maxmin, "min": _h_maxmin, "clamp": _h_clamp,
    "neg": _h_unary_float, "abs": _h_unary_float, "sign": _h_unary_float,
    "floor": _h_unary_float, "ceil": _h_unary_float, "round": _h_unary_float,
    "exp": _h_unary_float, "exp2": _h_unary_float, "log": _h_unary_float,
    "log2": _h_unary_float, "sqrt": _h_unary_float, "rsqrt": _h_unary_float,
    "sin": _h_unary_float, "cos": _h_unary_float, "tanh": _h_unary_float,
    "logistic": _h_unary_float, "integer_pow": _h_unary_float,
    "convert_element_type": _h_convert,
    "bitcast_convert_type": _h_bitcast,
    "and": _h_and, "or": _h_or, "xor": _h_xor, "not": _h_not,
    "shift_left": _h_shift, "shift_right_arithmetic": _h_shift,
    "shift_right_logical": _h_shift,
    "lt": _h_cmp, "le": _h_cmp, "gt": _h_cmp, "ge": _h_cmp,
    "eq": _h_cmp, "ne": _h_cmp, "is_finite": _h_is_finite,
    "select_n": _h_select,
    "broadcast_in_dim": _h_broadcast, "transpose": _h_transpose,
    "reshape": _h_shapepass, "squeeze": _h_shapepass,
    "expand_dims": _h_shapepass, "rev": _h_shapepass,
    "slice": _h_shapepass, "copy": _h_identity,
    "stop_gradient": _h_identity, "device_put": _h_identity,
    "dynamic_slice": _h_gather,
    "dynamic_update_slice": _h_dus,
    "concatenate": _h_joinall, "pad": _h_pad, "iota": _h_iota,
    "gather": _h_gather,
    "scatter": _h_scatter, "scatter-add": _h_scatter,
    "scatter_add": _h_scatter,
    "argmax": _h_argminmax, "argmin": _h_argminmax,
    "reduce_sum": _h_reduce_sum,
    "reduce_max": _h_reduce_minmax, "reduce_min": _h_reduce_minmax,
    "reduce_or": _h_reduce_bool, "reduce_and": _h_reduce_bool,
    "rem": _h_rem,
    "random_bits": _h_random, "random_seed": _h_random,
    "random_wrap": _h_random, "random_unwrap": _h_random,
    "random_fold_in": _h_random,
    "psum": _h_psum, "psum2": _h_psum,
    "all_gather": _h_identity, "ppermute": _h_identity,
    "axis_index": _h_axis_index,
    "scan": _h_scan, "while": _h_while, "cond": _h_cond,
    "jit": _h_pjit, "pjit": _h_pjit, "closed_call": _h_pjit,
    "core_call": _h_pjit,
    "custom_jvp_call": _h_custom_vjp,
    "custom_vjp_call": _h_custom_vjp,
    "custom_vjp_call_jaxpr": _h_custom_vjp,
    "remat": _h_remat, "remat2": _h_remat, "checkpoint": _h_remat,
    "shard_map": _h_shard_map,
    "pallas_call": _h_pallas,
}


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------

# Declared default input ranges (DESIGN.md §10): float tensors carry
# |x| in {0} U [2^-24, 2^8]; integer inputs (step counts, position ids,
# slot indices) stay in [0, 2^30]; bools are {0, 1}. Callers narrow or
# widen these per target via analyze_jaxpr(in_vals=...).
DEFAULT_FLOAT_RANGE = (-256.0, 256.0)
DEFAULT_FLOAT_MLO = 2.0 ** -24
DEFAULT_INT_HI = 2 ** 30


def default_inputs(closed, widths=DEFAULT_WIDTHS, float_range=None,
                   float_mlo=None):
    """Declared-range abstract inputs for every invar of a ClosedJaxpr."""
    nw = len(widths)
    lo, hi = float_range or DEFAULT_FLOAT_RANGE
    mlo = float_mlo or DEFAULT_FLOAT_MLO
    vals = []
    for v in closed.jaxpr.invars:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and _is_float_dtype(dt):
            vals.append(make_val(lo, hi, mlo=mlo, zero=True, nw=nw))
        elif dt is not None and np.dtype(dt) == np.bool_:
            vals.append(bool_int(nw))
        elif dt is not None and _is_int_dtype(dt):
            vals.append(IntVal(0, DEFAULT_INT_HI, err_zero(nw)))
        else:
            vals.append(top_int(nw))
    return vals


@dataclass
class AnalysisReport:
    """Result of one abstract-interpretation pass over a jaxpr."""
    widths: Tuple[Tuple[str, int], ...]
    out_vals: List
    sites: List[PamSite]
    opaque: Counter
    notes: List[str]
    n_eqns: int

    # -- range safety ------------------------------------------------------
    def range_safety(self) -> dict:
        pam = [s for s in self.sites if s.kind == "pam"]
        padiv = [s for s in self.sites if s.kind == "padiv"]
        wrap = [s for s in self.sites if s.wrap]
        overflow = [s for s in self.sites if s.overflow]
        denormal = [s for s in self.sites if s.denormal]
        if wrap:
            verdict = "wrap"
        elif overflow:
            verdict = "overflow"
        elif denormal:
            verdict = "denormal"
        else:
            verdict = "safe"
        worst = sorted(self.sites, key=lambda s: -s.e_hi)[:3]
        return {
            "verdict": verdict,
            "pam_sites": len(pam), "padiv_sites": len(padiv),
            "wrap": len(wrap), "overflow": len(overflow),
            "denormal": len(denormal),
            "opaque_eqns": int(sum(self.opaque.values())),
            "notes": sorted(self.notes),
            "worst_sites": [s.to_dict() for s in worst],
        }

    # -- error certificate -------------------------------------------------
    def joined_err(self) -> Err:
        nw = len(self.widths)
        e = err_zero(nw)
        for v in self.out_vals:
            if isinstance(v, AbsVal):
                e = e.join(v.err)
        return e

    def certificate(self) -> dict:
        e = self.joined_err()
        per = {}
        for i, (name, m) in enumerate(self.widths):
            per[name] = {
                "mantissa_bits": int(m),
                "rel_worst": float(e.rel[i]),
                "rel_mean": float(e.mrel[i]),
                "abs_worst": float(e.abs_[i]),
            }
        return {
            "per_width": per,
            "saturated": bool(any(r >= BIG for r in e.rel)),
            "n_eqns": int(self.n_eqns),
        }


def analyze_jaxpr(closed, in_vals=None, widths=DEFAULT_WIDTHS,
                  float_range=None, float_mlo=None) -> AnalysisReport:
    """Abstractly interpret a ClosedJaxpr under declared input ranges.

    ``in_vals`` overrides the per-invar abstract inputs (None entries fall
    back to the declared defaults); ``float_range``/``float_mlo`` narrow
    the default float contract for every input at once.
    """
    defaults = default_inputs(closed, widths, float_range, float_mlo)
    if in_vals is not None:
        vals = [d if v is None else v for v, d in zip(in_vals, defaults)]
        vals += defaults[len(vals):]
    else:
        vals = defaults
    it = Interp(widths)
    outs = it.run_closed(closed, vals)
    return AnalysisReport(widths=tuple(widths), out_vals=outs,
                          sites=list(it.sites.values()),
                          opaque=it.opaque, notes=sorted(it.notes),
                          n_eqns=it.n_eqns)
