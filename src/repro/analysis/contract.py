"""PA numeric-contract linter (layer 2 of the analysis subsystem,
DESIGN.md §9): a static dtype-and-provenance flow pass over a jaxpr.

The multiplication auditor answers "is there a multiply?"; this pass
answers "does the code around the PA ops respect the documented numeric
contract?" — the conditions under which the piecewise-affine bit tricks
are exact or bounded (DESIGN.md §2). Four rules:

  ``non_pow2_scalar_divisor`` (error)
      ``div`` by a non-power-of-two scalar float literal producing a
      TENSOR-shaped result. A pow2 divisor is an exact exponent
      subtract; anything else on a tensor is a hidden per-element
      reciprocal multiply. Scalar-shaped results stay exempt — the O(1)
      schedule (``lr_at``) legitimately divides by step counts.

  ``pam_wrap_risk_literal`` (error)
      A finite float scalar literal with ``|v| >= 2^64`` feeding a
      mul/div or a float->int bitcast. PAM's int32 magnitude add wraps
      when the product magnitude reaches 2^129 (DESIGN.md §2.3) —
      reaching it needs both operands around 2^64, so a baked-in
      constant that large puts every runtime operand at wrap risk.
      Comparison guards (the 2^127 overflow sentinels in
      resilience/detectors.py) are not flagged: compares are not PAM
      inputs.

  ``bitcast_width_mismatch`` (error)
      A float<->integer ``bitcast_convert_type`` whose two sides differ
      in width. Every FloatFormat pairs its storage float with the
      same-width integer carrier (f32<->int32, bf16/f16<->int16;
      ``core/floatbits.py``), and every PA bit constant is derived from
      that format's layout — a cross-width bitcast (e.g. bf16 against
      int32 constants) reinterprets the wrong exponent field.

  ``scalar_mul_in_scan`` (warn)
      A non-pow2-exempt scalar float mul/div INSIDE a scan/while body.
      The auditor's scalar exemption reads "O(1) per train step"; under
      a scanned (per-layer/per-token/per-microbatch) body it executes
      O(iterations) times. Warn-only: schedule math scanned over
      microbatches is still cheap, but it should be visible.

``contract_lint(jaxpr)`` returns ``{"errors": [...], "warnings": [...],
"counts": {rule: n}}``; each finding carries rule, severity, prim, site,
full frame chain, enclosing sub-jaxpr context, and a human detail line.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterator, Tuple

import numpy as np
import jax.numpy as jnp

from .audit import _eqn_frames, _is_pow2_scalar_literal
from .jaxpr_types import is_literal, open_jaxpr, sub_jaxprs

# Both PAM operands must be able to reach ~2^64 for the product to cross
# the 2^129 flush-to-zero wrap (DESIGN.md §2.3).
WRAP_RISK_ABS = 2.0 ** 64

_SCAN_PRIMS = ("scan", "while")


def _iter_eqns(jx, ctx: Tuple[str, ...] = ()) -> Iterator:
    """Yield (eqn, context) over a jaxpr and every sub-jaxpr, context being
    the chain of enclosing equation primitives (outermost first)."""
    for eqn in jx.eqns:
        yield eqn, ctx
        name = eqn.primitive.name
        for sub in sub_jaxprs(eqn):
            yield from _iter_eqns(sub, ctx + (name,))


def _is_float_dtype(dtype) -> bool:
    try:
        return np.issubdtype(np.dtype(dtype), np.floating)
    except TypeError:       # extended dtypes (PRNG keys) are not float
        return False


def _scalar_float_literal(var):
    """The literal's python float if var is a finite scalar float literal,
    else None."""
    if not is_literal(var):
        return None
    val = np.asarray(var.val)
    if val.size != 1 or not np.issubdtype(val.dtype, np.floating):
        return None
    f = float(val.reshape(()))
    return f if np.isfinite(f) else None


def _finding(rule, severity, eqn, ctx, detail):
    frames = _eqn_frames(eqn)
    return {"rule": rule, "severity": severity,
            "prim": eqn.primitive.name,
            "site": frames[0] if frames else "?",
            "frames": frames, "context": list(ctx), "detail": detail}


def contract_lint(jaxpr) -> Dict:
    """Run the PA contract rules over a (Closed)Jaxpr."""
    errors, warnings = [], []
    counts: Dict[str, int] = defaultdict(int)

    def emit(rule, severity, eqn, ctx, detail):
        counts[rule] += 1
        (errors if severity == "error" else warnings).append(
            _finding(rule, severity, eqn, ctx, detail))

    root = open_jaxpr(jaxpr)
    for eqn, ctx in _iter_eqns(root):
        name = eqn.primitive.name
        out_aval = getattr(eqn.outvars[0], "aval", None) if eqn.outvars \
            else None
        out_float = (out_aval is not None
                     and hasattr(out_aval, "dtype")
                     and _is_float_dtype(out_aval.dtype))

        if name == "div" and len(eqn.invars) > 1 and out_float \
                and out_aval.shape != ():
            v = _scalar_float_literal(eqn.invars[1])
            if v is not None and not _is_pow2_scalar_literal(eqn.invars[1]):
                emit("non_pow2_scalar_divisor", "error", eqn, ctx,
                     f"tensor divided by non-pow2 literal {v!r}")

        if name in ("mul", "div", "bitcast_convert_type"):
            for var in eqn.invars:
                v = _scalar_float_literal(var)
                if v is not None and abs(v) >= WRAP_RISK_ABS:
                    emit("pam_wrap_risk_literal", "error", eqn, ctx,
                         f"literal {v!r} (|v| >= 2^64) feeding {name} can "
                         f"cross the 2^129 PAM wrap")

        if name == "bitcast_convert_type":
            in_aval = getattr(eqn.invars[0], "aval", None)
            new_dtype = eqn.params.get("new_dtype")
            try:
                src = np.dtype(in_aval.dtype) if in_aval is not None else None
                dst = np.dtype(new_dtype) if new_dtype is not None else None
            except (TypeError, AttributeError):
                src = dst = None
            if src is not None and dst is not None:
                # jnp.issubdtype, not np: bf16/f16 are ml_dtypes extension
                # types that numpy does not classify as floating. A
                # float<->int bitcast is legal whenever the widths MATCH —
                # each FloatFormat pairs its storage float with the
                # same-width integer carrier (f32<->int32, bf16/f16<->int16;
                # core/floatbits.py) — and an error otherwise.
                for f_dt, o_dt in ((src, dst), (dst, src)):
                    if (jnp.issubdtype(f_dt, jnp.floating)
                            and jnp.issubdtype(o_dt, jnp.integer)
                            and f_dt.itemsize != o_dt.itemsize):
                        emit("bitcast_width_mismatch", "error", eqn, ctx,
                             f"{src}->{dst} bitcast: PA bit math requires "
                             f"the format's same-width integer carrier "
                             f"(core/floatbits.py)")
                        break

        if name in ("mul", "div") and out_float and out_aval.shape == () \
                and any(p in _SCAN_PRIMS for p in ctx):
            pow2_ok = (
                (name == "mul" and any(_is_pow2_scalar_literal(v)
                                       for v in eqn.invars))
                or (name == "div" and len(eqn.invars) > 1
                    and _is_pow2_scalar_literal(eqn.invars[1])))
            if not pow2_ok:
                emit("scalar_mul_in_scan", "warn", eqn, ctx,
                     f"scalar {name} inside {'/'.join(ctx)} runs "
                     f"O(iterations), not O(1) per step")

    return {"errors": errors, "warnings": warnings, "counts": dict(counts)}
