"""Jaxpr-level multiplication auditor (the paper's multiplication-free
claim, layer 1 of the analysis subsystem — DESIGN.md §9).

``jaxpr_mul_stats`` walks a (Closed)Jaxpr — recursing through every
sub-jaxpr carried in equation params: scan, while, cond branches, pjit,
shard_map, remat, custom_jvp/vjp, pallas_call — and counts
multiplication-family primitives (mul, div, pow, integer_pow, sqrt,
rsqrt, square) on floating tensor outputs, plus contractions
(dot_general, conv_general_dilated), which are multiplication work
regardless of output shape. Exemptions, each implementable without a
multiplier (contractions get none):

  * scalar-shaped elementwise results — the O(1) per-step schedule (lr,
    loss mean, bias-correction scalars);
  * mul where either operand — and div where the DIVISOR — is a scalar
    literal that is an exact power of two: an exponent add on the bit
    pattern (``floatbits.pow2_mul`` semantics; the paper's "power-of-two
    scales are exact under PAM"). ``2 / x`` is a real per-element
    reciprocal and is not exempt;
  * integer-dtype ops — addressing/bit arithmetic, not float compute.

Every violation carries full provenance: the complete non-library stack
frame chain (not just the top frame), the chain of enclosing sub-jaxpr
primitives it was found under (e.g. ``shard_map/scan``), and a kernel
family attributed from the source path (``site_family``). The leaf-path
family rules used by resilience forensics live here too (``leaf_family``)
so one taxonomy serves both the replay bisector and the auditor.

The full-PA train step must report ``tensor_total == 0``
(tests/test_pam_optim.py's audit gate; DESIGN.md §5, §9).
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

from .jaxpr_types import is_literal, open_jaxpr, sub_jaxprs

MUL_FAMILY = ("mul", "div", "pow", "integer_pow", "sqrt", "rsqrt", "square")
# Contractions are multiplication work regardless of output shape (a dot
# producing a scalar still multiplies per element) — no exemptions apply.
CONTRACTIONS = ("dot_general", "conv_general_dilated")

# Kernel families a violation (or a diverging state leaf) is attributed to.
# "model-code" marks sites outside any PA kernel: glue in models/, train/,
# serve/ — usually the cheapest place to fix a leak.
FAMILIES = ("pam_matmul", "pam_attention", "pam_optim", "pam_eltwise",
            "model-code")

# Leaf-path substrings -> the kernel family (DESIGN.md §4 kernel inventory)
# whose output stream feeds that leaf. ``opt`` state is written only by the
# fused PA-AdamW kernel; attention projections by the PAM attention path;
# matmul-heavy leaves by the PAM matmul; norm scales/biases by elementwise
# PA ops. Forensics reports the family so a divergence points at a kernel
# to cross-check, not just a tensor.
_FAMILY_RULES = (
    (("attn", "wq", "wk", "wv", "wo", "q_norm", "k_norm"), "pam_attention"),
    (("mlp", "embed", "head", "moe", "expert"), "pam_matmul"),
    (("norm", "scale", "bias"), "pam_eltwise"),
)


def leaf_family(path: str) -> str:
    """Kernel family attribution for a state-tree leaf path."""
    p = path.lower()
    if "'opt'" in p or p.startswith("opt") or "['opt']" in p:
        return "pam_optim"
    for keys, fam in _FAMILY_RULES:
        if any(k in p for k in keys):
            return fam
    return "pam_matmul"


# Source-path substrings -> kernel family, checked in order (first match
# wins). A site inside a kernel package is that kernel's leak; attention
# and softmax model code belongs to the attention family (that is the
# kernel that would absorb it); everything else is model-code.
_SITE_RULES = (
    ("kernels/pam_optim", "pam_optim"),
    ("optim/", "pam_optim"),
    ("kernels/flash_attention", "pam_attention"),
    ("kernels/pa_softmax", "pam_attention"),
    ("models/attention", "pam_attention"),
    ("kernels/pam_eltwise", "pam_eltwise"),
    ("kernels/pam_matmul", "pam_matmul"),
    ("kernels/pa_prims", "pam_matmul"),
    ("core/matmul", "pam_matmul"),
)


def site_family(site: str) -> str:
    """Kernel family attribution for a source site (``path/file.py:line``)."""
    s = site.replace("\\", "/").lower()
    for key, fam in _SITE_RULES:
        if key in s:
            return fam
    return "model-code"


def _shorten(path: str) -> str:
    """Repo-relative rendering of an absolute frame path."""
    p = path.replace("\\", "/")
    for marker in ("/src/repro/", "/tests/", "/benchmarks/", "/examples/"):
        i = p.find(marker)
        if i >= 0:
            return p[i + 1:]
    return p.rsplit("/", 1)[-1]


def _eqn_frames(eqn) -> List[str]:
    """Full non-library frame chain for an equation, innermost first.

    Robust by construction: returns ``[]`` (never raises) when source info
    is absent, and never assumes any particular outvar/invar layout.
    """
    try:
        tb = eqn.source_info.traceback
        if tb is None:
            return []
        out = []
        for f in tb.frames:
            fn = f.file_name
            if "site-packages" in fn or "dist-packages" in fn:
                continue
            if "/lib/python" in fn or fn.startswith("<"):
                continue
            out.append(f"{_shorten(fn)}:{f.line_num}")
        return out
    except Exception:   # noqa: BLE001 — source info is best-effort
        return []


def _eqn_site(eqn) -> str:
    frames = _eqn_frames(eqn)
    return frames[0] if frames else "?"


def _out_aval(eqn):
    """First classifiable aval: outvars, then invars (multi-output and
    output-free primitives must not raise — satellite fix)."""
    for v in tuple(eqn.outvars) + tuple(eqn.invars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            return aval
    return None


def _is_float_dtype(dtype) -> bool:
    try:
        return np.issubdtype(np.dtype(dtype), np.floating)
    except TypeError:       # extended dtypes (PRNG keys) are not float
        return False


def _is_pow2_scalar_literal(var) -> bool:
    if not is_literal(var):
        return False
    val = np.asarray(var.val)
    if val.size != 1 or not np.issubdtype(val.dtype, np.floating):
        return False
    f = abs(float(val.reshape(())))
    return f > 0 and np.isfinite(f) and np.frexp(f)[0] == 0.5


@dataclasses.dataclass
class MulSite:
    """One multiplication-audit violation with full provenance."""
    prim: str                  # primitive name (mul/div/dot_general/...)
    site: str                  # innermost non-library frame, file:line
    frames: Tuple[str, ...]    # full non-library chain, innermost first
    family: str                # kernel-family attribution (site_family)
    context: Tuple[str, ...]   # enclosing sub-jaxpr prims, outermost first
    shape: Tuple[int, ...]
    dtype: str

    def to_dict(self) -> dict:
        return {"prim": self.prim, "site": self.site,
                "frames": list(self.frames), "family": self.family,
                "context": list(self.context),
                "shape": list(self.shape), "dtype": self.dtype}

    def describe(self) -> str:
        ctx = "/".join(self.context) if self.context else "top"
        return (f"{self.prim}@{self.site} [{self.family}] "
                f"{self.dtype}{list(self.shape)} under {ctx}")


def format_violations(stats: Dict, limit: int = 10) -> str:
    """Human-readable failure message localizing each violation to
    file:line and kernel family (the audit gates' assertion text)."""
    vio = stats.get("violations", [])
    if not vio:
        return "audit clean: tensor_total == 0"
    lines = [f"{len(vio)} tensor-shaped multiplication(s) found:"]
    for v in vio[:limit]:
        ctx = "/".join(v["context"]) if v["context"] else "top"
        lines.append(f"  {v['prim']}@{v['site']} [{v['family']}] under {ctx}")
        for fr in v["frames"][1:4]:
            lines.append(f"      from {fr}")
    if len(vio) > limit:
        lines.append(f"  ... and {len(vio) - limit} more")
    return "\n".join(lines)


def jaxpr_mul_stats(jaxpr) -> Dict:
    """Audit a (Closed)Jaxpr for multiplication-family ops.

    Returns ``{"tensor": {prim: n}, "scalar": {prim: n}, "pow2": n,
    "integer": n, "tensor_total": n, "tensor_sites": [...],
    "violations": [...], "by_family": {family: n}}`` where ``tensor``
    counts the violations — floating, tensor-shaped, not a power-of-two
    literal scale — ``tensor_sites`` holds one ``prim@file:line`` entry
    per violation (dedup'd, for short failure messages), and
    ``violations`` holds the full :class:`MulSite` records (frame chain,
    kernel family, enclosing sub-jaxpr context).
    """
    stats = {"tensor": defaultdict(int), "scalar": defaultdict(int),
             "pow2": 0, "integer": 0}
    by_family: Dict[str, int] = defaultdict(int)
    violations: List[MulSite] = []

    def record(eqn, name, aval, ctx):
        frames = _eqn_frames(eqn)
        site = frames[0] if frames else "?"
        fam = site_family(site)
        stats["tensor"][name] += 1
        by_family[fam] += 1
        violations.append(MulSite(
            prim=name, site=site, frames=tuple(frames), family=fam,
            context=ctx, shape=tuple(getattr(aval, "shape", ()) or ()),
            dtype=str(getattr(aval, "dtype", "?"))))

    def walk(jx, ctx: Tuple[str, ...]):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in MUL_FAMILY or name in CONTRACTIONS:
                aval = _out_aval(eqn)
                # The pow2 exemption is an exponent add: either mul operand,
                # but ONLY the divisor of a div (2 / x is a real reciprocal).
                pow2_ok = (
                    (name == "mul" and any(_is_pow2_scalar_literal(v)
                                           for v in eqn.invars))
                    or (name == "div" and len(eqn.invars) > 1
                        and _is_pow2_scalar_literal(eqn.invars[1])))
                if aval is None:
                    pass  # unclassifiable — robustness over false alarms
                elif not _is_float_dtype(aval.dtype):
                    stats["integer"] += 1
                elif name in CONTRACTIONS:
                    record(eqn, name, aval, ctx)
                elif aval.shape == ():
                    stats["scalar"][name] += 1
                elif pow2_ok:
                    stats["pow2"] += 1
                else:
                    record(eqn, name, aval, ctx)
            # Generic sub-jaxpr recursion: any equation param that is (or
            # contains) a Jaxpr is walked under this equation's context —
            # verified per primitive in test_analysis.py.
            for sub in sub_jaxprs(eqn):
                walk(sub, ctx + (name,))

    walk(open_jaxpr(jaxpr), ())
    sites = [f"{v.prim}@{v.site}" for v in violations]
    return {"tensor": dict(stats["tensor"]), "scalar": dict(stats["scalar"]),
            "pow2": stats["pow2"], "integer": stats["integer"],
            "tensor_total": sum(stats["tensor"].values()),
            "tensor_sites": sorted(set(sites)),
            "violations": [v.to_dict() for v in violations],
            "by_family": dict(by_family)}
