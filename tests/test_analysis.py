"""Unit tests for the PA static-analysis subsystem (repro.analysis):

  * jaxpr auditor — sub-jaxpr recursion (scan/while/cond/pjit/custom_jvp/
    shard_map), full frame-chain provenance, kernel-family attribution,
    failure-message localization of an injected multiply;
  * PA contract linter — all four rules, positive and negative;
  * compiled-HLO audit — synthetic HLO modules exercising pow2 resolution
    through broadcast chains, per-computation scoping, contraction and
    integer handling;
  * collective wire-bytes model — tuple operands, iota replica_groups,
    async -start/-done dedup, group-size-1 skip;
  * AUDIT.json schema validation (benchmarks.check_bench_schema);
  * removal of the retired repro.launch.hlo_stats shim.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (
    collective_stats,
    contract_lint,
    format_violations,
    hlo_mul_stats,
    jaxpr_mul_stats,
    leaf_family,
    site_family,
)
from repro.analysis.audit import MulSite, _out_aval


def _jx(f, *args):
    return jax.make_jaxpr(f)(*args)


X = jnp.linspace(-1.0, 1.0, 16, dtype=jnp.float32).reshape(4, 4)


# ---------------------------------------------------------------------------
# Provenance: frame chains, localization, family attribution.
# ---------------------------------------------------------------------------

def _inner_mul(a):          # the injected leak, two frames below the trace
    return a * a


def _outer(a):
    return jnp.sum(_inner_mul(a))


def test_injected_multiply_localized_to_file_line_and_family():
    stats = jaxpr_mul_stats(_jx(_outer, X))
    assert stats["tensor_total"] == 1
    (v,) = stats["violations"]
    assert v["prim"] == "mul"
    assert re.search(r"tests/test_analysis\.py:\d+$", v["site"]), v["site"]
    # full non-library chain: the helper AND its caller are both present
    assert len(v["frames"]) >= 2, v["frames"]
    assert all("test_analysis.py" in fr for fr in v["frames"][:2])
    assert v["family"] == "model-code"
    assert stats["by_family"] == {"model-code": 1}
    # the human failure message carries file:line + family (acceptance)
    msg = format_violations(stats)
    assert re.search(r"mul@tests/test_analysis\.py:\d+ \[model-code\]", msg)
    assert "from tests/test_analysis.py" in msg


def test_format_violations_clean_and_truncated():
    assert "clean" in format_violations({"violations": []})
    many = {"violations": [
        {"prim": "mul", "site": f"f.py:{i}", "family": "model-code",
         "context": [], "frames": []} for i in range(15)]}
    msg = format_violations(many, limit=10)
    assert "15 tensor-shaped" in msg and "5 more" in msg


def test_site_family_rules():
    assert site_family("src/repro/kernels/pam_optim/fused.py:10") == "pam_optim"
    assert site_family("src/repro/optim/adamw.py:5") == "pam_optim"
    assert site_family(
        "src/repro/kernels/flash_attention/ref.py:7") == "pam_attention"
    assert site_family("src/repro/models/attention.py:80") == "pam_attention"
    assert site_family("src/repro/kernels/pa_softmax/k.py:1") == "pam_attention"
    assert site_family("src/repro/kernels/pam_eltwise/k.py:1") == "pam_eltwise"
    assert site_family("src/repro/kernels/pam_matmul/k.py:1") == "pam_matmul"
    assert site_family("src/repro/kernels/pa_prims.py:33") == "pam_matmul"
    assert site_family("src/repro/core/matmul.py:12") == "pam_matmul"
    assert site_family("src/repro/models/rwkv.py:165") == "model-code"
    assert site_family("?") == "model-code"


def test_leaf_family_rules():
    assert leaf_family("['opt']['m']['layers']") == "pam_optim"
    assert leaf_family("params.layers.attn.wq") == "pam_attention"
    assert leaf_family("params.layers.mlp.w_in") == "pam_matmul"
    assert leaf_family("params.final_norm.scale") == "pam_eltwise"
    assert leaf_family("params.something_else") == "pam_matmul"


def test_mulsite_describe_roundtrip():
    s = MulSite(prim="div", site="a.py:1", frames=("a.py:1", "b.py:2"),
                family="model-code", context=("scan",), shape=(4,),
                dtype="float32")
    assert s.to_dict()["frames"] == ["a.py:1", "b.py:2"]
    assert "div@a.py:1" in s.describe() and "under scan" in s.describe()


def test_out_aval_robust_to_odd_outvar_layouts():
    class _Var:
        def __init__(self, aval):
            if aval is not None:
                self.aval = aval

    class _Aval:
        def __init__(self):
            self.dtype = np.float32
            self.shape = (2,)

    class _Eqn:
        pass

    e = _Eqn()
    e.outvars, e.invars = [], [_Var(_Aval())]     # no outputs at all
    assert _out_aval(e) is not None               # falls back to invars
    e2 = _Eqn()
    e2.outvars, e2.invars = [_Var(None)], []      # outvar without aval
    assert _out_aval(e2) is None                  # never raises


# ---------------------------------------------------------------------------
# Sub-jaxpr recursion and context chains.
# ---------------------------------------------------------------------------

def test_recursion_scan_context():
    def f(x):
        def body(c, t):
            return c, t * t
        return jax.lax.scan(body, 0.0, x)

    stats = jaxpr_mul_stats(_jx(f, X))
    assert stats["tensor_total"] == 1
    assert stats["violations"][0]["context"] == ["scan"]


def test_recursion_while_and_cond():
    def f(x):
        def body(c):
            v, i = c
            v = jax.lax.cond(i < 1, lambda a: a * a, lambda a: a + 1.0, v)
            return (v, i + 1)
        v, _ = jax.lax.while_loop(lambda c: c[1] < 2, body, (x, 0))
        return v

    stats = jaxpr_mul_stats(_jx(f, X))
    assert stats["tensor_total"] >= 1
    ctx = stats["violations"][0]["context"]
    assert "while" in ctx and "cond" in ctx, ctx


def test_recursion_pjit_and_custom_jvp():
    @jax.custom_jvp
    def sq(a):
        return a * a

    @sq.defjvp
    def _sq_jvp(primals, tangents):
        (a,), (da,) = primals, tangents
        return sq(a), 2.0 * a * da

    stats = jaxpr_mul_stats(_jx(jax.jit(lambda x: jnp.sum(sq(x))), X))
    assert stats["tensor_total"] >= 1
    ctx = stats["violations"][0]["context"]
    assert any(c in ("jit", "pjit") for c in ctx), ctx
    assert any("custom_jvp" in c for c in ctx), ctx


def test_recursion_shard_map():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    f = jax.shard_map(lambda x: x * x, mesh=mesh, in_specs=(P(),),
                      out_specs=P(), check_vma=False)
    stats = jaxpr_mul_stats(_jx(f, X))
    assert stats["tensor_total"] == 1
    assert any("shard_map" in c for c in stats["violations"][0]["context"])


# ---------------------------------------------------------------------------
# PA contract linter.
# ---------------------------------------------------------------------------

def test_lint_non_pow2_scalar_divisor():
    out = contract_lint(_jx(lambda x: x / 3.0, X))
    assert out["counts"].get("non_pow2_scalar_divisor") == 1
    (err,) = [e for e in out["errors"]
              if e["rule"] == "non_pow2_scalar_divisor"]
    assert err["prim"] == "div" and "3.0" in err["detail"]
    # pow2 divisor and scalar-shaped results stay clean
    assert not contract_lint(_jx(lambda x: x / 4.0, X))["errors"]
    assert not contract_lint(
        _jx(lambda s: s / 3.0, jnp.float32(7.0)))["errors"]


def test_lint_wrap_risk_literal():
    big = float(2.0 ** 70)
    out = contract_lint(_jx(lambda x: x * big, X))
    assert out["counts"].get("pam_wrap_risk_literal") == 1
    assert "2^129" in out["errors"][0]["detail"] \
        or "wrap" in out["errors"][0]["detail"]
    # below the 2^64 threshold: allowed
    ok = contract_lint(_jx(lambda x: x * float(2.0 ** 40 + 1), X))
    assert not any(e["rule"] == "pam_wrap_risk_literal" for e in ok["errors"])


def test_lint_bitcast_width_mismatch():
    def bad(x):
        # f32 -> int16 splits the word across a trailing dim: a cross-width
        # bitcast can never be a PA carrier view.
        return jax.lax.bitcast_convert_type(x, jnp.int16)

    out = contract_lint(_jx(bad, X))
    assert out["counts"].get("bitcast_width_mismatch") == 1
    assert "carrier" in out["errors"][0]["detail"]

    def good(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def good_bf16(x):
        # width-matched narrow-format carrier view: the bf16-native engine's
        # bread and butter, allowed since the FloatFormat refactor.
        return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.int16)

    assert not contract_lint(_jx(good, X))["errors"]
    assert not contract_lint(_jx(good_bf16, X))["errors"]


def test_lint_scalar_mul_in_scan_warns():
    def f(x):
        def body(c, t):
            return c * np.float32(0.9), jnp.sum(t)   # non-pow2 scalar decay
        return jax.lax.scan(body, jnp.float32(1.0), x)

    out = contract_lint(_jx(f, X))
    assert out["counts"].get("scalar_mul_in_scan") == 1
    assert not out["errors"]                          # warn-only rule
    assert "O(iterations)" in out["warnings"][0]["detail"]

    def f_pow2(x):
        def body(c, t):
            return c * np.float32(0.5), jnp.sum(t)   # exponent shift: exempt
        return jax.lax.scan(body, jnp.float32(1.0), x)

    assert not contract_lint(_jx(f_pow2, X))["warnings"]


# ---------------------------------------------------------------------------
# Compiled-HLO audit.
# ---------------------------------------------------------------------------

_HLO_MODULE = """
HloModule jit_f

ENTRY %main (p0: f32[4,4]) -> f32[4,4] {
  %p0 = f32[4,4] parameter(0)
  %half = f32[] constant(1.1920929e-07)
  %bh = f32[4,4] broadcast(%half), dimensions={}
  %ok = f32[4,4] multiply(%p0, %bh)
  %c3 = f32[] constant(3)
  %b3 = f32[4,4] broadcast(%c3), dimensions={}
  ROOT %bad = f32[4,4] multiply(%ok, %b3), metadata={op_name="jit(f)/mul" source_file="/w/src/repro/models/foo.py" source_line=42}
}
"""


def test_hlo_pow2_through_broadcast_and_f32_rounding():
    """2^-23 prints as 1.1920929e-07 — pow2 only after float32 rounding; the
    non-pow2 multiply is a violation with metadata provenance."""
    s = hlo_mul_stats(_HLO_MODULE)
    assert s["pow2"] == 1
    assert s["tensor_total"] == 1
    (v,) = s["violations"]
    assert v["prim"] == "multiply"
    assert v["site"] == "src/repro/models/foo.py:42"
    assert v["family"] == "model-code"
    assert v["op_name"] == "jit(f)/mul"
    assert v["shape"] == [4, 4] and v["dtype"] == "f32"


def test_hlo_divide_dot_integer_and_scalar():
    hlo = """
ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  %c4 = f32[] constant(4)
  %b4 = f32[8] broadcast(%c4), dimensions={0}
  %okdiv = f32[8] divide(%p, %b4)
  %baddiv = f32[8] divide(%b4, %p)
  %i = s32[8] multiply(%ip, %ip)
  %sc = f32[] multiply(%s, %s)
  ROOT %d = f32[] dot(%p, %p), lhs_contracting_dims={0}, rhs_contracting_dims={0}
}
"""
    s = hlo_mul_stats(hlo)
    assert s["pow2"] == 1                      # divide BY pow2 exempt
    assert s["tensor"].get("divide") == 1      # pow2 NUMERATOR is real work
    assert s["integer"] == 1                   # s32 multiply
    assert s["scalar"].get("multiply") == 1    # scalar elementwise exempt
    assert s["tensor"].get("dot") == 1         # scalar-shaped dot still counts
    assert s["tensor_total"] == 2


def test_hlo_resolution_scoped_per_computation():
    """Fusion bodies reuse names: a %c that is a pow2 constant in one
    computation must not exempt a multiply whose %c is a parameter in
    another."""
    hlo = """
%fused (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4] parameter(0)
  %c = f32[] constant(0.5)
  %bc = f32[4] broadcast(%c), dimensions={}
  ROOT %m = f32[4] multiply(%param_0, %bc)
}

ENTRY %main (p: f32[4], c: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  %c = f32[4] parameter(1)
  ROOT %m2 = f32[4] multiply(%p, %c)
}
"""
    s = hlo_mul_stats(hlo)
    assert s["pow2"] == 1 and s["tensor_total"] == 1


def test_hlo_rsqrt_never_exempt():
    hlo = """
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  ROOT %r = f32[4] rsqrt(%p)
}
"""
    assert hlo_mul_stats(hlo)["tensor"].get("rsqrt") == 1


# ---------------------------------------------------------------------------
# Collective wire-bytes model (satellite coverage).
# ---------------------------------------------------------------------------

def test_collective_stats_explicit_groups_and_tuple_operands():
    hlo = """
  %ar = f32[1024] all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %tup = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b), replica_groups={{0,1,2,3}}, to_apply=%add
"""
    s = collective_stats(hlo)
    assert s["all-reduce"]["count"] == 2
    # ring all-reduce: 2*(g-1)/g * bytes; 4096B and (512+256)B operands
    want = 2 * 0.75 * 4096 + 2 * 0.75 * (512 + 256)
    assert s["all-reduce"]["bytes"] == pytest.approx(want)
    assert s["total_bytes"] == pytest.approx(want)


def test_collective_stats_iota_groups_and_start_done_dedup():
    hlo = """
  %ag-start = f32[256]{0} all-gather-start(%x), replica_groups=[2,4]<=[8], dimensions={0}
  %ag-done = f32[256]{0} all-gather-done(%ag-start)
"""
    s = collective_stats(hlo)
    assert s["all-gather"]["count"] == 1          # -done half not re-counted
    assert s["all-gather"]["bytes"] == pytest.approx(0.75 * 1024)


def test_collective_stats_group_size_one_skipped():
    hlo = "  %ar = f32[64] all-reduce(%x), replica_groups={{0}}, to_apply=%a\n"
    s = collective_stats(hlo)
    assert "all-reduce" not in s and s["total_bytes"] == 0
    # collective-permute is point-to-point: counted even with no groups
    cp = "  %cp = f32[64] collective-permute(%x), source_target_pairs={{0,1}}\n"
    s2 = collective_stats(cp)
    assert s2["collective-permute"]["count"] == 1
    assert s2["collective-permute"]["bytes"] == 256


# ---------------------------------------------------------------------------
# AUDIT.json schema validation.
# ---------------------------------------------------------------------------

def _mini_absint():
    # Fresh (mutation-safe) v2 per-jaxpr-target sections.
    per_width = {
        "f32": {"mantissa_bits": 23, "rel_worst": 0.1111, "rel_mean": -0.038,
                "abs_worst": 7.2},
        "f16": {"mantissa_bits": 10, "rel_worst": 0.1131, "rel_mean": -0.038,
                "abs_worst": 7.4},
        "bf16": {"mantissa_bits": 7, "rel_worst": 0.1268, "rel_mean": -0.038,
                 "abs_worst": 8.1},
    }
    return {
        "range_safety": {"verdict": "safe", "pam_sites": 4, "padiv_sites": 1,
                         "wrap": 0, "overflow": 0, "denormal": 0,
                         "opaque_eqns": 0, "notes": [], "worst_sites": []},
        "error_certificates": {"per_width": per_width, "saturated": False,
                               "n_eqns": 100},
    }


def _mini_audit_report():
    from benchmarks.check_bench_schema import (_AUDIT_FAMILIES,
                                               audit_fingerprints)
    targets = {}
    for fam in _AUDIT_FAMILIES:
        for mode in ("approx", "full"):
            targets[f"{fam}/{mode}/train"] = {
                "kind": "jaxpr", "tensor_total": 0,
                "contract": {"errors": 0, "warnings": 0}, "pow2": 3,
                **_mini_absint()}
    targets["shard_map/train_dp"] = {
        "kind": "shard_map", "tensor_total": 0,
        "contract": {"errors": 0, "warnings": 0}, "pow2": 3,
        "collective_count": 14}
    targets["decoder/full/train@hlo"] = {
        "kind": "hlo", "tensor_total": 0,
        "contract": {"errors": 0, "warnings": 0}, "pow2": 3}
    for kind in ("train", "decode"):
        targets[f"decoder/full_bf16/{kind}"] = {
            "kind": "jaxpr", "tensor_total": 0,
            "contract": {"errors": 0, "warnings": 0}, "pow2": 3,
            "absint_twin": "f32",
            "bf16_native": {"within_certificate": True,
                            "ops": {"pam": {"measured_rel_worst": 0.11,
                                            "static_rel_bound": 0.1268}}},
            **_mini_absint()}
    return {"kind": "audit", "schema_version": 2,
            "generated_utc": "2026-08-08T00:00:00Z", "backend": "cpu",
            "device_count": 4, "families": list(_AUDIT_FAMILIES),
            "fingerprints": audit_fingerprints(),
            "declared_ranges": {"float_range": (-256.0, 256.0),
                                "float_mlo": 2.0 ** -24,
                                "activation_ceiling": 2.0 ** 32},
            "targets": targets,
            "totals": {"targets": len(targets), "tensor_total": 0,
                       "contract_errors": 0, "pow2": 3 * len(targets),
                       "pam_sites": 4 * 2 * len(_AUDIT_FAMILIES), "wrap": 0,
                       "violating_targets": []}}


def test_audit_schema_accepts_clean_report():
    from benchmarks.check_bench_schema import validate_audit_report
    assert validate_audit_report(_mini_audit_report()) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda r: r["targets"]["rwkv/full/train"].update(
        tensor_total=2, tensor_sites=["mul@core/nn.py:152"]), "regressed"),
    (lambda r: r["targets"].pop("hybrid/approx/train"), "missing coverage"),
    (lambda r: r["targets"].pop("shard_map/train_dp"),
     "no shard_map multi-device target"),
    (lambda r: r["targets"]["shard_map/train_dp"].update(collective_count=0),
     "vacuous"),
    (lambda r: r["targets"].pop("decoder/full/train@hlo"),
     "no compiled-HLO-verified target"),
    (lambda r: r["targets"].pop("decoder/full_bf16/decode"),
     "bf16-native engines"),
    (lambda r: r["targets"]["decoder/full_bf16/train"].pop("bf16_native"),
     "measured-error block"),
    (lambda r: r["targets"]["decoder/full_bf16/train"]["bf16_native"]
     .update(within_certificate=False), "exceeds"),
    (lambda r: r["targets"]["decoder/full/train"]["contract"].update(
        errors=1), "PA-contract errors"),
    (lambda r: r["totals"].update(tensor_total=5), "!= sum over targets"),
    (lambda r: r["fingerprints"].pop("analysis"), "fingerprints missing"),
    (lambda r: r.update(schema_version=1), "schema_version"),
    (lambda r: r.pop("declared_ranges"), "declared_ranges"),
    (lambda r: r["targets"]["rwkv/approx/train"].pop("range_safety"),
     "missing 'range_safety'"),
    (lambda r: r["targets"]["decoder/full/train"]["range_safety"].update(
        wrap=2, verdict="wrap"), "PAM-wrap"),
    (lambda r: r["targets"]["hybrid/full/train"]["range_safety"].update(
        pam_sites=0), "went blind"),
    (lambda r: r["targets"]["decoder/full/train"]["error_certificates"]
     ["per_width"]["bf16"].update(rel_worst=0.01), "not monotone"),
    (lambda r: r["targets"]["encdec/full/train"]["error_certificates"]
     ["per_width"]["f16"].update(rel_worst=float("inf")),
     "finite and >= 0"),
    (lambda r: r["targets"]["vision_lm/full/train"].pop(
        "error_certificates"), "missing 'error_certificates'"),
])
def test_audit_schema_rejects_mutations(mutate, needle):
    from benchmarks.check_bench_schema import validate_audit_report
    rep = _mini_audit_report()
    mutate(rep)
    errs = validate_audit_report(rep)
    assert errs and any(needle in e for e in errs), (needle, errs)


def test_audit_file_staleness_detected(tmp_path):
    import json
    from benchmarks.check_bench_schema import validate_audit_file
    rep = _mini_audit_report()
    rep["fingerprints"]["analysis"] = "0" * 16
    p = tmp_path / "AUDIT.json"
    p.write_text(json.dumps(rep))
    errs = validate_audit_file(str(p))
    assert any("stale" in e and "make audit" in e for e in errs), errs


# ---------------------------------------------------------------------------
# Shim removal (the launch/hlo_stats deprecation shim shipped its
# DeprecationWarning for one PR and is now gone).
# ---------------------------------------------------------------------------

def test_launch_hlo_stats_shim_removed():
    import importlib
    import pytest as _pytest
    with _pytest.raises(ImportError):
        importlib.import_module("repro.launch.hlo_stats")
