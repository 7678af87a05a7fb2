"""Whole-repo multiplication-audit sweep (`make audit`, DESIGN.md §9).

Audits every registry family x PA mode across the hot programs — train
step, fused/unfused attention, optimizer update, continuous-engine
decode+sample — plus the shard_map multi-device checks and one
compiled-HLO target, and writes the machine-readable ``AUDIT.json``
baseline at the repo root. ``benchmarks/check_bench_schema.py`` validates
the committed file (schema + source-fingerprint freshness + every
tensor_total still zero) in the default test tier, so a PR that
re-introduces a multiply or lets the baseline go stale fails `make test`.

Traces are abstract where possible (``model.abstract()`` params,
``input_specs`` batches — no real arrays, so the full sweep is seconds
per target); the decode targets build a real tiny engine (the slot cache
is concrete state), and the HLO target pays one real XLA compile.

This module forces ``--xla_force_host_platform_device_count=4`` at import
(before jax initialises) so the in-process shard_map targets see a
4-device mesh — run it as its own process::

    PYTHONPATH=src python -m repro.launch.audit [--check|--lint] [--out PATH]

Every jaxpr target additionally carries abstract-interpretation sections
(``repro.analysis.absint``, DESIGN.md §10): ``range_safety`` — the
wrap/overflow/denormal reachability verdict under the declared input
ranges (``DECLARED_RANGES``) — and ``error_certificates`` — worst-case /
expected end-to-end PA relative-error bounds per mantissa width (f32,
f16, bf16 side by side). ``--lint`` runs the contract lint + range
analysis alone (`make lint-pa`): no decode-engine build, no shard_map
subprocess, no XLA compile, no file written.

Exit status is nonzero if any target shows a tensor-shaped multiply, a
PA-contract error, or a reachable unguarded PAM wrap; the failure message
localizes each violation to file:line and kernel family
(``analysis.audit.format_violations``).
"""
from __future__ import annotations

import os

# A CPU-only tool: pin the platform before jax initialises, so it never
# takes an accelerator from the process that owns it.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           ).strip()

import argparse
import datetime
import json
import sys
from typing import Dict

import jax

from repro.analysis import (analyze_jaxpr, contract_lint, format_violations,
                            hlo_mul_stats, jaxpr_mul_stats)

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     "..", "..", ".."))

# One representative assigned arch per registry family (configs/ARCHS).
FAMILY_ARCHS = {
    "decoder": "smollm-135m",
    "rwkv": "rwkv6-7b",
    "hybrid": "hymba-1.5b",
    "encdec": "whisper-tiny",
    "vision_lm": "llama-3.2-vision-90b",
}

# Both are mode="full" (the paper's fully multiplication-free regime);
# they differ in the backward variant (Table 3's exact vs approx derivs),
# which traces different backward programs and must BOTH audit to zero.
PA_MODES = {
    "full": dict(mode="full", deriv="exact", loss_deriv="exact"),
    "approx": dict(mode="full", deriv="approx", loss_deriv="exact"),
}

_OPT_KW = dict(peak_lr=3e-3, warmup_steps=5, total_steps=30)

# Declared input-range assumptions for the abstract interpreter
# (DESIGN.md §10). Every float program input — activations, params, grads,
# optimizer state — is assumed within this range with nonzero magnitudes
# no smaller than mlo; values the program PRODUCES are additionally
# assumed under the ±2^32 activation ceiling that the runtime exponent
# sentinels enforce (resilience/detectors.py). The range_safety verdicts
# and error_certificates in AUDIT.json are conditional on exactly these
# assumptions, and the seeded-violation tests in tests/test_absint.py
# prove the verdicts are not vacuous under wider declarations.
DECLARED_RANGES = {
    "float_range": (-256.0, 256.0),
    "float_mlo": 2.0 ** -24,
    "activation_ceiling": 2.0 ** 32,
}


# The bf16-native FloatFormat regime (core/floatbits.py): the program
# runs the int16-carrier engines end to end. Approx derivs everywhere —
# the exact-derivative factors are f32-only by design.
BF16_PA = dict(mode="full", deriv="approx", loss_deriv="approx",
               fmt="bf16")


def _pa(mode_key: str):
    from repro.core import PAConfig
    if mode_key == "full_bf16":
        return PAConfig(**BF16_PA)
    if mode_key == "f32_twin":
        # Same PA program as BF16_PA, f32 carrier — the absint twin.
        return PAConfig(**{**BF16_PA, "fmt": "f32"})
    return PAConfig(**PA_MODES[mode_key])


def _smoke_model(family: str, mode_key: str, **overrides):
    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config(FAMILY_ARCHS[family], pa=_pa(mode_key))
    if overrides:
        cfg = cfg.replace(**overrides)
    return build_model(cfg)


def _abstract_state(model):
    from repro.optim import OptConfig, init_opt_state
    opt_cfg = OptConfig(**_OPT_KW)
    params = model.abstract()
    opt_state = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params)
    return opt_cfg, params, opt_state


def _entry(stats: Dict, lint: Dict, kind: str, **extra) -> Dict:
    out = {
        "kind": kind,
        "tensor_total": stats["tensor_total"],
        "tensor": stats["tensor"],
        "tensor_sites": stats["tensor_sites"],
        "pow2": stats["pow2"],
        "integer": stats["integer"],
        "scalar_mul": sum(stats["scalar"].values()),
        "by_family": stats.get("by_family", {}),
        "contract": {"errors": len(lint["errors"]),
                     "warnings": len(lint["warnings"]),
                     "counts": lint["counts"]},
    }
    if stats["tensor_total"]:
        out["violations"] = stats["violations"]
    if lint["errors"]:
        out["contract"]["error_details"] = lint["errors"]
    out.update(extra)
    return out


def _analyze_entry(jaxpr) -> Dict:
    """Abstract-interpretation sections for one jaxpr target: the
    wrap/overflow/denormal reachability verdict and the per-mantissa-width
    PA error certificate (DESIGN.md §10)."""
    rep = analyze_jaxpr(jaxpr,
                        float_range=DECLARED_RANGES["float_range"],
                        float_mlo=DECLARED_RANGES["float_mlo"])
    return {"range_safety": rep.range_safety(),
            "error_certificates": rep.certificate()}


def _audit_jaxpr(jaxpr, kind: str = "jaxpr", **extra) -> Dict:
    out = _entry(jaxpr_mul_stats(jaxpr), contract_lint(jaxpr), kind, **extra)
    out.update(_analyze_entry(jaxpr))
    return out


# -- target builders --------------------------------------------------------

def train_jaxpr(model, microbatches: int = 1, batch: int = 4,
                seq_len: int = 16):
    from repro.train import TrainConfig, make_train_step
    opt_cfg, params, opt_state = _abstract_state(model)
    step = make_train_step(model, opt_cfg,
                           TrainConfig(microbatches=microbatches))
    specs = model.input_specs(batch, seq_len, "train")
    return jax.make_jaxpr(step)(params, opt_state, specs)


def optim_jaxpr(model):
    from repro.optim import adamw_update
    opt_cfg, params, opt_state = _abstract_state(model)
    fn = lambda p, g, s: adamw_update(p, g, s, opt_cfg, pa=model.cfg.pa)
    return jax.make_jaxpr(fn)(params, params, opt_state)


def attention_jaxpr(family: str, mode_key: str, fused: bool):
    model = _smoke_model(family, mode_key, attn_fused_pam=fused)
    params = model.abstract()
    specs = model.input_specs(4, 16, "train")
    return jax.make_jaxpr(jax.value_and_grad(model.loss))(params, specs)


def decode_jaxpr(model):
    """Fused decode+sample step of a real (tiny) continuous engine,
    temperature > 0 so the PA Gumbel-argmax sampler is in the program."""
    from repro.serve.continuous import ContinuousEngine
    from repro.serve.engine import ServeConfig
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(model, params,
                           ServeConfig(n_slots=2, max_len=32,
                                       temperature=1.0))
    return eng.decode_step_jaxpr()


def bf16_measured_block() -> Dict:
    """Measured error of the LIVE bf16-native engines against the static
    bf16 certificates (ISSUE 10 acceptance): for each primitive, run the
    int16-carrier op on random bf16 operands and compare against the exact
    real-arithmetic result of the SAME (exactly-embedded) values. The
    per-op measured worst relative error must sit within the analyzer's
    static per-width bound (single-op certificate: EPS_*_WORST +
    quant_eps(man_bits) output rounding)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.analysis.domains import (EPS_PAM_WORST, EPS_PADIV_WORST,
                                        quant_eps)
    from repro.core import floatbits as fb
    from repro.core.pam import pam_value, padiv_value

    mb = fb.BFLOAT16.man_bits
    rng = np.random.default_rng(0)
    n = 1 << 14

    def draw():
        mag = np.exp(rng.uniform(np.log(2.0 ** -24), np.log(256.0), n))
        x = (rng.choice([-1.0, 1.0], n) * mag).astype(np.float32)
        return jnp.asarray(x, jnp.bfloat16)

    a, b = draw(), draw()
    a32 = np.asarray(a.astype(jnp.float32))
    b32 = np.asarray(b.astype(jnp.float32))

    def rel_worst(got, exact):
        got = np.asarray(got.astype(jnp.float32), np.float64)
        exact = np.asarray(exact, np.float64)
        nz = exact != 0
        return float(np.max(np.abs(got[nz] - exact[nz])
                            / np.abs(exact[nz])))

    ops = {
        "pam": (rel_worst(pam_value(a, b), a32.astype(np.float64) * b32),
                float(EPS_PAM_WORST + quant_eps(mb))),
        "padiv": (rel_worst(padiv_value(a, b),
                            a32.astype(np.float64) / b32),
                  float(EPS_PADIV_WORST + quant_eps(mb))),
    }
    out = {"samples": int(n), "mantissa_bits": int(mb), "ops": {}}
    ok = True
    for op, (measured, static) in ops.items():
        within = measured <= static
        ok = ok and within
        out["ops"][op] = {"measured_rel_worst": measured,
                          "static_rel_worst": static,
                          "within_certificate": bool(within)}
    out["within_certificate"] = bool(ok)
    return out


def hlo_train_entry() -> Dict:
    """Compiled-HLO audit of the full-PA decoder train step (ROADMAP item
    5's honest form of the claim): what XLA emits after fusion, not what
    we staged. One layer / short sequence to bound compile time."""
    from repro.train import TrainConfig, make_train_step
    model = _smoke_model("decoder", "full", n_layers=1, max_seq_len=32)
    opt_cfg, params, opt_state = _abstract_state(model)
    step = make_train_step(model, opt_cfg, TrainConfig())
    specs = model.input_specs(4, 16, "train")
    text = jax.jit(step).lower(params, opt_state, specs).compile().as_text()
    stats = hlo_mul_stats(text)
    return _entry(stats, {"errors": [], "warnings": [], "counts": {}},
                  "hlo", arch=FAMILY_ARCHS["decoder"], pa_mode="full",
                  hlo_bytes=len(text))


def sweep(log=print) -> Dict:
    """Run every audit target; returns the AUDIT.json report body."""
    targets: Dict[str, Dict] = {}

    for family in FAMILY_ARCHS:
        for mode_key in PA_MODES:
            arch = FAMILY_ARCHS[family]
            meta = dict(arch=arch, pa_mode=mode_key)
            model = _smoke_model(family, mode_key)
            targets[f"{family}/{mode_key}/train"] = _audit_jaxpr(
                train_jaxpr(model), **meta)
            targets[f"{family}/{mode_key}/optim"] = _audit_jaxpr(
                optim_jaxpr(model), **meta)
            targets[f"{family}/{mode_key}/decode"] = _audit_jaxpr(
                decode_jaxpr(model), **meta)
            log(f"audit: {family}/{mode_key} train/optim/decode done")

    # Non-pow2 microbatch count: gradient averaging is a PAM by 1/n, the
    # historically leaky path (PR 4) — keep it pinned in the baseline.
    targets["decoder/full/train_micro3"] = _audit_jaxpr(
        train_jaxpr(_smoke_model("decoder", "full"), microbatches=3,
                    batch=6),
        arch=FAMILY_ARCHS["decoder"], pa_mode="full")

    # Fused PAM flash attention dispatches only under approx derivs
    # (models/attention._fused_pam_ok); audit both compositions.
    targets["decoder/approx/attn_fused"] = _audit_jaxpr(
        attention_jaxpr("decoder", "approx", fused=True),
        arch=FAMILY_ARCHS["decoder"], pa_mode="approx", attn_fused_pam=True)
    targets["decoder/approx/attn_unfused"] = _audit_jaxpr(
        attention_jaxpr("decoder", "approx", fused=False),
        arch=FAMILY_ARCHS["decoder"], pa_mode="approx", attn_fused_pam=False)
    log("audit: attention + microbatch targets done")

    # shard_map multi-device checks (grad psum + norm all-reduce + sharded
    # decode) — the module shares this process's forced 4-device platform.
    from repro.analysis.shard_check import run_checks
    shard = run_checks(execute=False)
    for name, chk in shard["checks"].items():
        targets[f"shard_map/{name}"] = {
            "kind": "shard_map", "arch": FAMILY_ARCHS["decoder"],
            "pa_mode": "approx",
            "tensor_total": chk["tensor_total"], "tensor": chk["tensor"],
            "tensor_sites": chk["tensor_sites"], "pow2": chk["pow2"],
            "integer": chk["integer"], "by_family": chk["by_family"],
            "collective_count": chk["collective_count"],
            "contract": {"errors": 0, "warnings": 0, "counts": {}},
        }
        if chk["tensor_total"]:
            targets[f"shard_map/{name}"]["violations"] = chk["violations"]
    log(f"audit: shard_map checks done "
        f"(devices={shard['device_count']}, ok={shard['ok']})")

    # bf16-native FloatFormat targets (ISSUE 10): stats + contract lint run
    # on the NATIVE int16-carrier program — zero tensor multiplies with
    # bf16 activations end to end. The abstract interpreter's bit domain is
    # the f32/int32 layout, so the range_safety / error_certificates
    # sections come from the f32 TWIN of the same model (identical PA
    # program, f32 carrier; its per_width["bf16"] entry IS the static bf16
    # certificate), and a measured block checks the live bf16 engines
    # against the static single-op certificates.
    measured = bf16_measured_block()
    bf16_model = _smoke_model("decoder", "full_bf16")
    twin_model = _smoke_model("decoder", "f32_twin")
    for kind, build in (("train", train_jaxpr), ("decode", decode_jaxpr)):
        native = build(bf16_model)
        ent = _entry(jaxpr_mul_stats(native), contract_lint(native), "jaxpr",
                     arch=FAMILY_ARCHS["decoder"], pa_mode="full_bf16",
                     fmt="bf16")
        ent.update(_analyze_entry(build(twin_model)))
        ent["absint_twin"] = "f32"
        ent["bf16_native"] = measured
        targets[f"decoder/full_bf16/{kind}"] = ent
    log("audit: bf16-native targets done "
        f"(measured within certificate: {measured['within_certificate']})")

    targets["decoder/full/train@hlo"] = hlo_train_entry()
    log("audit: compiled-HLO target done")

    violating = sorted(
        n for n, t in targets.items()
        if t["tensor_total"] or t["contract"]["errors"]
        or t.get("range_safety", {}).get("wrap", 0))
    report = {
        "kind": "audit",
        "schema_version": 2,
        "declared_ranges": dict(DECLARED_RANGES),
        "generated_utc":
            datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "families": sorted(FAMILY_ARCHS),
        "pa_modes": sorted(PA_MODES),
        "targets": targets,
        "totals": {
            "targets": len(targets),
            "tensor_total": sum(t["tensor_total"] for t in targets.values()),
            "contract_errors": sum(t["contract"]["errors"]
                                   for t in targets.values()),
            "pow2": sum(t["pow2"] for t in targets.values()),
            "pam_sites": sum(
                t.get("range_safety", {}).get("pam_sites", 0)
                for t in targets.values()),
            "wrap": sum(t.get("range_safety", {}).get("wrap", 0)
                        for t in targets.values()),
            "violating_targets": violating,
        },
    }
    from benchmarks.check_bench_schema import audit_fingerprints
    report["fingerprints"] = audit_fingerprints()
    return report


def lint_sweep(log=print) -> int:
    """Fast standalone gate (`make lint-pa`): PA contract lint + range
    analysis over the traced hot programs — no decode-engine build, no
    shard_map subprocess, no XLA compile, no file written. Returns the
    number of failing targets (contract errors or reachable PAM wrap)."""
    failed = 0
    for family in FAMILY_ARCHS:
        for mode_key in PA_MODES:
            model = _smoke_model(family, mode_key)
            for kind, jx in (("train", train_jaxpr(model)),
                             ("optim", optim_jaxpr(model))):
                lint = contract_lint(jx)
                an = _analyze_entry(jx)
                rs = an["range_safety"]
                bad = bool(lint["errors"]) or rs["wrap"] > 0
                failed += bad
                log(f"lint-pa: {family}/{mode_key}/{kind} "
                    f"verdict={rs['verdict']} pam_sites={rs['pam_sites']} "
                    f"wrap={rs['wrap']} contract_errors="
                    f"{len(lint['errors'])}"
                    f"{'  FAIL' if bad else ''}")
                if bad:
                    for err in lint["errors"]:
                        log(f"  contract {err['rule']}@{err['site']}: "
                            f"{err['detail']}")
                    for s in rs["worst_sites"]:
                        if s["e_hi"] >= 129 and not s["guarded"]:
                            log(f"  wrap {s['kind']}@{s['site']} "
                                f"e=[{s['e_lo']},{s['e_hi']}]")
    return failed


def _write_if_changed(report: Dict, path: str) -> bool:
    """Write the report unless it matches the existing file modulo the
    generation timestamp — keeps `make audit` idempotent in `make test`."""
    def stable(r):
        return {k: v for k, v in r.items() if k != "generated_utc"}
    try:
        with open(path) as f:
            old = json.load(f)
        if stable(old) == json.loads(json.dumps(stable(report))):
            return False
    except (OSError, ValueError):
        pass
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="whole-repo multiplication-audit sweep -> AUDIT.json")
    ap.add_argument("--out", default=os.path.join(_ROOT, "AUDIT.json"))
    ap.add_argument("--check", action="store_true",
                    help="audit only; do not write AUDIT.json")
    ap.add_argument("--lint", action="store_true",
                    help="fast mode: contract lint + range analysis only "
                         "(no decode engine, no shard_map, no compile, "
                         "no AUDIT.json write)")
    ns = ap.parse_args(argv)

    if ns.lint:
        return 1 if lint_sweep() else 0

    report = sweep()
    totals = report["totals"]
    failed = bool(totals["violating_targets"])
    if failed:
        for name in totals["violating_targets"]:
            t = report["targets"][name]
            print(f"audit: FAIL {name}", file=sys.stderr)
            if t["tensor_total"]:
                print(format_violations(t), file=sys.stderr)
            for err in t["contract"].get("error_details", []):
                print(f"  contract {err['rule']}@{err['site']}: "
                      f"{err['detail']}", file=sys.stderr)
    if not ns.check:
        wrote = _write_if_changed(report, ns.out)
        print(f"audit: {totals['targets']} targets, "
              f"tensor_total={totals['tensor_total']}, "
              f"contract_errors={totals['contract_errors']}, "
              f"pow2_exemptions={totals['pow2']} -> "
              f"{os.path.basename(ns.out)}"
              f" ({'updated' if wrote else 'unchanged'})")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
