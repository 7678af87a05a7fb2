import os
os.environ["JAX_PLATFORMS"] = "cpu"     # CPU-only tool: never take the chip
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf-iteration harness (§Perf): lower one cell with config overrides and
report its roofline terms — one command per hypothesis->change->measure
cycle. Appends every measurement to experiments/perf_log.jsonl.

  PYTHONPATH=src python -m repro.launch.perf --arch llama3.2-1b \
      --shape train_4k --set attn_softmax_dtype=bfloat16 --tag bf16-softmax
"""
import argparse
import json
import time

from repro.configs import SHAPES, get_config
from repro.models import build_model
from repro.launch.dryrun import DRY_PA, lower_cell, analyse, build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyse_cell, _LAYERS


def measure(arch: str, shape_name: str, overrides: dict, microbatches: int = 1):
    mesh = make_production_mesh(multi_pod=False)
    shape = SHAPES[shape_name]
    cell = {"arch": arch, "shape": shape_name, "status": "ok",
            "params_total": 0, "params_active": 0}

    def make_model(depth=None, scan=True):
        cfg = get_config(arch, pa=DRY_PA)
        if depth is not None:
            kw = {"n_layers": depth, "scan_layers": scan}
            if cfg.family == "vision_lm":
                kw["n_layers"] = depth * cfg.cross_attn_every
            if cfg.global_layers:
                kw["global_layers"] = tuple(i for i in cfg.global_layers
                                            if i < kw["n_layers"])
            if cfg.n_enc_layers:
                kw["n_enc_layers"] = min(cfg.n_enc_layers, max(1, depth))
            cfg = cfg.replace(**kw)
        if overrides:
            cfg = apply_overrides(cfg, overrides)
        return build_model(cfg)

    from repro.launch.dryrun import param_counts
    model = make_model()
    cell["params_total"], cell["params_active"] = param_counts(model)

    def scale_mb(a: dict) -> dict:
        # the microbatch loop is a lax.scan whose body cost_analysis counts
        # once -> scale flops/bytes/collectives linearly (slightly
        # overcounts the once-per-step optimizer+grad-reduce tail).
        if microbatches <= 1:
            return a
        a = dict(a)
        a["cost"] = {k: v * microbatches for k, v in a["cost"].items()}
        colls = {}
        for k, v in a["collectives"].items():
            if isinstance(v, dict):
                colls[k] = {"count": v["count"],
                            "bytes": v["bytes"] * microbatches}
            else:
                colls[k] = v * microbatches
        a["collectives"] = colls
        return a

    t0 = time.time()
    lowered = lower_cell(model, shape, mesh, microbatches=microbatches)
    compiled = lowered.compile()
    cell["compile_s"] = round(time.time() - t0, 2)
    cell.update(scale_mb(analyse(compiled, mesh)))
    for d in (1, 2):
        m_d = make_model(depth=d, scan=False)
        comp = lower_cell(m_d, shape, mesh, microbatches=microbatches).compile()
        cell[f"depth{d}"] = scale_mb(analyse(comp, mesh))
    return cell


def apply_overrides(cfg, overrides: dict):
    import dataclasses
    kw = {}
    moe_kw = {}
    for k, v in overrides.items():
        if k.startswith("moe."):
            moe_kw[k[4:]] = v
        else:
            kw[k] = v
    if moe_kw and cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, **moe_kw)
    return cfg.replace(**kw)


def _parse_val(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v in ("true", "True"):
        return True
    if v in ("false", "False"):
        return False
    return v


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--set", nargs="*", default=[], metavar="K=V")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--log", default="experiments/perf_log.jsonl")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = _parse_val(v)

    cell = measure(args.arch, args.shape, overrides, args.microbatches)
    r = analyse_cell(cell)
    rec = {"tag": args.tag, "arch": args.arch, "shape": args.shape,
           "overrides": overrides, "microbatches": args.microbatches,
           "compute_s": r["compute_s"], "memory_s": r["memory_s"],
           "collective_s": r["collective_s"], "dominant": r["dominant"],
           "useful_ratio": r["useful_ratio"], "mfu_bound": r["mfu_bound"],
           "peak_gib": r["peak_gib"], "compile_s": cell["compile_s"]}
    with open(args.log, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(f"[perf] {args.tag}: compute={r['compute_s']:.3f}s "
          f"memory={r['memory_s']:.3f}s collective={r['collective_s']:.3f}s "
          f"dominant={r['dominant']} mfu_bound={r['mfu_bound']:.2%} "
          f"peak={r['peak_gib']:.1f}GiB useful={r['useful_ratio']:.2f}")


if __name__ == "__main__":
    main()
