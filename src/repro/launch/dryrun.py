import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The three lines above MUST stay the first statements in this module — jax
locks the platform and the device count at first initialisation, the
production meshes need 512 placeholder CPU devices (2 pods x 16 x 16), and
a CPU-only tool must never take an accelerator.

For each cell this:
  1. builds the full-scale model (PA mode "full", impl "hw": the PAM-MXU
     dataflow stand-in — see DESIGN.md §3),
  2. jits the appropriate step (train_step / prefill / serve decode step)
     with in_shardings from the sharding rule engine,
  3. ``.lower(**abstract inputs).compile()`` — success proves the
     distribution config is coherent (shardings compose, collectives
     legal, memory analysable) on both the 16x16 and 2x16x16 meshes,
  4. records memory_analysis / cost_analysis / parsed collective bytes,
     plus unrolled depth-1/-2 variants for the roofline's per-layer
     extrapolation (scan bodies are counted once by cost_analysis).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import argparse
import json
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import PAConfig
from repro.configs import (ARCHS, ASSIGNED, SHAPES, get_config,
                           get_optimized_config, skip_reason)
from repro.models import build_model, abstract_params
from repro.models.registry import Model
from repro.optim import OptConfig, opt_state_meta
from repro.parallel.sharding import tree_shardings, tree_pspecs
from repro.train import make_train_step
from .mesh import make_production_mesh
from repro.analysis import collective_stats

DRY_PA = PAConfig(mode="full", impl="hw")


def _abstract(meta_tree):
    return abstract_params(meta_tree)


def _replicated(mesh):
    return NamedSharding(mesh, P())


def param_counts(model: Model):
    """(total, active) parameter counts; active discounts MoE experts."""
    cfg = model.cfg
    total = active = 0
    def walk(tree, in_moe):
        nonlocal total, active
        if hasattr(tree, "axes"):
            n = int(np.prod(tree.shape))
            total += n
            if in_moe and "expert" in tree.axes:
                active += n * cfg.moe.top_k // cfg.moe.num_experts
            else:
                active += n
            return
        for k, v in tree.items():
            walk(v, in_moe or k == "moe")
    walk(model.meta(), False)
    return total, active


def build_cell(arch: str, shape_name: str, *, depth=None, scan=True,
               optimized=False):
    """Model + step fn + abstract args + shardings for one cell."""
    shape = SHAPES[shape_name]
    cfg = (get_optimized_config(arch, pa=DRY_PA) if optimized
           else get_config(arch, pa=DRY_PA))
    if depth is not None:
        kw = {"n_layers": depth, "scan_layers": scan}
        if cfg.family == "vision_lm":
            kw["n_layers"] = depth * cfg.cross_attn_every
        if cfg.global_layers:
            kw["global_layers"] = tuple(i for i in cfg.global_layers if i < kw["n_layers"])
        if cfg.n_enc_layers:
            kw["n_enc_layers"] = min(cfg.n_enc_layers, max(1, depth))
        cfg = cfg.replace(**kw)
    model = build_model(cfg)
    return model, shape


def lower_cell(model: Model, shape, mesh, opt_cfg=None, microbatches: int = 1):
    """Returns (lowered, meta) for the cell's step on the mesh."""
    cfg = model.cfg
    opt_cfg = opt_cfg or OptConfig(moment_dtype="bfloat16" if cfg.fsdp else "float32")
    p_sh = tree_shardings(model.meta(), mesh, cfg.rules)
    p_abs = _abstract(model.meta())

    if shape.phase == "train":
        o_meta = opt_state_meta(model.meta(), opt_cfg)
        o_sh = tree_shardings(o_meta, mesh, cfg.rules)
        o_abs = _abstract(o_meta)
        b_abs = model.input_specs(shape.global_batch, shape.seq_len, "train")
        b_sh = {k: NamedSharding(mesh, s)
                for k, s in model.batch_pspecs(b_abs, mesh).items()}
        from repro.train import TrainConfig
        step = make_train_step(model, opt_cfg, TrainConfig(microbatches=microbatches))
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     donate_argnums=(0, 1))
        with mesh:
            return fn.lower(p_abs, o_abs, b_abs)

    if shape.phase == "prefill":
        c_meta = model.cache_meta(shape.global_batch, shape.seq_len)
        c_sh = tree_shardings(c_meta, mesh, cfg.rules)
        b_abs = model.input_specs(shape.global_batch, shape.seq_len, "prefill")
        b_sh = {k: NamedSharding(mesh, s)
                for k, s in model.batch_pspecs(b_abs, mesh).items()}
        fn = jax.jit(model.prefill, in_shardings=(p_sh, b_sh, c_sh),
                     donate_argnums=(2,))
        with mesh:
            return fn.lower(p_abs, b_abs, _abstract(c_meta))

    # decode: one new token against a seq_len-deep cache
    c_meta = model.cache_meta(shape.global_batch, shape.seq_len)
    c_sh = tree_shardings(c_meta, mesh, cfg.rules)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    from repro.parallel.sharding import spec_for
    tok_sh = NamedSharding(mesh, spec_for((shape.global_batch, 1),
                                          ("batch", None), mesh, cfg.rules))
    fn = jax.jit(model.decode,
                 in_shardings=(p_sh, c_sh, tok_sh, _replicated(mesh)),
                 donate_argnums=(1,))
    with mesh:
        return fn.lower(p_abs, _abstract(c_meta), tok, pos)


def analyse(compiled, mesh) -> dict:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):        # older jax returns [dict]
        ca = ca[0] if ca else {}
    txt = compiled.as_text()
    colls = collective_stats(txt)
    return {
        "chips": mesh.devices.size,
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device_gib": (ma.argument_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    + ma.output_size_in_bytes
                                    - ma.alias_size_in_bytes) / 2**30,
        },
        "cost": {"flops": float(ca.get("flops", 0.0)),
                 "bytes_accessed": float(ca.get("bytes accessed", 0.0))},
        "collectives": colls,
    }


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                with_depth_variants: bool = True, optimized: bool = False) -> dict:
    reason = skip_reason(arch, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skip", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    out = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape)}
    try:
        model, shape = build_cell(arch, shape_name, optimized=optimized)
        total, active = param_counts(model)
        out["params_total"] = total
        out["params_active"] = active
        t0 = time.time()
        lowered = lower_cell(model, shape, mesh)
        out["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t0, 2)
        out.update(analyse(compiled, mesh))
        out["status"] = "ok"

        if with_depth_variants and not multi_pod:
            # unrolled depth-1/-2 at full width: per-layer costs for the
            # roofline's scan-body correction (cost_analysis counts the
            # scanned body once).
            for d in (1, 2):
                m_d, _ = build_cell(arch, shape_name, depth=d, scan=False,
                                    optimized=optimized)
                low = lower_cell(m_d, shape, mesh)
                comp = low.compile()
                out[f"depth{d}"] = analyse(comp, mesh)
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        out["status"] = "fail"
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback"] = traceback.format_exc()[-2000:]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs x shapes x both meshes")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-depth-variants", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the confirmed perf profile (§Perf)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ASSIGNED:
            for shape in SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape, args.multi_pod)]

    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[dryrun] {tag}: cached")
            continue
        res = dryrun_cell(arch, shape, mp,
                          with_depth_variants=not args.no_depth_variants,
                          optimized=args.optimized)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        line = res.get("reason") or (
            f"status={res['status']} compile={res.get('compile_s')}s "
            f"peak={res.get('memory', {}).get('peak_per_device_gib', 0):.2f}GiB "
            f"coll={res.get('collectives', {}).get('total_bytes', 0)/2**20:.1f}MiB")
        print(f"[dryrun] {tag}: {line}")
        if res["status"] == "fail":
            print(res.get("error"))


if __name__ == "__main__":
    main()
