"""The system under test, as the benchmark sees it: the program's model
built from a configuration file, checked against the sizes the file
states. Nothing else of the program is imported by the yardstick."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import arch


def sizes(conf: dict) -> dict:
    """The reference's view of a configuration file: its architecture's
    sizes, and the numeric mode, dtypes and attention block that every
    architecture shares."""
    prec = conf["precision"]
    return dict(
        arch.of(conf).sizes(conf),
        param_dtype=prec["param_dtype"], compute_dtype=prec["compute_dtype"],
        attn_fused_pam=conf["program"]["attn_fused_pam"],
        kv_block=prec.get("attention_kv_block"),
        mode=conf["program"]["mode"],
    )


def build(conf: dict):
    """The program's model for a configuration; raises where the program
    would run anything else than the file states."""
    from repro.configs import get_config
    from repro.core import PAConfig
    from repro.models import build_model
    p = conf["program"]
    pa = PAConfig(mode=p["mode"], impl=p["impl"], fmt=p["fmt"])
    cfg = get_config(p["registry"], pa=pa, attn_fused_pam=p["attn_fused_pam"],
                     **p.get("overrides", {}))
    s = sizes(conf)
    bad = arch.of(conf).check(cfg, s)
    have = {"param_dtype": str(jnp.dtype(cfg.param_dtype)),
            "compute_dtype": str(jnp.dtype(cfg.cdtype))}
    bad.update({k: (v, s[k]) for k, v in have.items() if v != s[k]})
    if bad:
        raise RuntimeError(f"program config departs from the file: {bad}")
    return build_model(cfg)


def check_kv_block(conf: dict, s_len: int, t_len: int) -> None:
    """The fused attention's KV block for these shapes is the one the
    configuration states (the reference streams over the same blocks)."""
    kv = conf["precision"].get("attention_kv_block")
    if kv is None:
        return
    from repro.kernels import autotune
    from repro.kernels._backend import use_interpret
    got = autotune.tile_params("pam_attention", (s_len, t_len,
                                                 sizes(conf)["d_head"]),
                               use_interpret())[1]
    if int(got) != int(kv):
        raise RuntimeError(f"fused attention streams KV blocks of {got}, "
                           f"the configuration states {kv}")


def check_params(model, params) -> None:
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), model.abstract())
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != have:
        raise RuntimeError("benchmark weights do not fit the program's "
                           "parameter tree")


def mul_audit(jaxpr) -> int:
    """Tensor-shaped multiplies in a jaxpr (the program's own audit)."""
    from repro.analysis import jaxpr_mul_stats
    return int(jaxpr_mul_stats(jaxpr)["tensor_total"])
