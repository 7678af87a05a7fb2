"""The system under test, as the benchmark sees it: the program's model
built from a configuration file, checked against the sizes the file
states. Nothing else of the program is imported by the yardstick."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sizes(conf: dict) -> dict:
    """The reference's view of a configuration file."""
    prec = conf["precision"]
    return {
        "n_layers": conf["num_hidden_layers"], "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"], "d_head": conf["head_dim"],
        "d_ff": conf["intermediate_size"], "vocab_size": conf["vocab_size"],
        "rope_theta": conf["rope_theta"], "rms_norm_eps": conf["rms_norm_eps"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "param_dtype": prec["param_dtype"], "compute_dtype": prec["compute_dtype"],
        "attn_fused_pam": conf["program"]["attn_fused_pam"],
        "kv_block": prec.get("attention_kv_block"),
        "mode": conf["program"]["mode"],
    }


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
    have = {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "d_head": cfg.head_dim, "d_ff": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
        "tie_embeddings": cfg.tie_embeddings,
        "param_dtype": str(jnp.dtype(cfg.param_dtype)),
        "compute_dtype": str(jnp.dtype(cfg.cdtype)),
    }
    bad = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if (cfg.norm, cfg.activation, cfg.mlp_gated, cfg.family) != (
            "rmsnorm", "silu", True, "decoder"):
        bad["block"] = (cfg.norm, cfg.activation, cfg.mlp_gated, cfg.family)
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
                                                 conf["head_dim"]),
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
