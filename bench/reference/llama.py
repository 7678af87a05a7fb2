"""``LlamaForCausalLM`` (SmolLM's architecture): the plain reference of a
LLaMA-style decoder, its sizes and size check, its weight layout and its
FLOP count (the architecture contract is in ``bench/arch.py``).

A pre-norm decoder: RMSNorm, grouped-query attention with rotary
positions, RMSNorm, a SiLU-gated MLP; the token embedding is the tied
output head. The numerics (products, norms, attention, cross-entropy)
are ``bench/reference/model.py``'s, shared by every architecture.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .model import F32, Numerics, attention


def sizes(conf: dict) -> dict:
    """The reference's view of a configuration file's architecture."""
    return {
        "n_layers": conf["num_hidden_layers"], "d_model": conf["hidden_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"], "d_head": conf["head_dim"],
        "d_ff": conf["intermediate_size"], "vocab_size": conf["vocab_size"],
        "rope_theta": conf["rope_theta"], "rms_norm_eps": conf["rms_norm_eps"],
        "tie_embeddings": conf["tie_word_embeddings"],
    }


def check(cfg, s: dict) -> dict:
    """Where the program's model config departs from the sizes, and a
    block other than the RMSNorm, SiLU, gated-MLP decoder."""
    have = {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "d_head": cfg.head_dim, "d_ff": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
        "tie_embeddings": cfg.tie_embeddings,
    }
    bad = {k: (v, s[k]) for k, v in have.items() if v != s[k]}
    if (cfg.norm, cfg.activation, cfg.mlp_gated, cfg.family) != (
            "rmsnorm", "silu", True, "decoder"):
        bad["block"] = (cfg.norm, cfg.activation, cfg.mlp_gated, cfg.family)
    return bad


def layout(cfg: dict) -> dict:
    """{leaf path: (shape, init std or 'ones')} of the parameter tree:
    the token embedding (tied output head); per layer an RMSNorm scale,
    the q/k/v/o projections, a second RMSNorm scale and the gated MLP,
    stacked along a leading layer axis; the final RMSNorm scale. A
    projection of fan-in n has standard deviation 1/sqrt(n), the
    embedding 0.02."""
    L, d, v = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    q, kv, f = cfg["n_heads"] * cfg["d_head"], cfg["n_kv_heads"] * cfg["d_head"], cfg["d_ff"]
    lay = {
        ("embed",): ((v, d), 0.02),
        ("final_norm", "scale"): ((d,), "ones"),
        ("layers", "attn_norm", "scale"): ((L, d), "ones"),
        ("layers", "mlp_norm", "scale"): ((L, d), "ones"),
        ("layers", "attn", "wq"): ((L, d, q), d ** -0.5),
        ("layers", "attn", "wk"): ((L, d, kv), d ** -0.5),
        ("layers", "attn", "wv"): ((L, d, kv), d ** -0.5),
        ("layers", "attn", "wo"): ((L, q, d), q ** -0.5),
        ("layers", "mlp", "w_up"): ((L, d, f), d ** -0.5),
        ("layers", "mlp", "w_gate"): ((L, d, f), d ** -0.5),
        ("layers", "mlp", "w_down"): ((L, f, d), f ** -0.5),
    }
    if not cfg.get("tie_embeddings", True):
        raise ValueError("only tied embeddings are laid out")
    return lay


def flops_per_token(s: dict, seq: int) -> float:
    """Forward plus backward FLOPs per trained token (3x the forward, a
    product counted as 2): the projections, the gated MLP and the tied
    head, and causal attention's QK and AV over (seq + 1) / 2 keys on
    average. Recomputation is not counted; the count is the same in every
    numeric mode."""
    d, L, V = s["d_model"], s["n_layers"], s["vocab_size"]
    q, kv, f = s["n_heads"] * s["d_head"], s["n_kv_heads"] * s["d_head"], s["d_ff"]
    dense = L * (d * q + 2 * d * kv + q * d + 3 * d * f) + d * V
    attn = L * 2 * q * (seq + 1) / 2
    return 3.0 * 2.0 * (dense + attn)


# -- the decoder --------------------------------------------------------------

def block(nx: Numerics, cfg, h, lp, positions, stream_rows):
    b, s, _ = h.shape
    hq, hkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    eps = cfg["rms_norm_eps"]
    x = nx.rmsnorm(h, lp["attn_norm"]["scale"], eps)
    q = nx.mm(x, lp["attn"]["wq"]).reshape(b, s, hq, dh)
    k = nx.mm(x, lp["attn"]["wk"]).reshape(b, s, hkv, dh)
    v = nx.mm(x, lp["attn"]["wv"]).reshape(b, s, hkv, dh)
    q = nx.rope(q, positions, cfg["rope_theta"])
    k = nx.rope(k, positions, cfg["rope_theta"])
    a = attention(nx, q, k, v, positions, positions, stream_rows)
    h = h + nx.mm(a.reshape(b, s, hq * dh), lp["attn"]["wo"])
    x = nx.rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    up = nx.mm(x, lp["mlp"]["w_up"])
    gate = nx.silu(nx.mm(x, lp["mlp"]["w_gate"]))
    return h + nx.mm(nx.mul(up, gate), lp["mlp"]["w_down"])


def logits(nx: Numerics, cfg, params, tokens, stream_rows=None):
    """All-position logits of tokens (B, S); positions 0..S-1."""
    s = tokens.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    h = jnp.take(params["embed"], tokens, axis=0).astype(nx.dt)

    @jax.checkpoint
    def body(h, lp):
        return block(nx, cfg, h, lp, positions, stream_rows), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = nx.rmsnorm(h, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return nx.mm(h, params["embed"].T)


def loss(nx: Numerics, cfg, params, batch):
    stream = "all" if nx.pa and cfg.get("attn_fused_pam") else None
    lg = logits(nx, cfg, params, batch["tokens"], stream).astype(F32)
    return nx.cross_entropy(lg, batch["labels"], batch["mask"])
