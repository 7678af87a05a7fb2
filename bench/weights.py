"""Weights and per-run random streams, made from the run's seed.

The benchmark makes the parameters itself, on the device, in one jitted
call, so the program under test and the reference see the same numbers
and neither makes them. The layout is the published LLaMA-style decoder:
token embedding (tied output head), per layer an RMSNorm scale, the
q/k/v/o projections, a second RMSNorm scale and the gated MLP, stacked
along a leading layer axis, then the final RMSNorm scale. A projection
of fan-in n is normal with standard deviation 1/sqrt(n), the embedding
0.02, the norm scales one.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp


def key_words(seed: int, stream: str) -> np.ndarray:
    """Two 32-bit words for (seed, stream): any non-negative seed, also
    beyond 32 bits, with independent streams per use."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 63)
    return np.random.SeedSequence([int(seed), tag]).generate_state(2)


def layout(cfg: dict) -> dict:
    """{leaf path: (shape, init std or 'ones')} of the parameter tree."""
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


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, x in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return out


@functools.lru_cache(maxsize=None)
def _init_fn(items: tuple, dtype: str):
    def init(words):
        key = jax.random.wrap_key_data(words)
        flat = {}
        for i, (path, shape, std) in enumerate(items):
            if std == "ones":
                flat[path] = jnp.ones(shape, dtype)
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                flat[path] = (x * np.float32(std)).astype(dtype)
        return _nest(flat)
    return jax.jit(init)


def init_params(cfg: dict, seed: int):
    """The parameter tree for (cfg, seed), made on the default device."""
    items = tuple((p, s, std) for p, (s, std) in sorted(layout(cfg).items()))
    words = jnp.asarray(key_words(seed, "weights"), jnp.uint32)
    return _init_fn(items, cfg["param_dtype"])(words)


def markov_tokens(seed: int, stream: str, rows: int, seq: int, vocab: int,
                  determinism: float = 0.9) -> np.ndarray:
    """(rows, seq + 1) int32 token rows of a noisy affine Markov chain
    over the vocabulary: next = (a * cur + b) mod V with probability
    ``determinism``, else uniform. Learnable structure, so a training loss
    falls below that of uniform guessing."""
    r = np.random.default_rng(key_words(seed, stream))
    a = int(r.integers(1, vocab - 1)) | 1
    b = int(r.integers(0, vocab))
    toks = np.empty((rows, seq + 1), np.int64)
    toks[:, 0] = r.integers(0, vocab, rows)
    noise = r.random((rows, seq)) >= determinism
    rand = r.integers(0, vocab, (rows, seq))
    for t in range(seq):
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t],
                                  (a * toks[:, t] + b) % vocab)
    return toks.astype(np.int32)
