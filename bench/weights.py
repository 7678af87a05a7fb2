"""Weights and per-run random streams, made from the run's seed.

The benchmark makes the parameters itself, on the device, in one jitted
call, so the program under test and the reference see the same numbers
and neither makes them. The layout, each leaf's shape and standard
deviation, is the architecture's (``layout`` of its reference module,
``bench/arch.py``); each leaf is normal with that deviation, or ones.
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


def init_params(ref, cfg: dict, seed: int):
    """The parameter tree for (cfg, seed) in the layout of the
    architecture's reference module ``ref`` (``bench/arch.py``), made on
    the default device."""
    items = tuple((p, s, std) for p, (s, std) in sorted(ref.layout(cfg).items()))
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
