"""Bit-exact flight recorder: integer-only tree fingerprints + step journal.

Because every PA operation is an integer add on the bit representation
(Mogami 2020), a full-PA training or serving run is bit-exactly
reproducible in a way ordinary float stacks are not. This module turns
that determinism into an auditable artifact:

  * ``tree_leaf_digests`` / ``tree_digest`` — a fingerprint of a param/opt
    pytree computed entirely with integer ops INSIDE the jitted step:
    bitcast each leaf to uint32 words, mix each word with its position
    through the murmur3 finalizer (``fmix32`` — a bijection on uint32, so
    any single bit flip in any element provably changes that element's
    mixed hash), XOR-fold per leaf, then combine leaves keyed by a crc32
    of their tree PATH (order-independent — the digest is a function of
    {path: leaf bits}, not of iteration order). Integer multiplies are in
    the ``jaxpr_mul_stats`` integer exemption class (addressing/bit
    arithmetic), so arming the recorder keeps the full-PA train and
    decode steps at ``tensor_total == 0``.

  * ``FlightRecorder`` — a per-step journal of (step, data index, loss
    bits, grad-norm bits, per-leaf digests, combined digest), kept in a
    bounded in-memory ring (the ``tail`` persisted into each checkpoint's
    ``extra.json`` sidecar) and flushed to ``<workdir>/journal.jsonl``
    with the same write-tmp-then-rename atomicity contract as checkpoint
    dirs — a kill mid-write can never leave a torn digest line visible.

  * host-side fold helpers (``fold_token``/``request_digest_seed``) — the
    serving engine folds each emitted token id and the decode step's
    per-slot logits digest into a per-request digest, the unit the
    serve-bench determinism gate replays against.

``replay.py`` regenerates journals from a checkpoint anchor and verifies
them; ``forensics.py`` localizes the first diverging leaf (DESIGN.md §8).
"""
from __future__ import annotations

import json
import os
import zlib
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

JOURNAL_NAME = "journal.jsonl"
JOURNAL_VERSION = 1

_MASK32 = 0xFFFFFFFF
_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35


# ---------------------------------------------------------------------------
# In-jit integer-only fingerprint primitives.
# ---------------------------------------------------------------------------

def _fmix32(h):
    """murmur3 finalizer on uint32 — a BIJECTION, so distinct inputs map to
    distinct outputs (single-bit-flip sensitivity is structural, not
    probabilistic). Integer mul/shift/xor only: the multiplication audit's
    integer exemption class."""
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(_C1)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(_C2)
    h = h ^ (h >> np.uint32(16))
    return h


def leaf_words(x: jax.Array) -> jax.Array:
    """Flatten any leaf to a 1-D uint32 word stream via bitcast (f32 and
    4-byte ints directly; 2-byte dtypes — bf16 moments, f16 — widen from
    their uint16 bit pattern; 8-byte split into two words; bool/1-byte
    widen). Pure bit moves: no float ops at all."""
    x = jnp.asarray(x)
    size = jnp.dtype(x.dtype).itemsize
    if x.dtype == jnp.bool_:
        return x.reshape(-1).astype(jnp.uint32)
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    if size == 2:
        return (jax.lax.bitcast_convert_type(x, jnp.uint16)
                .reshape(-1).astype(jnp.uint32))
    if size == 1:
        return (jax.lax.bitcast_convert_type(x, jnp.uint8)
                .reshape(-1).astype(jnp.uint32))
    if size == 8:
        # bitcast to a smaller dtype appends a trailing word dimension
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    raise TypeError(f"leaf_words: unsupported dtype {x.dtype}")


def _xor_reduce(h: jax.Array, axes: Tuple[int, ...]) -> jax.Array:
    return jax.lax.reduce(h, np.uint32(0),
                          lambda a, b: jax.lax.bitwise_xor(a, b), axes)


def leaf_digest(x: jax.Array, salt: int = 0) -> jax.Array:
    """uint32 digest of one leaf: position-mixed XOR fold of its words.
    Each word is mixed with its index before folding, so transpositions
    and swaps change the digest, and ``fmix32``'s bijectivity guarantees
    any single bit flip in any word changes it too. The element count and
    the dtype are folded in last (distinguishes shapes/dtypes that share a
    word stream, e.g. a zero bf16 leaf and a zero f32 leaf)."""
    w = leaf_words(x)
    n = w.shape[0]
    idx = jax.lax.iota(jnp.uint32, n)
    h = _fmix32(w ^ _fmix32(idx ^ np.uint32(salt & _MASK32)))
    d = _xor_reduce(h, (0,))
    dtype_salt = zlib.crc32(jnp.dtype(x.dtype).name.encode()) & _MASK32
    return _fmix32(_fmix32(d ^ np.uint32(n & _MASK32))
                   ^ np.uint32(dtype_salt))


def tree_paths(tree: Any) -> List[str]:
    """Canonical leaf path strings (jax keystr) in flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def path_salts(paths: Sequence[str]) -> np.ndarray:
    """crc32 of each leaf path — the per-leaf salt that keys the combined
    digest by PATH rather than flatten position."""
    return np.array([zlib.crc32(p.encode()) & _MASK32 for p in paths],
                    np.uint32)


def tree_leaf_digests(tree: Any) -> jax.Array:
    """uint32[n_leaves] — one digest per leaf, salted by its path crc32,
    in canonical flatten order. This is the array the instrumented train
    step emits as ``metrics['leaf_digests']`` (jit-able, integer-only)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    salts = path_salts([jax.tree_util.keystr(p) for p, _ in flat])
    return jnp.stack([leaf_digest(leaf, int(s))
                      for (_, leaf), s in zip(flat, salts)])


def tree_digest(tree: Any) -> jax.Array:
    """uint32 scalar — order-independent combine of the per-leaf digests
    (each already path-salted): XOR fold + length mix."""
    d = tree_leaf_digests(tree)
    return _fmix32(_xor_reduce(_fmix32(d), (0,))
                   ^ np.uint32(d.shape[0] & _MASK32))


def rows_digest(x: jax.Array, salt: int = 0) -> jax.Array:
    """uint32[rows] — per-row digest of a 2-D float array (the serve-side
    logits fingerprint: one digest per decode slot, integer ops only)."""
    x = jnp.asarray(x, jnp.float32)
    w = jax.lax.bitcast_convert_type(x, jnp.uint32)
    idx = jax.lax.broadcasted_iota(jnp.uint32, w.shape, w.ndim - 1)
    h = _fmix32(w ^ _fmix32(idx ^ np.uint32(salt & _MASK32)))
    d = _xor_reduce(h, (w.ndim - 1,))
    return _fmix32(d ^ np.uint32(w.shape[-1] & _MASK32))


def float_bits(x) -> jax.Array:
    """uint32 bit pattern of a scalar float32 (loss/grad-norm bits)."""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.float32), jnp.uint32)


# ---------------------------------------------------------------------------
# Host-side mirrors (pure-int python: used for combining and request folds).
# ---------------------------------------------------------------------------

def fmix32_host(h: int) -> int:
    h &= _MASK32
    h ^= h >> 16
    h = (h * _C1) & _MASK32
    h ^= h >> 13
    h = (h * _C2) & _MASK32
    h ^= h >> 16
    return h


def combine_digests(leaf_digests: Sequence[int]) -> int:
    """Combined tree digest from per-leaf (already path-salted) digests —
    the host mirror of ``tree_digest``'s combine stage."""
    d = 0
    for ld in leaf_digests:
        d ^= fmix32_host(int(ld))
    return fmix32_host(d ^ (len(leaf_digests) & _MASK32))


def request_digest_seed(rid: int) -> int:
    """Initial per-request digest for serving: a mixed function of the
    request id only, so the digest stream is slot- and batch-independent."""
    return fmix32_host(0x9E3779B9 ^ (int(rid) & _MASK32))


def fold_token(digest: int, token: int, logits_digest: int) -> int:
    """Fold one emitted token (id + the decode step's logits-row digest)
    into a request digest. Host ints; mirrors nothing in-jit — the serve
    engine folds as tokens are emitted."""
    d = fmix32_host(int(digest) ^ fmix32_host(int(token) & _MASK32))
    return fmix32_host(d ^ int(logits_digest))


def _hex(v: int) -> str:
    return f"0x{int(v) & _MASK32:08x}"


def _unhex(s: str) -> int:
    return int(s, 16) & _MASK32


# ---------------------------------------------------------------------------
# The journal.
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Per-step flight journal with crash-safe persistence.

    In memory: ``records`` keyed by step (the healthy trajectory only —
    the train loop truncates on rollback exactly like its ``history``, so
    the journal is always the "as if the bad step never ran" view), plus
    a bounded ``ring`` tail for the checkpoint ``extra.json`` sidecar.

    On disk: ``<workdir>/journal.jsonl`` — one header line + one JSON line
    per step. ``flush()`` writes the WHOLE journal to ``<path>.tmp`` and
    ``os.replace``s it over the live file: the same atomicity contract as
    checkpoint dirs, so a kill mid-write leaves the previous intact
    journal, never a torn digest line. ``load`` additionally tolerates a
    torn trailing line (a non-atomic writer / disk tear) by skipping
    unparseable lines rather than failing the whole journal.
    """

    def __init__(self, path: str, ring: int = 64):
        self.path = path
        self.ring_size = ring
        self.records: Dict[int, dict] = {}
        self.ring: deque = deque(maxlen=ring)
        self.header: Optional[dict] = None
        self.torn_lines: int = 0

    # -- header / schema ----------------------------------------------------
    def attach(self, state_like: Any, step_cfg: Optional[dict] = None) -> None:
        """Bind the recorder to a state tree's structure: leaf paths, their
        crc32 salts, and the step configuration needed to rebuild a
        bit-identical program at replay time. Raises if a previously
        loaded journal was recorded against a different tree."""
        paths = tree_paths(state_like)
        header = {
            "kind": "header", "version": JOURNAL_VERSION,
            "n_leaves": len(paths),
            "paths_digest": _hex(zlib.crc32("\n".join(paths).encode())),
            "step_cfg": dict(step_cfg or {}),
        }
        if self.header is not None:
            for k in ("n_leaves", "paths_digest"):
                if self.header.get(k) != header[k]:
                    raise ValueError(
                        f"journal {self.path} was recorded against a "
                        f"different state tree ({k}: {self.header.get(k)!r} "
                        f"vs {header[k]!r}) — refusing to mix trajectories")
            # keep the recorded step_cfg (replay must rebuild THAT program)
            header["step_cfg"] = self.header.get("step_cfg",
                                                 header["step_cfg"])
        self.header = header
        self._paths = paths

    @property
    def paths(self) -> List[str]:
        return getattr(self, "_paths", [])

    def step_cfg(self) -> dict:
        return dict((self.header or {}).get("step_cfg", {}))

    # -- recording ----------------------------------------------------------
    def record_step(self, step: int, data_index: int, metrics: dict) -> dict:
        """Append one step's flight record from the instrumented step's
        metrics (``loss_bits`` / ``grad_norm_bits`` / ``leaf_digests``,
        all uint32 device scalars/arrays)."""
        leaves = [int(v) for v in np.asarray(metrics["leaf_digests"])]
        rec = {
            "step": int(step),
            "data_index": int(data_index),
            "loss_bits": _hex(int(np.asarray(metrics["loss_bits"]))),
            "grad_norm_bits": _hex(int(np.asarray(metrics["grad_norm_bits"]))),
            "digest": _hex(combine_digests(leaves)),
            "leaves": "".join(f"{v:08x}" for v in leaves),
        }
        self.records[rec["step"]] = rec
        self.ring.append(rec)
        return rec

    @staticmethod
    def record_leaves(rec: dict) -> List[int]:
        s = rec["leaves"]
        return [int(s[i:i + 8], 16) for i in range(0, len(s), 8)]

    def truncate(self, step: int) -> int:
        """Drop every record for steps >= ``step`` (the rollback contract:
        the journal mirrors the train loop's history truncation). Returns
        the number of records dropped."""
        drop = [s for s in self.records if s >= step]
        for s in drop:
            del self.records[s]
        kept = sorted(self.records)[-self.ring_size:]
        self.ring = deque((self.records[s] for s in kept),
                          maxlen=self.ring_size)
        return len(drop)

    def steps(self) -> List[int]:
        return sorted(self.records)

    def last_step(self) -> Optional[int]:
        return max(self.records) if self.records else None

    def tail(self) -> List[dict]:
        """The ring-buffer tail — persisted into checkpoint ``extra.json``
        so every checkpoint carries the journal window around its step."""
        return [dict(r) for r in self.ring]

    def sidecar(self) -> dict:
        """The ``extra.json`` flight section: header identity + ring tail."""
        head = dict(self.header or {})
        head.pop("kind", None)
        return {"journal": os.path.basename(self.path), "tail": self.tail(),
                **{k: head[k] for k in ("version", "n_leaves",
                                        "paths_digest") if k in head}}

    # -- persistence (atomic) -----------------------------------------------
    def flush(self) -> str:
        """Atomically persist the full journal: write header + records to
        ``<path>.tmp``, fsync, then ``os.replace`` over the live file. A
        crash at ANY point leaves either the previous journal or the new
        one — never a torn line."""
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w") as f:
                if self.header is not None:
                    f.write(json.dumps(self.header, sort_keys=True) + "\n")
                for s in sorted(self.records):
                    f.write(json.dumps(self.records[s], sort_keys=True) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except BaseException:
            # the atomic contract: never leave a partial tmp behind
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        return self.path

    def load_existing(self) -> int:
        """Merge records from the on-disk journal (no-op if absent).
        Unparseable lines — a torn tail from a non-atomic writer — are
        counted in ``torn_lines`` and skipped, never fatal. Returns the
        number of records loaded."""
        if not os.path.exists(self.path):
            return 0
        n = 0
        with open(self.path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    self.torn_lines += 1
                    continue
                if obj.get("kind") == "header":
                    self.header = obj
                elif "step" in obj:
                    self.records[int(obj["step"])] = obj
                    n += 1
                else:
                    self.torn_lines += 1
        kept = sorted(self.records)[-self.ring_size:]
        self.ring = deque((self.records[s] for s in kept),
                          maxlen=self.ring_size)
        return n

    @classmethod
    def load(cls, path: str, ring: int = 64) -> "FlightRecorder":
        rec = cls(path, ring=ring)
        rec.load_existing()
        return rec


def journal_path(workdir: str) -> str:
    return os.path.join(workdir, JOURNAL_NAME)
