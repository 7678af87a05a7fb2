"""Shape-bucketed tile-parameter autotune table shared by kernel families.

One registry for every kernel family's tunables, keyed by
``(op, backend, *power-of-two shape buckets)``. The PR-1 mechanism
(``register_tile_params`` on the matmul engine) is now a thin wrapper over
this table; ``pa_softmax`` (row-block size) and the fused PAM attention
(``bq``/``bk``/``g``) resolve through the same registry, so a measured
tuning sweep feeds every kernel through one interface.

Params are opaque tuples whose meaning is per-op:

  * ``pam_matmul``:        (bm, bn, bk, g)  keyed by (M, N, K)
  * ``pa_softmax``:        (rows,)          keyed by (R, C)
  * ``pam_attention``:     (bq, bk, g)      keyed by (S, T, Dh)
  * ``pam_attention_bwd``: (bq, bk, g)      keyed by (S, T, Dh) — the
    two-sweep recompute backward (dsig+dQ sweep and the KV-outer dK/dV
    sweep) resolves its tiles separately from the forward: its per-step
    work is 3-4 tile products vs the forward's 2, so the grid-step
    overhead/VMEM trade lands on different block sizes.
  * ``pam_optim``:         (rows, cols)     keyed by (n_elements,) — the
    fused PA-AdamW update kernel's per-leaf tile plane (DESIGN.md §5).
"""
from __future__ import annotations

# Defaults per (op, backend); per-shape entries in _TABLE override.
_DEFAULTS = {
    ("pam_matmul", "interpret"): (256, 256, 256, 16),
    # tpu tiles: untimed legal defaults. Blocks obey the (8, 128) rule;
    # bk = 128 keeps each in-kernel contraction at 128 steps (one static
    # chunk for tiles of up to 64 rows) and pads K = 576 to 640 rather
    # than 1024.
    ("pam_matmul", "tpu"): (128, 128, 128, 8),
    ("pa_softmax", "interpret"): (8,),
    ("pa_softmax", "tpu"): (8,),
    ("pam_attention", "interpret"): (256, 256, 16),
    ("pam_attention", "tpu"): (128, 128, 8),
    ("pam_attention_bwd", "interpret"): (256, 256, 16),
    ("pam_attention_bwd", "tpu"): (128, 128, 8),
    # pam_optim: the elementwise update chain has no reuse, so interpret
    # mode is pure grid-step overhead — the biggest measured plane wins
    # (512x4096 = one step for leaves up to 2M elements: 13.4ms vs 105ms
    # at 256x1024 on the 2M reference leaf). The tpu default is an untimed
    # sublane-aligned guess (16 rows: legal for bf16 moment tiles; seven
    # live (16, 1024) f32 planes ~ 0.5 MB VMEM).
    ("pam_optim", "interpret"): (512, 4096),
    ("pam_optim", "tpu"): (16, 1024),
}

_TABLE = {
    # pam_matmul: measured on the CPU interpret reference host (see
    # BENCH_pam_matmul.json trajectory): mid-size squares like one big tile
    # with g=16 groups.
    ("pam_matmul", "interpret", 256, 256, 256): (256, 256, 256, 16),
    ("pam_matmul", "interpret", 512, 512, 512): (256, 256, 512, 16),
    ("pam_matmul", "interpret", 1024, 1024, 1024): (256, 256, 512, 16),
    # pa_softmax: attention-scale score rows (R = B*H*S, C = T). Wider row
    # blocks amortise interpret-mode grid-step overhead on the big-R shapes
    # the attention path produces — measured 26x over the seed's rows=8 at
    # (4096, 512) (BENCH_pa_softmax.json). The tpu default stays at 8
    # (sublane-aligned); these entries are interpret-host measurements.
    ("pa_softmax", "interpret", 4096, 512): (256,),
    ("pa_softmax", "interpret", 2048, 512): (128,),
    ("pa_softmax", "interpret", 1024, 512): (64,),
    # pam_attention: measured at the BENCH_pam_attention.json reference
    # shape (BH=8, S=T=512, Dh=64) on the CPU interpret host — full-S query
    # tiles with half-T KV blocks win (34ms vs 50ms at 256/256).
    ("pam_attention", "interpret", 512, 512, 64): (512, 256, 16),
    # pam_attention_bwd: the two-sweep recompute backward at the same
    # reference shape. Both sweeps pay 3-4 tile products per grid step, so
    # interpret-mode grid overhead dominates and the biggest legal tiles
    # win: 512/512 = 160ms vs 185ms at 512/256 and 212ms at 256/256
    # (g=16 beats g=32 at every block size).
    ("pam_attention_bwd", "interpret", 512, 512, 64): (512, 512, 16),
}


def _bucket(x: int) -> int:
    return min(1 << max(0, int(x - 1).bit_length()), 4096)


def register_tile_params(op: str, shape, params, *,
                         backend: str = "interpret",
                         fmt: str = "f32") -> None:
    """Add/override the params tuple for an op's shape bucket. Non-f32
    formats register under a format-qualified backend key."""
    be = backend if fmt == "f32" else f"{backend}:{fmt}"
    _TABLE[(op, be) + tuple(_bucket(int(s)) for s in shape)] = tuple(params)


def tile_params(op: str, shape, interpret: bool, fmt: str = "f32"):
    """Resolve an op's params tuple for a problem shape.

    The format axis is part of the key: bf16 tiles pack twice the lanes, so
    measured optima differ from f32. Lookup falls back format-qualified ->
    plain backend entry -> backend default, so every format resolves even
    before a tuning sweep has run for it.
    """
    backend = "interpret" if interpret else "tpu"
    buckets = tuple(_bucket(int(s)) for s in shape)
    if fmt != "f32":
        hit = _TABLE.get((op, f"{backend}:{fmt}") + buckets)
        if hit is not None:
            return hit
    key = (op, backend) + buckets
    return _TABLE.get(key, _DEFAULTS[(op, backend)])
