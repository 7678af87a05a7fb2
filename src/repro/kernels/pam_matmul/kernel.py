"""Pallas TPU kernels: PAM matrix multiplication (the paper's hot path,
adapted from CUDA to the TPU memory hierarchy — DESIGN.md §2).

The MXU multiplies natively and cannot execute the bit-level PAM algorithm,
so the kernels run on the **VPU** (8x128 int lanes). Each grid step bitcasts
its (bm, bk) / (bk, bn) tiles to int32 once and contracts them as a sum of
rank-1 steps — an A column broadcast across lanes against a B row broadcast
across sublanes — with ``g`` steps accumulating in registers before each
group partial joins the sum (``pa_prims._contract``: static chunks of
steps, the whole 128-step contraction of a TPU tile where a step writes
few rows, a ``fori_loop`` over shorter chunks for tall ones). No
intermediate is larger than one (bm, bn) tile, so every kernel stays well
inside the scoped VMEM limit.

Grid is (B, M/bm, N/bn, K/bk) with the K dimension innermost so each
(b, i, j) output tile's accumulator lives in VMEM across all K steps
(classic Pallas matmul pipelining). Batch dims are folded into the leading
grid dimension of a *single* ``pallas_call`` — no vmap'd per-element
launches; an operand with batch size 1 is broadcast by pinning its batch
index map to 0.

Numeric contract (DESIGN.md §2.3): bit-exact vs ``pam_value`` for inputs
that are zero or finite with per-product magnitude below ~2^128 (clamped to
MAX_FINITE up to 2^129). Zero operands are pre-mapped to a magnitude
sentinel that lands every partner sum in the underflow-flush band, which
removes all per-element zero tests from the hot loop. Inf/NaN inputs are
outside the contract (same as the previous kernel generation); the eltwise
``pam`` kernel keeps full IEEE edge semantics.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bit-twiddling constants and the grouped tile product live in the shared
# kernels/pa_prims.py (plain numpy int32 immediates the kernel body closes
# over); per-format variants resolve through pa_prims.get_prims (the f32
# instance IS the module level); tile tunables resolve through the shared
# kernels/autotune.py table.
from repro.core import floatbits as _fb
from .. import autotune as _autotune
from ..pa_prims import (_SIGN, _MAG, _EXP, _MAN, _BIAS, _MIN_NORM, _MAX_EXPF,
                        _MAX_FINITE, _contract, get_prims)


# ---------------------------------------------------------------------------
# Tunables — PR-1 API preserved as wrappers over the shared autotune table.
# ---------------------------------------------------------------------------

def register_tile_params(m: int, n: int, k: int, params, *,
                         backend: str = "interpret",
                         fmt: str = "f32") -> None:
    """Add/override an autotune entry ((bm, bn, bk, g)) for a shape bucket."""
    bm, bn, bk, g = params
    _autotune.register_tile_params("pam_matmul", (m, n, k), (bm, bn, bk, g),
                                   backend=backend, fmt=fmt)


def tile_params(m: int, n: int, k: int, interpret: bool, fmt: str = "f32"):
    """Resolve (bm, bn, bk, g) for a problem shape from the autotune table."""
    return _autotune.tile_params("pam_matmul", (m, n, k), interpret, fmt)


def _fit(bm, bn, bk, g, m, n, k, *, group_dim: str = "k"):
    """Clamp tile params to the problem and restore divisibility invariants.

    ``group_dim`` names the contraction axis the grouped reduction runs
    over ("k" for the forward kernel, "n" for the exact-grad kernel); ``g``
    is lowered to the largest divisor of that axis' tile size.
    """
    bm_, bn_, bk_ = min(bm, m), min(bn, n), min(bk, k)
    axis = bk_ if group_dim == "k" else bn_
    g_ = max(1, min(g, axis))
    while axis % g_:                     # largest divisor of axis that is <= g
        g_ -= 1
    return bm_, bn_, bk_, g_


# ---------------------------------------------------------------------------
# Forward kernel: out[b] = A[b] ·̂ B[b]   (batched grid).
# ---------------------------------------------------------------------------

def _accumulate(acc_ref, part, step):
    """acc = part on the first contraction step, acc += part after it (no
    0.0 + x, so an all-zero contraction keeps its product's zero sign)."""
    @pl.when(step == 0)
    def _first():
        acc_ref[...] = part

    @pl.when(step > 0)
    def _rest():
        acc_ref[...] += part


def _fwd_kernel(a_ref, b_ref, o_ref, acc_ref, *, g: int, nk: int,
                fmt_name: str = "f32", lmul: bool = False):
    pp = get_prims(fmt_name, lmul)
    kk = pl.program_id(3)
    # (bm, bk) ·̂ (bk, bn) in the format's dtype, f32 partial
    _accumulate(acc_ref, pp.pam_dot(a_ref[0], b_ref[0], g), kk)

    @pl.when(kk == nk - 1)
    def _out():
        # Narrow formats round the f32 accumulator back to the operand
        # dtype on the single output store (a no-op cast on the f32 path).
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "g", "interpret",
                                    "fmt_name", "lmul"))
def pam_matmul_batched(a, b, *, bm: int, bn: int, bk: int, g: int,
                       interpret: bool, fmt_name: str = "f32",
                       lmul: bool = False):
    """(Ba, M, K) ·̂ (Bb, K, N) -> (max(Ba,Bb), M, N), one pallas_call.

    Ba/Bb must be equal or 1 (a size-1 batch is broadcast through its index
    map — the operand is never materialised B times). Pads M/N/K to tile
    multiples; PAM(0, x) == 0 under the sentinel scheme, so zero padding is
    exact. ``fmt_name`` selects the operand FloatFormat: "bf16" streams
    bf16 operands and output through HBM (half the bytes of f32) with int16
    carrier bit math; the VMEM accumulator stays f32.
    """
    fmt = _fb.FORMATS[fmt_name]
    Ba, m, k = a.shape
    Bb, k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert Ba == Bb or Ba == 1 or Bb == 1, (a.shape, b.shape)
    B = max(Ba, Bb)
    bm_, bn_, bk_, g_ = _fit(bm, bn, bk, g, m, n, k)
    mp = -(-m // bm_) * bm_
    np_ = -(-n // bn_) * bn_
    kp = -(-k // bk_) * bk_
    a = jnp.pad(a.astype(fmt.dtype), ((0, 0), (0, mp - m), (0, kp - k)))
    b = jnp.pad(b.astype(fmt.dtype), ((0, 0), (0, kp - k), (0, np_ - n)))
    nk = kp // bk_

    a_idx = ((lambda bi, i, j, kk: (bi, i, kk)) if Ba > 1
             else (lambda bi, i, j, kk: (0, i, kk)))
    b_idx = ((lambda bi, i, j, kk: (bi, kk, j)) if Bb > 1
             else (lambda bi, i, j, kk: (0, kk, j)))

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, g=g_, nk=nk, fmt_name=fmt_name,
                          lmul=lmul),
        grid=(B, mp // bm_, np_ // bn_, nk),
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), a_idx),
            pl.BlockSpec((1, bk_, bn_), b_idx),
        ],
        out_specs=pl.BlockSpec((1, bm_, bn_), lambda bi, i, j, kk: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, mp, np_), fmt.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
        name="pam_matmul",
    )(a, b)
    return out[:, :m, :n]


def pam_matmul_2d(a, b, *, bm: int = 128, bn: int = 128, bk: int = 512,
                  g: int = 8, interpret: bool = True, fmt_name: str = "f32",
                  lmul: bool = False):
    """Bit-exact PAM matmul for 2D operands (thin batched-grid wrapper)."""
    return pam_matmul_batched(a[None], b[None], bm=bm, bn=bn, bk=bk, g=g,
                              interpret=interpret, fmt_name=fmt_name,
                              lmul=lmul)[0]


# ---------------------------------------------------------------------------
# Exact-derivative backward kernel (paper Table 1 at matrix granularity):
#   dA[b, m, k] = sum_n pam(dfactor(A[m,k], B[k,n]), G[m,n])
# where dfactor(a, b) = (-1)^{S_b} 2^{E_b + 1{M_a+M_b >= 1}} is the signed
# power-of-two exact derivative of PAM. The contraction runs over N with the
# same grouped two-level reduction as the forward kernel; dfactor and the
# PAM-by-pow2 product are fused into one bit-level expression (no dfactor
# tensor is ever materialised).
# ---------------------------------------------------------------------------

def _exact_da_kernel(a_ref, b_ref, g_ref, o_ref, acc_ref, *, g: int, nn: int):
    a = a_ref[0]                                   # (bm, bkk)
    b = b_ref[0]                                   # (bkk, bn)
    gr = g_ref[0]                                  # (bm, bn)
    ai = jax.lax.bitcast_convert_type(a, jnp.int32)
    bi = jax.lax.bitcast_convert_type(b, jnp.int32)
    gi = jax.lax.bitcast_convert_type(gr, jnp.int32)
    maf_a = ai & _MAN                              # (bm, bkk) mantissa field
    # Contraction over N: the cotangent supplies columns (bm, bn), the B
    # side rows of its transpose (bn, bkk). Zero tests become AND-masks
    # (0 where dfactor(·, 0) == 0 or G == 0, else ~0).
    g_side = (gi & _SIGN, jnp.where(gr == 0.0, 0, -1).astype(jnp.int32),
              (gi & _MAG) - _BIAS)
    biT = bi.T
    b_side = (biT & _EXP, biT & _SIGN, biT & _MAN,
              jnp.where(b == 0.0, 0, -1).astype(jnp.int32).T)

    def product(cols, rows):
        sg, gzm, gmg = cols
        eb, sb, mb, bzm = rows
        # carry 1{M_a + M_b >= 1} lands directly in the exponent-field bit
        carry = (maf_a + mb) & _MIN_NORM
        magf = jnp.clip(eb + carry, _MIN_NORM, _MAX_EXPF)
        mag = magf + gmg
        mag = jnp.where(mag < _MIN_NORM, 0, jnp.minimum(mag, _MAX_FINITE))
        bits = ((sb ^ sg) | mag) & (bzm & gzm)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    j = pl.program_id(3)
    _accumulate(acc_ref, _contract(g_side, b_side, product, g), j)

    @pl.when(j == nn - 1)
    def _out():
        o_ref[0] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "g", "interpret"))
def pam_exact_grad_a_batched(a, b, gr, *, bm: int, bn: int, bk: int, g: int,
                             interpret: bool):
    """Exact-deriv dA for (Ba, M, K) ·̂ (Bb, K, N) with cotangent (B, M, N).

    Zero padding is exact: padded N columns carry G == 0 which the gmg
    sentinel flushes; padded K columns only produce extra dA columns that
    are cropped.
    """
    Ba, m, k = a.shape
    Bb, k2, n = b.shape
    Bg, m2, n2 = gr.shape
    assert k == k2 and m == m2 and n == n2
    B = max(Ba, Bb)
    assert Bg == B and (Ba in (1, B)) and (Bb in (1, B))
    bm_, bn_, bk_, g_ = _fit(bm, bn, bk, g, m, n, k, group_dim="n")
    mp = -(-m // bm_) * bm_
    np_ = -(-n // bn_) * bn_
    kp = -(-k // bk_) * bk_
    a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, mp - m), (0, kp - k)))
    b = jnp.pad(b.astype(jnp.float32), ((0, 0), (0, kp - k), (0, np_ - n)))
    gr = jnp.pad(gr.astype(jnp.float32), ((0, 0), (0, mp - m), (0, np_ - n)))
    nn = np_ // bn_

    a_idx = ((lambda bi, i, kk, j: (bi, i, kk)) if Ba > 1
             else (lambda bi, i, kk, j: (0, i, kk)))
    b_idx = ((lambda bi, i, kk, j: (bi, kk, j)) if Bb > 1
             else (lambda bi, i, kk, j: (0, kk, j)))

    out = pl.pallas_call(
        functools.partial(_exact_da_kernel, g=g_, nn=nn),
        grid=(B, mp // bm_, kp // bk_, nn),
        in_specs=[
            pl.BlockSpec((1, bm_, bk_), a_idx),
            pl.BlockSpec((1, bk_, bn_), b_idx),
            pl.BlockSpec((1, bm_, bn_), lambda bi, i, kk, j: (bi, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm_, bk_), lambda bi, i, kk, j: (bi, i, kk)),
        out_shape=jax.ShapeDtypeStruct((B, mp, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bk_), jnp.float32)],
        interpret=interpret,
        name="pam_exact_grad",
    )(a, b, gr)
    return out[:, :m, :k]
