"""Blockwise NVFP4 quantize–dequantize (QDQ) simulation.

Implements NVIDIA's two-level NVFP4 recipe:

  1. per-tensor fp32 scale          s_t = amax(|X|) / (E2M1_MAX * E4M3_MAX)
  2. per-block (16 elems) E4M3 scale s_b = RN_e4m3( blockamax(|X|) / (E2M1_MAX * s_t) )
  3. elements quantized to E2M1 in units of (s_b * s_t), round-to-nearest-even
     or stochastic rounding (SR — used on gradient GeMM operands, "G4").

Blocks always run along the GeMM **contraction** dimension (``axis``), so that
per-block scales factor out of dot products — the same layout Blackwell tensor
cores use and the layout our Pallas TPU kernels tile.

Everything here is the pure-XLA path; ``repro.kernels`` holds the fused Pallas
TPU version validated against this module.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .formats import BLOCK_SIZE, E2M1_GRID, E2M1_MAX, E4M3_MAX, TENSOR_SCALE_DENOM

_EPS = 1e-30


def round_e2m1_rn(a: jax.Array) -> jax.Array:
    """Round |values| (already in block-scale units) to the E2M1 grid, RNE.

    The E2M1 grid {0,.5,1,1.5,2,3,4,6} is uniform with spacing .5 below 2,
    spacing 1 on [2,4], spacing 2 on [4,6]; jnp.round is round-half-to-even, so
    rounding in units of the local spacing reproduces IEEE RNE exactly
    (verified against ml_dtypes.float4_e2m1fn casts in tests).
    """
    a = jnp.minimum(a, E2M1_MAX)
    r = jnp.where(
        a < 2.0,
        jnp.round(a * 2.0) * 0.5,
        jnp.where(a < 4.0, jnp.round(a), jnp.round(a * 0.5) * 2.0),
    )
    return jnp.minimum(r, E2M1_MAX)


def round_e2m1_sr(a: jax.Array, u: jax.Array) -> jax.Array:
    """Stochastically round |values| to the E2M1 grid.

    ``u`` is uniform[0,1) of the same shape. P(round up) equals the relative
    position within the enclosing grid interval — unbiased: E[SR(a)] = a.
    """
    a = jnp.minimum(a, E2M1_MAX)
    step = jnp.where(a < 2.0, 0.5, jnp.where(a < 4.0, 1.0, 2.0))
    lo = jnp.floor(a / step) * step
    hi = jnp.minimum(lo + step, E2M1_MAX)
    p_up = jnp.where(step > 0, (a - lo) / jnp.maximum(step, _EPS), 0.0)
    r = jnp.where(u < p_up, hi, lo)
    return jnp.minimum(r, E2M1_MAX)


def quantize_block_scales(block_amax: jax.Array, s_t: jax.Array) -> jax.Array:
    """E4M3 per-block decode scales from block amax and tensor scale.

    The single implementation shared by the training-side QDQ simulation
    (:func:`nvfp4_qdq`) and the serving-side page codec
    (``repro.serve.kvcache``): s_b = RN_e4m3(clip(amax_b / (E2M1_MAX * s_t))).
    ``s_t`` must broadcast against ``block_amax``. Returns float8_e4m3fn.
    """
    s = jnp.clip(block_amax / (E2M1_MAX * s_t), 0.0, E4M3_MAX)
    return s.astype(jnp.float8_e4m3fn)


def encode_e2m1_codes(rb: jax.Array, scale: jax.Array) -> jax.Array:
    """Blocked values -> 4-bit sign|magnitude E2M1 codes (uint8, low nibble).

    ``rb``: (..., n_blocks, block) values; ``scale``: (..., n_blocks)
    effective per-block decode scale (E4M3 block scale x tensor scale).
    Codes are ``sign*8 + grid_index`` with RN-to-grid elements — the same
    rounding the QDQ simulation uses (:func:`round_e2m1_rn`).
    """
    a = jnp.where(scale[..., None] > 0,
                  jnp.abs(rb) / jnp.maximum(scale[..., None], _EPS), 0.0)
    q = round_e2m1_rn(a)
    idx = jnp.searchsorted(jnp.asarray(E2M1_GRID), q).astype(jnp.uint8)
    sign = (rb < 0).astype(jnp.uint8)
    return sign * jnp.uint8(8) + idx


def decode_e2m1_codes(codes: jax.Array) -> jax.Array:
    """4-bit sign|magnitude codes -> signed E2M1 grid values (float32)."""
    grid = jnp.asarray(E2M1_GRID)
    mag = grid[codes & 7]
    return jnp.where(codes >= 8, -mag, mag)


def pack_nibbles(flat: jax.Array) -> jax.Array:
    """Pack pairs of 4-bit codes along the last axis (even length) -> uint8."""
    return flat[..., 0::2] | (flat[..., 1::2] << 4)


def unpack_nibbles(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_nibbles`: (..., k) uint8 -> (..., 2k) codes."""
    lo = (packed & 0x0F).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * packed.shape[-1],))


def nvfp4_qdq(
    x: jax.Array,
    axis: int = -1,
    *,
    sr: bool = False,
    key: Optional[jax.Array] = None,
    block_size: int = BLOCK_SIZE,
    tensor_amax: Optional[jax.Array] = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Quantize ``x`` to NVFP4 along ``axis`` and dequantize back (simulation).

    Two passes over ``x`` and no full-size intermediate: one reduction reads
    ``x`` for the per-block amax (1/16 of its size; the tensor amax is their
    max), and one elementwise pass reads ``x`` with the block scales,
    broadcast inside the fusion, and writes the result in ``x``'s dtype.
    Blocks are split in place, ``(.., n/16, 16, ..)`` at ``axis``, with no
    ``moveaxis``: for a leading or middle axis the lane dimension is untouched;
    for the last axis XLA:TPU still relayouts the input and the result once
    each, in ``x``'s dtype (it cannot split the lane dimension in place). The
    SR uniforms are drawn over the blocked shape with ``axis`` moved last,
    ``(.., n/16, 16)``, and moved back: each element keeps the bits of the
    moved-axis draw, which ``bench/reference.py`` reproduces.

    Args:
      x: input array (any float dtype; computation in fp32).
      axis: the GeMM contraction dimension — blocks of ``block_size`` run
        along it.
      sr: use stochastic rounding for the elements (scales are always RN).
      key: PRNG key, required when ``sr=True``.
      block_size: elements per scale block (16 for NVFP4).
      tensor_amax: optional externally-supplied per-tensor amax (used by the
        Averis weight-grad GeMM so both quantizations of the same tensor share
        one tensor scale; defaults to amax(|x|)).
      compute_dtype: dtype for the QDQ elementwise chain. float32 is exact;
        bfloat16 halves the HBM traffic of the simulation's temporaries (the
        E2M1 grid and its 0.5-granularity arithmetic are exactly representable
        in bf16 — only the scale division loses ulps, shifting rare
        tie-adjacent roundings). The fused Pallas kernel is the real fix on
        TPU; this flag is its XLA-path analogue (§Perf).

    Returns:
      The dequantized array, same shape/dtype as ``x``.
    """
    if sr and key is None:
        raise ValueError("stochastic rounding requires a PRNG key")
    orig_dtype = x.dtype
    axis = axis % x.ndim
    n = x.shape[axis]
    pad = (-n) % block_size
    if pad:
        x = jnp.pad(x, [(0, pad if d == axis else 0) for d in range(x.ndim)])
    padded = x.shape
    nb = padded[axis] // block_size
    # Behind a barrier, XLA cannot hoist this reshape (or the one at the end)
    # through the elementwise chain, which would write the scales out at
    # full size. The barriers change no value.
    xb = jax.lax.optimization_barrier(
        x.reshape(padded[:axis] + (nb, block_size) + padded[axis + 1:]))
    xf = xb.astype(compute_dtype)
    absx = jnp.abs(xf)

    # pass 1: block amax, and the tensor amax from it
    block_amax = jnp.max(absx, axis=axis + 1, keepdims=True)
    if tensor_amax is None:
        tensor_amax = jnp.max(block_amax)
    s_t = jnp.maximum(tensor_amax.astype(jnp.float32) / TENSOR_SCALE_DENOM, _EPS)
    s_b = quantize_block_scales(block_amax.astype(jnp.float32), s_t).astype(
        jnp.float32)
    scale = (s_b * s_t).astype(compute_dtype)  # effective per-block scale

    # pass 2: elementwise, the block scale broadcast in place
    eps = jnp.asarray(_EPS, jnp.float32).astype(compute_dtype)
    a = jnp.where(scale > 0, absx / jnp.maximum(scale, eps), 0)
    if sr:
        # u in the compute dtype: bf16 quantizes P(up) to ~1/256 steps — an
        # SR bias bounded by 0.4% of one grid step, negligible vs FP4 noise.
        moved = padded[:axis] + padded[axis + 1:] + (nb, block_size)
        u = jax.random.uniform(key, moved, dtype=jnp.float32).astype(
            compute_dtype)
        q = round_e2m1_sr(a, jnp.moveaxis(u, (-2, -1), (axis, axis + 1)))
    else:
        q = round_e2m1_rn(a)
    deq = jnp.sign(xf) * q * scale
    if jnp.dtype(compute_dtype).itemsize >= jnp.dtype(orig_dtype).itemsize:
        deq = deq.astype(orig_dtype)
    # else cast after the barrier: next to the chain, XLA may keep it in the
    # wider dtype and skip the compute dtype's rounding
    deq = jax.lax.optimization_barrier(deq).reshape(padded)
    if pad:
        deq = jax.lax.slice_in_dim(deq, 0, n, axis=axis)
    return deq.astype(orig_dtype)


def nvfp4_quant_error(x: jax.Array, axis: int = -1, **kw) -> jax.Array:
    """Relative Frobenius quantization error ||QDQ(x) - x||_F / ||x||_F."""
    q = nvfp4_qdq(x, axis, **kw)
    xf = x.astype(jnp.float32)
    return jnp.linalg.norm(q.astype(jnp.float32) - xf) / jnp.maximum(
        jnp.linalg.norm(xf), _EPS
    )
