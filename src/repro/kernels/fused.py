"""Fused Center→Hadamard→Quantize Pallas kernels — the FP4 hot path.

The unfused stage pipeline (``repro.core.pipeline.apply_stages``) evaluates
Center, Hadamard and Quantize as separate XLA ops, materializing the centered
residual and the rotated residual as full-size HBM intermediates between
them. These kernels collapse the whole recipe-side pipeline into one
``pallas_call``: a (TILE_L, TILE_M) tile is read from HBM **once**, centered
against the (precomputed) token mean, rotated lane-16-tile-wise with H16 on
the MXU, scaled against the per-tensor fp32 scale, rounded to E2M1 (RNE or
stochastic), and written back as EITHER

  * the dequantized values (``center_hadamard_qdq_2d`` — what the GeMM
    executor consumes), or
  * packed 4-bit codes + E4M3 block scales (``center_hadamard_pack_2d`` —
    the wire/deployment artifact; the mean rides along as its own output).

Two small reduction passes precede the main kernel (the paper's "only
reduction operations"): ``column_mean_2d`` for the token mean and a fused
center+rotate+amax pass for the per-tensor scale of the rotated residual —
neither writes a full-size intermediate.

All element math is shared with the unfused kernels (``_qdq_tile``), so the
fused outputs are bitwise those of the stage pipeline wherever fp32
summation order cannot bite (dyadic inputs — the golden suite's contract;
see ``tests/test_fused_kernels.py``).

Mosaic (the TPU kernel compiler) cannot split a lane axis into (n/16, 16)
blocks, store a scalar to VMEM, or convert fp8 on v5e, so compiled kernels
use other forms of the same math: H16 as a block-diagonal 128-lane MXU
matmul, block maxima by a butterfly of lane rolls, E4M3 rounding and bytes
by fp32 field arithmetic, nibble pairing and the per-block scale pick as
one-hot MXU matmuls, amax kept as a broadcast (8, 128) tile, s_t in SMEM.
Interpreted (off-TPU), the QDQ kernels keep the stage path's blocked views
(``_split_lanes``), so XLA:CPU sees the stage path's graph and the CPU
suite stays bitwise; the two forms agree exactly wherever fp32 sums are
exact.

Stage combinations are static kernel variants (center on/off × rotate
on/off × RN/SR × values/pack); the mean vector may run along lanes
(``mu (1, m)`` — activation streams, token axis 0) or sublanes
(``mu (l, 1)`` — the transposed dw orientation, where the token axis IS the
contraction axis).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import (BLOCK_SIZE, E2M1_MAX, E4M3_MAX, HADAMARD_16,
                                TENSOR_SCALE_DENOM)
from .mean_split import column_mean_2d, reduce_tile_m
from .nvfp4_quant import (DEFAULT_TILE_L, DEFAULT_TILE_M, _round_e2m1_rn,
                          _round_e2m1_sr)

_TILE = 16
_EPS = 1e-30
# interpret mode only: arrays up to this many elements run as ONE grid cell
# so the one-pass QDQ kernel (in-kernel amax) applies — 4M fp32 = 16 MB,
# nothing for a host core; real-TPU tiling keeps the VMEM-sized defaults
_ONEPASS_MAX_ELEMS = 1 << 22
# Mosaic stores no scalar to VMEM: the running amax is kept broadcast over
# one whole (8, 128) f32 tile, and the per-tensor scale s_t enters in SMEM
_AMAX_TILE = (8, 128)
_LANES = 128
# VMEM budget of one kernel's f32 tile (the 256 x 512 default): each
# kernel keeps about ten tile-sized temporaries live, against v5e's 16 MiB
# default scoped VMEM
_VMEM_TILE_ELEMS = DEFAULT_TILE_L * DEFAULT_TILE_M
_PACK_TILE_M = 2048               # -> 128 scale lanes per tile
_PACK_ROW_COLS = 4096
_SCALAR_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------
# Shared tile math
# --------------------------------------------------------------------------

def _hadamard_blockdiag(width: int) -> jax.Array:
    """kron(I, H16) of size (width, width): H16 on every aligned 16-block."""
    return jnp.asarray(np.kron(np.eye(width // _TILE, dtype=np.float32),
                               HADAMARD_16))


def _split_lanes(interpret: bool) -> bool:
    """Whether the kernels use the interpret-mode forms that view a lane
    axis as (n/16, 16) blocks (see ``_hadamard_operand`` and
    ``_block_quantize_split``). Mosaic refuses those views, so compiled
    kernels never do; tests flip this to run the Mosaic forms
    interpreted."""
    return interpret


def _hadamard_operand(n: int, split_lanes: bool) -> jax.Array:
    """The rotation matrix a kernel receives for a tile axis of length n.

    Compiled, Mosaic cannot split a lane axis into (n/16, 16), so the
    kernel rotates whole 128-lane chunks on the MXU with kron(I, H16); the
    off-block products are exact zeros, so every output is the same 16
    products, summed in the MXU's order. Interpreted, the kernel gets H16
    itself and contracts the (…, n/16, 16) view exactly as the stage
    path's ``hadamard_tiles`` does, so XLA:CPU sums in the same order and
    the CPU suite stays bitwise. The two forms differ only in fp32
    summation order (none where the sums are exact).
    """
    if split_lanes:
        return jnp.asarray(HADAMARD_16)
    return _hadamard_blockdiag(_LANES if n % _LANES == 0 else n)


def _rotate_lanes(x, h):
    """H16 along the lanes of an fp32 tile (see ``_hadamard_operand``)."""
    tl, tm = x.shape
    if h.shape[0] == _TILE:
        return jax.lax.dot_general(
            x.reshape(tl, tm // _TILE, _TILE), h, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(tl, tm)
    w = h.shape[0]
    parts = [jax.lax.dot_general(x[:, c:c + w], h, (((1,), (0,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
             for c in range(0, tm, w)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _rotate_sublanes(x, h):
    """H16 along the sublanes (axis 0) of an fp32 tile."""
    tl, tm = x.shape
    if h.shape[0] == _TILE:
        return jax.lax.dot_general(
            x.reshape(tl // _TILE, _TILE, tm), h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).transpose(0, 2, 1).reshape(tl, tm)
    return _rotate_lanes(x.T, h).T


def _center_rotate_tile(x, mu, h, *, center: bool, rotate: bool,
                        sub: bool = False):
    """Center and/or rotate one fp32 tile entirely in VMEM registers.

    ``sub``: the 16-blocks run along sublanes (axis 0) instead of lanes —
    the transposed GeMM orientation handled natively. H16 is symmetric
    (Sylvester), so contracting either index gives the same rotation.
    """
    if center:
        x = x - mu.astype(jnp.float32)
    if rotate:
        x = _rotate_sublanes(x, h) if sub else _rotate_lanes(x, h)
    return x


def _lane_block_max(a):
    """Max over each aligned 16-lane block, broadcast back over the block.

    A xor butterfly of lane rolls (Mosaic has no lane-splitting reshape).
    Each stage takes its partner ``lane ^ s`` from whichever of the two
    rolls delivers that lane, read off a rolled lane iota, so the result
    does not depend on the roll's direction convention.
    """
    n = a.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    for s in (1, 2, 4, 8):
        fwd = pltpu.roll(a, s, 1)
        bwd = pltpu.roll(a, n - s, 1)
        from_fwd = pltpu.roll(lane, s, 1) == (lane ^ s)
        a = jnp.maximum(a, jnp.where(from_fwd, fwd, bwd))
    return a


def _block_quantize(x, s_t, u=None):
    """Blocked E4M3 scales + E2M1 rounding of a preprocessed fp32 tile,
    blocks along lanes, in the form Mosaic compiles.

    Returns (grid values |q|, effective per-element scale, E4M3 block
    scale per element), all in the tile's own (tl, tm) layout — every
    element carries its block's scale — so no lane axis is ever split.
    Same values as ``_block_quantize_split``: the block max is exact and
    every other step is elementwise.
    """
    absx = jnp.abs(x)
    s_b = jnp.clip(_lane_block_max(absx) / (E2M1_MAX * s_t), 0.0, E4M3_MAX)
    s_b = _round_e4m3(s_b)
    scale = s_b * s_t
    a = jnp.where(scale > 0, absx / jnp.maximum(scale, _EPS), 0.0)
    if u is None:
        q = _round_e2m1_rn(a)
    else:
        q = _round_e2m1_sr(a, u)
    return q, scale, s_b


def _block_quantize_split(x, s_t, u=None, *, sub: bool = False):
    """The interpret-mode twin of ``_block_quantize``: the tile viewed as
    (…, n/16, 16) blocks, as the stage path (``core.nvfp4.nvfp4_qdq``)
    views it. The values are the same; this form keeps the XLA:CPU graph
    the stage path's, and the CPU suite pins the downstream GeMM sums of
    the two backends bitwise. ``sub`` runs the 16-blocks along axis 0.
    Returns (|q|, scale, blocked x) in the blocked layout.
    """
    tl, tm = x.shape
    if sub:
        xb = x.reshape(tl // BLOCK_SIZE, BLOCK_SIZE, tm)
        absx = jnp.abs(xb)
        block_amax = jnp.max(absx, axis=1, keepdims=True)
    else:
        xb = x.reshape(tl, tm // BLOCK_SIZE, BLOCK_SIZE)
        absx = jnp.abs(xb)
        block_amax = jnp.max(absx, axis=-1, keepdims=True)
    s_b = jnp.clip(block_amax / (E2M1_MAX * s_t), 0.0, E4M3_MAX)
    s_b = s_b.astype(jnp.float8_e4m3fn).astype(jnp.float32)  # RN to E4M3
    scale = s_b * s_t
    a = jnp.where(scale > 0, absx / jnp.maximum(scale, _EPS), 0.0)
    if u is None:
        q = _round_e2m1_rn(a)
    else:
        q = _round_e2m1_sr(a, u.reshape(a.shape))
    return q, scale, xb


def _round_e4m3(v):
    """Round non-negative fp32 values in [0, 448] to E4M3 (RNE), in fp32.

    ``v.astype(float8_e4m3fn).astype(float32)`` without an fp8 type (v5e has
    no fp8 unit): the quantum is 2^(e-3) for binade e, floored at the
    subnormal quantum 2^-9; dividing by a power of two is exact and
    ``round`` is half-to-even, so the result is bitwise the cast.
    """
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    e = jnp.maximum((bits >> 23) - 127, -6)          # binade, subnormals at -6
    step = jax.lax.bitcast_convert_type((e - 3 + 127) << 23, jnp.float32)
    return jnp.round(v / step) * step


def _bits_to_uniform(bits):
    """uint32 -> uniform [0, 1) on the top 24 bits (Mosaic converts no
    uint32 to float; the shifted value fits int32 exactly)."""
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))


def _grid_index(q):
    """E2M1 grid value -> grid index {0,.5,1,1.5,2,3,4,6} -> 0..7.

    Arithmetic (dot/searchsorted-free, Mosaic-friendly) and exact for the
    grid values ``_round_e2m1_*`` emits; matches
    ``core.nvfp4.encode_e2m1_codes``'s searchsorted on the same grid.
    """
    return jnp.where(q < 2.0, q * 2.0,
                     jnp.where(q < 4.0, q + 2.0, q * 0.5 + 4.0))


def _pair_matrix(width: int) -> jax.Array:
    """(width, width/2) one-hot pairing: column k = code[2k] + 16*code[2k+1]
    — the ``pack_nibbles`` byte, built on the MXU (exact: small integers)."""
    p = np.zeros((width, width // 2), np.float32)
    k = np.arange(width // 2)
    p[2 * k, k] = 1.0
    p[2 * k + 1, k] = 16.0
    return jnp.asarray(p, jnp.bfloat16)


def _block_select_matrix(width: int) -> jax.Array:
    """(width, width/16) one-hot picking the first lane of each 16-block
    (exact for E4M3 values, which bf16 holds exactly)."""
    p = np.zeros((width, width // BLOCK_SIZE), np.float32)
    k = np.arange(width // BLOCK_SIZE)
    p[BLOCK_SIZE * k, k] = 1.0
    return jnp.asarray(p, jnp.bfloat16)


def _pair_width(tm: int) -> int:
    """Pairing chunk: 256 code lanes -> one aligned 128-lane byte chunk."""
    return 2 * _LANES if tm % (2 * _LANES) == 0 else tm


# --------------------------------------------------------------------------
# Kernel bodies (static variants via functools.partial)
# --------------------------------------------------------------------------

def _amax_kernel(*refs, center: bool, rotate: bool, n_rows: int,
                 n_cols: int, tile_l: int, tile_m: int):
    """Sequential-grid amax of |rotate(center(x))| over (row, column)
    tiles, with padded rows and columns masked out."""
    it = iter(refs)
    x_ref = next(it)
    mu_ref = next(it) if center else None
    h_ref = next(it) if rotate else None
    o_ref = next(it)
    i, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    y = _center_rotate_tile(x, mu_ref[...] if center else None,
                            h_ref[...] if rotate else None,
                            center=center, rotate=rotate)
    row = i * tile_l + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    col = j * tile_m + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    keep = (row < n_rows) & (col < n_cols)
    part = jnp.max(jnp.where(keep, jnp.abs(y), 0.0), axis=(0, 1),
                   keepdims=True)
    part = jnp.broadcast_to(part, o_ref.shape)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = part

    @pl.when((i != 0) | (j != 0))
    def _acc():
        o_ref[...] = jnp.maximum(o_ref[...], part)


def _qdq_values(y, s_t, u, split_lanes: bool, sub: bool = False):
    """Dequantized QDQ of a preprocessed tile; ``split_lanes`` selects the
    interpret-mode form (see ``_block_quantize_split``)."""
    if split_lanes:
        q, scale, yb = _block_quantize_split(y, s_t, u, sub=sub)
        return (jnp.sign(yb) * q * scale).reshape(y.shape)
    if sub:
        return _qdq_values(y.T, s_t, None if u is None else u.T, False).T
    q, scale, _ = _block_quantize(y, s_t, u)
    return jnp.sign(y) * q * scale


def _values_kernel(*refs, center: bool, rotate: bool, sr: bool,
                   split_lanes: bool):
    """center → rotate → QDQ, dequantized tile out (the GeMM path)."""
    it = iter(refs)
    x_ref = next(it)
    mu_ref = next(it) if center else None
    h_ref = next(it) if rotate else None
    st_ref = next(it)
    bits_ref = next(it) if sr else None
    o_ref = next(it)
    x = x_ref[...].astype(jnp.float32)
    y = _center_rotate_tile(x, mu_ref[...] if center else None,
                            h_ref[...] if rotate else None,
                            center=center, rotate=rotate)
    u = _bits_to_uniform(bits_ref[...]) if sr else None
    o_ref[...] = _qdq_values(y, st_ref[0, 0], u,
                             split_lanes).astype(o_ref.dtype)


def _values_onepass_kernel(*refs, center: bool, rotate: bool, sr: bool,
                           n_rows: int, block_sub: bool, split_lanes: bool):
    """Single-tile variant of ``_values_kernel`` that also owns the amax
    pass: when the whole (padded) array is one grid cell, the per-tensor
    scale can be derived from the tile itself, so the preprocessed tile is
    computed ONCE instead of once per pass (the separate amax pass would
    redo the centering/rotation). amax is a max reduction — exact in any
    order — so s_t is bitwise the two-pass value. Padded rows are masked
    out of the amax (under a lane mu they center to -mu); padded regions
    that share a 16-block with real data are pre-padded with mu by the
    caller (``_pad_for_blocks``) so they contribute exact zeros.
    ``block_sub`` runs quantization (and rotation) blocks along axis 0 —
    the transposed GeMM orientation, quantized as the lane case of the
    transposed tile without transposing in HBM."""
    it = iter(refs)
    x_ref = next(it)
    mu_ref = next(it) if center else None
    h_ref = next(it) if rotate else None
    bits_ref = next(it) if sr else None
    o_ref = next(it)
    x = x_ref[...].astype(jnp.float32)
    y = _center_rotate_tile(x, mu_ref[...] if center else None,
                            h_ref[...] if rotate else None,
                            center=center, rotate=rotate, sub=block_sub)
    absy = jnp.abs(y)
    if y.shape[0] != n_rows:      # mask padded rows out of the amax
        row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        absy = jnp.where(row < n_rows, absy, 0.0)
    # a 0-d max interpreted, the stage path's scalar s_t; Mosaic reduces to
    # a (1, 1) vector (same value: max is exact)
    amax = (jnp.max(absy) if split_lanes
            else jnp.max(absy, axis=(0, 1), keepdims=True))
    s_t = jnp.maximum(amax / TENSOR_SCALE_DENOM, _EPS)
    u = _bits_to_uniform(bits_ref[...]) if sr else None
    out = _qdq_values(y, s_t, u, split_lanes, sub=block_sub)
    o_ref[...] = out.astype(o_ref.dtype)


def _e4m3_bits(v):
    """E4M3-exact non-negative fp32 values -> their float8_e4m3fn bytes
    (as int32), by field arithmetic on the fp32 bits."""
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    e = (bits >> 23) - 127
    normal = ((e + 7) << 3) | ((bits >> 20) & 7)
    subnormal = (v * 512.0).astype(jnp.int32)        # quantum 2^-9
    return jnp.where(e < -6, subnormal, normal)


def _pack_kernel(*refs, center: bool, rotate: bool, sr: bool):
    """center → rotate → quantize, packed nibble codes + E4M3 scale bytes
    out. Nibble pairing and the pick of one scale per block are one-hot
    matmuls on the MXU (Mosaic cannot split the lane axis). The outputs are
    integers, so this one form also runs interpreted (only the rotation
    takes its interpret-mode form there, from its operand)."""
    it = iter(refs)
    x_ref = next(it)
    mu_ref = next(it) if center else None
    h_ref = next(it) if rotate else None
    st_ref = next(it)
    bits_ref = next(it) if sr else None
    pair_ref = next(it)
    sel_ref = next(it)
    codes_ref = next(it)
    scales_ref = next(it)
    x = x_ref[...].astype(jnp.float32)
    y = _center_rotate_tile(x, mu_ref[...] if center else None,
                            h_ref[...] if rotate else None,
                            center=center, rotate=rotate)
    u = _bits_to_uniform(bits_ref[...]) if sr else None
    q, _, s_b = _block_quantize(y, st_ref[0, 0], u)
    codes = (jnp.where(y < 0, 8.0, 0.0) + _grid_index(q)).astype(jnp.bfloat16)
    pair = pair_ref[...]
    w = pair.shape[0]
    packed = [jnp.dot(codes[:, c:c + w], pair,
                      preferred_element_type=jnp.float32)
              for c in range(0, codes.shape[1], w)]
    packed = packed[0] if len(packed) == 1 else jnp.concatenate(packed, 1)
    codes_ref[...] = packed.astype(jnp.int32).astype(codes_ref.dtype)
    s_blk = jnp.dot(s_b.astype(jnp.bfloat16), sel_ref[...],
                    preferred_element_type=jnp.float32)
    scales_ref[...] = _e4m3_bits(s_blk).astype(scales_ref.dtype)


# --------------------------------------------------------------------------
# pallas_call plumbing
# --------------------------------------------------------------------------

def _pad_for_blocks(x: jax.Array, mu: Optional[jax.Array], pad_l: int,
                    pad_m: int, *, block_sub: bool = False) -> jax.Array:
    """Pad ``x`` for tiling WITHOUT corrupting shared block scales.

    Zero padding is correct wherever the padded entries either form whole
    blocks of their own or are not centered. But a padded region that (a)
    shares a 16-block with real data along the block axis and (b) is
    centered against a mean that broadcasts over it would center to ``-mu``
    and inflate that block's shared E4M3 scale — changing the quantization
    of the REAL entries (the stage path never pads, so this would also
    break bitwise parity). Those regions are padded with ``mu`` itself, so
    centering yields exact zeros there."""
    if mu is not None:
        if not block_sub and mu.shape[0] != 1 and pad_m:
            # lane blocks + sublane mu: padded tail columns share blocks
            x = jnp.concatenate(
                [x, jnp.broadcast_to(mu, (x.shape[0], pad_m)).astype(x.dtype)],
                axis=1)
            pad_m = 0
        if block_sub and mu.shape[0] == 1 and pad_l:
            # sublane blocks + lane mu: padded tail rows share blocks
            x = jnp.concatenate(
                [x, jnp.broadcast_to(mu, (pad_l, x.shape[1])).astype(x.dtype)],
                axis=0)
            pad_l = 0
    return jnp.pad(x, ((0, pad_l), (0, pad_m)))


def _mu_spec(mu: jax.Array, tile_l: int, tile_m: int):
    """BlockSpec for the mean operand: lane vector (1, m) or sublane (l, 1)."""
    if mu.shape[0] == 1:
        return pl.BlockSpec((1, tile_m), lambda i, j: (0, j))
    return pl.BlockSpec((tile_l, 1), lambda i, j: (i, 0))


def _pad_mu(mu: jax.Array, pad_l: int, pad_m: int) -> jax.Array:
    if mu.shape[0] == 1:
        return jnp.pad(mu, ((0, 0), (0, pad_m)))
    return jnp.pad(mu, ((0, pad_l), (0, 0)))


def _hadamard_arg(n: int, interpret: bool):
    """(matrix, BlockSpec) of the rotation for a tile axis of length ``n``;
    any grid rank (the block is constant)."""
    h = _hadamard_operand(n, _split_lanes(interpret))
    return h, pl.BlockSpec(h.shape, lambda *_: (0, 0))


def fused_amax_2d(
    x: jax.Array,
    mu: Optional[jax.Array] = None,
    *,
    rotate: bool = False,
    tile_l: int = DEFAULT_TILE_L,
    interpret: bool = True,
) -> jax.Array:
    """amax(|H(x - mu)|) without materializing the centered/rotated array.

    Tiles of up to 256 rows x ``REDUCE_MAX_COLS`` columns, sequential-grid max
    accumulation; padded rows and columns are masked (they would otherwise
    contribute |H(-mu)|). Returns a (1, 1) fp32 array.
    """
    l, m = x.shape
    center = mu is not None
    if rotate:
        assert m % _TILE == 0, (l, m)
    tile_l = min(tile_l, max(8, l))
    tile_m = reduce_tile_m(m)
    pad_l = (-l) % tile_l
    pad_m = (-m) % tile_m
    xp = jnp.pad(x, ((0, pad_l), (0, pad_m)))
    grid = (xp.shape[0] // tile_l, xp.shape[1] // tile_m)
    args = [xp]
    in_specs = [pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j))]
    if center:
        args.append(_pad_mu(mu, pad_l, pad_m))
        in_specs.append(_mu_spec(mu, tile_l, tile_m))
    if rotate:
        h, h_spec = _hadamard_arg(tile_m, interpret)
        args.append(h)
        in_specs.append(h_spec)
    out = pl.pallas_call(
        functools.partial(_amax_kernel, center=center, rotate=rotate,
                          n_rows=l, n_cols=m, tile_l=tile_l, tile_m=tile_m),
        out_shape=jax.ShapeDtypeStruct(_AMAX_TILE, jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(_AMAX_TILE, lambda i, j: (0, 0)),
        interpret=interpret,
    )(*args)
    return out[:1, :1]


def _main_call(kernel, x, mu, s_t, bits, out_shapes, out_specs,
               *, rotate, sr, tile_l, tile_m, interpret, extra=()):
    """Shared grid/spec assembly for the values and pack kernels.
    ``extra``: trailing (constant array, BlockSpec) inputs."""
    l, m = x.shape
    center = mu is not None
    pad_l = (-l) % tile_l
    pad_m = (-m) % tile_m
    xp = _pad_for_blocks(x, mu, pad_l, pad_m)
    grid = (xp.shape[0] // tile_l, xp.shape[1] // tile_m)
    x_spec = pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j))
    args = [xp]
    in_specs = [x_spec]
    if center:
        args.append(_pad_mu(mu, pad_l, pad_m))
        in_specs.append(_mu_spec(mu, tile_l, tile_m))
    if rotate:
        h, h_spec = _hadamard_arg(tile_m, interpret)
        args.append(h)
        in_specs.append(h_spec)
    args.append(s_t)
    in_specs.append(_SCALAR_SPEC)
    if sr:
        args.append(jnp.pad(bits, ((0, pad_l), (0, pad_m))))
        in_specs.append(x_spec)
    for arr, spec in extra:
        args.append(arr)
        in_specs.append(spec)
    return pl.pallas_call(
        functools.partial(kernel, center=center, rotate=rotate, sr=sr),
        out_shape=out_shapes,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
    )(*args), (pad_l, pad_m)


def _onepass_call(x, mu, bits, *, rotate, tile_l, tile_m, pad_l, pad_m,
                  interpret, block_sub=False):
    """Single-grid-cell QDQ with the per-tensor scale derived in-kernel."""
    l, m = x.shape
    xp = _pad_for_blocks(x, mu, pad_l, pad_m, block_sub=block_sub)
    args = [xp]
    in_specs = [pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j))]
    if mu is not None:
        args.append(_pad_mu(mu, pad_l, pad_m))
        in_specs.append(_mu_spec(mu, tile_l, tile_m))
    if rotate:
        h, h_spec = _hadamard_arg(tile_l if block_sub else tile_m,
                                     interpret)
        args.append(h)
        in_specs.append(h_spec)
    if bits is not None:
        args.append(jnp.pad(bits, ((0, pad_l), (0, pad_m))))
        in_specs.append(pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j)))
    out = pl.pallas_call(
        functools.partial(_values_onepass_kernel, center=mu is not None,
                          rotate=rotate, sr=bits is not None, n_rows=l,
                          block_sub=block_sub,
                          split_lanes=_split_lanes(interpret)),
        out_shape=jax.ShapeDtypeStruct((tile_l, tile_m), x.dtype),
        grid=(1, 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j)),
        interpret=interpret,
    )(*args)
    return out[:l, :m]


@functools.partial(jax.jit, static_argnames=(
    "rotate", "tile_l", "tile_m", "interpret", "block_axis"))
def center_hadamard_qdq_2d(
    x: jax.Array,
    mu: Optional[jax.Array] = None,
    tensor_amax: Optional[jax.Array] = None,
    bits: Optional[jax.Array] = None,
    *,
    rotate: bool = False,
    tile_l: int = DEFAULT_TILE_L,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
    block_axis: int = -1,
) -> jax.Array:
    """Fused (x - mu) → H16 → blockwise-NVFP4 QDQ.

    ``mu``: optional mean — (1, m) lane vector or (l, 1) sublane vector
    (transposed dw orientation); None skips centering. ``tensor_amax``:
    amax of the preprocessed array for the per-tensor scale (computed via
    :func:`fused_amax_2d` when None). ``bits``: uint32 → stochastic
    rounding. ``rotate`` requires 16 | the block axis.

    ``block_axis``: -1 (default) runs quantization/rotation blocks along
    lanes; 0 runs them along sublanes with a LANE mu (1, m) — the
    transposed GeMM orientation (quantize axis == token axis) handled
    without transpose copies where the one-pass kernel applies, and via an
    internal transpose round trip elsewhere.
    """
    l, m = x.shape
    if block_axis == 0:
        if rotate:
            assert l % _TILE == 0, (l, m)
        pad_l0 = (-l) % BLOCK_SIZE
        if (interpret and tensor_amax is None
                and (l + pad_l0) * m <= _ONEPASS_MAX_ELEMS):
            return _onepass_call(
                x, mu, bits, rotate=rotate, tile_l=l + pad_l0, tile_m=m,
                pad_l=pad_l0, pad_m=0, interpret=interpret, block_sub=True)
        # no native multi-tile variant: take the lane-block kernels in the
        # transposed orientation
        out = center_hadamard_qdq_2d(
            x.T, None if mu is None else mu.T, tensor_amax,
            None if bits is None else bits.T, rotate=rotate,
            tile_l=tile_l, tile_m=tile_m, interpret=interpret)
        return out.T
    if rotate:
        assert m % _TILE == 0, (l, m)
    tile_l = min(tile_l, max(8, l))
    # clamp to the array width but keep the tile a whole number of quant
    # blocks — padding adds tail columns that quantize to zero (or exact
    # zeros under a sublane mu, see _pad_for_blocks) and are sliced off
    tile_m = min(tile_m, max(BLOCK_SIZE, m))
    tile_m += (-tile_m) % BLOCK_SIZE
    if interpret and tensor_amax is None and l * m <= _ONEPASS_MAX_ELEMS:
        # the interpreter has no VMEM budget: grow the tile to the whole
        # array so the one-pass kernel below applies (it preprocesses the
        # data once instead of once in the amax pass + once in the main
        # pass — the dominant cost for rotate-heavy recipes)
        tile_l = max(tile_l, l)
        tile_m = max(tile_m, m + (-m) % BLOCK_SIZE)
    pad_l = (-l) % tile_l
    pad_m = (-m) % tile_m
    if tensor_amax is None and l + pad_l == tile_l and m + pad_m == tile_m:
        # single-tile fast path: the whole array is one grid cell, so the
        # kernel derives s_t from its own tile — one preprocessing of the
        # data instead of one per pass (amax is order-exact, bitwise the
        # two-pass result)
        return _onepass_call(
            x, mu, bits, rotate=rotate, tile_l=tile_l, tile_m=tile_m,
            pad_l=pad_l, pad_m=pad_m, interpret=interpret)
    if tensor_amax is None:
        tensor_amax = fused_amax_2d(x, mu, rotate=rotate, tile_l=tile_l,
                                    interpret=interpret)
    s_t = jnp.maximum(
        tensor_amax.astype(jnp.float32) / TENSOR_SCALE_DENOM, _EPS
    ).reshape(1, 1)
    out, _ = _main_call(
        functools.partial(_values_kernel,
                          split_lanes=_split_lanes(interpret)),
        x, mu, s_t, bits,
        jax.ShapeDtypeStruct(
            ((l + (-l) % tile_l), (m + (-m) % tile_m)), x.dtype),
        pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j)),
        rotate=rotate, sr=bits is not None,
        tile_l=tile_l, tile_m=tile_m, interpret=interpret)
    return out[:l, :m]


@functools.partial(jax.jit, static_argnames=(
    "rotate", "tile_l", "interpret"))
def center_hadamard_pack_2d(
    x: jax.Array,
    mu: Optional[jax.Array] = None,
    tensor_amax: Optional[jax.Array] = None,
    bits: Optional[jax.Array] = None,
    *,
    rotate: bool = False,
    tile_l: int = DEFAULT_TILE_L,
    interpret: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused quantize-and-pack: (packed codes, E4M3 block scales, s_t).

    One HBM read of ``x`` produces the deployment artifact directly:
    ``packed`` (l, m/2) uint8 nibble pairs (low nibble first — the
    ``core.nvfp4.pack_nibbles`` layout), ``scales`` (l, m/16)
    float8_e4m3fn, and the (1, 1) fp32 per-tensor scale. Requires
    m % 32 == 0 (whole packed nibble pairs per scale block).
    """
    l, m = x.shape
    assert m % (2 * BLOCK_SIZE) == 0, (l, m)
    if rotate:
        assert m % _TILE == 0, (l, m)
    # the scale block (tile_l, tile_m/16) must span 128 lanes or the whole
    # (padded) row: 2048-column tiles, or whole rows up to _PACK_ROW_COLS
    if m <= _PACK_ROW_COLS and m % _PACK_TILE_M != 0:
        tile_m = m
    else:
        tile_m = _PACK_TILE_M
    # keep tile_l * tile_m within the VMEM budget (32-row multiples: the
    # uint8 code tile)
    budget = max(32, (_VMEM_TILE_ELEMS // tile_m) // 32 * 32)
    tile_l = min(tile_l, budget, max(8, l))
    if tensor_amax is None:
        tensor_amax = fused_amax_2d(x, mu, rotate=rotate, tile_l=tile_l,
                                    interpret=interpret)
    s_t = jnp.maximum(
        tensor_amax.astype(jnp.float32) / TENSOR_SCALE_DENOM, _EPS
    ).reshape(1, 1)
    pad_l = (-l) % tile_l
    mp = m + (-m) % tile_m
    pair = _pair_matrix(_pair_width(tile_m))
    sel = _block_select_matrix(tile_m)
    (codes, scales), _ = _main_call(
        _pack_kernel, x, mu, s_t, bits,
        (jax.ShapeDtypeStruct((l + pad_l, mp // 2), jnp.uint8),
         jax.ShapeDtypeStruct((l + pad_l, mp // BLOCK_SIZE), jnp.uint8)),
        (pl.BlockSpec((tile_l, tile_m // 2), lambda i, j: (i, j)),
         pl.BlockSpec((tile_l, tile_m // BLOCK_SIZE), lambda i, j: (i, j))),
        rotate=rotate, sr=bits is not None,
        tile_l=tile_l, tile_m=tile_m, interpret=interpret,
        extra=((pair, pl.BlockSpec(pair.shape, lambda i, j: (0, 0))),
               (sel, pl.BlockSpec(sel.shape, lambda i, j: (0, 0)))))
    scales = jax.lax.bitcast_convert_type(
        scales[:l, :m // BLOCK_SIZE], jnp.float8_e4m3fn)
    return codes[:l, :m // 2], scales, s_t


def center_hadamard_quantize_pack(
    x: jax.Array,
    bits: Optional[jax.Array] = None,
    *,
    center: bool = True,
    rotate: bool = True,
    tile_l: int = DEFAULT_TILE_L,
    interpret: bool = True,
):
    """The full fused producer pipeline of one 2-D activation block.

    mean reduction → center+rotate+amax reduction → one fused
    quantize-and-pack pass. Returns ``(packed, scales, s_t, mu)`` with
    ``mu`` the (1, m) fp32 token mean (zeros when ``center=False``) — the
    complete wire/deployment artifact of the paper's recipe in exactly one
    full-size HBM read per pass and no full-size intermediate writes.
    """
    l, m = x.shape
    mu = column_mean_2d(x, tile_l=tile_l, interpret=interpret) if center \
        else None
    codes, scales, s_t = center_hadamard_pack_2d(
        x, mu, None, bits, rotate=rotate, tile_l=tile_l,
        interpret=interpret)
    if mu is None:
        mu = jnp.zeros((1, m), jnp.float32)
    return codes, scales, s_t, mu
