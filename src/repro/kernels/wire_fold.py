"""Packed-wire gradient fold: decode E2M1 shards inside the reduction.

PR 4 put the paper's G4 recipe on the gradient wire (``parallel/
collectives.py``): every DP shard encodes its bucket as mean + blockwise
NVFP4 residual and the reduce left-folds the S shards in global shard
order. But the codec was QDQ-*simulated* — each shard dequantized its own
bucket back to a full fp32 buffer before the fold, so ``fold_shards`` read
``4 x S`` bytes/elem no matter how small the wire format was. This module
folds the **packed wire bytes directly**, the same move PR 8 made for KV
reads:

    per shard s (one :class:`repro.parallel.collectives.WirePacket`):
      codes_s   (B/2,)  uint8   packed E2M1 nibble pairs (low nibble first)
      scales_s  (B/16,) uint8   raw E4M3 per-16-block scale bytes
      amax_s    ()      fp32    per-bucket amax -> s_t = amax/(6*448)
      mean_s    ()      fp32    exact bucket mean (centered recipes)

    fold(S packets) = [ left_fold_s  decode(codes_s, scales_s, s_t_s)/S ]
                      + left_fold_s  mean_s/S          (centered only)

so the fold reads ~0.56 bytes/elem/shard (0.5 codes + 1/16 scales) instead
of 4, and the rank-one mean term costs O(S) scalar adds — the same analytic
mean fold the paged-attention kernel applies to logits, here applied to the
reduction itself.

Numerics contract (pinned in tests/test_wire_fold.py): every backend
computes **bitwise** the reference ``fold_packets_reference`` — decode all
shards, ``lax.scan`` left fold in shard order, then add the scalar-folded
mean. E2M1 decode is gather-free bit arithmetic (``_decode_e2m1_arith``,
shared with ``kernels/paged_attention.py``), block-scale application is an
exact fp32 product, and the accumulation order is the same fixed left fold
as ``collectives.fold_shards`` — so PR 4's device-count invariance carries
over to the packed wire unchanged. Relative to the decoded wire the *only*
reassociation is the mean: the decoded fold sums ``(res_s + mu_s)/S``
elementwise while the packed fold sums the two terms separately (exactly
why ``--wire {packed,decoded}`` are distinct, each internally bitwise).

Backends (the PR 8 playbook):

* ``_fold_packets_pallas`` — grid (row tiles, S) with the shard axis
  innermost and sequential; the output block is the fold accumulator.
  Nibble interleave and scale expansion are one-hot MXU matmuls (Mosaic
  cannot split or interleave lanes). Compiled on TPU, interpreted
  elsewhere.
* ``_fold_packets_xla`` — a ``lax.scan`` twin whose chunk is one shard's
  packed payload: decode-in-body, never materializing the (S, B) fp32
  stack. The shipping CPU path (interpreted Pallas in the reduce hot loop
  would be pure overhead).

``backend="auto"`` picks Pallas on TPU and the XLA twin elsewhere.
Unfoldable inputs fall back to the reference decode-then-scan and are
counted (``quant/wire_fold_fallback``, warned once per reason — the
``quant/fused_fallback`` pattern).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BLOCK_SIZE, TENSOR_SCALE_DENOM
from repro.kernels.paged_attention import (_block_scales_mxu,
                                           _decode_e2m1_arith,
                                           _expand_matrix,
                                           _interleave_matrices,
                                           _unpack_codes_mxu)

_EPS = 1e-30

# The Pallas fold views a payload as rows of _FOLD_ROW elements (128 code
# bytes each) and folds _FOLD_TILE_ROWS rows of one shard per grid step (a
# multiple of 32, the uint8 sublane tile; or all rows when there are few).
_FOLD_ROW = 256
_FOLD_TILE_ROWS = (512, 256, 128, 64, 32)


# --------------------------------------------------------------------------
# Fallback accounting (the quant/fused_fallback pattern)
# --------------------------------------------------------------------------

def reset_wire_fold_fallback_warnings() -> None:
    """Clear the once-per-reason warning dedup on the process hub (tests)."""
    from repro.obs.telemetry import global_hub
    global_hub().reset_warnings("wire_fold")


def _wire_fold_fallback(reason: str) -> None:
    """Loud fallback: a packed fold went to the decode-then-scan reference
    (or a packed encode went back to the decoded wire). Counted per
    occurrence, warned once per (hub, reason)."""
    from repro.obs.telemetry import report_downgrade
    report_downgrade(
        "quant/wire_fold_fallback", "wire_fold", reason,
        f"packed wire fold fell back: {reason}. Counted in telemetry "
        f"as quant/wire_fold_fallback.", stacklevel=3)


# --------------------------------------------------------------------------
# Shared decode math (bitwise the core/nvfp4 QDQ chain)
# --------------------------------------------------------------------------

def shard_tensor_scales(amax: jax.Array) -> jax.Array:
    """Per-shard fp32 tensor scales from per-bucket amax: the exact
    ``nvfp4_qdq`` formula ``max(amax / (E2M1_MAX*E4M3_MAX), eps)``."""
    return jnp.maximum(amax.astype(jnp.float32) / TENSOR_SCALE_DENOM, _EPS)


def _row_matrices():
    """The one-hot nibble-interleave and scale-expansion matrices of one
    _FOLD_ROW-element row (see ``paged_attention._interleave_matrices``)."""
    return (*_interleave_matrices(_FOLD_ROW // 2),
            _expand_matrix(_FOLD_ROW // BLOCK_SIZE, BLOCK_SIZE))


def _decode_rows(codes, scales, s_t, mats) -> jax.Array:
    """(R, 128) code bytes + (R, 16) E4M3 scale bytes + scalar s_t ->
    (R, 256) fp32 values: ``vals * (s_b * s_t)``, the QDQ's own
    ``sign * q * scale`` (exact fp32 products of exact grid values)."""
    lo_m, hi_m, expand = mats
    vals = _decode_e2m1_arith(_unpack_codes_mxu(codes, lo_m, hi_m))
    return vals * (_block_scales_mxu(scales, expand) * s_t)


def decode_wire_values(codes: jax.Array, scales_u8: jax.Array,
                       s_t: jax.Array) -> jax.Array:
    """One shard's packed payload -> fp32 residual values, (B,).

    ``codes`` (B/2,) uint8 nibble pairs, ``scales_u8`` (B/16,) raw E4M3
    bytes, ``s_t`` scalar fp32. Bitwise ``nvfp4_qdq`` of the residual: the
    arithmetic decodes are bit-exact to ``core.nvfp4.decode_e2m1_codes`` and
    the fp8 cast. The payload is decoded as rows of 256 elements (zero
    bytes pad the last row and are sliced off): a (B/2, 2) nibble
    interleave would put a 2-wide minor dim in HBM, which the TPU pads to
    128 lanes (64x the bytes; a vocab-sized bucket would not fit).
    """
    half = codes.shape[-1]
    pad = (-half) % (_FOLD_ROW // 2)
    rows = jnp.pad(codes, (0, pad)).reshape(-1, _FOLD_ROW // 2)
    srows = jnp.pad(scales_u8, (0, pad // 8)).reshape(
        -1, _FOLD_ROW // BLOCK_SIZE)
    return _decode_rows(rows, srows, s_t, _row_matrices()).reshape(-1)[
        :2 * half]


def _fold_means(mean: jax.Array, num_shards: int) -> jax.Array:
    """Left fold of the S fp32 mean scalars: ``sum_s mean_s / S`` in shard
    order — the O(S) analytic half of the centered fold."""
    acc, _ = jax.lax.scan(
        lambda c, m: (c + m.astype(jnp.float32) / num_shards, None),
        jnp.float32(0.0), mean)
    return acc


# --------------------------------------------------------------------------
# Reference: decode every shard, then the collectives.fold_shards scan
# --------------------------------------------------------------------------

def fold_packets_reference(codes: jax.Array, scales: jax.Array,
                           amax: jax.Array, mean: Optional[jax.Array],
                           num_shards: int) -> jax.Array:
    """THE pinned contract: decode-then-``lax.scan`` left fold.

    Materializes the (S, B) decoded residual stack, folds it with exactly
    ``collectives.fold_shards``' scan, then adds the scalar-folded mean.
    Every other backend must be bitwise-equal to this.
    """
    s_t = shard_tensor_scales(amax)
    decoded = jax.vmap(decode_wire_values)(codes, scales, s_t)
    acc0 = jnp.zeros(decoded.shape[1:], jnp.float32)
    acc, _ = jax.lax.scan(
        lambda c, x: (c + x.astype(jnp.float32) / num_shards, None),
        acc0, decoded)
    if mean is not None:
        acc = acc + _fold_means(mean, num_shards)
    return acc


# --------------------------------------------------------------------------
# XLA twin: decode inside the shard scan (the shipping CPU path)
# --------------------------------------------------------------------------

def _fold_packets_xla(codes: jax.Array, scales: jax.Array, amax: jax.Array,
                      mean: Optional[jax.Array],
                      num_shards: int) -> jax.Array:
    """Chunked ``lax.scan`` fold: each scan step decodes ONE shard's packed
    chunk in-body and accumulates — same ops in the same order as the
    reference (bitwise-equal), but the (S, B) fp32 stack never exists; the
    loop reads 0.5625 bytes/elem per shard."""
    b = 2 * codes.shape[-1]
    s_t = shard_tensor_scales(amax)

    def body(acc, xs):
        c, sc, st = xs
        return acc + decode_wire_values(c, sc, st) / num_shards, None

    acc, _ = jax.lax.scan(body, jnp.zeros((b,), jnp.float32),
                          (codes, scales, s_t))
    if mean is not None:
        acc = acc + _fold_means(mean, num_shards)
    return acc


# --------------------------------------------------------------------------
# Pallas kernel: grid (row tiles, S), the output block accumulates
# --------------------------------------------------------------------------

def _packet_fold_kernel(st_ref, codes_ref, scales_ref, lo_ref, hi_ref,
                        ex_ref, o_ref, *, num_shards: int):
    """Grid (row tiles, S), shards innermost: block (1, tr, 128) packed
    bytes + (1, tr, 16) scale bytes of shard s are decoded and the output
    block is the fold accumulator (``o = part`` at s == 0, then
    ``o + part``) — exactly ``collectives._fold_kernel`` with the decode
    pulled inside. Codes and scales are read packed and the residual
    exists only in VMEM; VMEM and trace size do not grow with S."""
    s = pl.program_id(1)
    part = _decode_rows(codes_ref[0], scales_ref[0], st_ref[s],
                        (lo_ref[...], hi_ref[...], ex_ref[...])) / num_shards

    @pl.when(s == 0)
    def _init():
        o_ref[...] = part

    @pl.when(s != 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


def _fold_packets_pallas(codes: jax.Array, scales: jax.Array,
                         amax: jax.Array, mean: Optional[jax.Array],
                         num_shards: int, *,
                         interpret: bool) -> jax.Array:
    """Pallas fold of (S, B/2)+(S, B/16) packed shards.

    The payload is viewed as rows of 256 elements — (S, R, 128) code bytes
    and (S, R, 16) scale bytes, whose last two dims Mosaic tiles whole —
    zero-padded to whole row tiles (zero bytes decode to +0 and are sliced
    off). Scale bytes stay uint8: v5e has no fp8 unit."""
    s_dim, half = codes.shape
    b = 2 * half
    rows = -(-b // _FOLD_ROW)
    tile_r = next((t for t in _FOLD_TILE_ROWS if rows % t == 0),
                  min(rows, _FOLD_TILE_ROWS[0]))
    rows = -(-rows // tile_r) * tile_r
    bp = rows * _FOLD_ROW
    if bp != b:
        codes = jnp.pad(codes, ((0, 0), (0, (bp - b) // 2)))
        scales = jnp.pad(scales, ((0, 0), (0, (bp - b) // BLOCK_SIZE)))
    n_bytes, n_scales = _FOLD_ROW // 2, _FOLD_ROW // BLOCK_SIZE
    lo_m, hi_m, expand = _row_matrices()
    const = lambda a: pl.BlockSpec(a.shape, lambda r, s: (0, 0))
    acc = pl.pallas_call(
        functools.partial(_packet_fold_kernel, num_shards=num_shards),
        out_shape=jax.ShapeDtypeStruct((rows, _FOLD_ROW), jnp.float32),
        grid=(rows // tile_r, s_dim),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tile_r, n_bytes), lambda r, s: (s, r, 0)),
            pl.BlockSpec((1, tile_r, n_scales), lambda r, s: (s, r, 0)),
            const(lo_m), const(hi_m), const(expand),
        ],
        out_specs=pl.BlockSpec((tile_r, _FOLD_ROW), lambda r, s: (r, 0)),
        interpret=interpret,
    )(shard_tensor_scales(amax), codes.reshape(s_dim, rows, n_bytes),
      scales.astype(jnp.uint8).reshape(s_dim, rows, n_scales),
      lo_m, hi_m, expand).reshape(-1)[:b]
    if mean is not None:
        acc = acc + _fold_means(mean, num_shards)
    return acc


# --------------------------------------------------------------------------
# Dispatch
# --------------------------------------------------------------------------

def fold_packets(codes: jax.Array, scales: jax.Array, amax: jax.Array,
                 mean: Optional[jax.Array], num_shards: int, *,
                 backend: str = "auto",
                 interpret: Optional[bool] = None) -> jax.Array:
    """Fold S stacked wire packets into the (B,) fp32 reduced bucket.

    ``codes`` (S, B/2) uint8, ``scales`` (S, B/16) uint8 raw E4M3 bytes,
    ``amax`` (S,) fp32, ``mean`` (S,) fp32 or None (uncentered payloads —
    the mean add is skipped entirely so ``-0.0`` accumulators survive).
    ``backend``: "auto" (Pallas on TPU, XLA twin elsewhere) | "pallas" |
    "xla" | "reference". All backends are bitwise-equal (pinned).
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not (codes.ndim == 2 and codes.shape[0] == num_shards
            and (2 * codes.shape[1]) % BLOCK_SIZE == 0
            and scales.shape == (num_shards,
                                 2 * codes.shape[1] // BLOCK_SIZE)):
        _wire_fold_fallback(
            f"packet stack shapes codes={codes.shape} scales={scales.shape} "
            f"do not form S={num_shards} block-aligned shards")
        return fold_packets_reference(codes, scales, amax, mean, num_shards)
    if backend == "pallas":
        return _fold_packets_pallas(codes, scales, amax, mean, num_shards,
                                    interpret=interpret)
    if backend == "xla":
        return _fold_packets_xla(codes, scales, amax, mean, num_shards)
    return fold_packets_reference(codes, scales, amax, mean, num_shards)
