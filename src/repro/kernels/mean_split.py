"""Pallas TPU kernels for the Averis hot path.

Two kernels:

  * ``column_mean_2d`` — feature-wise mean over the token axis, computed as a
    sequential-grid accumulation over row tiles (TPU grid iteration is
    sequential, so accumulating into the output block is race-free). This is
    the only reduction Averis adds over vanilla NVFP4.

  * ``mean_split_qdq_2d`` — the fusion that makes Averis cheap: subtract the
    (precomputed) mean vector from each tile and blockwise-NVFP4 QDQ the
    residual in the SAME VMEM pass. The centered residual X_R is never
    round-tripped through HBM unquantized — one load of X, one store of
    QDQ(X - 1*mu), exactly the memory traffic of vanilla quantization.

Compare the tiled-Hadamard baseline, which needs an extra 16x16 matmul per
tile *and* (unfused) an extra HBM round-trip — the roofline gap the paper's
Table 2 reports (4.5-4.7x) and that our bench_table2 reproduces.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import BLOCK_SIZE, TENSOR_SCALE_DENOM
from .nvfp4_quant import DEFAULT_TILE_L, DEFAULT_TILE_M, _qdq_tile

_EPS = 1e-30


# Column tile of the reduction passes (mean here, amax in ``fused``): rows
# of up to 256 x 2048 fp32 = 2 MiB per block, well inside v5e's 16 MiB of
# scoped VMEM; a vocab-wide row (151,936) cannot be one block.
REDUCE_MAX_COLS = 2048


def reduce_tile_m(m: int) -> int:
    """Column tile of a reduction pass over (l, m): the whole row while it
    fits, else a 128-multiple divisor of ``m``, else ``REDUCE_MAX_COLS``
    with padded columns."""
    if m <= REDUCE_MAX_COLS:
        return m
    for cand in (REDUCE_MAX_COLS, 3 * REDUCE_MAX_COLS // 4,
                 REDUCE_MAX_COLS // 2):
        if m % cand == 0:
            return cand
    return REDUCE_MAX_COLS


def _mean_kernel(x_ref, o_ref, *, n_rows: int):
    """Grid (column tiles, row tiles), rows innermost: the (1, tile_m)
    output block accumulates its columns' sums in row-tile order."""
    i = pl.program_id(1)
    part = jnp.sum(x_ref[...].astype(jnp.float32), axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = part / n_rows

    @pl.when(i != 0)
    def _acc():
        o_ref[...] += part / n_rows


@functools.partial(jax.jit, static_argnames=("tile_l", "interpret"))
def column_mean_2d(
    x: jax.Array, *, tile_l: int = DEFAULT_TILE_L, interpret: bool = True
) -> jax.Array:
    """mu = (1/l) 1^T X for X (l, m); returns (1, m) fp32."""
    l, m = x.shape
    tile_l = min(tile_l, max(8, l))
    tile_m = reduce_tile_m(m)
    pad_l = (-l) % tile_l
    pad_m = (-m) % tile_m
    # zero rows don't perturb the sum; padded columns are sliced off
    xp = jnp.pad(x, ((0, pad_l), (0, pad_m)))
    grid = (xp.shape[1] // tile_m, xp.shape[0] // tile_l)
    out = pl.pallas_call(
        functools.partial(_mean_kernel, n_rows=l),
        out_shape=jax.ShapeDtypeStruct((1, xp.shape[1]), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((tile_l, tile_m), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((1, tile_m), lambda j, i: (0, j)),
        interpret=interpret,
    )(xp)
    return out[:, :m]


def _split_qdq_kernel(x_ref, mu_ref, st_ref, o_ref):
    x = x_ref[...].astype(jnp.float32) - mu_ref[...].astype(jnp.float32)
    o_ref[...] = _qdq_tile(x, st_ref[0, 0]).astype(o_ref.dtype)


def _split_qdq_kernel_sr(x_ref, mu_ref, st_ref, bits_ref, o_ref):
    x = x_ref[...].astype(jnp.float32) - mu_ref[...].astype(jnp.float32)
    u = (bits_ref[...] >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    o_ref[...] = _qdq_tile(x, st_ref[0, 0], u).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile_l", "tile_m", "interpret")
)
def mean_split_qdq_2d(
    x: jax.Array,
    mu: jax.Array,
    residual_amax: jax.Array,
    bits: Optional[jax.Array] = None,
    *,
    tile_l: int = DEFAULT_TILE_L,
    tile_m: int = DEFAULT_TILE_M,
    interpret: bool = True,
) -> jax.Array:
    """Fused (X - 1*mu) -> blockwise NVFP4 QDQ along the last axis.

    ``mu``: (1, m) mean vector; ``residual_amax``: scalar amax(|X - 1*mu|)
    for the per-tensor scale (one fused max-reduction on the producer side, or
    reuse of the mean kernel's pass in deployment).
    """
    l, m = x.shape
    tile_l = min(tile_l, max(8, l))
    tile_m = min(tile_m, max(BLOCK_SIZE, m))
    pad_l = (-l) % tile_l
    pad_m = (-m) % tile_m
    s_t = jnp.maximum(
        residual_amax.astype(jnp.float32) / TENSOR_SCALE_DENOM, _EPS
    ).reshape(1, 1)
    xp = jnp.pad(x, ((0, pad_l), (0, pad_m)))
    # Padded rows become -mu after the subtract; they are sliced away below
    # and never contribute to block scales of real data columns (scales are
    # per-row-block along the lane dim).
    mup = jnp.pad(mu.reshape(1, m), ((0, 0), (0, pad_m)))
    grid = (xp.shape[0] // tile_l, xp.shape[1] // tile_m)
    x_spec = pl.BlockSpec((tile_l, tile_m), lambda i, j: (i, j))
    mu_spec = pl.BlockSpec((1, tile_m), lambda i, j: (0, j))
    st_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    out_shape = jax.ShapeDtypeStruct(xp.shape, x.dtype)
    if bits is None:
        out = pl.pallas_call(
            _split_qdq_kernel,
            out_shape=out_shape,
            grid=grid,
            in_specs=[x_spec, mu_spec, st_spec],
            out_specs=x_spec,
            interpret=interpret,
        )(xp, mup, s_t)
    else:
        bp = jnp.pad(bits, ((0, pad_l), (0, pad_m)))
        out = pl.pallas_call(
            _split_qdq_kernel_sr,
            out_shape=out_shape,
            grid=grid,
            in_specs=[x_spec, mu_spec, st_spec, x_spec],
            out_specs=x_spec,
            interpret=interpret,
        )(xp, mup, s_t, bp)
    return out[:l, :m]
