"""Paged FP4 flash-decode attention: dequantize-inside-the-kernel KV reads.

Decode attention is bandwidth-bound, and the committed FP4 pages of the
serving KV cache (``serve/kvcache.py``) are ~0.30x the bytes of bf16 — but
the reference read path (``QuantizedKVAdapter._dense_view``) re-inflates
them into a dense ``(b, cap, 2, n_kv, hd)`` bf16 tensor on every step, so
attention pays 2 B/elem anyway. This module reads the page payload *as
stored* — packed E2M1 code nibbles, E4M3 block scales, one fp32 amax per
(page, stream), and the bf16 per-page token mean — and never materializes a
dense KV tensor at any sequence length.

The paper's structure is what makes the kernel cheap. In centered mode the
dominant component of a page's K/V rows is the rank-one token mean ``mu``,
which is *constant across the page's tokens*; its contribution to every
``q . k`` logit in that page is therefore the single scalar ``q . mu_k``,
computed once per (page, head) and added to the page's logits before
softmax, and its contribution to the output through the V stream is
``mu_v * sum(p)`` — one vector scaled by the page's softmax mass. Only the
small zero-mean residual is dequantized from E2M1, tile by tile, in
registers/VMEM. ("Massive Spikes in LLMs are Bias Vectors" reaches the same
rank-one conclusion from the spike side.)

Design: flash-decode (split-K over pages) with online-softmax partials.
Each source of keys contributes an ``(m, l, acc)`` partial —

* committed pages: dequantized per 16-token tile, mean folded analytically;
* the bf16 tail page: exact values, masked to the valid prefix;
* the speculative span (verify only): exact scratch K/V, causally masked —

and partials merge with the standard ``m* = max(m_i)``,
``l* = sum(l_i * exp(m_i - m*))``, ``acc* = sum(acc_i * exp(m_i - m*))``.
All accumulation is float32, and the masked online softmax keeps the
running max finite (``NEG_INF = -1e30``, matching ``models/attention.py``)
so empty pages and all-masked rows stay NaN-free.

Two interchangeable page-partial backends implement the same algorithm:

* ``_page_partials_pallas`` — the Pallas kernel, grid ``(b, n_kv, page
  groups)`` with groups innermost (sequential on TPU, so the output blocks
  double as the online-softmax accumulators); E2M1 and E4M3 decode are
  gather-free arithmetic on the code bits, and the nibble interleave and
  per-block scale expansion are exact one-hot MXU matmuls (Mosaic cannot
  interleave or split lanes, and v5e has no fp8). Runs compiled on TPU,
  interpreted elsewhere (the ``kernels/fused.py`` convention).
* ``_page_partials_xla`` — a ``lax.scan`` twin over pages built on the
  shared ``core/nvfp4`` codec helpers. Identical math and the same
  online-softmax schedule (``_pages_per_step`` pages per step), still no
  dense KV tensor; it is what the serving engine uses off-TPU, where
  interpreted Pallas in the decode hot loop would be pure overhead.

``backend="auto"`` picks Pallas on TPU and the XLA twin elsewhere.

Numerics contract: the fused path folds the mean as ``q.res + q.mu`` while
the dense reference computes ``q.(res + mu)``; with float32 views and
float32 softmax both differ only by float32 reassociation (~2^-24
relative), which is why engine-level greedy decode is token-identical to
``_dense_view`` in practice and why tests compare within one jit regime
(see ``tests/test_paged_attention.py``). Committed page payloads are
untouched: this module only changes *reads*.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BLOCK_SIZE, TENSOR_SCALE_DENOM
from repro.core.nvfp4 import decode_e2m1_codes, unpack_nibbles

# Finite mask value (matches models/attention.py): exp(NEG_INF - NEG_INF)=1
# on fully-masked rows instead of the NaN that -inf would produce.
NEG_INF = -1e30
_EPS = 1e-30
# fp32 operands stay fp32 on the MXU (the TPU's default f32 matmul rounds
# them to bf16); the one-hot codec matmuls are bf16 by construction. Every
# f32 contraction here - kernel, XLA twin, tail/span blocks - uses it, so the
# read's numerics do not depend on the caller's default matmul precision.
_F32 = jax.lax.Precision.HIGHEST
_einsum = functools.partial(jnp.einsum, precision=_F32)

Partial = Tuple[jax.Array, jax.Array, jax.Array]   # (m, l, acc)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# In-kernel E2M1 decode: gather-free arithmetic on the code bits
# --------------------------------------------------------------------------

def _decode_e2m1_arith(codes: jax.Array) -> jax.Array:
    """4-bit sign|magnitude E2M1 codes -> signed float32 grid values.

    Pure arithmetic on small integers (no table gather, no exp2 — Pallas/
    TPU friendly): magnitude ``m = code & 7`` maps to the grid
    {0, .5, 1, 1.5, 2, 3, 4, 6} as ``m/2`` (m < 4), ``m - 2`` (m < 6) or
    ``2m - 8``, every step exact in fp32. Bit-exact to
    ``core.nvfp4.decode_e2m1_codes`` over all 256 byte values (asserted in
    tests/test_paged_attention.py).
    """
    m = (codes & 7).astype(jnp.float32)
    mag = jnp.where(m < 4.0, 0.5 * m, jnp.where(m < 6.0, m - 2.0, 2.0 * m - 8.0))
    return jnp.where(codes >= 8, -mag, mag)


def _decode_e4m3_bits(b: jax.Array) -> jax.Array:
    """float8_e4m3fn bytes (any int dtype) -> float32 values, by field
    arithmetic: v5e has no fp8 unit, so Mosaic cannot convert fp8.

    Exponent field ``e = (b >> 3) & 15``, mantissa ``m = b & 7``: normals
    are ``(8 + m) * 2^(e - 10)``, subnormals ``m * 2^-9`` — built as an
    exact integer times 2^-9. Bit-exact to the fp8 cast for every non-NaN
    byte (E4M3 block scales are clipped to 448, so never NaN).
    """
    b = b.astype(jnp.int32)
    e = (b >> 3) & 15
    m = b & 7
    mag = jnp.where(e == 0, m, (8 + m) << jnp.maximum(e - 1, 0))
    v = mag.astype(jnp.float32) * (1.0 / 512.0)
    return jnp.where(b >= 128, -v, v)


def _interleave_matrices(half: int) -> Tuple[jax.Array, jax.Array]:
    """One-hot (half, 2*half) bf16 matrices scattering low/high nibbles to
    the even/odd columns of the ``pack_nibbles`` order. Mosaic cannot
    interleave lanes, so the kernels do it on the MXU: every output is one
    small integer times 1 plus zeros, exact."""
    lo = np.zeros((half, 2 * half), np.float32)
    k = np.arange(half)
    lo[k, 2 * k] = 1.0
    hi = np.zeros_like(lo)
    hi[k, 2 * k + 1] = 1.0
    return jnp.asarray(lo, jnp.bfloat16), jnp.asarray(hi, jnp.bfloat16)


def _expand_matrix(n_blocks: int, block_size: int) -> jax.Array:
    """One-hot (n_blocks, n_blocks*block_size) bf16 matrix repeating each
    block scale over its block (exact: E4M3 values are bf16 values)."""
    e = np.repeat(np.eye(n_blocks, dtype=np.float32), block_size, axis=1)
    return jnp.asarray(e, jnp.bfloat16)


def _unpack_codes_mxu(codes_u8, lo_m, hi_m) -> jax.Array:
    """(R, W) packed bytes -> (R, 2W) int32 codes in ``pack_nibbles``
    order, interleaved on the MXU (``_interleave_matrices``)."""
    c = codes_u8.astype(jnp.int32)
    lo = (c & 15).astype(jnp.float32).astype(jnp.bfloat16)
    hi = (c >> 4).astype(jnp.float32).astype(jnp.bfloat16)
    codes = (jnp.dot(lo, lo_m, preferred_element_type=jnp.float32)
             + jnp.dot(hi, hi_m, preferred_element_type=jnp.float32))
    return codes.astype(jnp.int32)


def _block_scales_mxu(scale_bytes, expand) -> jax.Array:
    """(R, nb) E4M3 scale bytes -> (R, nb*block) fp32 per-element scales."""
    s_b = _decode_e4m3_bits(scale_bytes).astype(jnp.bfloat16)
    return jnp.dot(s_b, expand, preferred_element_type=jnp.float32)


def _dequant_tile_mxu(codes_u8, scale_bytes, s_t, lo_m, hi_m, expand):
    """One page tile (P, hd//2) u8 + (P, hd//block) E4M3 bytes + scalar
    s_t -> float32 residual (P, hd), in the form Mosaic compiles: the same
    values, in the same op order, as ``_page_partials_xla``'s dequant
    (``vals * (s_b * s_t)``)."""
    vals = _decode_e2m1_arith(_unpack_codes_mxu(codes_u8, lo_m, hi_m))
    return vals * (_block_scales_mxu(scale_bytes, expand) * s_t)


# --------------------------------------------------------------------------
# Pallas page-partials kernel
# --------------------------------------------------------------------------

def _pages_per_step(n_pages: int, page: int) -> int:
    """Pages per online-softmax step, shared by both backends: ~128 tokens
    per step. The running max moves once per step, so the kernel and the
    twin exponentiate the same ``logits - m`` only if they step alike — a
    TPU's f32 ``exp`` is off by several ULPs, and a different schedule
    rounds differently (several 1e-6 at real widths)."""
    return max(1, min(n_pages, 128 // page))


def _flash_kernel(pidx_ref, st_ref, q_ref, ck_ref, sk_ref, cv_ref, sv_ref,
                  lo_ref, hi_ref, ex_ref, *rest, n_pages: int, group: int,
                  sm_scale: float, centered: bool):
    """Grid (b, n_kv, page groups), groups innermost; each step reads
    ``group`` pages (``_pages_per_step``), as the XLA twin does. The output
    blocks (indexed independently of the group axis) are the online-softmax
    accumulators: init at t == 0, accumulate while the group holds a
    committed page, final values stand when the loop ends. Pages past
    ``pidx`` in the last group are masked out of the max and of ``p``;
    committed pages are always full, so no per-token mask is needed.
    ``pidx`` and the per-page tensor scales ``st`` (flat (b, n_pages, 2))
    are SMEM scalars."""
    if centered:
        mk_ref, mv_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    bi, t = pl.program_id(0), pl.program_id(2)
    n_valid = pidx_ref[bi]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(t * group < n_valid)
    def _accumulate():
        mats = (lo_ref[...], hi_ref[...], ex_ref[...])
        q = q_ref[0, 0]                                       # (sg, hd) f32
        pages = []
        for i in range(group):
            j = t * group + i
            st = jnp.minimum(j, n_pages - 1) * 2              # padded: clip
            res_k = _dequant_tile_mxu(ck_ref[0, i, 0], sk_ref[0, i, 0],
                                      st_ref[(bi * n_pages) * 2 + st],
                                      *mats)                  # (P, hd)
            logits = jax.lax.dot_general(                     # (sg, P)
                q, res_k, (((1,), (1,)), ((), ())),
                precision=_F32, preferred_element_type=jnp.float32)
            if centered:
                mu_k = mk_ref[0, i, 0].astype(jnp.float32)    # (1, hd)
                # the whole page's mean contribution: one scalar per row
                logits = logits + jnp.sum(q * mu_k, axis=-1, keepdims=True)
            pages.append((logits * sm_scale, j < n_valid, st))

        m_prev = m_ref[0, 0]                                  # (sg, 1)
        m_new = m_prev
        for logits, ok, _ in pages:
            m_new = jnp.maximum(m_new, jnp.where(
                ok, jnp.max(logits, axis=-1, keepdims=True), NEG_INF))
        alpha = jnp.exp(m_prev - m_new)
        upd = psum = mean_upd = None
        for i, (logits, ok, st) in enumerate(pages):
            p = jnp.where(ok, jnp.exp(logits - m_new), 0.0)   # (sg, P)
            ps = jnp.sum(p, axis=-1, keepdims=True)
            res_v = _dequant_tile_mxu(cv_ref[0, i, 0], sv_ref[0, i, 0],
                                      st_ref[(bi * n_pages) * 2 + st + 1],
                                      *mats)
            pv = jax.lax.dot_general(
                p, res_v, (((1,), (0,)), ((), ())),
                precision=_F32, preferred_element_type=jnp.float32)
            upd = pv if upd is None else upd + pv
            psum = ps if psum is None else psum + ps
            if centered:
                # mu_v * sum(p); a masked page adds 0 whatever its mean
                mean_v = jnp.where(
                    ok, ps * mv_ref[0, i, 0].astype(jnp.float32), 0.0)
                mean_upd = (mean_v if mean_upd is None
                            else mean_upd + mean_v)
        if centered:
            upd = upd + mean_upd
        acc_ref[0, 0] = acc_ref[0, 0] * alpha + upd
        m_ref[0, 0] = m_new
        l_ref[0, 0] = l_ref[0, 0] * alpha + psum


def _page_partials_pallas(q, ck, sk, cv, sv, pamax, mk, mv, pidx, *,
                          block_size: int, sm_scale: float,
                          interpret: Optional[bool] = None) -> Partial:
    """Pallas page partials. q (b, n_kv, sg, hd) f32; codes/scales per
    stream (b, np, P, n_kv, hd//2|nb); pamax (b, np, 2) f32; means
    (b, np, n_kv, hd) or None; pidx (b,) int32.

    Mosaic blocks the last two dims to full extent (or (8, 128) multiples),
    so each stream's pages are laid out head-major, (b, np, n_kv, P, ·),
    in the copy that already splits K from V, and padded to whole page
    groups; scale bytes enter as uint8 (no fp8 on v5e); ``pidx`` and the
    tensor scales ride in SMEM.
    """
    b, nkv, sg, hd = q.shape
    np_, p = ck.shape[1], ck.shape[2]
    nb = sk.shape[-1]
    centered = mk is not None
    interp = _interpret_default() if interpret is None else interpret
    group = _pages_per_step(np_, p)
    n_groups = -(-np_ // group)
    pad = n_groups * group - np_

    def paged(a):                                 # (b, np+pad, n, P, ·)
        a = jnp.moveaxis(a, 3, 2)
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    as_bytes = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint8)
    s_t = jnp.maximum(pamax.astype(jnp.float32) / TENSOR_SCALE_DENOM, _EPS)
    lo_m, hi_m = _interleave_matrices(hd // 2)
    expand = _expand_matrix(nb, block_size)

    kernel = functools.partial(_flash_kernel, n_pages=np_, group=group,
                               sm_scale=float(sm_scale), centered=centered)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    page_spec = lambda w: pl.BlockSpec((1, group, 1, p, w),
                                       lambda bi, ki, t: (bi, t, ki, 0, 0))
    head_spec = lambda w: pl.BlockSpec((1, 1, sg, w),
                                       lambda bi, ki, t: (bi, ki, 0, 0))
    const = lambda a: pl.BlockSpec(a.shape, lambda bi, ki, t: (0, 0))
    in_specs = [smem, smem, head_spec(hd),
                page_spec(hd // 2), page_spec(nb),
                page_spec(hd // 2), page_spec(nb),
                const(lo_m), const(hi_m), const(expand)]
    args = [pidx.astype(jnp.int32), s_t.reshape(-1), q,
            paged(ck), as_bytes(paged(sk)),
            paged(cv), as_bytes(paged(sv)), lo_m, hi_m, expand]
    if centered:
        mean_spec = pl.BlockSpec((1, group, 1, 1, hd),
                                 lambda bi, ki, t: (bi, t, ki, 0, 0))
        in_specs += [mean_spec, mean_spec]
        args += [paged(mk[:, :, None]), paged(mv[:, :, None])]
    out_shape = [
        jax.ShapeDtypeStruct((b, nkv, sg, hd), jnp.float32),       # acc
        jax.ShapeDtypeStruct((b, nkv, sg, 1), jnp.float32),        # m
        jax.ShapeDtypeStruct((b, nkv, sg, 1), jnp.float32),        # l
    ]
    out_specs = [head_spec(hd), head_spec(1), head_spec(1)]
    acc, m, l = pl.pallas_call(
        kernel, grid=(b, nkv, n_groups), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        interpret=interp)(*args)
    return m, l, acc


# --------------------------------------------------------------------------
# XLA twin: lax.scan over pages, shared core/nvfp4 codec, same algorithm
# --------------------------------------------------------------------------

def _page_partials_xla(q, ck, sk, cv, sv, pamax, mk, mv, pidx, *,
                       block_size: int, sm_scale: float) -> Partial:
    """Same partials as the Pallas kernel via a chunked page loop — the
    engine's off-TPU hot path. Pages are processed G at a time (G sized so
    each iteration covers ~128 tokens): XLA CPU/GPU amortize loop dispatch
    over one large gather/dequant/einsum instead of paying it per 16-token
    page, which is what lets the fused read beat the dense-view path it
    replaces. The loop bound stays DYNAMIC (max live page over the batch),
    so a short context never pays dequant for empty capacity — matching
    the fixed ``_dense_view`` fallback's work profile. Within a chunk,
    pages a slot has not committed yet (j >= pidx[b]) are masked out of
    both the running max and p, so they contribute exact no-ops."""
    b, nkv, sg, hd = q.shape
    np_, p = ck.shape[1], ck.shape[2]
    centered = mk is not None
    G = _pages_per_step(np_, p)                    # pages per loop iteration

    def dequant(codes, scales, s_t):
        """codes (b,G,P,n,hd//2), scales (b,G,P,n,nb), s_t (b,G)."""
        vals = decode_e2m1_codes(unpack_nibbles(codes))   # (b,G,P,n,hd)
        scale = scales.astype(jnp.float32) * s_t[:, :, None, None, None]
        rb = vals.reshape(vals.shape[:-1] + (hd // block_size, block_size))
        return (rb * scale[..., None]).reshape(vals.shape)

    def body(t, carry):
        m, l, acc = carry
        js = t * G + jnp.arange(G)                          # (G,)
        pa = jnp.take(pamax, js, axis=1, mode="clip")       # (b,G,2)
        s_tk = jnp.maximum(pa[..., 0] / TENSOR_SCALE_DENOM, _EPS)
        s_tv = jnp.maximum(pa[..., 1] / TENSOR_SCALE_DENOM, _EPS)
        res_k = dequant(jnp.take(ck, js, axis=1, mode="clip"),
                        jnp.take(sk, js, axis=1, mode="clip"), s_tk)
        logits = _einsum("bnsh,bgpnh->bnsgp", q, res_k)  # (b,n,sg,G,P)
        if centered:
            mkc = jnp.take(mk, js, axis=1,
                           mode="clip").astype(jnp.float32)  # (b,G,n,hd)
            qmu = _einsum("bnsh,bgnh->bnsg", q, mkc)
            logits = logits + qmu[..., None]
        logits = (logits * sm_scale).reshape(b, nkv, sg, G * p)

        valid = (js[None, :] < pidx[:, None])               # (b, G)
        vmask = jnp.broadcast_to(valid[:, None, None, :, None],
                                 (b, 1, 1, G, p)).reshape(b, 1, 1, G * p)
        masked = jnp.where(vmask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(masked, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        pmat = jnp.where(vmask, jnp.exp(logits - m_new), 0.0)
        psum = jnp.sum(pmat, axis=-1, keepdims=True)
        res_v = dequant(jnp.take(cv, js, axis=1, mode="clip"),
                        jnp.take(sv, js, axis=1, mode="clip"), s_tv)
        upd = _einsum("bnsk,bknh->bnsh", pmat,
                      res_v.reshape(b, G * p, nkv, hd))
        if centered:
            mvc = jnp.take(mv, js, axis=1,
                           mode="clip").astype(jnp.float32)  # (b,G,n,hd)
            pg = pmat.reshape(b, nkv, sg, G, p).sum(-1)      # (b,n,sg,G)
            upd = upd + _einsum("bnsg,bgnh->bnsh", pg, mvc)
        return (m_new, l * alpha + psum, acc * alpha + upd)

    init = (jnp.full((b, nkv, sg, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, nkv, sg, 1), jnp.float32),
            jnp.zeros((b, nkv, sg, hd), jnp.float32))
    n_live = jnp.minimum(jnp.max(pidx), np_ - 1) + 1
    return jax.lax.fori_loop(0, (n_live + G - 1) // G, body, init)


# --------------------------------------------------------------------------
# Exact blocks (bf16 tail page / speculative span) and partial combination
# --------------------------------------------------------------------------

def _block_partial(q, kb, vb, valid, *, sm_scale: float) -> Partial:
    """Softmax partial over one exact K/V block. q (b, n, sg, hd) f32;
    kb/vb (b, n, T, hd) f32; valid (b, sg, T) or (b, 1, T) bool. No mean
    term — the tail and the speculative span are stored exact."""
    logits = _einsum("bnsh,bnth->bnst", q, kb) * sm_scale
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(valid[:, None], jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = _einsum("bnst,bnth->bnsh", p, vb)
    return m, l, acc


def combine_partials(parts: Sequence[Partial]) -> Partial:
    """Merge flash partials: m* = max, everything else rescaled onto m*.
    All-empty partials (m = NEG_INF, l = 0) merge as exact no-ops."""
    m = functools.reduce(jnp.maximum, [p[0] for p in parts])
    l = sum(p[1] * jnp.exp(p[0] - m) for p in parts)
    acc = sum(p[2] * jnp.exp(p[0] - m) for p in parts)
    return m, l, acc


def _finalize(part: Partial) -> jax.Array:
    m, l, acc = part
    return acc / jnp.maximum(l, _EPS)


# --------------------------------------------------------------------------
# Public entry points
# --------------------------------------------------------------------------

def paged_attend_gqa(q, codes, scales, pamax, mean, tail, pos, *,
                     page_size: int, block_size: int = BLOCK_SIZE,
                     span=None, sm_scale: Optional[float] = None,
                     backend: str = "auto",
                     interpret: Optional[bool] = None) -> jax.Array:
    """GQA decode attention straight off the paged FP4 payload.

    q:      (b, s, n_heads, hd) — post-RoPE queries. s == 1 for plain
            decode (the token at ``pos`` was just appended to the tail);
            s == S for a speculative verify span (``span`` required).
    codes:  (b, n_pages, P, 2, n_kv, hd//2) uint8 — packed E2M1, as stored.
    scales: (b, n_pages, P, 2, n_kv, hd//block) f8e4m3 — as stored.
    pamax:  (b, n_pages, 2) float32 per-page per-stream amax.
    mean:   (b, n_pages, 2, n_kv, hd) bf16 per-page mean, or None (fp4).
    tail:   (b, P, 2, n_kv, hd) bf16 — the exact in-flight page.
    pos:    (b,) int32 — position of the first query token.
    span:   optional (b, S, 2, n_kv, hd) exact scratch K/V (verify path).

    Returns (b, s, n_heads, hd) float32 attended values.

    Committed pages j < pos // P are read quantized; the tail page overlays
    the current page exactly (when an append just committed page
    ``pos // P``, the full tail still covers it, mirroring
    ``_dense_view``'s overlay-wins semantics); span tokens are causally
    masked per query and dropped past the slot capacity, matching the dense
    path's ``mode="drop"`` scatter.
    """
    b, s, nh, hd = q.shape
    nkv = codes.shape[4]
    g = nh // nkv
    p = page_size
    np_ = codes.shape[1]
    cap = np_ * p
    if span is None:
        assert s == 1, "plain decode reads exactly one query token"
    else:
        assert s == span.shape[1], (q.shape, span.shape)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)

    pos = pos.astype(jnp.int32)
    pidx = pos // p
    qf = jnp.moveaxis(q.astype(jnp.float32).reshape(b, s, nkv, g, hd),
                      1, 2).reshape(b, nkv, s * g, hd)

    ck, cv = codes[:, :, :, 0], codes[:, :, :, 1]
    sk, sv = scales[:, :, :, 0], scales[:, :, :, 1]
    mk = mean[:, :, 0] if mean is not None else None
    mv = mean[:, :, 1] if mean is not None else None

    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "pallas":
        pages = _page_partials_pallas(qf, ck, sk, cv, sv, pamax, mk, mv,
                                      pidx, block_size=block_size,
                                      sm_scale=sm_scale, interpret=interpret)
    elif backend == "xla":
        pages = _page_partials_xla(qf, ck, sk, cv, sv, pamax, mk, mv, pidx,
                                   block_size=block_size, sm_scale=sm_scale)
    else:
        raise ValueError(f"unknown paged-attention backend {backend!r}")

    # exact tail page: tokens [pidx*P, pos) for a span step, [pidx*P, pos]
    # for plain decode (the new token is already appended; a boundary
    # append leaves the freshly committed page fully covered by the tail)
    tail_len = pos - pidx * p + (1 if span is None else 0)
    tail_valid = (jnp.arange(p)[None, :] < tail_len[:, None])[:, None, :]
    tk = jnp.swapaxes(tail[:, :, 0].astype(jnp.float32), 1, 2)  # (b,n,P,hd)
    tv = jnp.swapaxes(tail[:, :, 1].astype(jnp.float32), 1, 2)
    parts = [pages, _block_partial(qf, tk, tv, tail_valid, sm_scale=sm_scale)]

    if span is not None:
        S = span.shape[1]
        spk = jnp.swapaxes(span[:, :, 0].astype(jnp.float32), 1, 2)
        spv = jnp.swapaxes(span[:, :, 1].astype(jnp.float32), 1, 2)
        qi = jnp.arange(s * g)[:, None] // g            # query token index
        sj = jnp.arange(S)[None, :]
        causal = (sj <= qi)[None]                       # (1, sg, S)
        in_cap = (pos[:, None] + jnp.arange(S)[None, :] < cap)[:, None, :]
        parts.append(_block_partial(qf, spk, spv, causal & in_cap,
                                    sm_scale=sm_scale))

    out = _finalize(combine_partials(parts))            # (b, nkv, sg, hd)
    return jnp.moveaxis(out.reshape(b, nkv, s, g, hd), 2, 1).reshape(
        b, s, nh, hd)


def paged_attend_mla(q_abs, q_rope, codes, scales, pamax, mean, kr, tail,
                     pos, *, page_size: int, block_size: int = BLOCK_SIZE,
                     sm_scale: float) -> jax.Array:
    """MLA absorbed-decode attention off the paged FP4 *latent* payload.

    The compressed c latent doubles as both score key and value stream
    (``scores = q_abs . c + q_rope . kr``; context is the attended c), so
    only c is quantized; the small RoPE key ``kr`` stays an exact bf16 ring
    (its head dim is not 16-block-alignable in the reduced configs). XLA
    page loop only — the latent read is already bandwidth-light and the
    extra exact ``q_rope . kr`` logit term has no Pallas twin yet.

    q_abs (b, nh, rkv); q_rope (b, nh, dr); codes (b, np, P, rkv//2) u8;
    scales (b, np, P, rkv//block) f8; pamax (b, np) f32; mean (b, np, rkv)
    or None; kr (b, cap, dr) exact; tail (b, P, rkv) exact; pos (b,).
    Returns the attended latent (b, nh, rkv) float32.
    """
    b, nh, rkv = q_abs.shape
    np_, p = codes.shape[1], codes.shape[2]
    centered = mean is not None
    qa = q_abs.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    pos = pos.astype(jnp.int32)
    pidx = pos // p
    krp = kr.astype(jnp.float32).reshape(b, np_, p, -1)

    G = max(1, min(np_, 128 // p))                 # pages per loop iteration

    def dequant(cj, sj, s_t):
        vals = decode_e2m1_codes(unpack_nibbles(cj))          # (b,G,P,rkv)
        scale = sj.astype(jnp.float32) * s_t[:, :, None, None]
        rb = vals.reshape(b, G, p, rkv // block_size, block_size)
        return (rb * scale[..., None]).reshape(b, G, p, rkv)

    def body(t, carry):
        m, l, acc = carry
        js = t * G + jnp.arange(G)                            # (G,)
        s_t = jnp.maximum(jnp.take(pamax, js, axis=1, mode="clip")
                          / TENSOR_SCALE_DENOM, _EPS)         # (b,G)
        res = dequant(jnp.take(codes, js, axis=1, mode="clip"),
                      jnp.take(scales, js, axis=1, mode="clip"),
                      s_t)                                    # (b,G,P,rkv)
        logits = (_einsum("bhr,bgpr->bhgp", qa, res)
                  + _einsum("bhd,bgpd->bhgp", qr,
                            jnp.take(krp, js, axis=1, mode="clip")))
        if centered:
            mc = jnp.take(mean, js, axis=1,
                          mode="clip").astype(jnp.float32)    # (b,G,rkv)
            qmu = _einsum("bhr,bgr->bhg", qa, mc)
            logits = logits + qmu[..., None]
        logits = (logits * sm_scale).reshape(b, nh, G * p)

        valid = (js[None, :] < pidx[:, None])                 # (b, G)
        vmask = jnp.broadcast_to(valid[:, None, :, None],
                                 (b, 1, G, p)).reshape(b, 1, G * p)
        masked = jnp.where(vmask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(masked, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        pmat = jnp.where(vmask, jnp.exp(logits - m_new), 0.0)
        psum = jnp.sum(pmat, axis=-1, keepdims=True)
        upd = _einsum("bhk,bkr->bhr", pmat,
                      res.reshape(b, G * p, rkv))
        if centered:
            pg = pmat.reshape(b, nh, G, p).sum(-1)            # (b,nh,G)
            upd = upd + _einsum("bhg,bgr->bhr", pg, mc)
        return (m_new, l * alpha + psum, acc * alpha + upd)

    init = (jnp.full((b, nh, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, nh, 1), jnp.float32),
            jnp.zeros((b, nh, rkv), jnp.float32))
    # dynamic page bound: work scales with the longest live context in the
    # batch, not the slot capacity (same discipline as _dense_view)
    n_live = jnp.minimum(jnp.max(pidx), np_ - 1) + 1
    m, l, acc = jax.lax.fori_loop(0, (n_live + G - 1) // G, body, init)

    # exact tail: latent tokens [pidx*P, pos] plus their kr ring entries
    tail_len = pos - pidx * p + 1
    tval = jnp.arange(p)[None, :] < tail_len[:, None]         # (b, P)
    tc = tail.astype(jnp.float32)                             # (b, P, rkv)
    kr_tail = jnp.take_along_axis(
        krp, pidx[:, None, None, None], axis=1)[:, 0]         # (b, P, dr)
    logits_t = (_einsum("bhr,bpr->bhp", qa, tc)
                + _einsum("bhd,bpd->bhp", qr, kr_tail)) * sm_scale
    logits_t = jnp.where(tval[:, None], logits_t, NEG_INF)
    mt = jnp.max(logits_t, axis=-1, keepdims=True)
    pt = jnp.where(tval[:, None], jnp.exp(logits_t - mt), 0.0)
    lt = jnp.sum(pt, axis=-1, keepdims=True)
    at = _einsum("bhp,bpr->bhr", pt, tc)

    return _finalize(combine_partials([(m, l, acc), (mt, lt, at)]))
