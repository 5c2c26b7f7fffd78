"""Attention variants: GQA (+bias, +qk_norm, causal/bidirectional) and MLA.

All weight GeMMs route through the quantization context (W4A4G4); the
attention score/value einsums stay in bf16 — the paper's W4A4G4 scope covers
weight GeMMs, not the attention quadratic form (DESIGN.md §3).

Long sequences use query-chunked attention (lax.scan over query blocks) so a
32k prefill never materializes an s x s score matrix — O(s * chunk) transient
memory instead, the XLA analogue of a flash kernel's tiling.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.obs import scopes
from repro.parallel.sharding import constrain
from .cache import dense_gqa_adapter, dense_mla_adapter
from .layers import Param, QuantCtx, apply_rope, rms_norm, rope_angles

NEG_INF = -1e30
Q_CHUNK = 1024


# --------------------------------------------------------------------------
# Core (grouped) scaled-dot-product attention with query chunking
# --------------------------------------------------------------------------

def _attend_block(q, k, v, qpos, kpos, causal, softmax_dtype=jnp.float32):
    """q: (b,sq,nkv,g,hd)  k/v: (b,t,nkv,hd)  qpos: (b,sq)  kpos: (t,)."""
    hd = q.shape[-1]
    neg = jnp.asarray(NEG_INF if softmax_dtype == jnp.float32 else -3e38,
                      softmax_dtype)
    scores = jnp.einsum(
        "bqkgh,btkh->bqkgt", q, k, preferred_element_type=softmax_dtype
    ) / jnp.sqrt(jnp.asarray(hd, softmax_dtype))
    if causal:
        mask = qpos[:, :, None] >= kpos[None, None, :]      # (b,sq,t)
        scores = jnp.where(mask[:, :, None, None, :], scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    # preferred type also fixes the AD cotangent dtype of the whole score
    # chain — keeping it at softmax_dtype is what makes the bf16 path
    # actually shrink backward HBM traffic (§Perf iteration 3->4).
    out = jnp.einsum("bqkgt,btkh->bqkgh", w.astype(v.dtype), v,
                     preferred_element_type=softmax_dtype)
    return out.astype(v.dtype)


def attention_core(
    q: jax.Array,          # (b, sq, n_heads, hd)
    k: jax.Array,          # (b, t, n_kv, hd)
    v: jax.Array,          # (b, t, n_kv, hd)
    qpos: jax.Array,       # (b, sq) absolute query positions
    kpos: jax.Array,       # (t,)   absolute key positions
    causal: bool,
    q_chunk: int = Q_CHUNK,
    softmax_dtype=jnp.float32,
) -> jax.Array:
    b, sq, nh, hd = q.shape
    nkv = k.shape[2]
    hv = v.shape[-1]  # may differ from hd (MLA: qk vs v head dims)
    g = nh // nkv
    with jax.named_scope(scopes.ATTN_CORE):
        qg = q.reshape(b, sq, nkv, g, hd)
        if sq <= q_chunk or sq % q_chunk != 0:
            out = _attend_block(qg, k, v, qpos, kpos, causal, softmax_dtype)
        else:
            nc = sq // q_chunk
            qc = qg.reshape(b, nc, q_chunk, nkv, g, hd)
            pc = qpos.reshape(b, nc, q_chunk)

            def body(_, xs):
                qi, pi = xs
                return None, _attend_block(qi, k, v, pi, kpos, causal,
                                           softmax_dtype)

            _, outs = jax.lax.scan(
                body, None, (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(pc, 1, 0))
            )
            out = jnp.moveaxis(outs, 0, 1).reshape(b, nc * q_chunk, nkv, g,
                                                   hv)
        return out.reshape(b, sq, nh, hv)


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def gqa_defs(cfg: ModelConfig) -> Dict[str, Param]:
    d, nh, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": Param((d, nh * hd), ("embed", "heads")),
        "wk": Param((d, nkv * hd), ("embed", "kv_heads")),
        "wv": Param((d, nkv * hd), ("embed", "kv_heads")),
        "wo": Param((nh * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = Param((nh * hd,), ("heads",), init="zeros")
        p["bk"] = Param((nkv * hd,), ("kv_heads",), init="zeros")
        p["bv"] = Param((nkv * hd,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = Param((hd,), (None,), init="ones")
        p["k_norm"] = Param((hd,), (None,), init="ones")
    return p


def _project_qkv(p, x, ctx: QuantCtx, cfg: ModelConfig):
    b, s, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = ctx.gemm(x, p["wq"], site=1, role="attn_qkv")
    k = ctx.gemm(x, p["wk"], site=2, role="attn_qkv")
    v = ctx.gemm(x, p["wv"], site=3, role="attn_qkv")
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def gqa_apply(
    p,
    x: jax.Array,                     # (b, s, d)
    positions: jax.Array,             # (b, s) or (b, 3, s) for mrope
    ctx: QuantCtx,
    cfg: ModelConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
    decode_pos: Optional[jax.Array] = None,   # (b,) write index when decoding
    adapter=None,                             # cache adapter (decode only)
    chunk_valid: Optional[jax.Array] = None,  # scalar: valid chunk tokens
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Returns (output (b,s,d), new_cache_or_None).

    Modes: train (cache=None), prefill (cache=None but caller keeps k/v via
    gqa_prefill), decode (cache given, s==1, decode_pos given), chunked
    prefill (cache given, chunk_valid given). In decode the cache write +
    attendable read go through ``adapter`` (see models/cache.py) so dense
    bf16 and quantized paged layouts share this code path. In chunked
    prefill the cache is a *dense per-request context buffer* whose slot j
    holds the K/V of absolute token j: the chunk's K/V rows are written at
    their absolute positions (zeros past ``chunk_valid``, so the buffer
    stays clean for later chunks and the final paged insert), then the
    chunk queries attend over the whole buffer under plain causal masking
    — buffer slots at or past the chunk end hold zeros whose positions are
    causally masked for every valid query.
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x, ctx, cfg)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta, cfg.mrope_sections)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))

    smd = jnp.dtype(cfg.attn_softmax_dtype)
    if cache is None:
        qpos = positions if positions.ndim == 2 else positions[:, 0, :]
        kpos = qpos[0]
        out = attention_core(q, k, v, qpos, kpos, cfg.causal,
                             softmax_dtype=smd)
        new_cache = {"k": k, "v": v}
    elif chunk_valid is not None:
        # Chunked prefill over the dense context buffer (b, cap, n_kv, hd).
        qpos = positions if positions.ndim == 2 else positions[:, 0, :]
        cap = cache["k"].shape[1]
        keep = (jnp.arange(s) < chunk_valid)[None, :, None, None]
        kw = jnp.where(keep, k, 0).astype(cache["k"].dtype)
        vw = jnp.where(keep, v, 0).astype(cache["v"].dtype)
        bidx = jnp.arange(b)[:, None]
        span = qpos                                  # (b, s) absolute slots
        ck = cache["k"].at[bidx, span].set(kw, mode="drop")
        cv = cache["v"].at[bidx, span].set(vw, mode="drop")
        new_cache = {"k": ck, "v": cv}
        out = attention_core(q, ck, cv, qpos, jnp.arange(cap), causal=True,
                             softmax_dtype=smd)
    else:
        assert decode_pos is not None
        if adapter is None:
            adapter = dense_gqa_adapter(cfg)
        # Quantized adapters with read_backend="fused" attend straight off
        # the stored page payload (kernels/paged_attention) — no dense KV
        # view is ever built. A non-f32 softmax policy cannot be honored by
        # the f32 online-softmax kernel: loud counted fallback to the dense
        # path (quant/paged_attn_fallback).
        fused = (getattr(adapter, "read_backend", "dense") == "fused"
                 and hasattr(adapter, "update_attend"))
        if fused and not adapter.fused_read_ok(smd):
            adapter.note_fallback(
                f"attn_softmax_dtype={cfg.attn_softmax_dtype} (the fused "
                f"paged read accumulates its online softmax in float32)")
            fused = False
        if s == 1:
            if fused:
                out, new_cache = adapter.update_attend(
                    cache, (k[:, 0], v[:, 0]), decode_pos, q)
            else:
                (ck, cv), new_cache = adapter.update(
                    cache, (k[:, 0], v[:, 0]), decode_pos)
                qpos = decode_pos[:, None]
        else:
            # Speculative verify: the S-token span [t0, d1..d_{S-1}] writes
            # into per-layer scratch (committed storage untouched until the
            # adapter's commit_span); queries attend causally over the
            # dense view with the span overlaid at its absolute positions
            # (fused: the span is its own causally-masked exact block).
            if fused:
                out, new_cache = adapter.update_span_attend(
                    cache, (k, v), decode_pos, q)
            else:
                (ck, cv), new_cache = adapter.update_span(cache, (k, v),
                                                          decode_pos)
                qpos = decode_pos[:, None] + jnp.arange(s)[None, :]
        if not fused:
            t = ck.shape[1]
            kpos = jnp.arange(t)
            out = attention_core(q, ck, cv, qpos, kpos, causal=True,
                                 softmax_dtype=smd)

    # The quantized decode reads (fused kernel AND the f32 dense view) hand
    # back float32 context; round to the residual dtype at one shared point
    # so the out-projection and layer carry stay bf16 on every path.
    out = out.astype(x.dtype).reshape(b, s, cfg.num_heads * hd)
    y = ctx.gemm(out, p["wo"], site=4, role="attn_o")
    return y, new_cache


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    return dense_gqa_adapter(cfg).layer_spec(batch, max_len)


# --------------------------------------------------------------------------
# MLA (MiniCPM3 / DeepSeek-style multi-head latent attention)
# --------------------------------------------------------------------------

def mla_defs(cfg: ModelConfig) -> Dict[str, Param]:
    d, nh = cfg.d_model, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": Param((d, rq), ("embed", "rank")),
        "q_ln": Param((rq,), (None,), init="ones"),
        "wq_b": Param((rq, nh * (dn + dr)), ("rank", "heads")),
        "wkv_a": Param((d, rkv + dr), ("embed", "rank")),
        "kv_ln": Param((rkv,), (None,), init="ones"),
        "wkv_b": Param((rkv, nh * (dn + dv)), ("rank", "heads")),
        "wo": Param((nh * dv, d), ("heads", "embed")),
    }


def _mla_q(p, x, ctx, cfg, positions):
    b, s, _ = x.shape
    nh = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = rms_norm(ctx.gemm(x, p["wq_a"], site=1, role="attn_qkv"), p["q_ln"])
    q = ctx.gemm(cq, p["wq_b"], site=2, role="attn_qkv").reshape(b, s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    cos, sin = rope_angles(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def mla_apply(
    p,
    x: jax.Array,
    positions: jax.Array,
    ctx: QuantCtx,
    cfg: ModelConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
    decode_pos: Optional[jax.Array] = None,
    adapter=None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    b, s, _ = x.shape
    nh = cfg.num_heads
    rkv = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    q_nope, q_rope = _mla_q(p, x, ctx, cfg, positions)

    if cache is None:
        # Train / prefill: materialize per-head K, V from the latent.
        ckv = ctx.gemm(x, p["wkv_a"], site=3, role="attn_qkv")
        c, k_rope = ckv[..., :rkv], ckv[..., rkv:]
        c = rms_norm(c, p["kv_ln"])
        cos, sin = rope_angles(positions, dr, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # (b,s,1,dr)
        kv = ctx.gemm(c, p["wkv_b"], site=4, role="attn_qkv").reshape(b, s, nh, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, nh, dr))], axis=-1
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        qpos = positions
        out = attention_core(q, k, v, qpos, qpos[0], cfg.causal,
                             softmax_dtype=jnp.dtype(cfg.attn_softmax_dtype))
        y = ctx.gemm(out.reshape(b, s, nh * dv), p["wo"], site=5, role="attn_o")
        new_cache = {"c": c, "kr": k_rope[:, :, 0, :]}
        return y, new_cache

    # Decode: absorbed attention directly over the latent cache. The absorbed
    # einsums contract per-head (not plain 2-D GeMMs); they run in bf16 —
    # serving-path only, outside the paper's W4A4G4 training scope.
    assert s == 1 and decode_pos is not None
    ckv = ctx.gemm(x, p["wkv_a"], site=3, role="attn_qkv")
    c_new, kr_new = ckv[..., :rkv], ckv[..., rkv:]
    c_new = rms_norm(c_new, p["kv_ln"])
    cos, sin = rope_angles(positions, dr, cfg.rope_theta)
    kr_new = apply_rope(kr_new[:, :, None, :], cos, sin)[:, :, 0, :]
    if adapter is None:
        adapter = dense_mla_adapter(cfg)

    wkv_b = p["wkv_b"].astype(x.dtype).reshape(rkv, nh, dn + dv)
    w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]
    q_abs = jnp.einsum("bqnd,rnd->bqnr", q_nope, w_k,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    # Quantized latent adapters with read_backend="fused" attend straight
    # off the stored c-page payload (kernels/paged_attention); the absorbed
    # score path always accumulates in f32, so no softmax-dtype fallback
    # exists here.
    fused = (getattr(adapter, "read_backend", "dense") == "fused"
             and hasattr(adapter, "update_attend"))
    if fused:
        ctx_lat, new_cache = adapter.update_attend(
            cache, (c_new[:, 0], kr_new[:, 0]), decode_pos,
            q_abs[:, 0], q_rope[:, 0],
            sm_scale=1.0 / math.sqrt(dn + dr))
        ctx_c = ctx_lat[:, None].astype(x.dtype)
    else:
        (cc, ckr), new_cache = adapter.update(
            cache, (c_new[:, 0], kr_new[:, 0]), decode_pos)
        t = cc.shape[1]
        scores = (
            jnp.einsum("bqnr,btr->bqnt", q_abs, cc,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqnd,btd->bqnt", q_rope, ckr,
                         preferred_element_type=jnp.float32)
        ) / jnp.sqrt(jnp.float32(dn + dr))
        mask = (decode_pos[:, None, None, None]
                >= jnp.arange(t)[None, None, None, :])
        scores = jnp.where(mask, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        ctx_c = jnp.einsum("bqnt,btr->bqnr", w.astype(cc.dtype), cc,
                           preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("bqnr,rnd->bqnd", ctx_c, w_v,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    y = ctx.gemm(out.reshape(b, s, nh * dv), p["wo"], site=5, role="attn_o")
    return y, new_cache


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    return dense_mla_adapter(cfg).layer_spec(batch, max_len)
