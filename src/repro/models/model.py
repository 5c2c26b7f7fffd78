"""Model API: init / forward / loss / prefill / decode_step / input_specs.

A ``Model`` wraps a ``ModelConfig`` and exposes pure functions over plain
nested-dict parameters. Layers are scanned over stacked (L, ...) params
(compile-time O(1) in depth); every weight GeMM routes through the
quantization context (the paper's W4A4G4 recipes).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.qgemm import QuantConfig
from repro.obs import scopes
from repro.parallel.sharding import constrain
from .cache import default_adapter, grow_caches
from .layers import (
    Param,
    QuantCtx,
    init_tree,
    logical_tree,
    rms_norm,
)
from .transformer import (
    attn_ffn_block_apply,
    block_cache_spec,
    block_defs,
    shared_block_cache_spec,
    shared_block_defs,
    ssm_block_apply,
)

REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "everything": jax.checkpoint_policies.everything_saveable,
}


class Model:
    def __init__(self, cfg: ModelConfig, remat_policy: str = "nothing",
                 cache_adapter=None):
        self.cfg = cfg
        self.remat_policy = remat_policy
        # Decode-cache adapter (models/cache.py): dense bf16 by default;
        # the serving engine installs quantized paged adapters here.
        self.adapter = (cache_adapter if cache_adapter is not None
                        else default_adapter(cfg))

    # ------------------------------------------------------------------ params
    def _top_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        defs: Dict[str, Any] = {"final_norm": Param((cfg.d_model,), (None,), init="ones")}
        if cfg.input_mode == "tokens":
            defs["embed"] = Param((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))
        if not cfg.tie_embeddings:
            defs["head"] = Param((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        return defs

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        k_top, k_layers, k_shared = jax.random.split(key, 3)
        params = init_tree(self._top_defs(), k_top, dtype)
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: init_tree(block_defs(cfg), k, dtype)
        )(layer_keys)
        if cfg.hybrid_attn_every:
            params["shared"] = init_tree(shared_block_defs(cfg), k_shared, dtype)
        return params

    def abstract_params(self) -> Dict[str, Any]:
        """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_logical(self) -> Dict[str, Any]:
        cfg = self.cfg
        log = logical_tree(self._top_defs())
        log["layers"] = logical_tree(block_defs(cfg), prepend=("layer",))
        if cfg.hybrid_attn_every:
            log["shared"] = logical_tree(shared_block_defs(cfg))
        return log

    # ------------------------------------------------------------------ inputs
    def _positions(self, batch: Dict[str, jax.Array], b: int, s: int) -> jax.Array:
        if self.cfg.rope_type == "mrope":
            return batch["positions"]
        ar = jnp.arange(s, dtype=jnp.int32)
        return jnp.broadcast_to(ar[None, :], (b, s))

    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        if cfg.input_mode == "tokens":
            tokens = batch["tokens"]
            x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
        else:
            x = batch["embeddings"].astype(cdt)
        b, s = x.shape[:2]
        x = constrain(x, ("batch", "seq", "embed_act"))
        return x, self._positions(batch, b, s)

    # ------------------------------------------------------------------ stacks
    def _maybe_remat(self, fn):
        if self.cfg.remat:
            return jax.checkpoint(
                fn, policy=REMAT_POLICIES[self.remat_policy], static_argnums=()
            )
        return fn

    def _run_stack(
        self,
        params,
        x: jax.Array,
        positions: jax.Array,
        ctx: QuantCtx,
        mode: str,                       # train | prefill | decode | chunk
        caches: Optional[Dict] = None,   # stacked (L,...) / hybrid dict
        decode_pos: Optional[jax.Array] = None,
        chunk_valid: Optional[jax.Array] = None,
    ):
        cfg = self.cfg
        if cfg.family == "ssm":
            return self._run_ssm(params, x, ctx, mode, caches)
        if cfg.family == "hybrid":
            return self._run_hybrid(params, x, positions, ctx, mode, caches,
                                    decode_pos)
        return self._run_attn(params, x, positions, ctx, mode, caches,
                              decode_pos, chunk_valid)

    def _segments(self, ctx: QuantCtx):
        """Policy-uniform contiguous layer runs (one run == one scan).

        Layers are scanned over stacked params, so a per-layer recipe can't
        branch inside the scan body; instead the stack is partitioned into
        maximal runs whose (role -> recipe) table is constant and each run
        is scanned with its own statically-resolved QuantCtx. A uniform
        policy yields the single pre-policy scan.
        """
        return ctx.policy.segments(self.cfg.num_layers)

    def prepare_qweights(self, params, policy) -> Dict[str, Any]:
        """The per-step quantized-weight cache: pre-quantize every weight-GeMM
        operand of the model once, keyed by (param site, plan operand).

        Must be called *outside* ``jax.grad`` and the gradient-accumulation
        loop (the trainer calls it once per optimizer step): inside them,
        params are fresh per-trace tracers and weight QDQ can never be
        reused. The returned tree is threaded through ``QuantCtx.qweights``;
        stacked-layer entries flow into each segment's ``lax.scan`` as xs
        (per-layer QDQ inside a scan body would otherwise re-run every
        microbatch — the hot-path waste this cache removes). Layout::

            {"segments": {(s0, s1): {site_path: (wq_fwd..., wq_dx...)}},
             "lm_head": (wq_fwd..., wq_dx...)}        # when quantized

        The hybrid (shared-attention) family keeps inline weight QDQ for its
        scanned SSM groups (its group scan is not segment-partitioned).
        """
        from repro.core.policy import PrecisionPolicy
        from repro.core.qgemm import (prepared_weight_single,
                                      prepared_weight_stack)
        from .transformer import gemm_weight_sites

        cfg = self.cfg
        policy = PrecisionPolicy.parse(policy)
        cdt = jnp.dtype(cfg.compute_dtype)
        out: Dict[str, Any] = {"segments": {}}
        if cfg.family != "hybrid":
            sites = gemm_weight_sites(cfg)
            for s0, s1 in policy.segments(cfg.num_layers):
                seg: Dict[Tuple[int, ...], Any] = {}
                for gpath, (role, ppath, per_expert) in sites.items():
                    leaf = params["layers"]
                    for k in ppath:
                        leaf = leaf[k]
                    seg[gpath] = prepared_weight_stack(
                        leaf, (s0, s1), policy.resolve(role, s0), cdt,
                        per_expert=per_expert)
                out["segments"][(s0, s1)] = seg
        if cfg.quantize_lm_head:
            w = params["embed"].T if cfg.tie_embeddings else params["head"]
            out["lm_head"] = prepared_weight_single(
                w, policy.resolve("lm_head", None), cdt)
        return out

    def _segment_qweights(self, ctx: QuantCtx, s0: int, s1: int):
        """One segment's stacked prepared weights from the per-step cache
        (None -> inline QDQ, the inference/no-cache path)."""
        if ctx.qweights is None:
            return None
        return ctx.qweights["segments"].get((s0, s1))

    def _run_attn(self, params, x, positions, ctx, mode, caches, decode_pos,
                  chunk_valid=None):
        cfg = self.cfg

        def layer(x, p_l, prep_l, cache_l, idx, seg_start):
            lctx = QuantCtx(ctx.policy, jax.random.fold_in(ctx.key, idx),
                            layer=seg_start, prepared=prep_l)
            return attn_ffn_block_apply(
                p_l, x, positions, lctx, cfg, cache_l, decode_pos,
                self.adapter, chunk_valid,
            )

        if mode == "train":
            # Probe tapes must leave the layer scan as ys (remat/scan bodies
            # are pure — a side-channel dict would capture dead tracers).
            # With probes off the tape is a leafless {} at every level, so
            # the jaxpr — and hence the compiled step — is byte-identical
            # to the pre-probe build.
            probe_on = ctx.probes is not None
            aux_total = jnp.zeros((), jnp.float32)
            tape_segs = []
            for s0, s1 in self._segments(ctx):
                prepped = self._segment_qweights(ctx, s0, s1)

                def probed_layer(x, p_l, prep_l, idx, _s0=s0):
                    tape: Dict[str, Any] = {}
                    lctx = QuantCtx(
                        ctx.policy, jax.random.fold_in(ctx.key, idx),
                        layer=_s0, prepared=prep_l,
                        probes=tape if probe_on else None)
                    xo, _, aux = attn_ffn_block_apply(
                        p_l, x, positions, lctx, cfg, None, decode_pos,
                        self.adapter, chunk_valid)
                    return xo, aux, tape

                fn = self._maybe_remat(probed_layer)

                def body(c, xs, _fn=fn):
                    p_l, prep_l, idx = xs
                    xo, aux, tape = _fn(c, p_l, prep_l, idx)
                    return xo, (aux, tape)

                x, (auxs, tapes) = jax.lax.scan(
                    body, x,
                    (_slice_layers(params["layers"], s0, s1), prepped,
                     jnp.arange(s0, s1)),
                )
                aux_total = aux_total + jnp.sum(auxs)
                tape_segs.append(tapes)
            if probe_on:
                # Per-segment scans stack stats to (s1-s0,); concatenating
                # the segments yields one (num_layers,) array per site stat.
                ctx.probes.update(_concat_layers(tape_segs))
            return x, None, aux_total

        new_cache_segs, aux_total = [], jnp.zeros((), jnp.float32)
        for s0, s1 in self._segments(ctx):
            prepped = self._segment_qweights(ctx, s0, s1)

            def body(c, xs, _s0=s0):
                p_l, prep_l, cache_l, idx = xs
                xo, new_cache, aux = layer(c, p_l, prep_l, cache_l, idx, _s0)
                return xo, (new_cache, aux)

            x, (nc, auxs) = jax.lax.scan(
                body, x,
                (_slice_layers(params["layers"], s0, s1), prepped,
                 _slice_layers(caches, s0, s1),
                 jnp.arange(s0, s1)),
            )
            new_cache_segs.append(nc)
            aux_total = aux_total + jnp.sum(auxs)
        return x, _concat_layers(new_cache_segs), aux_total

    def _run_ssm(self, params, x, ctx, mode, caches):
        cfg = self.cfg

        def layer(x, p_l, prep_l, cache_l, idx, seg_start):
            lctx = QuantCtx(ctx.policy, jax.random.fold_in(ctx.key, idx),
                            layer=seg_start, prepared=prep_l)
            return ssm_block_apply(p_l, x, lctx, cfg, cache_l)

        if mode == "train":
            probe_on = ctx.probes is not None
            tape_segs = []
            for s0, s1 in self._segments(ctx):
                prepped = self._segment_qweights(ctx, s0, s1)

                def probed_layer(x, p_l, prep_l, idx, _s0=s0):
                    tape: Dict[str, Any] = {}
                    lctx = QuantCtx(
                        ctx.policy, jax.random.fold_in(ctx.key, idx),
                        layer=_s0, prepared=prep_l,
                        probes=tape if probe_on else None)
                    xo, _ = ssm_block_apply(p_l, x, lctx, cfg, None)
                    return xo, tape

                fn = self._maybe_remat(probed_layer)

                def body(c, xs, _fn=fn):
                    p_l, prep_l, idx = xs
                    xo, tape = _fn(c, p_l, prep_l, idx)
                    return xo, tape

                x, tapes = jax.lax.scan(
                    body, x,
                    (_slice_layers(params["layers"], s0, s1), prepped,
                     jnp.arange(s0, s1)),
                )
                tape_segs.append(tapes)
            if probe_on:
                ctx.probes.update(_concat_layers(tape_segs))
            return x, None, jnp.zeros((), jnp.float32)

        new_cache_segs = []
        for s0, s1 in self._segments(ctx):
            def body(c, xs, _s0=s0):
                p_l, cache_l, idx = xs
                xo, new_cache = layer(c, p_l, None, cache_l, idx, _s0)
                return xo, new_cache

            x, nc = jax.lax.scan(
                body, x,
                (_slice_layers(params["layers"], s0, s1),
                 _slice_layers(caches, s0, s1),
                 jnp.arange(s0, s1)),
            )
            new_cache_segs.append(nc)
        return x, _concat_layers(new_cache_segs), jnp.zeros((), jnp.float32)

    def _run_hybrid(self, params, x, positions, ctx, mode, caches, decode_pos):
        cfg = self.cfg
        if len(self._segments(ctx)) > 1:
            raise NotImplementedError(
                "per-layer precision policies are not supported for the "
                "hybrid (shared-attention) stack; use role-level clauses")
        every = cfg.hybrid_attn_every
        groups = cfg.num_layers // every
        layers_g = jax.tree.map(
            lambda a: a.reshape((groups, every) + a.shape[1:]), params["layers"]
        )
        shared = params["shared"]

        def group(x, p_g, ssm_cache_g, shared_cache_g, gidx):
            def inner(c, xs):
                p_l, cache_l, li = xs
                lctx = QuantCtx(ctx.policy,
                                jax.random.fold_in(ctx.key, gidx * every + li),
                                layer=0)
                xo, new_cache = ssm_block_apply(p_l, c, lctx, cfg, cache_l)
                return xo, new_cache

            inner_caches = (
                ssm_cache_g if ssm_cache_g is not None else _none_tree(every)
            )
            x, new_ssm = jax.lax.scan(
                inner, x, (p_g, inner_caches, jnp.arange(every))
            )
            sctx = QuantCtx(ctx.policy,
                            jax.random.fold_in(ctx.key, 10_000 + gidx),
                            layer=0)
            x, new_shared, _ = attn_ffn_block_apply(
                shared, x, positions, sctx, cfg, shared_cache_g, decode_pos,
                self.adapter,
            )
            return x, new_ssm, new_shared

        if mode == "train":
            fn = self._maybe_remat(
                lambda x, p_g, gidx: group(x, p_g, None, None, gidx)[0]
            )

            def body(c, xs):
                p_g, gidx = xs
                return fn(c, p_g, gidx), None

            x, _ = jax.lax.scan(body, x, (layers_g, jnp.arange(groups)))
            return x, None, jnp.zeros((), jnp.float32)

        ssm_caches, shared_caches = (
            caches if caches is not None
            else (_none_tree(groups), _none_tree(groups))
        )
        if caches is not None:
            ssm_caches = jax.tree.map(
                lambda a: a.reshape((groups, every) + a.shape[1:]), ssm_caches
            )

        def body(c, xs):
            p_g, sc_g, shc_g, gidx = xs
            xo, new_ssm, new_shared = group(c, p_g, sc_g, shc_g, gidx)
            return xo, (new_ssm, new_shared)

        x, (new_ssm, new_shared) = jax.lax.scan(
            body, x, (layers_g, ssm_caches, shared_caches, jnp.arange(groups))
        )
        new_ssm = jax.tree.map(
            lambda a: a.reshape((groups * every,) + a.shape[2:]), new_ssm
        )
        return x, (new_ssm, new_shared), jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------ public
    def forward(
        self, params, batch: Dict[str, jax.Array], ctx: QuantCtx
    ) -> Tuple[jax.Array, jax.Array]:
        """Training/eval forward: returns (logits (b,s,V), aux_loss)."""
        x, positions = self._embed_inputs(params, batch)
        x, _, aux = self._run_stack(params, x, positions, ctx, mode="train")
        logits = self._lm_head(params, x, ctx)
        return logits, aux

    def _lm_head(self, params, x, ctx: QuantCtx) -> jax.Array:
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"])
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        if cfg.quantize_lm_head:
            prep = (ctx.qweights or {}).get("lm_head")
            logits = ctx.child(99).gemm(x, w, site=0, role="lm_head",
                                        prepared=prep)
        else:
            logits = jnp.einsum(
                "bsd,dv->bsv", x, w.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ).astype(x.dtype)
        return constrain(logits, ("batch", "seq", "vocab"))

    def loss(
        self, params, batch: Dict[str, jax.Array], ctx: QuantCtx
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        logits, aux = self.forward(params, batch, ctx)
        lg = logits.astype(jnp.float32)
        if cfg.input_mode == "tokens":
            targets = batch["tokens"][:, 1:]
            lg = lg[:, :-1]
        else:
            targets = batch["labels"]
        with jax.named_scope(scopes.LOSS):
            logz = jax.scipy.special.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, targets[..., None],
                                       axis=-1)[..., 0]
            ce = jnp.mean(logz - gold)
        total = ce + cfg.aux_loss_coef * aux
        return total, {"ce": ce, "aux": aux}

    def prefill(self, params, batch, ctx: QuantCtx):
        """Inference prefill: returns (last-position logits, stacked caches)."""
        x, positions = self._embed_inputs(params, batch)
        x, caches, _ = self._run_stack(params, x, positions, ctx, mode="prefill")
        logits = self._lm_head(params, x[:, -1:, :], ctx)
        return logits, caches

    def prefill_padded(self, params, batch, valid, ctx: QuantCtx):
        """Prefill over bucket-padded tokens; logits taken at ``valid - 1``.

        ``valid`` (scalar int32, may be traced) counts real prompt tokens;
        the rest of the batch's time axis is padding whose keys are causally
        invisible to valid queries (padding sits at later positions). Caches
        cover the padded span — the caller masks them down to ``valid`` when
        inserting into slot storage. One jit per bucket size instead of one
        per distinct prompt length.
        """
        x, positions = self._embed_inputs(params, batch)
        x, caches, _ = self._run_stack(params, x, positions, ctx, mode="prefill")
        x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        logits = self._lm_head(params, x_last, ctx)
        return logits, caches

    def prefill_chunk(self, params, batch, start, valid, ctx_caches,
                      ctx: QuantCtx):
        """One chunk of an incremental prefill (GQA attention families only).

        ``batch["tokens"]``: (b, B) bucket-padded chunk; ``start`` (scalar)
        is the chunk's absolute offset in the prompt; ``valid`` (scalar) the
        number of real tokens in the chunk; ``ctx_caches`` the stacked dense
        context buffers {"k","v"}: (L, b, cap, n_kv, hd) holding tokens
        [0, start). Returns (logits at the chunk's last valid position,
        updated buffers). All shapes are fixed by (B, cap): jit compiles
        once per chunk bucket, never per prompt length.
        """
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid") or cfg.attention != "gqa":
            raise NotImplementedError(
                f"chunked prefill requires a GQA attention stack; {cfg.name} "
                f"is family={cfg.family}/attention={cfg.attention}")
        if cfg.rope_type == "mrope":
            raise NotImplementedError("chunked prefill: mrope positions are "
                                      "prompt-global; use whole-prompt prefill")
        x, _ = self._embed_inputs(params, batch)
        b, s = x.shape[:2]
        positions = (jnp.asarray(start, jnp.int32)
                     + jnp.arange(s, dtype=jnp.int32))[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
        x, new_caches, _ = self._run_stack(
            params, x, positions, ctx, mode="chunk", caches=ctx_caches,
            chunk_valid=valid,
        )
        x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        logits = self._lm_head(params, x_last, ctx)
        return logits, new_caches

    def verify_step(self, params, inputs, pos, caches, ctx: QuantCtx):
        """Score an S-token span per slot in one call (speculative verify).

        ``inputs``: {"tokens": (b, S)} — each slot's current token followed
        by S-1 draft tokens; ``pos``: (b,) the span's first write/attend
        position. This is ``decode_step`` generalized from s==1 to a span
        (the decode-with-cache analogue of ``prefill_chunk``): queries
        attend causally over the slot cache with the span overlaid at its
        absolute positions, and the span's K/V land in per-layer *scratch*
        leaves on the returned caches — committed storage is untouched
        until the cache adapter's ``commit_span``, so rejected draft tokens
        roll back by simply not being committed. Returns
        (logits (b, S, V), caches-with-scratch); ``logits[:, j]`` is the
        target's next-token distribution after span input ``j``.
        """
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid") or cfg.attention != "gqa":
            raise NotImplementedError(
                f"speculative verify requires a GQA attention stack; "
                f"{cfg.name} is family={cfg.family}/attention={cfg.attention}")
        if cfg.rope_type == "mrope":
            raise NotImplementedError(
                "speculative verify: mrope positions are prompt-global")
        x, _ = self._embed_inputs(params, inputs)
        b, s = x.shape[:2]
        positions = (pos[:, None].astype(jnp.int32)
                     + jnp.arange(s, dtype=jnp.int32)[None, :])
        positions = jnp.broadcast_to(positions, (b, s))
        x, new_caches, _ = self._run_stack(
            params, x, positions, ctx, mode="verify", caches=caches,
            decode_pos=pos,
        )
        logits = self._lm_head(params, x, ctx)
        return logits, new_caches

    def decode_step(self, params, inputs, pos, caches, ctx: QuantCtx):
        """One decode step. inputs: {"token": (b,)} or {"embedding": (b,1,d)};
        pos: (b,) write/attend positions; caches as returned by cache_specs.
        Returns (logits (b,1,V), new_caches)."""
        cfg = self.cfg
        cdt = jnp.dtype(cfg.compute_dtype)
        if cfg.input_mode == "tokens":
            x = jnp.take(params["embed"], inputs["token"], axis=0)[:, None, :]
            x = x.astype(cdt)
        else:
            x = inputs["embedding"].astype(cdt)
        b = x.shape[0]
        if cfg.rope_type == "mrope":
            positions = jnp.broadcast_to(pos[:, None, None], (b, 3, 1)).astype(jnp.int32)
        else:
            positions = pos[:, None].astype(jnp.int32)
        x = constrain(x, ("batch", "seq", "embed_act"))
        x, new_caches, _ = self._run_stack(
            params, x, positions, ctx, mode="decode", caches=caches,
            decode_pos=pos,
        )
        logits = self._lm_head(params, x, ctx)
        return logits, new_caches

    def grow_caches(self, caches, extra: int):
        """Pad prefill caches' time axis by ``extra`` decode slots
        (spec-driven; SSM recurrent states pass through untouched)."""
        return grow_caches(self.cfg, caches, extra)

    # ------------------------------------------------------------------ specs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        cdt = jnp.dtype(cfg.compute_dtype)
        if shape.kind in ("train", "prefill"):
            if cfg.input_mode == "tokens":
                specs: Dict[str, Any] = {
                    "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)
                }
            else:
                specs = {
                    "embeddings": jax.ShapeDtypeStruct((b, s, cfg.d_model), cdt)
                }
                if shape.kind == "train":
                    specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            if cfg.rope_type == "mrope":
                specs["positions"] = jax.ShapeDtypeStruct((b, 3, s), jnp.int32)
            return specs
        # decode
        if cfg.input_mode == "tokens":
            return {"token": jax.ShapeDtypeStruct((b,), jnp.int32)}
        return {"embedding": jax.ShapeDtypeStruct((b, 1, cfg.d_model), cdt)}

    def input_logical(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        if shape.kind in ("train", "prefill"):
            log: Dict[str, Any] = {}
            if cfg.input_mode == "tokens":
                log["tokens"] = ("batch", "seq")
            else:
                log["embeddings"] = ("batch", "seq", "embed_act")
                if shape.kind == "train":
                    log["labels"] = ("batch", "seq")
            if cfg.rope_type == "mrope":
                log["positions"] = ("batch", None, "seq")
            return log
        if cfg.input_mode == "tokens":
            return {"token": ("batch",)}
        return {"embedding": ("batch", None, "embed_act")}

    def cache_specs(self, shape: ShapeConfig):
        """Stacked cache ShapeDtypeStructs for decode cells."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if cfg.family not in ("ssm", "hybrid"):
            per_layer = self.adapter.layer_spec(b, s)
        else:
            per_layer = block_cache_spec(cfg, b, s)
        stacked = jax.tree.map(
            lambda sds: jax.ShapeDtypeStruct((cfg.num_layers,) + sds.shape, sds.dtype),
            per_layer,
        )
        if cfg.family == "hybrid":
            groups = cfg.num_layers // cfg.hybrid_attn_every
            shared = shared_block_cache_spec(cfg, b, s)
            shared_stacked = jax.tree.map(
                lambda sds: jax.ShapeDtypeStruct((groups,) + sds.shape, sds.dtype),
                shared,
            )
            return (stacked, shared_stacked)
        return stacked

    def cache_logical(self, shape: ShapeConfig):
        cfg = self.cfg
        # Production model-axis (TP) size is 16 on both meshes. When the KV
        # head count doesn't divide it, the cache time axis takes the model
        # axis instead (collective-softmax decode) — otherwise a 32k cache
        # would be replicated 16x (e.g. qwen1.5-32b: 40 kv heads).
        tp = 16
        kv_shardable = cfg.num_kv_heads % tp == 0
        seq_ax = "seq_sp" if kv_shardable else "kv_seq"
        if cfg.family in ("ssm", "hybrid"):
            ssm_log = {
                "conv": ("layer", "batch", None, "conv_ch"),
                "ssm": ("layer", "batch", "ssm_heads", None, None),
            }
            if cfg.family == "ssm":
                return ssm_log
            shared_log = {
                "k": ("layer", "batch", seq_ax, "kv_heads", None),
                "v": ("layer", "batch", seq_ax, "kv_heads", None),
            }
            return (ssm_log, shared_log)
        if cfg.attention == "mla":
            # the latent rank dim never shards; time takes the model axis
            return {
                "c": ("layer", "batch", "kv_seq", None),
                "kr": ("layer", "batch", "kv_seq", None),
            }
        return {
            "k": ("layer", "batch", seq_ax, "kv_heads", None),
            "v": ("layer", "batch", seq_ax, "kv_heads", None),
        }


def _none_tree(n: int):
    """Scan-compatible placeholder for 'no cache' (per-layer None)."""
    return None


def _slice_layers(tree, s0: int, s1: int):
    """Slice a stacked (L, ...) pytree to one policy segment (None passes)."""
    if tree is None:
        return None
    return jax.tree.map(lambda a: a[s0:s1], tree)


def _concat_layers(segs):
    """Re-stack per-segment scan outputs along the layer axis."""
    if len(segs) == 1:
        return segs[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *segs)


def make_quant_ctx(spec: str, key: jax.Array, **overrides) -> QuantCtx:
    """QuantCtx from a recipe name or a full PrecisionPolicy spec string
    (``"averis;lm_head=bf16;layers.0-1=nvfp4_hadamard"``)."""
    from repro.core.policy import PrecisionPolicy

    return QuantCtx(PrecisionPolicy.parse(spec, **overrides), key)
