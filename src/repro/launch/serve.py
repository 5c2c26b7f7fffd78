"""Serving driver: continuous batching by default, one-shot batch with
``--static``. All weight GeMMs run under the selected FP4 recipe (the paper's
"NVFP4 forward evaluation" deployment mode); the KV cache is dense bf16 or
paged mean-centered NVFP4 (``--kv-cache fp4-centered``, see repro.serve).
Prompts prefill in bucketed chunks interleaved with decode
(``--prefill-chunk``/``--prefill-budget``); ``--prefix-cache`` shares
committed KV pages across requests with equal page-aligned prompt prefixes;
``--speculate {ngram,self}`` turns on speculative decoding — K draft tokens
per step (``--draft-tokens``) verified in one jitted call, with rejected
drafts rolled back before any quantized page is encoded.

    # continuous batching over staggered request groups, FP4 KV cache
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --kv-cache fp4-centered --prefill-chunk 32 --prefix-cache

    # legacy fixed-shape batch path
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --static --quant nvfp4 --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.core.policy import PrecisionPolicy
from repro.models.layers import QuantCtx
from repro.models.model import Model
from repro.serve import EngineConfig, make_engine
from repro.serve.sampling import sample_tokens


def generate(model: Model, params, tokens, gen: int, quant_mode: str,
             key=None, temperature: float = 0.0, top_k: int = 0,
             seed: int = 0):
    """Static-batch generation; returns (b, gen) int32 tokens.

    Greedy by default; ``temperature``/``top_k`` enable seeded sampling via
    ``repro.serve.sampling`` (shared with the engine).
    """
    key = key if key is not None else jax.random.key(seed)
    ctx = QuantCtx(PrecisionPolicy.parse(quant_mode), key)
    b, s = tokens.shape
    temps = jnp.full((b,), temperature, jnp.float32)
    topks = jnp.full((b,), top_k, jnp.int32)
    seeds = jnp.arange(b, dtype=jnp.int32)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, ctx))
    logits, caches = prefill(params, tokens)
    caches = model.grow_caches(caches, gen)
    step = jax.jit(
        lambda p, tok, pos, c: model.decode_step(p, {"token": tok}, pos, c, ctx)
    )
    out = []
    tok = sample_tokens(logits[:, -1], temps, topks, key, seeds)
    for i in range(gen):
        out.append(tok)
        pos = jnp.full((b,), s + i, jnp.int32)
        logits, caches = step(params, tok, pos, caches)
        tok = sample_tokens(logits[:, 0], temps, topks, key, seeds,
                            jnp.full((b,), i + 1, jnp.int32))
    return jnp.stack(out, axis=1)


def _build(args):
    cfg = reduced(args.arch) if args.reduced else get_config(args.arch)
    if not cfg.is_decoder:
        raise SystemExit(f"{args.arch} is encoder-only — no decode path")
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    return cfg, model, params


def _prompts(args, cfg, n: int):
    return jax.random.randint(jax.random.key(args.seed + 1),
                              (n, args.prompt_len), 0, cfg.vocab_size)


def run_static(args) -> None:
    cfg, model, params = _build(args)
    tokens = _prompts(args, cfg, args.batch)
    t0 = time.perf_counter()
    out = generate(model, params, tokens, args.gen, args.quant,
                   temperature=args.temperature, top_k=args.top_k,
                   seed=args.seed)
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name} recipe={args.quant} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} mode=static")
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", np.asarray(out[0])[:12])


def run_engine(args) -> None:
    cfg, model, params = _build(args)
    max_len = args.max_len or args.prompt_len + args.gen
    tracer = None
    if args.trace_out:
        from repro.obs import ChromeTracer
        tracer = ChromeTracer(process_name=f"serve:{args.arch}")
    hub = None
    if args.telemetry or args.telemetry_out:
        from repro.obs import JsonlSink, Telemetry
        hub = Telemetry(JsonlSink(args.telemetry_out)
                        if args.telemetry_out else None)
    eng = make_engine(model, params, EngineConfig(
        n_slots=args.slots, max_len=max_len, kv_cache=args.kv_cache,
        kv_read=args.kv_read,
        page_size=args.page_size, quant_mode=args.quant, seed=args.seed,
        prefill_chunk=args.prefill_chunk,
        prefill_token_budget=args.prefill_budget,
        prefix_cache=args.prefix_cache,
        speculate=args.speculate, draft_tokens=args.draft_tokens,
        self_draft_layers=args.draft_layers,
        draft_quant_mode=args.draft_quant,
        disagg=args.disagg,
    ), tracer=tracer, telemetry=hub)
    tokens = np.asarray(_prompts(args, cfg, args.requests))

    # Submit in staggered groups: the engine admits/retires mid-flight, which
    # is the continuous-batching behavior a single static batch can't show.
    groups = np.array_split(np.arange(args.requests), max(args.groups, 1))
    print(f"arch={cfg.name} recipe={args.quant} kv-cache={args.kv_cache} "
          f"slots={args.slots} requests={args.requests} "
          f"groups={len(groups)} prompt={args.prompt_len} gen={args.gen}")
    for i in groups[0]:
        eng.submit(tokens[i], args.gen, temperature=args.temperature,
                   top_k=args.top_k, seed=args.seed + int(i))
    finished = []
    for gi, group in enumerate(groups[1:], start=1):
        for _ in range(args.stagger_steps):
            finished.extend(eng.step())
        for i in group:
            eng.submit(tokens[i], args.gen, temperature=args.temperature,
                       top_k=args.top_k, seed=args.seed + int(i))
    finished.extend(eng.drain())

    summ = eng.metrics.summary()
    print(f"finished {len(finished)} requests, "
          f"{int(summ['generated_tokens'])} tokens, "
          f"{summ['throughput_tok_s']:.1f} tok/s, "
          f"ttft {summ['mean_ttft_s'] * 1e3:.0f}ms, "
          f"p95 step {summ['p95_step_ms']:.0f}ms, "
          f"occupancy {summ['mean_occupancy']:.2f}")
    print(f"kv-cache bytes/token (all layers): "
          f"{summ['cache_bytes_per_token']:.0f}")
    print(f"kv read path: "
          f"{'fused' if summ['kv_read_fused'] else 'dense'}, "
          f"{summ['kv_bytes_read_per_token']:.0f} bytes/token read "
          f"(dense-equiv {summ['kv_dense_equiv_bytes_per_token']:.0f}), "
          f"decode read {summ['decode_kv_read_gbps']:.2f} GB/s")
    print(f"prefill tokens computed {int(summ['prefill_tokens_computed'])} "
          f"(padded {int(summ['prefill_tokens_padded'])}), "
          f"prefix hit-rate {summ['prefix_hit_rate']:.2f} "
          f"({int(summ['prefix_hit_tokens'])} tokens reused), "
          f"compiles prefill/decode/verify/draft "
          f"{int(summ['compile_count_prefill'])}/"
          f"{int(summ['compile_count_decode'])}/"
          f"{int(summ['compile_count_verify'])}/"
          f"{int(summ['compile_count_draft'])}")
    if args.disagg:
        print(f"disagg: {int(summ['migration_packets'])} migrations, "
              f"{summ['migration_bytes_per_token']:.0f} bytes/token on the "
              f"wire ({summ['migration_vs_dense_bf16']:.2f}x dense bf16), "
              f"p50 transfer {summ['p50_transfer_ms'] * 1e3:.0f}us")
    if args.speculate != "off":
        print(f"speculative ({args.speculate}, K={args.draft_tokens}): "
              f"accept-rate {summ['accept_rate']:.2f}, "
              f"{summ['spec_tokens_per_step']:.2f} tokens/step "
              f"over {int(summ['spec_steps'])} spec steps")
    if summ["skipped_hadamard"]:
        print(f"WARNING: {int(summ['skipped_hadamard'])} ragged-axis "
              f"Hadamard skip(s) — a rotation stage silently downgraded "
              f"(see core/pipeline.plan_summary)")
    if summ["paged_attn_fallback"]:
        print(f"WARNING: {int(summ['paged_attn_fallback'])} paged-attention "
              f"read fallback(s) — fused FP4 KV reads dropped to the dense "
              f"_dense_view path")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"wrote Chrome trace ({len(tracer.events)} events, "
              f"{len(tracer.span_names())} span types) to {args.trace_out} "
              f"— load in chrome://tracing or ui.perfetto.dev")
    if hub is not None and args.telemetry_out:
        hub.emit("serve.summary", **summ)
        if hub.sink is not None:
            hub.sink.close()
        print(f"wrote telemetry JSONL to {args.telemetry_out}")
    by_rid = sorted(finished, key=lambda r: r.rid)
    print("sample:", by_rid[0].generated[:12])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ALL_ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="nvfp4")
    ap.add_argument("--static", action="store_true",
                    help="legacy one-shot fixed-shape batch path")
    ap.add_argument("--batch", type=int, default=4, help="--static batch size")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # sampling (shared by both paths)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="0 = full support")
    # engine knobs
    ap.add_argument("--kv-cache", default="bf16",
                    choices=["bf16", "fp4", "fp4-centered"])
    ap.add_argument("--kv-read", default="fused",
                    choices=["fused", "dense"],
                    help="quantized-cache decode read path: fused attends "
                         "off the stored page payload (packed codes + "
                         "scales + mean); dense dequantizes the reference "
                         "_dense_view first")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="chunked-prefill chunk size (jit shapes come from "
                         "the power-of-two bucket grid up to this size)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prompt tokens prefilled per engine step "
                         "(0 = one chunk per step)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse committed KV pages across requests that "
                         "share a page-aligned prompt prefix")
    ap.add_argument("--speculate", default="off",
                    choices=["off", "ngram", "self"],
                    help="speculative decoding drafter: prompt-lookup "
                         "n-gram (no extra weights) or truncated-layer "
                         "self-draft")
    ap.add_argument("--draft-tokens", type=int, default=4,
                    help="draft tokens per speculative step (K)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="self-draft depth (0 = half the layers)")
    ap.add_argument("--draft-quant", default="",
                    help="draft-model recipe / policy spec "
                         "(default: same as --quant)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode serving: a "
                         "PrefillEngine commits FP4 pages and ships them "
                         "over the in-process page wire to a DecodeEngine "
                         "(stored bytes travel verbatim — greedy outputs "
                         "are token-identical to the unified engine)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache horizon (0 = prompt+gen)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--groups", type=int, default=2,
                    help="staggered submission groups")
    ap.add_argument("--stagger-steps", type=int, default=4,
                    help="engine steps between group submissions")
    ap.add_argument("--telemetry", action="store_true",
                    help="back ServeMetrics on a repro.obs Telemetry hub "
                         "(per-step records; summary unchanged)")
    ap.add_argument("--telemetry-out", default="",
                    help="JSONL sink path for per-step serve records "
                         "(implies --telemetry)")
    ap.add_argument("--trace-out", default="",
                    help="Chrome-trace (Perfetto JSON) output of engine "
                         "phase spans (admit/prefill/decode/verify/...)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.static:
        run_static(args)
    else:
        run_engine(args)


if __name__ == "__main__":
    main()
