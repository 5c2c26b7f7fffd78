"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-0.6b --quant averis --steps 500 --batch 8 --seq 256 \
        --ckpt-dir /tmp/run0 --ckpt-every 100

Wires together: arch config registry -> Model -> deterministic data ->
quantized train step -> supervisor (checkpoint/restart/fault tolerance).
On a real TPU pod the same entry point runs under `jax.distributed` with the
production mesh (--mesh data,model / pod,data,model); on CPU it runs
single-device (mesh flags are accepted and applied when devices allow).

Fault-tolerance posture (DESIGN.md §4): deterministic step-indexed data, atomic
retained checkpoints, supervisor restart loop with NaN guard + step timeout.
Cross-host failure detection on a pod is the coordinator's heartbeat; the
supervisor here is the per-job logic that consumes it.
"""
from __future__ import annotations

import argparse
import contextlib
import itertools
import logging

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig, make_stream
from repro.models.model import Model
from repro.optim import adamw
from repro.train.fault import SupervisorConfig, run_supervised
from repro.train.trainer import TrainConfig, init_train_state, make_train_step


def span_steps(step_fn, tracer=None, profile: bool = False):
    """``step_fn`` with each call in a ``train.step`` span that ends when
    the step's outputs are ready: a ``ChromeTracer`` span (which also lands
    in a profiler trace) and, with ``profile``, a
    ``jax.profiler.StepTraceAnnotation`` numbering the steps."""
    calls = itertools.count()

    def step(*args):
        k = next(calls)
        with contextlib.ExitStack() as spans:
            if profile:
                spans.enter_context(jax.profiler.StepTraceAnnotation(
                    "train.step", step_num=k))
            if tracer is not None:
                spans.enter_context(tracer.span("train.step", cat="train",
                                                step=k))
            return jax.block_until_ready(step_fn(*args))

    return step


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ALL_ARCHS)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU-sized) config")
    ap.add_argument("--quant", default="averis",
                    help="uniform recipe shorthand (bf16/nvfp4/averis/...)")
    ap.add_argument("--quant-policy", default="",
                    help="per-site PrecisionPolicy spec, overrides --quant; "
                         "e.g. 'averis;lm_head=bf16;layers.0-1=nvfp4_hadamard'"
                         " (grammar: repro/core/policy.py)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    help="comm recipe applied to grads every step (none | "
                         "int8_ef | bf16 | nvfp4 | nvfp4_centered | ...); "
                         "legacy alias ef_int8 accepted")
    ap.add_argument("--comm-recipe", default="",
                    help="DP gradient-wire recipe for the sharded step "
                         "(fp32/bf16/int8_ef/nvfp4/nvfp4_centered); defaults "
                         "to the policy's comm= clause, then fp32")
    ap.add_argument("--comm-bucket-mb", type=float, default=4.0,
                    help="gradient bucket size (MiB of grad-dtype elements)")
    ap.add_argument("--dp-shards", type=int, default=0,
                    help="virtual DP shard count for the sharded step "
                         "(0 = one per data-parallel device); >1 on one "
                         "device simulates the multi-device wire bitwise")
    ap.add_argument("--wire", default="packed", choices=("packed", "decoded"),
                    help="nvfp4 wire representation: 'packed' folds E2M1 "
                         "nibble packets directly (decode-inside-the-fold), "
                         "'decoded' ships the QDQ-simulated fp32 buffer; "
                         "non-nvfp4 recipes ignore this")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", action="store_true",
                    help="in-graph quant-health probes (repro.obs): per-site "
                         "R / clip / underflow stats in the step metrics and "
                         "the per-step log line")
    ap.add_argument("--telemetry-out", default="",
                    help="JSONL sink path for per-step telemetry records "
                         "(implies --telemetry)")
    ap.add_argument("--trace-out", default="",
                    help="Chrome-trace (Perfetto JSON) output: one "
                         "train.step span per step of the fused step")
    ap.add_argument("--profile-dir", default="",
                    help="write a jax.profiler trace (.xplane.pb) of the "
                         "step loop under this directory, each step a "
                         "train.step span; device ops carry the step's "
                         "named scopes (repro/obs/scopes.py)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    cfg = reduced(args.arch) if args.reduced else get_config(args.arch)
    model = Model(cfg)
    telemetry_on = bool(args.telemetry or args.telemetry_out)
    tcfg = TrainConfig(
        quant_mode=args.quant,
        quant_policy=args.quant_policy,
        microbatches=args.micro,
        grad_compression=args.grad_compression,
        comm_recipe=args.comm_recipe,
        comm_bucket_mb=args.comm_bucket_mb,
        wire_format=args.wire,
        quant_probes=telemetry_on,
        optimizer=adamw.OptimizerConfig(
            peak_lr=args.lr, warmup_steps=args.warmup, total_steps=args.steps
        ),
    )
    from repro.train.trainer import resolve_policy
    policy = resolve_policy(tcfg, model)
    logging.info("precision policy: %s", policy.describe(cfg.num_layers))

    # Mesh-aware step: with >1 device (or virtual shards requested), the DP
    # reduction runs through the collectives wire; 1 device + dp_shards=1 is
    # the plain single-device path (identity wire).
    n_dev = len(jax.devices())
    dp_shards = args.dp_shards or n_dev
    sharded = n_dev > 1 or dp_shards > 1 or args.comm_recipe
    tracer = None
    if args.trace_out:
        from repro.obs import ChromeTracer
        tracer = ChromeTracer(process_name=f"train:{args.arch}")
    hub = None
    if telemetry_on:
        from repro.obs import JsonlSink, Telemetry
        hub = Telemetry(JsonlSink(args.telemetry_out)
                        if args.telemetry_out else None)
    stream = make_stream(cfg, DataConfig(seed=args.seed,
                                         batch_size=args.batch,
                                         seq_len=args.seq,
                                         vocab_size=cfg.vocab_size))
    if sharded:
        mesh = jax.make_mesh((n_dev,), ("data",))
        raw_step = make_train_step(model, tcfg, mesh=mesh,
                                   dp_shards=dp_shards)
        if raw_step.dp_shards == 1:
            # a 1-shard wire carries nothing — do not log active-wire
            # numbers for a codec that never runs
            logging.info(
                "sharded step: %d device(s), 1 DP shard -> identity wire "
                "(comm recipe %r has no effect; pass --dp-shards > 1 to "
                "simulate the multi-device wire)",
                n_dev, raw_step.comm_recipe)
        else:
            ws = raw_step.comm_layout.wire_summary()
            logging.info(
                "sharded step: %d device(s), %d DP shard(s), wire=%s "
                "(%s), %d bucket(s), %.0f wire bytes/step/shard (%.2fx "
                "bf16 reduce)",
                n_dev, raw_step.dp_shards, raw_step.comm_recipe,
                getattr(raw_step, "wire_format", "packed"),
                ws["num_buckets"], ws["total_bytes_per_step"],
                ws["ratio_vs_bf16"])
        step_fn = jax.jit(raw_step, donate_argnums=(0, 1),
                          in_shardings=raw_step.in_shardings,
                          out_shardings=raw_step.out_shardings)

        def init_fn():
            # placed where the step keeps its state: the first step then
            # compiles once and donates in place
            return jax.device_put(
                init_train_state(model, tcfg, jax.random.key(args.seed),
                                 dp_shards=dp_shards),
                raw_step.in_shardings[:2])
    else:
        step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))

        def init_fn():
            return init_train_state(model, tcfg, jax.random.key(args.seed))

    def on_metrics(step, metrics):
        health = ""
        qp = metrics.get("quant_probes")
        if qp:
            from repro.obs.probes import probe_summary
            top = probe_summary(qp)
            health = (f" | R<={top['max_mean_bias_ratio']:.2f}"
                      f"@{top['worst_r_site']}"
                      f" clip<={top['max_clip_rate']:.4f}"
                      f" underflow<={top['max_underflow_rate']:.4f}")
            if hub is not None:
                hub.gauge("train/max_mean_bias_ratio",
                          top["max_mean_bias_ratio"])
                hub.gauge("train/max_clip_rate", top["max_clip_rate"])
                hub.emit("train.step", step=step,
                         loss=float(metrics["loss"]),
                         grad_norm=float(metrics.get("grad_norm", 0)),
                         **{k: v for k, v in top.items()
                            if not isinstance(v, str)},
                         sites=top["worst_r_site"])
        elif hub is not None:
            hub.emit("train.step", step=step, loss=float(metrics["loss"]),
                     grad_norm=float(metrics.get("grad_norm", 0)))
        if step % args.log_every == 0:
            print(f"step {step:6d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics.get('grad_norm', 0)):.3f} "
                  f"lr {float(metrics.get('lr', 0)):.2e}{health}",
                  flush=True)

    if tracer is not None or args.profile_dir:
        step_fn = span_steps(step_fn, tracer, profile=bool(args.profile_dir))
    sup = SupervisorConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                           ckpt_dir=args.ckpt_dir)
    profiling = contextlib.nullcontext()
    if args.profile_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the spans, not every Python call
        profiling = jax.profiler.trace(args.profile_dir,
                                       profiler_options=opts)
    with profiling:
        out = run_supervised(step_fn, init_fn, stream.batch,
                             jax.random.key(args.seed + 1), sup,
                             on_metrics=on_metrics)
    if tracer is not None:
        tracer.save(args.trace_out)
        logging.info("wrote Chrome trace (%d events) to %s — load in "
                     "chrome://tracing or ui.perfetto.dev",
                     len(tracer.events), args.trace_out)
    if args.profile_dir:
        logging.info("wrote the profiler trace under %s (XProf, "
                     "TensorBoard or jax.profiler.ProfileData)",
                     args.profile_dir)
    if hub is not None and args.telemetry_out:
        hub.emit("train.summary", **{
            k: v for k, v in hub.snapshot()["gauges"].items()})
        if hub.sink is not None:
            hub.sink.close()
        logging.info("wrote telemetry JSONL to %s", args.telemetry_out)
    print(f"done: {out['steps']} steps, {out['restarts']} restarts, "
          f"final loss {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
