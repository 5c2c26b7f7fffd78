#!/usr/bin/env python3
"""Smoke run of the program on one TPU chip at qwen3-0.6b's full width.

    python chip_smoke.py              # one chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: the DP gradient wire only

One process, phases in order, exit non-zero at the first failure. Without
a TPU (for example under ``JAX_PLATFORMS=cpu``) it exits non-zero before
any phase; it has no CPU fallback.

Phases (one chip):

* kernels — every Pallas kernel of the TPU path, compiled (no interpret
  mode) at real widths, each checked for ``tpu_custom_call`` in its HLO and
  compared with its XLA twin or reference within the bound its CPU test
  sets (bitwise where that test pins bitwise);
* train — full-width, full-depth qwen3-0.6b, ``averis`` recipe, batch
  2 x seq 1024, 4 steps through ``make_train_step`` and ``run_supervised``
  (fresh temporary checkpoint directory); every loss finite, no restart;
* serve — the same config through ``make_engine`` with the fp4-centered KV
  cache and the fused paged read: 4 requests of 256 prompt tokens, 16
  generated tokens each;
* downgrades — the process hub's ``quant/*`` fallback counters stay 0.

``--chips 4`` runs only the sharded train step with the packed
``nvfp4_centered`` gradient wire, once on a 4-device ``data`` mesh and
once on a 1-device mesh with 4 virtual DP shards, and requires bitwise
equal losses and parameters.

Step times printed here are a smoke reading, not a benchmark. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 1024, 4
SERVE_REQUESTS, SERVE_PROMPT, SERVE_GEN = 4, 256, 16
WIRE_LAYERS, WIRE_BATCH, WIRE_SEQ, WIRE_STEPS = 4, 8, 256, 3
DOWNGRADE_COUNTERS = ("quant/fused_fallback", "quant/paged_attn_fallback",
                      "quant/wire_fold_fallback", "quant/skipped_hadamard")


class SmokeFailure(Exception):
    pass


# kernel comparisons that failed; every kernel is still run and reported,
# and the kernels phase fails at its end if this is not empty
_kernel_failures: list = []


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _compiled(fn, *args):
    """jit-compile ``fn`` for ``args``; the executable and its HLO text."""
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    return exe, exe.as_text()


def _kernel(name, fn, args):
    """Run a Pallas-path function compiled, requiring a Mosaic kernel."""
    exe, hlo = _compiled(fn, *args)
    check("tpu_custom_call" in hlo,
          f"{name}: no tpu_custom_call in the compiled HLO (interpreted?)")
    return exe(*args)


def _reference(fn, args):
    """Run an XLA twin / reference at fp32 ('highest') matmul precision."""
    import jax
    with jax.default_matmul_precision("highest"):
        exe, _ = _compiled(fn, *args)
    return exe(*args)


def _report_bitwise(name, got, want) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    diff = np.abs(got.astype(np.float64) - want.astype(np.float64))
    n_bad = int(np.sum(got != want))
    log(f"kernel {name}: max abs err {diff.max():.3e}, "
        f"{n_bad} of {got.size} elements differ (bound: bitwise)")
    if n_bad:
        _kernel_failures.append(f"{name}: not bitwise equal to its reference")


def _report_close(name, got, want, tol) -> None:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
    err = np.abs(got - want)
    excess = np.max(err - (tol + tol * np.abs(want)))
    log(f"kernel {name}: max abs err {err.max():.3e}, max rel err "
        f"{np.max(err / np.maximum(np.abs(want), 1e-30)):.3e} "
        f"(bound: rtol=atol={tol:g})")
    if excess > 0:
        _kernel_failures.append(f"{name}: error exceeds rtol=atol={tol:g}")


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.fused import (center_hadamard_pack_2d,
                                     center_hadamard_qdq_2d, fused_amax_2d)
    from repro.kernels.mean_split import column_mean_2d
    from repro.kernels.paged_attention import paged_attend_gqa
    from repro.kernels import wire_fold
    from repro.parallel import collectives as coll

    rng = np.random.default_rng(SEED)

    # Fused Center -> Hadamard -> Quantize at the GeMM widths d_model and
    # d_ff. Dyadic inputs (integers / 4), as in tests/test_fused_kernels.py,
    # keep every fp32 sum exact, so the pin is bitwise.
    for l, m in ((2048, 1024), (2048, 3072)):
        tag = f"{l}x{m}"
        x = jnp.asarray(rng.integers(-32, 33, (l, m)).astype(np.float32) / 4)
        bits = jax.random.bits(jax.random.key(SEED), (l, m), jnp.uint32)
        mu = _kernel(f"column_mean_2d {tag}",
                     lambda a: column_mean_2d(a, interpret=False), (x,))
        _report_bitwise(f"column_mean_2d {tag}", mu,
                        _reference(ref.column_mean_2d_ref, (x,)))
        _report_bitwise(
            f"fused_amax_2d {tag}",
            _kernel(f"fused_amax_2d {tag}",
                    lambda a, u: fused_amax_2d(a, u, rotate=True,
                                               interpret=False), (x, mu)),
            _reference(lambda a, u: jnp.max(jnp.abs(
                ref._preprocess_ref(a, u, True))).reshape(1, 1), (x, mu)))
        _report_bitwise(
            f"center_hadamard_qdq_2d lanes {tag}",
            _kernel(f"qdq lanes {tag}",
                    lambda a, u: center_hadamard_qdq_2d(
                        a, u, None, None, rotate=True, interpret=False),
                    (x, mu)),
            _reference(lambda a, u: ref.center_hadamard_qdq_2d_ref(
                a, u, None, rotate=True), (x, mu)))
        _report_bitwise(
            f"center_hadamard_qdq_2d lanes+SR {tag}",
            _kernel(f"qdq lanes+SR {tag}",
                    lambda a, u, b: center_hadamard_qdq_2d(
                        a, u, None, b, rotate=True, interpret=False),
                    (x, mu, bits)),
            _reference(lambda a, u, b: ref.center_hadamard_qdq_2d_ref(
                a, u, b, rotate=True), (x, mu, bits)))
        _report_bitwise(
            f"center_hadamard_qdq_2d sublanes {tag}",
            _kernel(f"qdq sublanes {tag}",
                    lambda a, u: center_hadamard_qdq_2d(
                        a, u, None, None, rotate=True, interpret=False,
                        block_axis=0), (x, mu)),
            _reference(lambda a, u: ref.center_hadamard_qdq_2d_ref(
                a.T, u.T, None, rotate=True).T, (x, mu)))
        got = _kernel(f"pack {tag}",
                      lambda a, u: center_hadamard_pack_2d(
                          a, u, None, None, rotate=True, interpret=False),
                      (x, mu))
        want = _reference(lambda a, u: ref.center_hadamard_pack_2d_ref(
            a, u, None, rotate=True), (x, mu))
        for part, g, w in zip(("codes", "scales", "s_t"), got, want):
            _report_bitwise(f"center_hadamard_pack_2d {part} {tag}",
                            np.asarray(g.astype(jnp.float32)
                                       if part == "scales" else g),
                            np.asarray(w.astype(jnp.float32)
                                       if part == "scales" else w))

    # Paged FP4 decode attention at qwen3-0.6b's head layout: n_kv 8,
    # head_dim 128, 16 query heads, 64-token pages, 4 slots at 1..7
    # committed pages. Bound of tests/test_paged_attention.py's
    # kernel-vs-twin test: rtol = atol = 2e-6.
    b, n_pages, page, nkv, hd, nh = 4, 8, 64, 8, 128, 16
    pos = jnp.asarray([511, 300, 130, 64], jnp.int32)
    codes = jnp.asarray(rng.integers(0, 256, (b, n_pages, page, 2, nkv,
                                              hd // 2)), jnp.uint8)
    scales = jax.lax.bitcast_convert_type(
        jnp.asarray(rng.integers(40, 72, (b, n_pages, page, 2, nkv,
                                          hd // 16)), jnp.uint8),
        jnp.float8_e4m3fn)
    pamax = jnp.asarray(rng.uniform(1.0, 4.0, (b, n_pages, 2)), jnp.float32)
    mean = jnp.asarray(rng.standard_normal((b, n_pages, 2, nkv, hd)),
                       jnp.bfloat16)
    tail = jnp.asarray(rng.standard_normal((b, page, 2, nkv, hd)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, 1, nh, hd)), jnp.bfloat16)
    for kv_mode, mn in (("fp4", None), ("fp4-centered", mean)):
        def attend(backend, interpret, mn=mn):
            return lambda q_, c_, s_, p_, t_: paged_attend_gqa(
                q_, c_, s_, p_, mn, t_, pos, page_size=page,
                backend=backend, interpret=interpret)
        args = (q, codes, scales, pamax, tail)
        _report_close(f"paged_attend_gqa {kv_mode}",
                      _kernel(f"paged {kv_mode}", attend("pallas", False),
                              args),
                      _reference(attend("xla", None), args), 2e-6)

    # Packed gradient wire: 4 shards of an 8 MiB fp32 bucket, encoded by the
    # fused pack kernel, folded by the packet kernel; and the decoded-shard
    # fold. tests/test_wire_fold.py pins both bitwise.
    n, n_shards = 1 << 21, 4
    recipe = coll.get_comm_recipe("nvfp4_centered")
    decoded = jnp.asarray(0.5 + rng.standard_normal((n_shards, n)),
                          jnp.float32)
    stacked = _kernel(
        "wire encode", lambda d: jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[coll.encode_bucket(recipe, d[s], packed=True)[0]
              for s in range(n_shards)]), (decoded,))
    fold_args = (stacked.codes, stacked.scales, stacked.amax, stacked.mean)
    _report_bitwise(
        "fold_packets S=4 x 8 MiB",
        _kernel("fold_packets",
                lambda c, s, a, mn: wire_fold.fold_packets(
                    c, s, a, mn, n_shards, backend="pallas",
                    interpret=False), fold_args),
        _reference(lambda c, s, a, mn: wire_fold.fold_packets_reference(
            c, s, a, mn, n_shards), fold_args))
    _report_bitwise(
        "fold_shards (decoded) S=4 x 8 MiB",
        _kernel("fold_shards",
                lambda d: coll._fold_shards_pallas(d, n_shards,
                                                   interpret=False),
                (decoded,)),
        _reference(lambda d: jax.lax.scan(
            lambda c, r: (c + r / n_shards, None),
            jnp.zeros(d.shape[1:], jnp.float32), d)[0], (decoded,)))
    check(not _kernel_failures, "; ".join(_kernel_failures))


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def phase_train() -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_stream
    from repro.models.model import Model
    from repro.optim import adamw
    from repro.train.fault import SupervisorConfig, run_supervised
    from repro.train.trainer import (TrainConfig, init_train_state,
                                     make_train_step)

    cfg = get_config("qwen3-0.6b")
    model = Model(cfg)
    tcfg = TrainConfig(quant_mode="averis", optimizer=adamw.OptimizerConfig(
        warmup_steps=2, total_steps=TRAIN_STEPS))
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
    stream = make_stream(cfg, DataConfig(seed=SEED, batch_size=TRAIN_BATCH,
                                         seq_len=TRAIN_SEQ,
                                         vocab_size=cfg.vocab_size))
    log(f"train: {cfg.name} {cfg.num_layers} layers d_model {cfg.d_model} "
        f"vocab {cfg.vocab_size}, recipe averis, batch {TRAIN_BATCH} x seq "
        f"{TRAIN_SEQ}, {TRAIN_STEPS} steps")
    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    stamps = [time.perf_counter()]

    def on_metrics(step, metrics):
        stamps.append(time.perf_counter())
        log(f"train step {step}: loss {float(metrics['loss']):.6f} "
            f"({stamps[-1] - stamps[-2]:.3f} s incl. compile on step 0; "
            f"smoke reading, not a benchmark)")

    try:
        out = run_supervised(
            step_fn,
            lambda: init_train_state(model, tcfg, jax.random.key(SEED)),
            stream.batch, jax.random.key(SEED + 1),
            SupervisorConfig(total_steps=TRAIN_STEPS, ckpt_every=TRAIN_STEPS,
                             ckpt_dir=ckpt_dir),
            on_metrics=on_metrics)
        losses = list(out["losses"])
        restarts = out["restarts"]
        del out
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    gc.collect()
    check(len(losses) == TRAIN_STEPS,
          f"train: {len(losses)} of {TRAIN_STEPS} steps ran")
    check(bool(np.all(np.isfinite(losses))), f"train: losses {losses}")
    check(restarts == 0, f"train: {restarts} restarts")
    log(f"train: {TRAIN_STEPS} steps, 0 restarts, losses {losses}")


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def phase_serve() -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import EngineConfig, make_engine

    cfg = get_config("qwen3-0.6b")
    model = Model(cfg)
    params = model.init(jax.random.key(SEED))
    eng = make_engine(model, params, EngineConfig(
        n_slots=4, max_len=SERVE_PROMPT + SERVE_GEN, kv_cache="fp4-centered",
        kv_read="fused", quant_mode="averis", prefill_chunk=64, seed=SEED))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(SEED + 2), (SERVE_REQUESTS, SERVE_PROMPT), 0,
        cfg.vocab_size))
    log(f"serve: {cfg.name}, kv-cache fp4-centered, kv-read fused, recipe "
        f"averis, {SERVE_REQUESTS} requests x {SERVE_PROMPT} prompt tokens, "
        f"{SERVE_GEN} generated")
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        eng.submit(p, SERVE_GEN, seed=SEED + i)
    finished = eng.drain()
    dt = time.perf_counter() - t0
    summ = eng.metrics.summary()
    log(f"serve: {len(finished)} finished in {dt:.3f} s incl. compile "
        f"(smoke reading, not a benchmark)")
    log(f"kv read path: {'fused' if summ['kv_read_fused'] else 'dense'}, "
        f"{summ['kv_bytes_read_per_token']:.0f} bytes/token read")
    check(len(finished) == SERVE_REQUESTS,
          f"serve: {len(finished)} of {SERVE_REQUESTS} requests finished")
    for r in finished:
        toks = np.asarray(r.generated)
        check(toks.size == SERVE_GEN,
              f"serve: request {r.rid} generated {toks.size} tokens")
        check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
              f"serve: request {r.rid} token out of vocabulary: {toks}")
    check(bool(summ["kv_read_fused"]), "serve: kv read path is not fused")
    log(f"serve: sample {sorted(finished, key=lambda r: r.rid)[0].generated}")


def phase_downgrades() -> None:
    from repro.obs.telemetry import global_hub
    hub = global_hub()
    counts = {name: hub.counter(name) for name in DOWNGRADE_COUNTERS}
    log(f"downgrade counters: {counts}")
    check(all(v == 0 for v in counts.values()),
          f"downgrades: nonzero counters {counts}")


# --------------------------------------------------------------------------
# four chips: the DP gradient wire, 4 devices vs 1 device x 4 shards
# --------------------------------------------------------------------------

def _digest(tree) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def phase_dp_wire() -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_stream
    from repro.models.model import Model
    from repro.optim import adamw
    from repro.train.trainer import (TrainConfig, init_train_state,
                                     make_train_step)

    import dataclasses
    cfg = dataclasses.replace(get_config("qwen3-0.6b"),
                              num_layers=WIRE_LAYERS)
    model = Model(cfg)
    tcfg = TrainConfig(quant_mode="averis", comm_recipe="nvfp4_centered",
                       wire_format="packed",
                       optimizer=adamw.OptimizerConfig(
                           warmup_steps=1, total_steps=WIRE_STEPS))
    stream = make_stream(cfg, DataConfig(seed=SEED, batch_size=WIRE_BATCH,
                                         seq_len=WIRE_SEQ,
                                         vocab_size=cfg.vocab_size))
    devices = jax.devices()
    meshes = {"4 devices": Mesh(np.asarray(devices[:4]), ("data",)),
              "1 device x 4 shards": Mesh(np.asarray(devices[:1]),
                                          ("data",))}
    log(f"dp wire: {cfg.name} cut to {WIRE_LAYERS} layers at full width, "
        f"averis, comm nvfp4_centered (packed), 4 DP shards, batch "
        f"{WIRE_BATCH} x seq {WIRE_SEQ}, {WIRE_STEPS} steps")
    results = {}
    for name, mesh in meshes.items():
        raw = make_train_step(model, tcfg, mesh=mesh, dp_shards=4)
        check(raw.wire_format == "packed", f"{name}: wire {raw.wire_format}")
        step = jax.jit(raw, donate_argnums=(0, 1),
                       in_shardings=raw.in_shardings,
                       out_shardings=raw.out_shardings)
        params, opt = jax.device_put(
            init_train_state(model, tcfg, jax.random.key(SEED), dp_shards=4),
            raw.in_shardings[:2])
        losses = []
        for i in range(WIRE_STEPS):
            t0 = time.perf_counter()
            params, opt, metrics = step(params, opt, stream.batch(i),
                                        jax.random.fold_in(
                                            jax.random.key(SEED + 1), i))
            losses.append(float(metrics["loss"]))
            log(f"dp wire [{name}] step {i}: loss {losses[-1]!r} "
                f"({time.perf_counter() - t0:.3f} s incl. compile on step 0;"
                f" smoke reading, not a benchmark)")
        results[name] = (losses, _digest(params), _digest(opt))
        del params, opt
        gc.collect()
    (l4, p4, o4), (l1, p1, o1) = results.values()
    log(f"dp wire: losses 4 devices {l4} vs 1 device x 4 shards {l1}")
    log(f"dp wire: param digest {p4[:16]} vs {p1[:16]}, "
        f"optimizer digest {o4[:16]} vs {o1[:16]}")
    check(bool(np.all(np.isfinite(l4))), f"dp wire: losses {l4}")
    check(l4 == l1 and p4 == p1 and o4 == o1,
          "dp wire: 4-device and 1-device x 4-shard runs differ")
    log("dp wire: bitwise equal losses, parameters and optimizer state")


# --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, train, serve; 4: the DP wire only")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log(f"device: {devices[0].device_kind}, {len(devices)} device(s), "
        f"jax {jax.__version__}, compile cache {cache_dir}")

    phases = ([phase_dp_wire] if args.chips == 4 else
              [phase_kernels, phase_train, phase_serve, phase_downgrades])
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        log(f"== phase {name}")
        try:
            phase()
        except SmokeFailure as e:
            print(f"chip_smoke: phase {name} FAILED: {e}", file=sys.stderr)
            return 1
        log(f"== phase {name} passed ({time.perf_counter() - t0:.1f} s)")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
