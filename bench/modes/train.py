"""Training cells: the program's jitted, donated train step on a seeded
token stream.

One run builds the step (``make_train_step`` under ``jax.jit`` with the
params and optimizer state donated, as ``launch/train.py`` runs it) and its
state from the seed's weights, drives it through the job's ``check_steps``
first steps and reads what the comparison needs, then hands the same step
and state to the measured window. Once the window has closed and the
device's peak memory has been read, the program's state is freed and the
plain reference (``reference.py``) trains from the same weights and batches;
``compare.py`` sets the two side by side.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import compare, flops, reference, registry, trace_reduce
from repro.configs.base import ModelConfig
from repro.models.model import Model
from repro.optim import adamw
from repro.train.trainer import TrainConfig, make_train_step

_OPT_KEYS = ("peak_lr", "end_lr_frac", "warmup_steps", "total_steps",
             "schedule", "b1", "b2", "eps", "weight_decay", "clip_norm")


def model_config(model: Dict) -> ModelConfig:
    """The program's configuration for a configuration file's numbers."""
    n = reference.dims(model)
    arch, dt = model["architecture"], model["run_dtypes"]
    return ModelConfig(
        name=model["name"], family="dense", num_layers=n["L"],
        d_model=n["d"], d_ff=n["f"], vocab_size=n["V"], attention="gqa",
        num_heads=n["nh"], num_kv_heads=n["nkv"], head_dim=n["hd"],
        qkv_bias=arch["qkv_bias"], qk_norm=arch["qk_norm"],
        rope_theta=float(model["rope_theta"]),
        tie_embeddings=model["tie_word_embeddings"],
        param_dtype=dt["param_dtype"], compute_dtype=dt["compute_dtype"],
        attn_softmax_dtype=dt["attn_softmax_dtype"])


@dataclasses.dataclass
class Built:
    model: reference._Hashable
    job: Dict
    step: Callable
    program_model: Model


def build(model: Dict, job: Dict) -> Built:
    """The program's model and its jitted, donated train step."""
    pm = Model(model_config(model))
    opt = adamw.OptimizerConfig(**{k: job["optimizer"][k] for k in _OPT_KEYS})
    tcfg = TrainConfig(quant_mode=job["recipe"],
                       microbatches=job["microbatches"], optimizer=opt)
    step = jax.jit(make_train_step(pm, tcfg), donate_argnums=(0, 1))
    return Built(reference._Hashable(model), job, step, pm)


_INIT_OPT = jax.jit(adamw.init_state)


def stream(model: Dict, job: Dict, seed: int):
    return registry.generator(job["generator"]).make(
        job, seed, model["vocab_size"])


def _check_layout(b: Built, params) -> None:
    want = jax.eval_shape(b.program_model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype) for w, g in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the benchmark's weights do not match the "
                         "program's parameter layout")


class Run:
    """The program's step with its state, driven batch by batch."""

    def __init__(self, b: Built, seed: int):
        self.b, self.seed = b, seed
        self.key = reference.seed_key(seed)
        dtype = b.model["run_dtypes"]["param_dtype"]
        self.params = jax.tree.map(lambda a: a.astype(dtype),
                                   reference.WEIGHTS(b.model, self.key))
        _check_layout(b, self.params)
        self.opt = _INIT_OPT(self.params)
        self.feed = stream(b.model, b.job, seed)
        self.k = 0

    def dispatch(self, timed: bool = False):
        """Feed batch ``k`` to the step; returns its metrics (the loss and
        the gradient's global norm before clipping), not yet fetched, and
        with ``timed`` the host's seconds in the batch and the dispatch."""
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = {"tokens": jax.device_put(self.feed.batch(self.k))}
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.params, self.opt, met = self.b.step(
                self.params, self.opt, batch,
                jax.random.fold_in(self.key, self.k))
        self.k += 1
        if timed:
            return met, t1 - t0, time.perf_counter() - t1
        return met

    def check_steps(self) -> Dict:
        """The first steps, and the numbers the comparison reads."""
        b1 = self.b.job["optimizer"]["b1"]
        losses, norms, grad1 = [], [], None
        for i in range(self.b.job["check_steps"]):
            met = self.dispatch()
            losses.append(float(met["loss"]))
            norms.append(float(met["grad_norm"]))
            if i == 0:
                # the clipped gradient, from Adam's first moment
                grad1 = jax.device_get(reference.NORMS(self.opt["m"],
                                                       1.0 / (1.0 - b1)))
        change = jax.device_get(reference.CHANGES(self.params, self.b.model,
                                                  self.key))
        return {"losses": losses, "grad_norms": norms, "grad1": grad1,
                "change": change}

    def window(self, seconds: float) -> Dict:
        """Steps back to back for ``seconds``; one step in flight while the
        host fetches the loss of the one before. Each step's host time in
        the batch, the dispatch and the fetch is kept."""
        losses: List[float] = []
        done: List[float] = []
        host = {"batch": [], "dispatch": [], "fetch": []}

        def step():
            met, t_batch, t_step = self.dispatch(timed=True)
            host["batch"].append(t_batch)
            host["dispatch"].append(t_step)
            return met["loss"]

        def fetch(loss):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.loss_fetch"):
                losses.append(float(loss))
            done.append(time.perf_counter())
            host["fetch"].append(done[-1] - t)

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            pending = step()
            while True:
                nxt = step()
                fetch(pending)
                pending = nxt
                if done[-1] - t0 >= seconds:
                    break
            fetch(pending)
        dt = done[-1] - t0
        return {"steps": len(losses), "seconds": dt, "losses": losses,
                "intervals": np.diff([t0] + done), "host": host}

    def free(self) -> None:
        self.params = self.opt = None
        gc.collect()


def step_intervals(w: Dict) -> str:
    """The host-clock gaps between the window's loss fetches, in ms, and
    the longest host time in each phase: a stall of the host shows as one
    long batch or dispatch, a slower chip as a higher median interval."""
    iv = 1e3 * np.asarray(w["intervals"])
    q = np.percentile(iv, [0, 10, 50, 90, 100])
    phases = ", ".join(f"{k} {1e3 * max(v):.2f} at step {int(np.argmax(v))}"
                       for k, v in w["host"].items())
    return (f"step intervals (ms) min {q[0]:.2f} p10 {q[1]:.2f} median "
            f"{q[2]:.2f} p90 {q[3]:.2f} max {q[4]:.2f} at step "
            f"{int(np.argmax(iv))}; longest host time (ms): {phases}")


def compiled_memory(r: Run) -> Dict[str, int]:
    """What the compiled step asks of the device, by XLA's own count."""
    batch = {"tokens": jax.device_put(r.feed.batch(0))}
    ma = r.b.step.lower(r.params, r.opt, batch,
                        jax.random.fold_in(r.key, 0)).compile(
                        ).memory_analysis()
    out = {k: int(getattr(ma, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "alias", "temp", "generated_code")}
    out["total"] = (out["argument"] + out["output"] - out["alias"]
                    + out["temp"])
    return out


def peak_memory() -> Optional[int]:
    """The fullest chip's peak: the buffers in use plus the region the
    runtime reserves for the programs' temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


class CompileCount:
    """Counts backend compiles inside a ``with`` block (none belong in the
    measured window)."""

    def __init__(self):
        self.n = 0

    def _event(self, event, duration, **kw):
        if "backend_compile" in event:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)


def run(cell: Dict, seed: int, seconds: float, trace: bool,
        setup_t0: float, log: Callable[[str], None] = print) -> Dict:
    """One run of a training cell; see ``bench/run.py`` for the result."""
    model, job = cell["model"], cell["job"]
    b = build(model, job)
    r = Run(b, seed)
    prog = r.check_steps()
    log(f"check steps: losses {prog['losses']}")
    window_s = min(seconds, job["trace_seconds"]) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    setup_s = time.perf_counter() - setup_t0
    with CompileCount() as compiles:
        if trace:
            jax.profiler.start_trace(trace_dir)
        try:
            w = r.window(window_s)
        finally:
            if trace:
                jax.profiler.stop_trace()
    mem = peak_memory()
    log(f"memory: peak in use + reserved {mem}; "
        f"{jax.local_devices()[0].memory_stats()}")
    compiled = compiled_memory(r)
    log(f"compiled step memory (bytes): {compiled}")
    r.free()
    del r
    reduced = None
    if trace:
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {w['steps']} steps in {w['seconds']:.3f} s, "
        f"{compiles.n} compiles; {step_intervals(w)}")
    tokens = w["steps"] * job["batch"] * job["seq"]
    feed = stream(model, job, seed)
    ref = reference.train_record(
        model, job, seed, [feed.batch(i) for i in range(job["check_steps"])])
    log(f"reference losses {ref['losses']}")
    values = compare.readings(prog, ref)
    correct, checks = compare.judge(values, cell["limits"])
    failed = sum(not np.isfinite(x) for x in prog["losses"] + w["losses"])
    return {
        "correct": correct and failed == 0,
        "attempted": len(prog["losses"]) + w["steps"],
        "failed": failed,
        "end_to_end": {
            "train_tokens_per_s": (tokens / w["seconds"], "tokens/s"),
            "setup_s": (setup_s, "s")},
        "context": {
            "mode": "train", "tokens_per_s": tokens / w["seconds"],
            "flops_per_token": flops.train_flops_per_token(model, job["seq"]),
            "trace": reduced},
        "memory_peak_bytes": mem,
        "compiled_memory": compiled,
        "checks": checks,
        "compiles_in_window": compiles.n,
    }
