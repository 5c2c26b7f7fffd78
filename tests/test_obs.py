"""Observability: telemetry hub, Chrome tracer, in-graph quant-health probes.

The load-bearing guarantees:

* probes OFF is the default and is *bitwise free* — train loss/params and
  serve tokens/committed-KV-page payloads reproduce the pre-PR goldens
  (``tests/goldens/obs_goldens.json``, captured by
  ``tests/goldens/capture_obs_goldens.py`` on the probe-free tree);
* probes ON never perturbs values — identical loss bits, plus a tape whose
  numbers match an independent numpy reference on dyadic inputs;
* the serve tracer emits a valid Chrome-trace with the engine's phase
  span taxonomy, without changing a single generated token.
"""
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs.probes import (PROBE_FIELDS, biased_fixture, comm_bucket_stats,
                              gemm_site_stats, numpy_reference_stats,
                              probe_summary)
from repro.obs.telemetry import JsonlSink, Telemetry, global_hub
from repro.obs.trace import ChromeTracer

_GOLDEN = os.path.join(os.path.dirname(__file__), "goldens",
                       "obs_goldens.json")


@pytest.fixture(scope="module")
def goldens():
    with open(_GOLDEN) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# Host runtime layer: Telemetry hub, JSONL sink, Chrome tracer
# --------------------------------------------------------------------------

def test_telemetry_counters_gauges_series():
    t = Telemetry()
    t.count("a")
    t.count("a", 2)
    t.gauge("g", 3.5)
    for v in [1.0, 2.0, 3.0, 4.0]:
        t.observe("s", v)
    assert t.counter("a") == 3.0
    assert t.counter("missing") == 0.0
    assert t.values("s") == [1.0, 2.0, 3.0, 4.0]
    assert t.mean("s") == 2.5
    assert t.percentile("s", 0) == 1.0
    assert t.percentile("s", 100) == 4.0
    assert t.percentile("s", 50) == 2.5          # linear interpolation
    snap = t.snapshot()
    assert snap["counters"]["a"] == 3.0
    assert snap["gauges"]["g"] == 3.5
    assert snap["histograms"]["s"]["count"] == 4
    assert snap["histograms"]["s"]["max"] == 4.0
    t.reset()
    assert t.counter("a") == 0.0 and t.values("s") == []


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    hub = Telemetry(JsonlSink(path))
    hub.emit("ev1", x=1, tag="a")
    hub.emit("ev2", y=[1, 2])
    hub.sink.close()
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    recs = [json.loads(l) for l in lines]
    assert recs[0]["event"] == "ev1" and recs[0]["x"] == 1
    assert recs[1]["y"] == [1, 2]
    assert all("time" in r for r in recs)


def test_telemetry_without_sink_is_noop():
    hub = Telemetry()
    hub.emit("ev", x=1)          # must not raise
    assert hub.sink is None


def test_chrome_tracer_format(tmp_path):
    tr = ChromeTracer(process_name="test")
    with tr.span("phase.outer", cat="t", answer=42):
        with tr.span("phase.inner", cat="t"):
            pass
    tr.instant("mark")
    tr.counter("queue", {"depth": 3})
    doc = tr.to_json()
    assert isinstance(doc["traceEvents"], list)
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"M", "X", "i", "C"} <= phs
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"phase.outer", "phase.inner"}
    for e in xs:
        assert e["dur"] >= 0 and "ts" in e and "pid" in e and "tid" in e
    outer = next(e for e in xs if e["name"] == "phase.outer")
    assert outer["args"]["answer"] == 42
    assert tr.span_names() == {"mark", "phase.inner", "phase.outer"}
    out = tmp_path / "trace.json"
    tr.save(str(out))
    assert json.loads(out.read_text())["traceEvents"]


def test_tracer_span_closes_on_exception():
    tr = ChromeTracer()
    with pytest.raises(RuntimeError):
        with tr.span("will.raise"):
            raise RuntimeError("boom")
    assert any(e["ph"] == "X" and e["name"] == "will.raise"
               for e in tr.events)


# --------------------------------------------------------------------------
# Probe math: numpy cross-validation on dyadic inputs
# --------------------------------------------------------------------------

def _dyadic(key, shape):
    """Quarter-integer values: exact in fp32, and small enough that every
    partial sum in the probe's reductions is exact too — the jax float32
    path and the numpy float64 path then agree to the last ulp."""
    return (jax.random.randint(key, shape, -32, 33) * 0.25).astype(
        jnp.float32)


@pytest.mark.parametrize("mode", ["nvfp4", "averis", "bf16"])
def test_gemm_site_stats_matches_numpy_reference(mode):
    from repro.core.qgemm import recipe

    # 64 tokens (a power of two): the token-mean division is exact, so the
    # centered residual stays dyadic and both paths round identically
    x = _dyadic(jax.random.key(7), (64, 64))
    # a strong token mean makes the centered/uncentered paths diverge, so a
    # recipe mix-up in either implementation cannot cancel out
    x = x + jnp.where(jnp.arange(64) % 2 == 0, 4.0, -4.0)[None, :]
    cfg = recipe(mode)
    got = jax.jit(lambda v: gemm_site_stats(v, cfg))(x)
    ref = numpy_reference_stats(np.asarray(x), cfg)
    assert set(got) == set(PROBE_FIELDS) == set(ref)
    for k in PROBE_FIELDS:
        np.testing.assert_allclose(np.asarray(got[k]), ref[k], rtol=2e-6,
                                   atol=0, err_msg=f"{mode}:{k}")
    assert np.asarray(got["bins"]).shape == (8,)


def test_site_stats_centered_vs_uncentered_clip():
    """The acceptance fixture: on massively-biased activations the centered
    recipe's clip rate is strictly below the uncentered one's, per layer."""
    from repro.core.qgemm import recipe

    x = biased_fixture(jax.random.key(0), 64, 256, 4, bias=8.0)
    for li in range(4):
        un = gemm_site_stats(x[li], recipe("nvfp4"))
        ce = gemm_site_stats(x[li], recipe("averis"))
        assert float(ce["clip_rate"]) < float(un["clip_rate"])
        # R and the raw-range stats don't depend on the recipe
        np.testing.assert_allclose(np.asarray(un["mean_bias_ratio"]),
                                   np.asarray(ce["mean_bias_ratio"]))
        assert float(un["mean_bias_ratio"]) > 0.9
        assert float(un["amax_shrink"]) < 0.6


def test_probe_summary_reduction():
    tape = {
        "mlp_up/0.1": {"mean_bias_ratio": np.array([0.1, 0.9]),
                       "clip_rate": np.array([0.01, 0.02]),
                       "underflow_rate": np.array([0.0, 0.3]),
                       "amax_shrink": np.array([0.5, 0.4])},
        "lm_head/99.0": {"mean_bias_ratio": np.array(0.2),
                         "clip_rate": np.array(0.05),
                         "underflow_rate": np.array(0.1),
                         "amax_shrink": np.array(0.9)},
    }
    top = probe_summary(tape)
    assert top["max_mean_bias_ratio"] == pytest.approx(0.9)
    assert top["worst_r_site"] == "mlp_up/0.1"
    assert top["max_clip_rate"] == pytest.approx(0.05)
    assert top["max_underflow_rate"] == pytest.approx(0.3)
    assert top["min_amax_shrink"] == pytest.approx(0.4)


def test_comm_bucket_stats_fields():
    from repro.parallel.collectives import encode_bucket, get_comm_recipe

    flat = _dyadic(jax.random.key(3), (512,)) + 6.0   # mean-biased bucket
    for name in ("nvfp4", "nvfp4_centered"):
        r = get_comm_recipe(name)
        wire, _ = encode_bucket(r, flat, None)
        stats = comm_bucket_stats(r, flat, wire)
        assert set(stats) == set(PROBE_FIELDS) | {"ef_norm"}
        assert float(stats["mean_bias_ratio"]) > 0.5
        assert 0.0 < float(stats["amax_shrink"]) <= 1.0
        assert float(stats["ef_norm"]) >= 0.0
    # centering shrinks what the wire must carry -> smaller EF residual
    cen = comm_bucket_stats(get_comm_recipe("nvfp4_centered"), flat,
                            encode_bucket(get_comm_recipe("nvfp4_centered"),
                                          flat, None)[0])
    unc = comm_bucket_stats(get_comm_recipe("nvfp4"), flat,
                            encode_bucket(get_comm_recipe("nvfp4"),
                                          flat, None)[0])
    assert float(cen["ef_norm"]) < float(unc["ef_norm"])


def test_skipped_hadamard_counter():
    from repro.core import pipeline
    from repro.core.qgemm import qgemm, recipe

    pipeline.reset_hadamard_skip_warnings()
    hub = global_hub()
    before = hub.counter("quant/skipped_hadamard")
    # only ragged TOKEN counts hit the skip, and the token axis is a
    # contraction dim only in the dw GeMM — so drive the backward pass
    x = jax.random.normal(jax.random.key(0), (5, 32))   # ragged token axis
    w = jax.random.normal(jax.random.key(1), (32, 16))
    cfg = recipe("nvfp4_hadamard")

    def loss(wv):
        return jnp.sum(qgemm(x, wv, cfg, jax.random.key(2)))

    with pytest.warns(UserWarning, match="Hadamard stage skipped"):
        jax.grad(loss)(w)
    assert hub.counter("quant/skipped_hadamard") > before


# --------------------------------------------------------------------------
# quantwatch report
# --------------------------------------------------------------------------

def test_quantwatch_fixture_verdict():
    from repro.launch.quantwatch import fixture_report

    rep = fixture_report(["nvfp4", "averis"], layers=3, tokens=32, dim=128)
    assert set(rep["recipes"]) == {"nvfp4", "averis"}
    for mode, rec in rep["recipes"].items():
        assert len(rec["per_layer"]) == 3
        for pl in rec["per_layer"]:
            assert {"mean_bias_ratio", "clip_rate", "underflow_rate",
                    "amax_shrink", "bins"} <= set(pl)
    assert rep["recipes"]["averis"]["centered"]
    assert not rep["recipes"]["nvfp4"]["centered"]
    v = rep["verdict"]
    assert v["centered_lower_clip"], v
    assert v["max_centered_clip_rate"] < v["min_uncentered_clip_rate"]


# --------------------------------------------------------------------------
# Bench staleness validation
# --------------------------------------------------------------------------

def test_bench_staleness_check():
    from benchmarks.run import check_staleness

    head = 1_700_000_000.0
    assert check_staleness("2023-11-14T00:00:00Z", head)        # before HEAD
    assert not check_staleness("2023-11-16T00:00:00Z", head)    # after HEAD
    assert not check_staleness("2023-11-14T00:00:00Z", None)    # no git
    assert check_staleness("not-a-date", head)                  # unparsable


# --------------------------------------------------------------------------
# Bitwise zero-impact goldens (probes off) and zero-perturbation (probes on)
# --------------------------------------------------------------------------

def _train_run(quant_probes):
    from repro.configs import reduced
    from repro.models import Model
    from repro.train import trainer

    cfg = reduced("qwen3-0.6b", remat=False)
    model = Model(cfg)
    tcfg = trainer.TrainConfig(quant_mode="averis", microbatches=2,
                               quant_probes=quant_probes)
    params, opt_state = trainer.init_train_state(model, tcfg,
                                                 jax.random.key(0))
    step = jax.jit(trainer.make_train_step(model, tcfg))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 32), 0,
                                          cfg.vocab_size)}
    losses, out = [], {}
    for i in range(2):
        params, opt_state, out = step(params, opt_state, batch,
                                      jax.random.key(100 + i))
        losses.append(np.float32(np.asarray(out["loss"])).tobytes().hex())
    return losses, params, out


@pytest.mark.slow
def test_train_probes_off_bitwise_golden(goldens):
    from tests.goldens.capture_obs_goldens import tree_digest

    losses, params, out = _train_run(quant_probes=False)
    assert "quant_probes" not in out
    assert losses == goldens["train"]["loss_bits"]
    assert tree_digest(params) == goldens["train"]["params_digest"]


@pytest.mark.slow
def test_train_probes_on_zero_perturbation(goldens):
    from tests.goldens.capture_obs_goldens import tree_digest

    losses, params, out = _train_run(quant_probes=True)
    # probes never perturb: same loss bits and params as the probe-free run
    assert losses == goldens["train"]["loss_bits"]
    assert tree_digest(params) == goldens["train"]["params_digest"]
    tape = out["quant_probes"]
    assert tape, "probe tape empty with quant_probes=True"
    roles = {site.split("/")[0] for site in tape}
    assert {"attn_qkv", "attn_o", "mlp_up", "mlp_down", "lm_head"} <= roles
    for site, stats in tape.items():
        assert set(stats) == set(PROBE_FIELDS)
        r = np.asarray(stats["mean_bias_ratio"])
        assert np.all((r >= 0) & np.isfinite(r)), site
        cl = np.asarray(stats["clip_rate"])
        assert np.all((cl >= 0) & (cl <= 1)), site
    top = probe_summary(tape)
    assert top["worst_r_site"] in tape


def _serve_run(tracer=None, telemetry=None):
    from repro.configs import reduced
    from repro.models import Model
    from repro.serve.engine import Engine, EngineConfig

    cfg = reduced("qwen3-0.6b", remat=False)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (4, 16), 0, cfg.vocab_size), np.int32)
    eng = Engine(model, params, EngineConfig(
        n_slots=2, max_len=32, kv_cache="fp4-centered", page_size=16,
        quant_mode="bf16", prefix_cache=True),
        tracer=tracer, telemetry=telemetry)
    for i, p in enumerate(prompts):
        eng.submit(p, 8, seed=i)
    finished = eng.drain()
    tokens = np.asarray([r.generated for r in
                         sorted(finished, key=lambda r: r.rid)])
    return tokens, eng


@pytest.mark.slow
def test_serve_probes_off_bitwise_golden(goldens):
    from tests.goldens.capture_obs_goldens import tree_digest

    tokens, eng = _serve_run()
    assert tokens.tolist() == goldens["serve"]["tokens"]
    pages = {k.hex(): tree_digest(e[0])
             for k, e in eng.pool._entries.items()}
    assert pages == goldens["serve"]["pages"]


@pytest.mark.slow
def test_serve_tracer_and_telemetry_zero_impact(goldens, tmp_path):
    from tests.goldens.capture_obs_goldens import tree_digest

    tracer = ChromeTracer(process_name="test-serve")
    hub = Telemetry(JsonlSink(str(tmp_path / "serve.jsonl")))
    tokens, eng = _serve_run(tracer=tracer, telemetry=hub)
    # tracing/telemetry never change a token or a committed page payload
    assert tokens.tolist() == goldens["serve"]["tokens"]
    pages = {k.hex(): tree_digest(e[0])
             for k, e in eng.pool._entries.items()}
    assert pages == goldens["serve"]["pages"]

    # the span taxonomy: >= 6 distinct engine phase names, valid trace JSON
    names = tracer.span_names()
    assert {"engine.step", "engine.admit", "engine.prefill_chunk",
            "engine.prefill_insert", "engine.decode",
            "engine.retire"} <= names
    assert len(names) >= 6
    doc = json.loads(json.dumps(tracer.to_json()))
    assert any(e["ph"] == "X" for e in doc["traceEvents"])

    # ServeMetrics rides the hub: latency brackets + TTFT/TPOT percentiles
    summ = eng.metrics.summary()
    for k in ("p50_ttft_s", "p99_ttft_s", "p50_tpot_s", "p99_tpot_s"):
        assert k in summ and summ[k] >= 0.0
    assert summ["p99_ttft_s"] >= summ["p50_ttft_s"]
    assert len(eng.metrics.step_latencies_s) > 0
    hub.sink.close()
    recs = [json.loads(l) for l in
            (tmp_path / "serve.jsonl").read_text().splitlines()]
    assert any(r["event"] == "serve.step" for r in recs)


# --------------------------------------------------------------------------
# Named scopes in the train step; host spans in the profiler's trace
# --------------------------------------------------------------------------

_DOT = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+) = \S+ (?:dot|convolution)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scopes(op_name):
    """The components of an ``op_name``, transform wrappers split off:
    ``transpose(jvp(lm_head))/qgemm.dx`` -> {transpose, jvp, lm_head,
    qgemm.dx}."""
    return set(re.split(r"[/();]", op_name)) - {""}


@pytest.mark.parametrize("mode", ["bf16", "nvfp4_hadamard", "averis"])
def test_train_step_hlo_names_every_dot_by_scope(mode):
    """Every dot of the compiled step sits under a GeMM and a product term,
    a recipe stage (the Hadamard's H16 product) or attention; stage scopes
    exist only where the recipe has stages; AdamW's scopes are there."""
    from repro.configs import reduced
    from repro.models import Model
    from repro.obs import scopes
    from repro.train import trainer

    model = Model(reduced("qwen3-0.6b", num_layers=2))
    tcfg = trainer.TrainConfig(quant_mode=mode)
    state = jax.eval_shape(
        lambda k: trainer.init_train_state(model, tcfg, k), jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    hlo = jax.jit(trainer.make_train_step(model, tcfg)).lower(
        *state, batch, jax.random.key(1)).compile().as_text()
    names = [_OP_NAME.search(line) for line in hlo.splitlines()]
    every = set().union(*(_scopes(m.group(1)) for m in names if m))
    stages = set(scopes.STAGE.values())
    dots = 0
    for line in hlo.splitlines():
        if not _DOT.match(line):
            continue
        dots += 1
        m = _OP_NAME.search(line)
        got = _scopes(m.group(1)) if m else set()
        in_gemm = (got & set(scopes.QGEMM.values())
                   and got & set(scopes.TERM.values()))
        assert in_gemm or got & stages or scopes.ATTN_CORE in got, line
    assert dots
    assert bool(every & stages) == (mode != "bf16")
    assert ({scopes.ADAMW_CLIP, scopes.ADAMW_UPDATE, scopes.LOSS,
             scopes.QGEMM_WEIGHTS, "lm_head", "mlp_down"} <= every)


def _host_events(trace_dir, names):
    """``(name, stats)`` of the host events named in ``names`` of the one
    ``.xplane.pb`` under ``trace_dir``."""
    [path] = list(trace_dir.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    return [(ev.name, dict(ev.stats)) for pl in data.planes
            if pl.name.startswith("/host:") for ln in pl.lines
            for ev in ln.events if ev.name in names]


def test_tracer_spans_land_in_profiler_trace(tmp_path):
    tr = ChromeTracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("engine.step"):
            jnp.ones(4).block_until_ready()
        tr.instant("engine.pool_hit")
    spans = {"engine.step", "engine.pool_hit"}
    assert {name for name, _ in _host_events(tmp_path, spans)} == spans
    assert tr.span_names() == {"engine.step", "engine.pool_hit"}


def test_train_launcher_spans_each_fused_step(tmp_path, monkeypatch):
    """``--trace-out`` writes one ``train.step`` span per step and
    ``--profile-dir`` a profiler trace with the numbered steps."""
    from repro.launch import train as launch

    # keeps the launcher's persistent compile cache out of this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = tmp_path / "train_trace.json"
    launch.main(["--arch", "qwen3-0.6b", "--reduced", "--quant", "bf16",
                 "--steps", "2", "--batch", "2", "--seq", "16",
                 "--ckpt-dir", str(tmp_path / "ckpt"),
                 "--trace-out", str(out),
                 "--profile-dir", str(tmp_path / "prof")])
    evs = json.loads(out.read_text())["traceEvents"]
    assert [e["args"]["step"] for e in evs
            if e["ph"] == "X" and e["name"] == "train.step"] == [0, 1]
    steps = sorted(st["step_num"] for _, st in
                   _host_events(tmp_path / "prof", {"train.step"})
                   if "step_num" in st)
    assert steps == [0, 1]
