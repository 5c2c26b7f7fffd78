"""Paged FP4 flash-decode attention: kernel vs dense reference, engine-level
greedy identity, and the loud-fallback contract.

Kernel comparisons follow the repo's jit-regime policy (see
test_fused_kernels.py): both sides run inside ONE jitted function, so any
gap is real math divergence plus float32 reassociation — the fused read
computes ``q . res + q . mu`` where the dense reference computes
``q . (res + mu)``, so equality is ~2^-24 relative, not bitwise. Engine
greedy identity is the production contract: argmax over bf16 logits after
the shared rounding point in models/attention.py.
"""
import dataclasses
import warnings

import numpy as np
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import pytest

from repro.configs import reduced
from repro.core.nvfp4 import decode_e2m1_codes
from repro.kernels.paged_attention import (
    _decode_e2m1_arith,
    paged_attend_gqa,
    paged_attend_mla,
)
from repro.models.model import Model
from repro.obs.telemetry import global_hub
from repro.serve.engine import Engine, EngineConfig
from repro.serve.kvcache import (
    QuantizedKVAdapter,
    QuantizedLatentAdapter,
    reset_paged_attn_fallback_warnings,
)


@pytest.fixture(autouse=True, scope="module")
def _bound_compile_population():
    """The fused/dense engine matrix here adds ~a hundred live jitted
    executables on top of the rest of the suite's; past that population the
    XLA:CPU backend can segfault inside a *later* module's backend_compile
    (observed in test_pipeline_golden / test_speculative only when this
    module runs before them in one process). Drop this module's compiled
    state on the way out so later modules compile under the same
    population as before this file existed."""
    yield
    jax.clear_caches()
    import gc
    gc.collect()


# --------------------------------------------------------------- helpers

def _fill_kv_cache(adapter, kv):
    """Append a (b, T, 2, n, hd) history token-by-token through the real
    write path, so committed pages/tail match what serving produces."""
    b, T = kv.shape[:2]
    cache = {k: jnp.zeros(s.shape, s.dtype)
             for k, s in adapter.layer_spec(b, T).items()}
    ones = jnp.ones((b,), bool)
    for t in range(T):
        cache = adapter._append(cache, kv[:, t], jnp.full((b,), t, jnp.int32),
                                ones)
    return cache


def _ref_attend(dense, q, qpos, sm_scale):
    """Masked-softmax reference over a dense (b, cap, 2, n, hd) f32 view.

    ``qpos``: (b, s) absolute position of each query token (attends keys at
    positions <= qpos)."""
    b, s, nh, hd = q.shape
    g = nh // dense.shape[3]
    kf = jnp.repeat(dense[:, :, 0], g, axis=2)          # (b, cap, nh, hd)
    vf = jnp.repeat(dense[:, :, 1], g, axis=2)
    logits = jnp.einsum("bsnh,btnh->bsnt", q.astype(jnp.float32), kf,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = (jnp.arange(dense.shape[1])[None, None, :]
            <= qpos[:, :, None])[:, :, None, :]
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bsnt,btnh->bsnh", w, vf)


def _rand_kv(key, b, T, n, hd, bias=0.0):
    kv = jax.random.normal(key, (b, T, 2, n, hd), jnp.float32)
    return (kv + bias).astype(jnp.bfloat16)


# ----------------------------------------------- E2M1 arithmetic decode

def test_arith_decode_matches_table():
    """The gather-free arithmetic E2M1 decode (Pallas-friendly) is bit-exact
    to the table decode over all 16 codes."""
    codes = jnp.arange(16, dtype=jnp.uint8)
    np.testing.assert_array_equal(np.asarray(_decode_e2m1_arith(codes)),
                                  np.asarray(decode_e2m1_codes(codes)))


# ----------------------------------------------- kernel vs dense reference

@pytest.mark.parametrize("centered", [True, False])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_gqa_plain_matches_dense_reference(centered, backend):
    p, n, hd, b = 16, 2, 32, 2
    T = 3 * p + 5                                   # 3 committed pages + tail
    adapter = QuantizedKVAdapter(num_kv_heads=n, head_dim=hd, page_size=p,
                                 centered=centered)
    kv = _rand_kv(jax.random.key(0), b, T, n, hd, bias=0.7)
    cache = _fill_kv_cache(adapter, kv)
    pos = jnp.full((b,), T - 1, jnp.int32)
    q = jax.random.normal(jax.random.key(1), (b, 1, 4, hd), jnp.bfloat16)
    sm = 1.0 / np.sqrt(hd)

    @jax.jit
    def both(cache, q):
        out = paged_attend_gqa(
            q, cache["codes"], cache["scales"], cache["pamax"],
            cache.get("mean"), cache["tail"], pos, page_size=p,
            sm_scale=sm, backend=backend, interpret=True)
        ref = _ref_attend(adapter._dense_view(cache, pos // p), q,
                          pos[:, None], sm)
        return out, ref

    out, ref = both(cache, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("centered", [True, False])
def test_gqa_span_matches_dense_reference(centered):
    """Speculative verify: the S-token scratch span is its own exact block,
    causally masked per query and dropped past capacity."""
    p, n, hd, b, S = 16, 2, 32, 2, 4
    T = 2 * p + 9
    adapter = QuantizedKVAdapter(num_kv_heads=n, head_dim=hd, page_size=p,
                                 centered=centered)
    kv = _rand_kv(jax.random.key(2), b, T, n, hd)
    cache = _fill_kv_cache(adapter, kv)
    pos = jnp.full((b,), T, jnp.int32)              # span starts after history
    span = _rand_kv(jax.random.key(3), b, S, n, hd)
    q = jax.random.normal(jax.random.key(4), (b, S, 4, hd), jnp.bfloat16)
    sm = 1.0 / np.sqrt(hd)

    @jax.jit
    def both(cache, span, q):
        out = paged_attend_gqa(
            q, cache["codes"], cache["scales"], cache["pamax"],
            cache.get("mean"), cache["tail"], pos, page_size=p,
            sm_scale=sm, span=span, backend="xla")
        dense = adapter._dense_view(cache, pos // p)
        sp = pos[:, None] + jnp.arange(S)[None, :]
        dense = dense.at[jnp.arange(b)[:, None], sp].set(
            span.astype(jnp.float32), mode="drop")
        return out, _ref_attend(dense, q, sp, sm)

    out, ref = both(cache, span, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_pallas_interpret_matches_xla_twin():
    """The Pallas kernel (interpret mode off-TPU) and the XLA scan twin are
    the same math over the same payload."""
    p, n, hd, b = 16, 2, 32, 2
    T = 2 * p + 3
    adapter = QuantizedKVAdapter(num_kv_heads=n, head_dim=hd, page_size=p,
                                 centered=True)
    kv = _rand_kv(jax.random.key(5), b, T, n, hd, bias=-0.4)
    cache = _fill_kv_cache(adapter, kv)
    pos = jnp.full((b,), T - 1, jnp.int32)
    q = jax.random.normal(jax.random.key(6), (b, 1, 4, hd), jnp.bfloat16)

    def run(backend):
        return paged_attend_gqa(
            q, cache["codes"], cache["scales"], cache["pamax"],
            cache["mean"], cache["tail"], pos, page_size=p,
            backend=backend, interpret=True)

    np.testing.assert_allclose(np.asarray(run("pallas")),
                               np.asarray(run("xla")),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("centered", [True, False])
def test_gqa_pallas_partial_page_group_matches_xla_twin(centered):
    """Both backends step the online softmax over the same page groups;
    when the capacity is not a whole number of groups the kernel pads the
    last group, and the padded pages (and committed-but-future ones) must
    add nothing."""
    p, n, hd, b = 16, 2, 32, 2
    T = 8 * p + 3                                   # 9 pages: groups of 8
    adapter = QuantizedKVAdapter(num_kv_heads=n, head_dim=hd, page_size=p,
                                 centered=centered)
    kv = _rand_kv(jax.random.key(10), b, T, n, hd, bias=0.3)
    cache = _fill_kv_cache(adapter, kv)
    assert cache["codes"].shape[1] == 9
    pos = jnp.asarray([T - 1, 5 * p + 2], jnp.int32)
    q = jax.random.normal(jax.random.key(11), (b, 1, 4, hd), jnp.bfloat16)

    def run(backend):
        return paged_attend_gqa(
            q, cache["codes"], cache["scales"], cache["pamax"],
            cache.get("mean"), cache["tail"], pos, page_size=p,
            backend=backend, interpret=True)

    np.testing.assert_allclose(np.asarray(run("pallas")),
                               np.asarray(run("xla")),
                               rtol=2e-6, atol=2e-6)


def _dot_precisions(jaxpr):
    """Precision params of every dot_general in ``jaxpr``, sub-jaxprs
    (loop bodies) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    out += _dot_precisions(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    out += _dot_precisions(sub)
    return out


@pytest.mark.parametrize("path", ["gqa", "gqa_span", "mla"])
def test_read_contractions_pinned_to_highest(path):
    """Every contraction of the paged read (XLA twin, tail page, span,
    MLA) is at Precision.HIGHEST, as the Pallas kernel's dots are. A TPU's
    default f32 matmul rounds operands to bf16 — the softmax weights of the
    tail's p @ v among them — and XLA:CPU ignores precision, so only the
    traced graph can show a contraction left at the caller's default."""
    p, n, hd, b, S = 16, 2, 32, 2, 3
    np_ = 3
    pos = jnp.full((b,), 2 * p + 3, jnp.int32)
    if path == "mla":
        rkv, dr, nh = 32, 8, 4
        args = (jnp.zeros((b, nh, rkv)), jnp.zeros((b, nh, dr)),
                jnp.zeros((b, np_, p, rkv // 2), jnp.uint8),
                jnp.zeros((b, np_, p, rkv // 16), jnp.float8_e4m3fn),
                jnp.ones((b, np_)), jnp.zeros((b, np_, rkv), jnp.bfloat16),
                jnp.zeros((b, np_ * p, dr)), jnp.zeros((b, p, rkv)), pos)
        fn = lambda *a: paged_attend_mla(*a, page_size=p, sm_scale=0.1)
    else:
        s = S if path == "gqa_span" else 1
        span = jnp.zeros((b, S, 2, n, hd)) if path == "gqa_span" else None
        args = (jnp.zeros((b, s, 4, hd), jnp.bfloat16),
                jnp.zeros((b, np_, p, 2, n, hd // 2), jnp.uint8),
                jnp.zeros((b, np_, p, 2, n, hd // 16), jnp.float8_e4m3fn),
                jnp.ones((b, np_, 2)),
                jnp.zeros((b, np_, 2, n, hd), jnp.bfloat16),
                jnp.zeros((b, p, 2, n, hd), jnp.bfloat16), pos)
        fn = lambda *a: paged_attend_gqa(*a, page_size=p, span=span,
                                         backend="xla")
    precisions = _dot_precisions(jax.make_jaxpr(fn)(*args).jaxpr)
    highest = jax.lax.Precision.HIGHEST
    assert precisions
    assert all(pr == (highest, highest) for pr in precisions), precisions


def test_adversarial_large_mean_tiny_residual():
    """The paper's Fig. 2 shape: a page whose content is almost entirely a
    shared bias vector. The analytic mean fold must reproduce the dense
    read exactly (same payload), and centered storage must beat uncentered
    against the exact pre-quantization values."""
    p, n, hd, b = 16, 2, 32, 1
    T = 2 * p                                       # exactly 2 committed pages
    key = jax.random.key(7)
    mu = 40.0 * jax.random.normal(key, (1, 1, 2, n, hd), jnp.float32)
    res = 1e-3 * jax.random.normal(jax.random.key(8), (b, T, 2, n, hd),
                                   jnp.float32)
    kv = (mu + res).astype(jnp.bfloat16)
    q = jax.random.normal(jax.random.key(9), (b, 1, 4, hd), jnp.bfloat16)
    pos = jnp.full((b,), T - 1, jnp.int32)
    sm = 1.0 / np.sqrt(hd)

    outs = {}
    for centered in (True, False):
        adapter = QuantizedKVAdapter(num_kv_heads=n, head_dim=hd,
                                     page_size=p, centered=centered)
        cache = _fill_kv_cache(adapter, kv)

        @jax.jit
        def both(cache, q, adapter=adapter):
            out = paged_attend_gqa(
                q, cache["codes"], cache["scales"], cache["pamax"],
                cache.get("mean"), cache["tail"], pos, page_size=p,
                sm_scale=sm, backend="xla")
            ref = _ref_attend(adapter._dense_view(cache, pos // p), q,
                              pos[:, None], sm)
            return out, ref

        out, ref = both(cache, q)
        # fused == dense on the SAME payload, even when mu dominates
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        outs[centered] = np.asarray(out)

    exact = np.asarray(_ref_attend(kv.astype(jnp.float32), q,
                                   pos[:, None], sm))
    err_c = np.abs(outs[True] - exact).max()
    err_u = np.abs(outs[False] - exact).max()
    assert err_c < err_u, (err_c, err_u)


def test_mla_latent_matches_dense_reference():
    p, r, dr, nh, b = 16, 32, 8, 4, 2
    T = 2 * p + 6
    adapter = QuantizedLatentAdapter(kv_lora_rank=r, rope_head_dim=dr,
                                     page_size=p, centered=True)
    cache = {k: jnp.zeros(s.shape, s.dtype)
             for k, s in adapter.layer_spec(b, T).items()}
    key = jax.random.key(10)
    cs = jax.random.normal(key, (b, T, r), jnp.bfloat16) + 0.5
    krs = jax.random.normal(jax.random.key(11), (b, T, dr), jnp.bfloat16)
    ones = jnp.ones((b,), bool)
    for t in range(T):
        cache = adapter._append(cache, cs[:, t], krs[:, t],
                                jnp.full((b,), t, jnp.int32), ones)
    pos = jnp.full((b,), T - 1, jnp.int32)
    qa = jax.random.normal(jax.random.key(12), (b, nh, r), jnp.bfloat16)
    qr = jax.random.normal(jax.random.key(13), (b, nh, dr), jnp.bfloat16)
    sm = 1.0 / np.sqrt(r + dr)

    @jax.jit
    def both(cache, qa, qr):
        out = paged_attend_mla(
            qa, qr, cache["codes"], cache["scales"], cache["pamax"],
            cache["mean"], cache["kr"], cache["tail"], pos,
            page_size=p, sm_scale=sm)
        cc = adapter._dense_view(cache, pos // p)           # (b, cap, r)
        scores = (jnp.einsum("bnr,btr->bnt", qa.astype(jnp.float32), cc)
                  + jnp.einsum("bnd,btd->bnt", qr.astype(jnp.float32),
                               cache["kr"].astype(jnp.float32))) * sm
        mask = (jnp.arange(cc.shape[1])[None, None, :]
                <= pos[:, None, None])
        w = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return out, jnp.einsum("bnt,btr->bnr", w, cc)

    out, ref = both(cache, qa, qr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------- engine-level identity

@pytest.fixture(scope="module")
def tiny_gqa():
    cfg = reduced("qwen3-0.6b", remat=False)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (3, 16), 0, cfg.vocab_size), np.int32)
    return cfg, model, params, prompts


@pytest.fixture(scope="module")
def tiny_mla():
    cfg = reduced("minicpm3-4b", remat=False)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (2, 12), 0, cfg.vocab_size), np.int32)
    return cfg, model, params, prompts


def _drain(model, params, prompts, gen=8, **cfg_kw):
    eng = Engine(model, params, EngineConfig(**cfg_kw))
    for i, p in enumerate(prompts):
        eng.submit(p, gen, seed=i)
    fin = eng.drain()
    assert len(fin) == len(prompts)
    return eng, np.asarray(
        [r.generated for r in sorted(fin, key=lambda r: r.rid)])


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["fp4", "fp4-centered"])
def test_engine_greedy_identity_gqa(tiny_gqa, mode):
    """Fused payload reads produce the exact greedy tokens of the dense
    _dense_view path, and the committed page payloads are byte-identical
    (this PR changes only reads)."""
    cfg, model, params, prompts = tiny_gqa
    kw = dict(n_slots=2, max_len=40, kv_cache=mode, page_size=16,
              quant_mode="bf16")
    ed, outd = _drain(model, params, prompts, kv_read="dense", **kw)
    ef, outf = _drain(model, params, prompts, kv_read="fused", **kw)
    np.testing.assert_array_equal(outf, outd)
    for leaf in ("codes", "scales", "pamax") + (
            ("mean",) if mode == "fp4-centered" else ()):
        np.testing.assert_array_equal(
            np.asarray(ef.caches[leaf]).view(np.uint8),
            np.asarray(ed.caches[leaf]).view(np.uint8))
    summ = ef.metrics.summary()
    assert summ["kv_read_fused"] == 1.0
    assert (summ["kv_bytes_read_per_token"]
            < 0.4 * summ["kv_dense_equiv_bytes_per_token"])
    assert summ["paged_attn_fallback"] == 0.0


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["fp4", "fp4-centered"])
def test_engine_greedy_identity_gqa_speculative(tiny_gqa, mode):
    """Speculative verify through update_span_attend: fused == dense, and
    both == the plain (non-speculative) fused run (PR 5's rollback
    contract survives the read-path change)."""
    cfg, model, params, prompts = tiny_gqa
    kw = dict(n_slots=2, max_len=48, kv_cache=mode, page_size=16,
              quant_mode="bf16", speculate="ngram", draft_tokens=3)
    ed, outd = _drain(model, params, prompts, gen=10, kv_read="dense", **kw)
    ef, outf = _drain(model, params, prompts, gen=10, kv_read="fused", **kw)
    np.testing.assert_array_equal(outf, outd)
    ep, outp = _drain(model, params, prompts, gen=10, kv_read="fused",
                      n_slots=2, max_len=48, kv_cache=mode, page_size=16,
                      quant_mode="bf16")
    np.testing.assert_array_equal(outf, outp)
    assert ef.metrics.summary()["spec_steps"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["fp4", "fp4-centered"])
def test_engine_greedy_identity_mla(tiny_mla, mode):
    cfg, model, params, prompts = tiny_mla
    kw = dict(n_slots=2, max_len=40, kv_cache=mode, page_size=16,
              quant_mode="bf16")
    ed, outd = _drain(model, params, prompts, gen=6, kv_read="dense", **kw)
    ef, outf = _drain(model, params, prompts, gen=6, kv_read="fused", **kw)
    np.testing.assert_array_equal(outf, outd)
    assert ef.metrics.summary()["kv_read_fused"] == 1.0


# --------------------------------------------------- fallback contract

def test_fallback_counted_and_warned_once():
    adapter = QuantizedKVAdapter(num_kv_heads=2, head_dim=32, page_size=16)
    assert adapter.fused_read_ok(jnp.float32)
    assert not adapter.fused_read_ok(jnp.bfloat16)
    reset_paged_attn_fallback_warnings()
    hub = global_hub()
    before = hub.counter("quant/paged_attn_fallback")
    with pytest.warns(UserWarning, match="paged FP4 attention fell back"):
        adapter.note_fallback("test-reason")
    with warnings.catch_warnings():
        warnings.simplefilter("error")              # second note: no warning
        adapter.note_fallback("test-reason")
    assert hub.counter("quant/paged_attn_fallback") == before + 2


@pytest.mark.slow
def test_engine_softmax_dtype_fallback(tiny_gqa):
    """A bf16 softmax policy cannot run the f32 online-softmax kernel: the
    engine falls back loudly to the dense view and still decodes."""
    cfg, model, params, prompts = tiny_gqa
    cfg16 = dataclasses.replace(cfg, attn_softmax_dtype="bfloat16")
    model16 = Model(cfg16)
    params16 = model16.init(jax.random.key(0))
    reset_paged_attn_fallback_warnings()
    hub = global_hub()
    before = hub.counter("quant/paged_attn_fallback")
    kw = dict(n_slots=2, max_len=40, kv_cache="fp4-centered", page_size=16,
              quant_mode="bf16")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ef, outf = _drain(model16, params16, prompts, kv_read="fused", **kw)
        ed, outd = _drain(model16, params16, prompts, kv_read="dense", **kw)
    assert hub.counter("quant/paged_attn_fallback") > before
    assert ef.metrics.summary()["paged_attn_fallback"] > 0
    np.testing.assert_array_equal(outf, outd)


def test_engine_rejects_unknown_kv_read(tiny_gqa):
    cfg, model, params, _ = tiny_gqa
    with pytest.raises(ValueError, match="kv_read"):
        Engine(model, params, EngineConfig(kv_read="mystery"))


def test_dense_read_backend_never_counts_fallback(tiny_gqa):
    cfg, model, params, prompts = tiny_gqa
    hub = global_hub()
    before = hub.counter("quant/paged_attn_fallback")
    _, _ = _drain(model, params, prompts[:1], gen=4, n_slots=1, max_len=32,
                  kv_cache="fp4-centered", page_size=16, quant_mode="bf16",
                  kv_read="dense")
    assert hub.counter("quant/paged_attn_fallback") == before
