"""Bitwise goldens: fused Center→Hadamard→Quantize kernels vs the unfused
stage pipeline.

Inputs are dyadic (integers/4) so every fp32 reduction is exact regardless
of summation order — any mismatch is a real math divergence, not ULP noise.
Comparisons run inside ONE jit regime: XLA CPU's fast-math rewrites (e.g.
division-by-constant → reciprocal multiply for the per-tensor scale) make
eager-vs-jit bitwise comparison meaningless, while same-regime equality is
exactly the production contract (the train/serve steps are fully jitted).

SR goldens key both sides from the same uint32 bit stream: the fused
backend derives uniforms from ``jax.random.bits`` (top 24 bits), which is
its documented SR stream; the stage backend's ``jax.random.uniform`` stream
is intentionally not replicated.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pipeline as P
from repro.core.qgemm import qgemm, recipe
from repro.kernels import ref
from repro.kernels.fused import (
    center_hadamard_pack_2d,
    center_hadamard_qdq_2d,
    center_hadamard_quantize_pack,
    fused_amax_2d,
)
from repro.kernels.mean_split import column_mean_2d


def _dyadic(shape, seed=0, lo=-32, hi=33):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(lo, hi, size=shape).astype(np.float32) / 4.0)


def _bits(shape, seed=7):
    return jax.random.bits(jax.random.key(seed), shape, jnp.uint32)


@pytest.mark.parametrize("center", [False, True])
@pytest.mark.parametrize("rotate", [False, True])
@pytest.mark.parametrize("sr", [False, True])
def test_fused_qdq_bitwise_vs_unfused(center, rotate, sr):
    x = _dyadic((64, 128))
    bits = _bits(x.shape) if sr else None

    @jax.jit
    def both(xx, bb):
        mu = column_mean_2d(xx) if center else None
        got = center_hadamard_qdq_2d(xx, mu, None, bb, rotate=rotate)
        want = ref.center_hadamard_qdq_2d_ref(xx, mu, bb, rotate=rotate)
        return got, want

    got, want = both(x, bits)
    assert jnp.array_equal(got, want), float(jnp.max(jnp.abs(got - want)))


@pytest.mark.parametrize("center", [False, True])
@pytest.mark.parametrize("rotate", [False, True])
@pytest.mark.parametrize("sr", [False, True])
def test_fused_pack_bitwise_vs_unfused(center, rotate, sr):
    """Packed nibbles, E4M3 block scales, and s_t all match the unfused
    stage chain + shared codec bit-for-bit."""
    x = _dyadic((32, 64), seed=1)
    bits = _bits(x.shape, seed=9) if sr else None

    @jax.jit
    def both(xx, bb):
        mu = column_mean_2d(xx) if center else None
        return (center_hadamard_pack_2d(xx, mu, None, bb, rotate=rotate),
                ref.center_hadamard_pack_2d_ref(xx, mu, bb, rotate=rotate))

    (pk, sc, st), (rpk, rsc, rst) = both(x, bits)
    assert jnp.array_equal(pk, rpk)
    assert jnp.array_equal(sc.astype(jnp.float32), rsc.astype(jnp.float32))
    assert jnp.array_equal(st, rst)


def test_fused_quantize_pack_returns_mean():
    x = _dyadic((32, 64), seed=2)
    pk, sc, st, mu = jax.jit(center_hadamard_quantize_pack)(x)
    assert pk.shape == (32, 32) and pk.dtype == jnp.uint8
    assert sc.shape == (32, 4) and sc.dtype == jnp.float8_e4m3fn
    assert st.shape == (1, 1)
    assert jnp.array_equal(
        mu, jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True))


def test_fused_amax_masks_padded_rows():
    """Rows beyond the array must not contribute |H(-mu)| to the amax."""
    x = _dyadic((100, 64), seed=3)

    @jax.jit
    def both(xx):
        mu = column_mean_2d(xx, tile_l=32)
        a = fused_amax_2d(xx, mu, rotate=True, tile_l=32)
        b = jnp.max(jnp.abs(ref._preprocess_ref(xx, mu, True)))
        return a.reshape(()), b

    a, b = both(x)
    assert jnp.array_equal(a, b)


def test_fused_sublane_mu_orientation():
    """Transposed (dw) orientation: (l, 1) per-row mean subtraction."""
    x = _dyadic((64, 128), seed=4)

    @jax.jit
    def both(xx):
        mu_t = column_mean_2d(xx).T              # (m, 1) for xx.T (m, l)
        got = center_hadamard_qdq_2d(xx.T, mu_t, None, None)
        want = ref.center_hadamard_qdq_2d_ref(xx.T, mu_t, None)
        return got, want

    got, want = both(x)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize(
    "mode", ["nvfp4", "nvfp4_hadamard", "averis", "averis_hadamard"])
def test_fused_backend_matches_stages_end_to_end(mode):
    """qgemm fwd + both grads are bitwise-identical across backends (RN)."""
    x = _dyadic((48, 64), seed=5)
    w = _dyadic((64, 32), seed=6)
    key = jax.random.key(3)
    cs = recipe(mode, sr_grad=False)
    cf = recipe(mode, sr_grad=False, backend="fused")

    @jax.jit
    def both(xx, ww):
        def run(cfg):
            return jax.value_and_grad(
                lambda a, b: jnp.sum(qgemm(a, b, cfg, key) ** 2),
                argnums=(0, 1))(xx, ww)
        return run(cs), run(cf)

    (ys, (gxs, gws)), (yf, (gxf, gwf)) = both(x, w)
    assert jnp.array_equal(ys, yf)
    assert jnp.array_equal(gxs, gxf)
    assert jnp.array_equal(gws, gwf)


def test_fused_backend_sr_runs_and_is_quantized():
    """SR streams differ by design between backends; the fused SR path must
    still produce finite, actually-quantized values."""
    x = _dyadic((48, 64), seed=7)
    w = _dyadic((64, 32), seed=8)
    cf = recipe("averis_hadamard", backend="fused")
    y, (gx, gw) = jax.jit(lambda a, b: jax.value_and_grad(
        lambda aa, bb: jnp.sum(qgemm(aa, bb, cf, jax.random.key(1)) ** 2),
        argnums=(0, 1))(a, b))(x, w)
    assert jnp.isfinite(y)
    assert jnp.all(jnp.isfinite(gx)) and jnp.all(jnp.isfinite(gw))


def test_fused_fallback_counts_and_matches_stages():
    """A ragged Hadamard axis routes to the stage path (bitwise-identical
    result) and counts into quant/fused_fallback."""
    from repro.obs.telemetry import global_hub

    P.reset_fused_fallback_warnings()
    x = _dyadic((48, 120), seed=9)         # 120 % 16 != 0
    w = _dyadic((120, 32), seed=10)
    key = jax.random.key(2)
    before = global_hub().counter("quant/fused_fallback")
    with pytest.warns(UserWarning, match="fused quant backend fell back"):
        @jax.jit
        def both(xx, ww):
            ys = qgemm(xx, ww, recipe("averis_hadamard", sr_grad=False), key)
            yf = qgemm(xx, ww, recipe("averis_hadamard", sr_grad=False,
                                      backend="fused"), key)
            return ys, yf
        ys, yf = both(x, w)
    assert global_hub().counter("quant/fused_fallback") > before
    assert jnp.array_equal(ys, yf)


def test_fused_ragged_token_axis_pads_with_mu():
    """Centered operand with a ragged quantize==token axis: the padded tail
    shares a 16-block with real data, so it must be padded with mu (exact
    zeros after centering), not with raw zeros (which center to -mu and
    inflate the shared block scale). Adversarial layout: large mean, tiny
    tail-block values — zero padding would shift every tail-block code."""
    x = np.full((120, 64), 8.0, np.float32)     # 120 % 16 != 0
    x[112:120, :] = 0.25                        # tail block amax << |mu|
    x = jnp.asarray(x)
    w = _dyadic((64, 32), seed=15)
    key = jax.random.key(4)
    cs = recipe("averis", sr_grad=False)
    cf = recipe("averis", sr_grad=False, backend="fused")

    @jax.jit
    def both(xx, ww):
        def run(cfg):
            return jax.value_and_grad(
                lambda a, b: jnp.sum(qgemm(a, b, cfg, key) ** 2),
                argnums=(0, 1))(xx, ww)
        return run(cs), run(cf)

    (ys, (gxs, gws)), (yf, (gxf, gwf)) = both(x, w)
    assert jnp.array_equal(ys, yf)
    assert jnp.array_equal(gxs, gxf)
    assert jnp.array_equal(gws, gwf)


def test_fused_sublane_blocks_native_matches_transposed():
    """block_axis=0 (native sublane blocks, lane mu) is bitwise the
    transposed lane-block orientation."""
    x = _dyadic((64, 96), seed=16)

    @jax.jit
    def both(xx):
        mu = column_mean_2d(xx)                  # (1, m) lane vector
        nat = center_hadamard_qdq_2d(xx, mu, None, None, rotate=True,
                                     block_axis=0)
        via_t = center_hadamard_qdq_2d(xx.T, mu.T, None, None,
                                       rotate=True).T
        return nat, via_t

    nat, via_t = both(x)
    assert jnp.array_equal(nat, via_t)


def test_fused_center_shares_one_mean_with_mean_term():
    """The fused residual operand and the stage-path mean operand consume
    the same memoized mean (one reduction per source tensor)."""
    x = _dyadic((48, 64), seed=11)
    cf = recipe("averis", sr_grad=False, backend="fused")
    res_op = P.Operand((P.Center(0, "residual"), P.Quantize(-1)))
    mean_op = P.Operand((P.Center(0, "mean"), P.Quantize(-1)))

    @jax.jit
    def run(xx):
        splits = {}
        rq = P.apply_stages(xx, res_op, cf, splits=splits)
        mq = P.apply_stages(xx, mean_op, cf, splits=splits)
        return rq, mq, splits[0][0]

    rq, mq, mu = run(x)
    assert mu.shape == (64,)
    assert jnp.array_equal(
        mu, jnp.mean(x.astype(jnp.float32), axis=0))
    assert rq.shape == x.shape and mq.shape == (64,)


def test_policy_backend_clause():
    from repro.core.policy import PrecisionPolicy

    p = PrecisionPolicy.parse("averis;lm_head=bf16;backend=fused")
    assert p.default.backend == "fused"
    assert all(c.cfg.backend == "fused" for c in p.clauses)
    with pytest.raises(ValueError):
        PrecisionPolicy.parse("averis;backend=warp")
    with pytest.raises(ValueError):
        recipe("averis", backend="warp")


def test_wire_fused_encode_and_fold_bitwise():
    """The fused wire encode and the Pallas shard fold are bitwise the
    stage/scan paths' results inside one jit regime."""
    import repro.parallel.collectives as C

    flat = _dyadic((4096,), seed=12)
    ef = _dyadic((4096,), seed=13, lo=-8, hi=9) / 4.0
    rec = C.get_comm_recipe("nvfp4_centered")

    @jax.jit
    def both(f, e):
        wf = C._fused_bucket_qdq(f + e, center=True) + 0.0
        splits = {}
        mu = P.apply_stages(f + e, C.MEAN_OP, C._WIRE_QCFG, splits=splits)
        rq = P.apply_stages(f + e, C.RESIDUAL_NVFP4_OP, C._WIRE_QCFG,
                            splits=splits)
        return wf, rq + mu

    wf, ws = both(flat, ef)
    assert jnp.array_equal(wf, ws)
    assert rec.center

    stacked = _dyadic((4, 4096), seed=14)
    folded_k = C._fold_shards_pallas(stacked, 4)
    acc = jnp.zeros((4096,), jnp.float32)
    for s in range(4):
        acc = acc + stacked[s] / 4
    assert jnp.array_equal(folded_k, acc)


@pytest.mark.parametrize("shape", [(64, 384), (128, 96)])
def test_mosaic_forms_interpreted_match_reference(shape, monkeypatch):
    """The forms the compiled kernels use (block-diagonal MXU rotation,
    lane-roll block max, arithmetic E4M3 rounding, MXU nibble pairing),
    run interpreted, are bitwise the unfused reference on dyadic inputs
    (every fp32 sum exact) — in both orientations, RN and SR, QDQ and
    pack. The chip compiles these forms; the CPU suite otherwise runs the
    split-lane twins."""
    import repro.kernels.fused as F

    monkeypatch.setattr(F, "_split_lanes", lambda interpret: False)
    jax.clear_caches()
    x = _dyadic(shape, seed=21)
    bits = _bits(shape, seed=22)

    @jax.jit
    def both(xx, bb):
        mu = column_mean_2d(xx)
        out = {
            "lanes": (center_hadamard_qdq_2d(xx, mu, None, None, rotate=True),
                      ref.center_hadamard_qdq_2d_ref(xx, mu, None,
                                                     rotate=True)),
            "lanes_sr": (center_hadamard_qdq_2d(xx, mu, None, bb,
                                                rotate=True),
                         ref.center_hadamard_qdq_2d_ref(xx, mu, bb,
                                                        rotate=True)),
            "sublanes": (center_hadamard_qdq_2d(xx, mu, None, None,
                                                rotate=True, block_axis=0),
                         ref.center_hadamard_qdq_2d_ref(
                             xx.T, mu.T, None, rotate=True).T),
            "amax": (fused_amax_2d(xx, mu, rotate=True, tile_l=32),
                     jnp.max(jnp.abs(ref._preprocess_ref(xx, mu, True)))
                     .reshape(1, 1)),
        }
        # the multi-tile (two-pass) kernel: an explicit amax skips the
        # single-tile fast path
        amax = out["amax"][1]
        out["two_pass"] = (center_hadamard_qdq_2d(xx, mu, amax, None,
                                                  rotate=True, tile_l=16,
                                                  tile_m=shape[1] // 2
                                                  if shape[1] % 256 == 0
                                                  else shape[1]),
                           out["lanes"][1])
        pk, sc, st = center_hadamard_pack_2d(xx, mu, None, None, rotate=True)
        rpk, rsc, rst = ref.center_hadamard_pack_2d_ref(xx, mu, None,
                                                        rotate=True)
        out["pack"] = (pk, rpk)
        out["pack_scales"] = (sc.astype(jnp.float32), rsc.astype(jnp.float32))
        out["pack_s_t"] = (st, rst)
        return out

    try:
        for name, (got, want) in both(x, bits).items():
            assert jnp.array_equal(got, want), name
    finally:
        jax.clear_caches()
