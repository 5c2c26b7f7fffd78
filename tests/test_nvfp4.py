"""NVFP4 quantizer: exactness vs ml_dtypes, SR unbiasedness, properties."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.formats import BLOCK_SIZE, E2M1_MAX, TENSOR_SCALE_DENOM
from repro.core.nvfp4 import (
    nvfp4_qdq,
    nvfp4_quant_error,
    quantize_block_scales,
    round_e2m1_rn,
    round_e2m1_sr,
)

SET = dict(deadline=None, max_examples=30)


def test_rn_matches_ml_dtypes_cast():
    ml_dtypes = pytest.importorskip("ml_dtypes")
    fp4 = getattr(ml_dtypes, "float4_e2m1fn", None)
    if fp4 is None:  # pre-FP4 ml_dtypes
        pytest.skip("ml_dtypes lacks float4_e2m1fn")
    v = np.linspace(-8, 8, 8001).astype(np.float32)
    ours = np.sign(v) * np.asarray(round_e2m1_rn(jnp.abs(jnp.asarray(v))))
    ref = v.astype(fp4).astype(np.float32)
    np.testing.assert_array_equal(ours, ref)


def test_rn_grid_fixed_points():
    grid = np.array([0, 0.5, 1, 1.5, 2, 3, 4, 6], np.float32)
    out = np.asarray(round_e2m1_rn(jnp.asarray(grid)))
    np.testing.assert_array_equal(out, grid)


def test_sr_hits_neighbors_only():
    a = jnp.full((10000,), 2.3, jnp.float32)
    u = jax.random.uniform(jax.random.key(0), a.shape)
    out = np.asarray(round_e2m1_sr(a, u))
    assert set(np.unique(out)) <= {2.0, 3.0}


def test_sr_unbiased():
    # E[SR(a)] == a for a mid-interval value
    for val, lo, hi in [(2.3, 2.0, 3.0), (4.7, 4.0, 6.0), (0.6, 0.5, 1.0)]:
        a = jnp.full((200000,), val, jnp.float32)
        u = jax.random.uniform(jax.random.key(1), a.shape)
        out = np.asarray(round_e2m1_sr(a, u))
        assert abs(out.mean() - val) < 3 * (hi - lo) / np.sqrt(len(out)), val


def test_qdq_zero_preserved():
    x = jnp.zeros((32, 64))
    assert float(jnp.abs(nvfp4_qdq(x)).max()) == 0.0


def test_qdq_bounded_by_tensor_amax():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32) * 100)
    q = nvfp4_qdq(x)
    # elements never exceed block_amax rounded up by the e4m3 scale step (~2x
    # worst case at tiny scales; in practice <= amax * (1 + 2^-3)).
    assert float(jnp.abs(q).max()) <= float(jnp.abs(x).max()) * 1.25


@settings(**SET)
@given(
    rows=st.integers(1, 33),
    cols=st.integers(1, 70),
    scale=st.floats(1e-3, 1e3),
    seed=st.integers(0, 2**31 - 1),
)
def test_qdq_relative_error_bound(rows, cols, scale, seed):
    """Blockwise FP4 error per element is bounded by ~ block_amax / 12
    (half the largest grid spacing, plus e4m3 scale rounding slack)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32) * scale)
    q = np.asarray(nvfp4_qdq(x, -1), np.float32)
    xn = np.asarray(x, np.float32)
    pad = (-cols) % BLOCK_SIZE
    xp = np.pad(xn, ((0, 0), (0, pad)))
    qp = np.pad(q, ((0, 0), (0, pad)))
    blocks_x = xp.reshape(rows, -1, BLOCK_SIZE)
    blocks_q = qp.reshape(rows, -1, BLOCK_SIZE)
    amax = np.abs(blocks_x).max(axis=-1, keepdims=True)
    err = np.abs(blocks_q - blocks_x)
    # spacing at the top of the grid is 2 (4->6) => half-spacing amax/6;
    # the e4m3 scale quantization adds <= 2^-3 relative slack.
    bound = amax / 6.0 * 1.2 + 1e-6
    assert (err <= bound + 1e-7 * amax).all()


@settings(**SET)
@given(seed=st.integers(0, 2**31 - 1))
def test_qdq_idempotent(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(16, 48)).astype(np.float32))
    q1 = nvfp4_qdq(x, -1)
    q2 = nvfp4_qdq(q1, -1)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=0, atol=1e-6)


@settings(**SET)
@given(seed=st.integers(0, 2**31 - 1), axis=st.sampled_from([0, 1, -1]))
def test_qdq_sign_symmetry(seed, axis):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(24, 40)).astype(np.float32))
    q_pos = np.asarray(nvfp4_qdq(x, axis))
    q_neg = np.asarray(nvfp4_qdq(-x, axis))
    np.testing.assert_allclose(q_pos, -q_neg, atol=1e-7)


@settings(**SET)
@given(seed=st.integers(0, 2**31 - 1), k=st.floats(0.1, 64.0))
def test_qdq_scale_equivariant(seed, k):
    """QDQ(k*x) == k*QDQ(x) up to e4m3 scale requantization for pow2 k."""
    k = float(2 ** round(np.log2(k)))  # powers of two are exactly equivariant
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    a = np.asarray(nvfp4_qdq(x * k, -1))
    b = np.asarray(nvfp4_qdq(x, -1)) * k
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_sr_gemm_unbiased_vs_rn():
    """SR over many keys averages to the true value; RN has a fixed bias."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    acc = np.zeros_like(np.asarray(x))
    n = 200
    for i in range(n):
        acc += np.asarray(nvfp4_qdq(x, -1, sr=True, key=jax.random.key(i)))
    mean_err = np.abs(acc / n - np.asarray(x)).mean()
    rn_err = np.abs(np.asarray(nvfp4_qdq(x, -1)) - np.asarray(x)).mean()
    assert mean_err < rn_err * 0.5  # SR averages toward the truth


def test_error_metric_sane():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    e = float(nvfp4_quant_error(x))
    assert 0.02 < e < 0.25


def _moved_axis_qdq(x, axis=-1, *, sr=False, key=None, block_size=BLOCK_SIZE,
                    tensor_amax=None, compute_dtype=jnp.float32):
    """Frozen copy of the earlier ``nvfp4_qdq``: block axis moved last and
    split into a 16-wide minor dimension, full-size ``|x|``. The two-pass
    rewrite must equal it bit for bit."""
    orig_dtype = x.dtype
    xf = x.astype(compute_dtype)
    xf = jnp.moveaxis(xf, axis, -1)
    moved_shape = xf.shape
    n = moved_shape[-1]
    pad = (-n) % block_size
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, pad)])
    xb = xf.reshape(xf.shape[:-1] + (-1, block_size))
    absx = jnp.abs(xb)
    if tensor_amax is None:
        tensor_amax = jnp.max(absx)
    s_t = jnp.maximum(tensor_amax.astype(jnp.float32) / TENSOR_SCALE_DENOM,
                      1e-30)
    block_amax = jnp.max(absx, axis=-1, keepdims=True)
    s_b = quantize_block_scales(block_amax.astype(jnp.float32), s_t).astype(
        jnp.float32)
    scale = (s_b * s_t).astype(compute_dtype)
    eps = jnp.asarray(1e-30, jnp.float32).astype(compute_dtype)
    a = jnp.where(scale > 0, absx / jnp.maximum(scale, eps), 0)
    if sr:
        u = jax.random.uniform(key, xb.shape, dtype=jnp.float32).astype(
            compute_dtype)
        q = round_e2m1_sr(a, u)
    else:
        q = round_e2m1_rn(a)
    deq = jnp.sign(xb) * q * scale
    deq = deq.reshape(moved_shape[:-1] + (n + pad,))
    if pad:
        deq = deq[..., :n]
    return jnp.moveaxis(deq, -1, axis).astype(orig_dtype)


_BF16, _F32 = jnp.bfloat16, jnp.float32
# (shape, axis, sr, dtype, tensor_amax, compute_dtype, vmap over axis 0)
_PIN_CASES = [
    *[((64, 96), ax, sr, dt, None, _F32, False)
      for ax in (0, 1, -1) for sr in (False, True) for dt in (_BF16, _F32)],
    *[((40, 56), ax, sr, _BF16, None, _F32, False)          # ragged: 40, 56
      for ax in (0, -1) for sr in (False, True)],
    *[((3, 48, 40), ax, sr, _BF16, None, _F32, False)       # rank 3
      for ax in (0, 1, -1) for sr in (False, True)],
    *[((3, 48, 40), ax, False, dt, None, _F32, True)        # vmapped layers
      for ax in (0, -1) for dt in (_BF16, _F32)],
    *[((64, 96), ax, sr, _BF16, 7.5, _F32, False)           # given amax
      for ax in (0, -1) for sr in (False, True)],
    *[((40, 56), ax, sr, dt, None, _BF16, False)            # bf16 chain
      for ax in (0, -1) for sr in (False, True) for dt in (_BF16, _F32)],
    ((48, 40), -1, True, _F32, 3.0, _BF16, False),
]


@pytest.mark.parametrize(
    "shape,axis,sr,dtype,amax,compute,vmapped", _PIN_CASES,
    ids=lambda v: jnp.dtype(v).name if isinstance(v, type) else str(v))
def test_two_pass_bitwise_equals_moved_axis_form(shape, axis, sr, dtype, amax,
                                                 compute, vmapped):
    x = (jax.random.normal(jax.random.key(11), shape) * 3).astype(dtype)
    kw = dict(sr=sr, key=jax.random.key(5) if sr else None,
              compute_dtype=compute)
    if amax is not None:
        kw["tensor_amax"] = jnp.float32(amax)

    def run(fn):
        f = lambda a: fn(a, axis, **kw)
        return np.asarray(jax.jit(jax.vmap(f) if vmapped else f)(x))

    got, want = run(nvfp4_qdq), run(_moved_axis_qdq)
    assert got.dtype == want.dtype and got.shape == want.shape == shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
