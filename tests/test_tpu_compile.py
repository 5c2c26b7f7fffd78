"""Every Pallas kernel of the TPU path compiles for a v5e chip.

Interpret mode (the rest of the suite) accepts block shapes and ops that
Mosaic, the TPU's kernel compiler, refuses. These tests compile each
kernel with ``interpret=False`` at qwen3-0.6b's real widths against a
described ``v5e:2x2`` topology — no chip attached, nothing runs — and
check that the compiled HLO holds the Mosaic kernel (``tpu_custom_call``).

The topology is described only inside the module fixture (never while a
module is imported): only one process at a time may load the TPU library,
so under several test workers only the worker running this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import wire_fold
from repro.kernels.fused import (center_hadamard_pack_2d,
                                 center_hadamard_qdq_2d, fused_amax_2d)
from repro.kernels.mean_split import column_mean_2d
from repro.kernels.paged_attention import paged_attend_gqa
from repro.parallel import collectives as coll

D_MODEL, D_FF, VOCAB = 1024, 3072, 151936
TOKENS = 2048
N_KV, HEAD_DIM, N_HEADS, PAGE, SLOTS, PAGES = 8, 128, 16, 64, 4, 8
# 4-chip DP, and the 32-way DP of the multi-pod mesh (pod x data)
SHARDS, BUCKET = (4, 32), 1 << 22


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on one described v5e chip; the persistent
    compilation cache is off meanwhile (its entries cannot be read back
    without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


def _fused_cases():
    f32 = jnp.float32

    def xm(l, m):
        return [((l, m), f32), ((1, m), f32)]

    return {
        "column_mean_2d-d_ff": (
            lambda x: column_mean_2d(x, interpret=False),
            [((TOKENS, D_FF), f32)]),
        "column_mean_2d-vocab": (
            lambda x: column_mean_2d(x, interpret=False),
            [((TOKENS, VOCAB), f32)]),
        "fused_amax_2d-d_ff": (
            lambda x, mu: fused_amax_2d(x, mu, rotate=True, interpret=False),
            xm(TOKENS, D_FF)),
        "fused_amax_2d-vocab": (
            lambda x, mu: fused_amax_2d(x, mu, rotate=True, interpret=False),
            xm(TOKENS, VOCAB)),
        "qdq_lanes-d_model": (
            lambda x, mu: center_hadamard_qdq_2d(x, mu, None, None,
                                                 rotate=True,
                                                 interpret=False),
            xm(TOKENS, D_MODEL)),
        "qdq_lanes_sr-d_ff": (
            lambda x, mu, b: center_hadamard_qdq_2d(x, mu, None, b,
                                                    rotate=True,
                                                    interpret=False),
            xm(TOKENS, D_FF) + [((TOKENS, D_FF), jnp.uint32)]),
        "qdq_sublanes-d_ff": (
            lambda x, mu: center_hadamard_qdq_2d(x, mu, None, None,
                                                 rotate=True,
                                                 interpret=False,
                                                 block_axis=0),
            xm(TOKENS, D_FF)),
        "qdq_lanes-vocab": (
            lambda x, mu: center_hadamard_qdq_2d(x, mu, None, None,
                                                 rotate=True,
                                                 interpret=False),
            xm(TOKENS, VOCAB)),
        "pack-d_model": (
            lambda x, mu: center_hadamard_pack_2d(x, mu, None, None,
                                                  rotate=True,
                                                  interpret=False),
            xm(TOKENS, D_MODEL)),
        "pack-d_ff": (
            lambda x, mu: center_hadamard_pack_2d(x, mu, None, None,
                                                  rotate=True,
                                                  interpret=False),
            xm(TOKENS, D_FF)),
    }


def _paged_case(centered):
    def fn(q, codes, scales, pamax, tail, pos, *mean):
        return paged_attend_gqa(q, codes, scales, pamax,
                                mean[0] if centered else None, tail, pos,
                                page_size=PAGE, backend="pallas",
                                interpret=False)
    shapes = [((SLOTS, 1, N_HEADS, HEAD_DIM), jnp.bfloat16),
              ((SLOTS, PAGES, PAGE, 2, N_KV, HEAD_DIM // 2), jnp.uint8),
              ((SLOTS, PAGES, PAGE, 2, N_KV, HEAD_DIM // 16),
               jnp.float8_e4m3fn),
              ((SLOTS, PAGES, 2), jnp.float32),
              ((SLOTS, PAGE, 2, N_KV, HEAD_DIM), jnp.bfloat16),
              ((SLOTS,), jnp.int32)]
    if centered:
        shapes.append(((SLOTS, PAGES, 2, N_KV, HEAD_DIM), jnp.bfloat16))
    return fn, shapes


CASES = {
    **_fused_cases(),
    "paged_attend_gqa-fp4": _paged_case(False),
    "paged_attend_gqa-fp4_centered": _paged_case(True),
    **{f"fold_packets-S{n}": (
        lambda c, s, a, m, n=n: wire_fold.fold_packets(
            c, s, a, m, n, backend="pallas", interpret=False),
        [((n, BUCKET // 2), jnp.uint8), ((n, BUCKET // 16), jnp.uint8),
         ((n,), jnp.float32), ((n,), jnp.float32)]) for n in SHARDS},
    **{f"fold_shards_decoded-S{n}": (
        lambda x, n=n: coll._fold_shards_pallas(x, n, interpret=False),
        [((n, BUCKET), jnp.float32)]) for n in SHARDS},
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
