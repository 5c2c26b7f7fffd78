"""The plain reference against the program's own GeMM on one linear layer:
with the key that the program's schedule gives that layer, the reference
draws the same stochastic-rounding bits, so both backward GeMMs agree to
the summation order; with another key they differ by the rounding noise."""
import jax
import jax.numpy as jnp
import pytest

from bench import reference
from repro.core.qgemm import AVERIS, qgemm

SEED = 2 ** 33 + 5


def _operands():
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    x = (jax.random.normal(k1, (256, 128)) + 0.3).astype(jnp.bfloat16)
    w = jax.random.normal(k2, (128, 192)) * 0.05
    g = (jax.random.normal(k3, (256, 192)) * 1e-3 + 2e-4).astype(jnp.bfloat16)
    return x, w, g


def _program_grads(x, w, g, key):
    _, vjp = jax.vjp(lambda x, w: qgemm(x, w, AVERIS, key), x, w)
    return vjp(g)


def _reference_grads(x, w, g, key):
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda x, w: reference.linear(
            "averis", x, w, key, "bfloat16", "bfloat16"),
            x.astype(jnp.float32), w)
        return vjp(g.astype(jnp.float32))


def _rel(a, b):
    a = a.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("layer,block,site", [(0, "attn", "wq"),
                                              (3, "mlp", "w_down")])
def test_reference_draws_the_programs_rounding_bits(layer, block, site):
    x, w, g = _operands()
    step = jax.random.fold_in(reference.seed_key(SEED), 1)
    # the program's schedule, as its model applies it
    tags = {"attn": 1, "mlp": 2}
    sites = {"wq": 1, "w_down": 22}
    prog_key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(step, layer), tags[block]), sites[site])
    ref_key = reference.gemm_key(jax.random.fold_in(step, layer), block, site)
    dxp, dwp = _program_grads(x, w, g, prog_key)
    dxr, dwr = _reference_grads(x, w, g, ref_key)
    assert _rel(dxp, dxr) < 1e-3 and _rel(dwp, dwr) < 1e-3
    dxo, dwo = _reference_grads(x, w, g, jax.random.fold_in(ref_key, 7))
    assert _rel(dxo, dxr) > 0.05 and _rel(dwo, dwr) > 0.05


def test_split_sums_is_the_same_arithmetic():
    x, w, _ = _operands()
    a = x.astype(jnp.float32)
    assert float(jnp.max(jnp.abs(reference._dot_split(a, w)
                                 - reference._dot(a, w)))) < 1e-5
