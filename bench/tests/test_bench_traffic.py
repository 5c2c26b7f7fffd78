"""The training token stream is a pure function of (seed, step)."""
import numpy as np
import pytest

from bench import registry

JOB = {"batch": 2, "seq": 64, "stream": {"chain_alpha": 6.0, "n_states": 64}}


def _make(seed):
    return registry.generator("token_stream").make(JOB, seed, 151936)


@pytest.mark.parametrize("seed", [0, 2147483659, 2**40 + 3])
def test_same_seed_same_batches(seed):
    a, b = _make(seed), _make(seed)
    for step in (0, 1, 17):
        np.testing.assert_array_equal(a.batch(step), b.batch(step))
    x = a.batch(0)
    assert x.shape == (2, 64) and x.dtype == np.int32
    assert x.min() >= 0 and x.max() < 151936


def test_rows_differ_across_steps_seeds_and_rows():
    a = _make(5)
    b0, b1 = a.batch(0), a.batch(1)
    assert not np.array_equal(b0, b1)
    assert not np.array_equal(b0[0], b0[1])
    assert not np.array_equal(b0, _make(6).batch(0))

