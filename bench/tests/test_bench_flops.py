"""Operation and parameter counts of bench/flops.py."""
import jax
import pytest

from bench import flops, registry


@pytest.mark.parametrize("name", ["qwen3-0.6b", "qwen1.5-0.5b"])
def test_param_count_matches_the_program(name):
    from repro.configs import get_config
    from repro.models.model import Model

    model = registry.load_json("configs", name)
    shapes = jax.eval_shape(Model(get_config(name)).init, jax.random.key(0))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops.param_counts(model)["total"] == total


@pytest.mark.parametrize("name,matmul,gflop", [
    ("qwen3-0.6b", 595_984_384, 3.928571904),
    ("qwen1.5-0.5b", 463_863_808, 2.934325248)])
def test_train_flops_per_token(name, matmul, gflop):
    model = registry.load_json("configs", name)
    assert flops.param_counts(model)["matmul"] == matmul
    # 6 x matmul params + causal attention over 1024 positions
    assert flops.train_flops_per_token(model, 1024) == pytest.approx(
        gflop * 1e9, rel=1e-12)
