"""Fault tolerance: injected failures -> restart from checkpoint reproduces
the no-fault trajectory (deterministic data + checkpointed state)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import reduced
from repro.data.pipeline import DataConfig, TokenStream
from repro.models.model import Model
from repro.optim import adamw
from repro.train import checkpoint
from repro.train.fault import FaultInjector, SupervisorConfig, run_supervised
from repro.train.trainer import TrainConfig, init_train_state, make_train_step


def _setup(tmp_path, quant="bf16", total=12, ckpt_every=4):
    cfg = reduced("qwen3-0.6b", num_layers=2, d_model=32, d_ff=64,
                  vocab_size=64, num_heads=2, num_kv_heads=1, head_dim=16)
    model = Model(cfg)
    tcfg = TrainConfig(
        quant_mode=quant,
        optimizer=adamw.OptimizerConfig(peak_lr=1e-3, warmup_steps=2,
                                        total_steps=total),
    )
    step_fn = jax.jit(make_train_step(model, tcfg))
    data = TokenStream(DataConfig(seed=5, batch_size=4, seq_len=32,
                                  vocab_size=64))
    sup = SupervisorConfig(total_steps=total, ckpt_every=ckpt_every,
                           ckpt_dir=str(tmp_path), keep=5)

    def init_fn():
        return init_train_state(model, tcfg, jax.random.key(0))

    def batch_fn(step):
        return data.batch(step)

    return step_fn, init_fn, batch_fn, sup


def test_recovery_reproduces_no_fault_run(tmp_path):
    key = jax.random.key(1)
    # clean run
    step_fn, init_fn, batch_fn, sup = _setup(tmp_path / "clean")
    clean = run_supervised(step_fn, init_fn, batch_fn, key, sup)
    assert clean["restarts"] == 0 and len(clean["losses"]) == 12

    # faulty run: two injected failures
    step_fn2, init_fn2, batch_fn2, sup2 = _setup(tmp_path / "faulty")
    inj = FaultInjector(fail_at=(5, 9))
    faulty = run_supervised(step_fn2, init_fn2, batch_fn2, key, sup2,
                            injector=inj)
    assert faulty["restarts"] == 2
    # the FINAL states must agree exactly: restart replayed identical steps
    for a, b in zip(jax.tree.leaves(clean["final_params"]),
                    jax.tree.leaves(faulty["final_params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    # loss histories agree on the overlapping (replayed) steps
    np.testing.assert_allclose(clean["losses"][-3:], faulty["losses"][-3:],
                               rtol=1e-5, atol=1e-5)


def test_restart_budget_enforced(tmp_path):
    step_fn, init_fn, batch_fn, _ = _setup(tmp_path / "a")
    sup = SupervisorConfig(total_steps=12, ckpt_every=4,
                           ckpt_dir=str(tmp_path / "a"), max_restarts=1)
    inj = FaultInjector(fail_at=(2,))

    # one fault is fine...
    run_supervised(step_fn, init_fn, batch_fn, jax.random.key(1), sup,
                   injector=inj)

    class AlwaysFail:
        def check(self, step):
            raise RuntimeError("dead host")

    # fresh ckpt dir: a permanently-failing job must exhaust its budget
    sup_b = SupervisorConfig(total_steps=12, ckpt_every=4,
                             ckpt_dir=str(tmp_path / "b"), max_restarts=1)
    with pytest.raises(RuntimeError, match="restart budget"):
        run_supervised(step_fn, init_fn, batch_fn, jax.random.key(1), sup_b,
                       injector=AlwaysFail())


def test_resume_from_existing_checkpoint(tmp_path):
    """A fresh supervisor picks up where the previous one stopped."""
    step_fn, init_fn, batch_fn, sup = _setup(tmp_path, total=8, ckpt_every=4)
    run_supervised(step_fn, init_fn, batch_fn, jax.random.key(1), sup)
    assert checkpoint.latest_step(str(tmp_path)) == 8
    # second supervisor with a longer horizon resumes at 8, no restarts
    sup2 = SupervisorConfig(total_steps=10, ckpt_every=4, ckpt_dir=str(tmp_path))
    out = run_supervised(step_fn, init_fn, batch_fn, jax.random.key(1), sup2)
    assert out["restarts"] == 0
    assert len(out["losses"]) == 2  # only steps 8..9 executed


def test_step_failure_before_first_step_reraises_once(tmp_path):
    """A step function that cannot run at all (a compile refusal on the
    first call) surfaces at once: re-raised, not restarted."""
    _, init_fn, batch_fn, sup = _setup(tmp_path)
    calls = []

    def refused(params, opt_state, batch, key):
        calls.append(1)
        raise ValueError("compiler refused the program")

    with pytest.raises(ValueError, match="compiler refused"):
        run_supervised(refused, init_fn, batch_fn, jax.random.key(1), sup)
    assert len(calls) == 1
    assert checkpoint.latest_step(str(tmp_path)) is None


def test_step_failure_after_first_step_restarts(tmp_path):
    """Once the step function has completed in this process, its own
    exceptions go through the restart path like injected faults."""
    step_fn, init_fn, batch_fn, sup = _setup(tmp_path, total=8, ckpt_every=4)
    failed = []

    def flaky(params, opt_state, batch, key):
        if len(failed) == 0 and int(opt_state["step"]) == 5:
            failed.append(1)
            raise RuntimeError("transient device error")
        return step_fn(params, opt_state, batch, key)

    out = run_supervised(flaky, init_fn, batch_fn, jax.random.key(1), sup)
    assert out["restarts"] == 1 and out["steps"] == 8
