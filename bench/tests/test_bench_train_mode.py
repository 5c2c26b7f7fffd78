"""The train mode's whole run on the CPU at a reduced size: the program's
step, the window, the reference and the comparison; with the step broken
underneath, and with the control in the program's place, ``correct``
comes out false. (``bench/run.py`` itself refuses the CPU; these tests
call the mode directly.)"""
import time

import jax
import pytest

from bench import calibrate, compare, reference, registry
from bench.modes import train

SEED = 2147483659
# Readings at this size (bf16, seed above): loss 2e-5..7e-5, grad1 1.4e-3,
# update 1.8e-3; the control reads grad1 1.9e-2 and update 6.6e-3.
LIMITS = {"loss1": 3e-4, "loss2": 3e-4, "loss3": 3e-4, "grad1": 6e-3,
          "update": 4e-3}


def tiny_cell(recipe, qkv_bias=False, qk_norm=True, limits=LIMITS):
    model = dict(registry.load_json("configs", "qwen3-0.6b"),
                 num_hidden_layers=2, hidden_size=64, intermediate_size=160,
                 vocab_size=256, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16)
    model["architecture"] = dict(model["architecture"], qkv_bias=qkv_bias,
                                 qk_norm=qk_norm)
    job = dict(registry.load_json("traffic", f"train-{recipe}"), batch=2,
               seq=32, trace_seconds=0.5)
    return {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1,
            "model": model, "job": job, "limits": dict(limits)}


def _run(cell, trace=False, seed=SEED):
    return train.run(cell, seed, 0.5, trace, time.perf_counter(),
                     log=lambda s: None)


@pytest.mark.parametrize("recipe,bias,norm,limits", [
    ("bf16", False, True, LIMITS),
    ("bf16", True, False, dict.fromkeys(LIMITS, 0.5)),
    ("averis", True, False, dict.fromkeys(LIMITS, 0.5))])
def test_sound_run_is_correct(recipe, bias, norm, limits):
    res = _run(tiny_cell(recipe, bias, norm, limits))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 3
    assert res["compiles_in_window"] == 0
    assert res["compiled_memory"]["total"] > 0
    tps, unit = res["end_to_end"]["train_tokens_per_s"]
    assert tps > 0 and unit == "tokens/s"
    assert res["end_to_end"]["setup_s"][0] > 0
    assert set(res["checks"]) == set(LIMITS)


def _unchanged(real):
    def step(p, o, batch, key):
        _, _, met = real(p, o, batch, key)
        return p, o, met
    return step


def _half_batch(real):
    def step(p, o, batch, key):
        n = batch["tokens"].shape[0] // 2
        return real(p, o, {"tokens": batch["tokens"][:n]}, key)
    return step


def _loss_altered(real):
    def step(p, o, batch, key):
        p, o, met = real(p, o, batch, key)
        return p, o, dict(met, loss=met["loss"] * 1.001)
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _loss_altered])
def test_broken_step_is_not_correct(monkeypatch, fault):
    make = train.make_train_step
    monkeypatch.setattr(train, "make_train_step",
                        lambda *a, **k: fault(make(*a, **k)))
    res = _run(tiny_cell("bf16"))
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    cell = tiny_cell("bf16")
    model, job = cell["model"], cell["job"]
    batches = [train.stream(model, job, SEED).batch(k)
               for k in range(job["check_steps"])]
    ref = reference.train_record(model, job, SEED, batches)
    ctl = reference.train_record(model, job, SEED, batches, lowp=True)
    ok, checks = compare.judge(compare.readings(ctl, ref), cell["limits"])
    assert not ok, checks


@pytest.mark.parametrize("recipe", ["bf16", "averis"])
def test_lowered_param_dtype_is_not_correct(recipe):
    """The control: the program with its parameters one precision step
    below the configuration's, through its own ``param_dtype`` option."""
    cell = tiny_cell(recipe, limits={"update": 0.5})
    cell["model"] = calibrate.lowered(cell["model"])
    assert cell["model"]["run_dtypes"]["param_dtype"] == "bfloat16"
    res = _run(cell)
    assert not res["correct"], res["checks"]


def test_calibration_readings():
    rows, summary, _ = calibrate.calibrate(tiny_cell("bf16"), [SEED], 1,
                                        log=lambda s: None)
    assert {r["kind"] for r in rows} == {
        "program", "control", "control_compute", "half_batch", "split_sums",
        "unchanged"}
    assert summary["unchanged"]["grad1"] == 1.0
    # parameters one step below float32 lose Adam's first small updates
    assert summary["control"]["update"] > 0.5
    assert summary["program"]["gnorm1"] < summary["half_batch"]["gnorm1"]


def test_depth_sweep_reads_each_depth():
    rows = calibrate.depth_sweep(tiny_cell("averis"), [1, 2], [SEED],
                                 log=lambda s: None)
    assert [r["depth"] for r in rows] == [1, 2]
    for r in rows:
        assert 0 <= r["gap_program"] < 0.05 and 0 <= r["gap_split_sums"] < 0.05


def test_traced_run_feeds_the_per_layer_readers():
    res = _run(tiny_cell("bf16"), trace=True)
    ctx = res["context"]
    assert ctx["trace"]["window_s"] > 0
    values = {m.NAME: m.read(ctx, {"bf16_flops_per_s": 197e12})
              for m in registry.metrics()}
    # no TPU plane in a CPU trace: the device reader finds nothing
    assert values["idle_share.train"] is None
    assert 0 < values["mfu.train"] < 100
    jax.clear_caches()
