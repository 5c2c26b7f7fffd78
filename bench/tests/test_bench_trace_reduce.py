"""bench/trace_reduce.py on a hand-made trace with known answers."""
import pytest

from bench import trace_reduce as tr

DEV, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def hlo(name, opcode, kind=""):
    tail = f", kind={kind}, calls=%fused_computation" if kind else ""
    return f"%{name} = bf16[8,128]{{1,0}} {opcode}(bf16[8,128]{{1,0}} %p){tail}"


def toy():
    ops, mods = tr.OPS_LINE, tr.MODULES_LINE
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "bench.step", 100, 50),
        ev(HOST, "python", "bench.loss_fetch", 600, 300),
        ev(DEV, mods, "jit_train_step(12)", 150, 450),
        ev(DEV, ops, hlo("while.9", "while"), 150, 300),     # spans its body
        ev(DEV, ops, hlo("fusion.1", "fusion", "kOutput"), 150, 200),
        ev(DEV, ops, hlo("fusion.2", "fusion", "kLoop"), 350, 100),
        ev(DEV, ops, hlo("custom-call.3", "custom-call"), 500, 100),
        ev(DEV, ops, hlo("fusion.2", "fusion", "kLoop"), 900, 200),  # cut
        ev(DEV1, ops, hlo("fusion.1", "fusion", "kOutput"), 0, 500),
        ev("/device:TPU:0 SparseCore", ops, "ignored", 0, 1000),
    ]


def test_busy_idle_modules_kernels_and_gaps():
    r = tr.reduce(toy())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    assert r["devices"] == 2
    # TPU:0 busy [150, 450) + [500, 600) + [900, 1000); TPU:1 [0, 500)
    assert r["busy_s"] == pytest.approx((500 + 500) / 2 * ns)
    assert r["op_s"] == pytest.approx((200 + 100 + 100 + 100 + 500) / 2 * ns)
    assert r["categories"] == {
        "fusion kOutput": pytest.approx(700 / 2 * ns),
        "fusion kLoop": pytest.approx(200 / 2 * ns),
        "custom-call": pytest.approx(100 / 2 * ns)}    # every time is averaged over the devices that ran an operation
    assert r["modules"] == {"jit_train_step": pytest.approx(450 / 2 * ns)}
    assert r["kernels"] == {"custom-call.3": pytest.approx(100 / 2 * ns)}
    assert r["device_ops"][0] == ["fusion.1",
                                  pytest.approx(700 / 2 * ns)]
    gaps = dict(r["idle_gaps"])
    # TPU:0 gaps [0,150) and [450,500) (under no span) and [600,900)
    # (loss fetch); TPU:1 gap [500,1000) (mid 750: loss fetch)
    assert gaps["bench.loss_fetch"] == pytest.approx(800 / 2 * ns)
    assert gaps["outside any bench span"] == pytest.approx(200 / 2 * ns)


def test_no_device_ops_reads_no_busy_time():
    r = tr.reduce([ev(HOST, "python", "bench.window", 0, 10)])
    assert r["devices"] == 0 and r["busy_s"] == 0.0
    assert r["device_ops"] == []


def test_names_and_categories_from_hlo_text():
    text = ("%fusion.12 = (bf16[2,1024]{1,0}, f32[8]{0}) fusion(bf16[2,1024]"
            "{1,0} %p.1), kind=kLoop, calls=%fused_computation.3")
    assert tr.op_name(text) == "fusion.12"
    assert tr.op_category(text) == "fusion kLoop"
    assert tr.op_category(hlo("copy.4", "copy")) == "copy"
    assert tr.op_category(hlo("while.1", "while")) == "while"


def test_recorded_chip_excerpt():
    """An excerpt of a trace recorded on one TPU v5 lite: the first 14.5 ms
    of the qwen3-0.6b.train-averis window, where the device waits for the
    first batch and then starts the train step."""
    import json
    from pathlib import Path

    import numpy as np

    data = json.loads((Path(__file__).parent / "data" /
                       "trace_excerpt.json").read_text())
    w0, w1 = data["window_ns"]
    events = data["events"]
    r = tr.reduce(events, window=(w0, w1))
    mask = np.zeros(int(w1 - w0), bool)
    for e in events:
        if e["line"] == tr.OPS_LINE:
            a = int(max(e["start_ns"], w0) - w0)
            mask[a:int(min(e["start_ns"] + e["dur_ns"], w1) - w0)] = True
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(mask.sum() * 1e-9)
    assert r["busy_s"] == pytest.approx(0.006389932)
    step = next(e for e in events if e["name"].startswith("jit_train_step"))
    assert r["modules"]["jit_train_step"] == pytest.approx(
        (w1 - step["start_ns"]) * 1e-9)
    assert r["device_ops"][0] == ["fusion.963", pytest.approx(0.000726083)]
    # the device idles while the host makes the first batch
    assert dict(r["idle_gaps"]) == {"bench.batch": pytest.approx(0.006975641),
                                    "bench.step": pytest.approx(0.001096779)}
    assert r["categories"]["copy"] == pytest.approx(0.002503915)
