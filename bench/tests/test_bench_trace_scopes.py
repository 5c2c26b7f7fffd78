"""bench/trace_scopes.py on hand-made HLO text and traces."""
import pytest

from bench import trace_reduce as tr
from bench import trace_scopes as ts

DEV, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
STEP = "jit(train_step)/jvp()/while/body/closed_call"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def op(name, opcode="fusion"):
    return f"%{name} = bf16[8,128]{{1,0}} {opcode}(bf16[8,128]{{1,0}} %p)"


def test_op_scopes_from_hlo_text():
    text = "\n".join([
        "HloModule jit_train_step, entry_computation_layout={...}",
        "%fused_computation.3 (param_0: f32[8]) -> f32[8] {",
        '  %param_0 = f32[8]{0} parameter(0)',
        '  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(train_step)/adamw.update/mul" '
        'stack_frame_id=3}',
        "}",
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {",
        '  %fusion.12 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%fused_computation.3, metadata={op_name='
        '"jit(train_step)/adamw.update/mul"}',
        "  %copy.4 = f32[8]{0} copy(%fusion.12)",
        '  ROOT tuple.2 = (f32[8]{0}) tuple(%copy.4), metadata={op_name='
        '"jit(train_step)/tuple"}',
        "}",
    ])
    got = ts.op_scopes(text)
    assert got["fusion.12"] == "jit(train_step)/adamw.update/mul"
    assert got["multiply.1"] == "jit(train_step)/adamw.update/mul"
    assert got["copy.4"] == "" and got["param_0"] == ""
    assert got["tuple.2"] == "jit(train_step)/tuple"
    assert "HloModule" not in got and "main.9" not in got


@pytest.mark.parametrize("op_name,category,path", [
    (f"{STEP}/mlp_up/qgemm.dx/quantize/reduce_max", "qdq",
     "mlp_up/qgemm.dx/quantize"),
    (f"{STEP}/checkpoint/rematted_computation/attn_qkv/qgemm.fwd/center/sub",
     "qdq", "remat/attn_qkv/qgemm.fwd/center"),
    ("jit(train_step)/qgemm.weights/hadamard/dot_general", "qdq",
     "qgemm.weights/hadamard"),
    ("jit(train_step)/transpose(jvp(lm_head))/qgemm.dw/matmul/dot_general",
     "matmul", "lm_head/qgemm.dw/matmul"),
    (f"{STEP}/attn_o/qgemm.fwd/mean_row/dot_general;reshape;attn.core/x",
     "matmul", "attn_o/qgemm.fwd/mean_row"),
    (f"{STEP}/checkpoint/attn.core/bqkgh,btkh->bqkgt/dot_general",
     "attention", "attn.core"),
    ("jit(train_step)/adamw.clip/reduce_sum", "optimizer", "adamw.clip"),
    ("jit(train_step)/jvp(lm_head)/qgemm.fwd/convert_element_type", "other",
     "lm_head/qgemm.fwd"),
    ("jit(train_step)/jvp(loss)/reduce_max", "other", "loss"),
    (f"{STEP}/mul", "unscoped", ""),
    ("", "unscoped", ""),
])
def test_category_and_path_of_op_name(op_name, category, path):
    assert ts.category(op_name) == category
    assert ts.path(op_name) == path


def toy():
    ops, mods = tr.OPS_LINE, tr.MODULES_LINE
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(DEV, mods, "jit_train_step(12)", 100, 800),
        ev(DEV, mods, "jit__threefry_fold_in(7)", 920, 20),
        ev(DEV, ops, op("while.9", "while"), 100, 300),       # container
        ev(DEV, ops, op("fusion.1"), 100, 200),                # matmul
        ev(DEV, ops, op("fusion.2"), 300, 100),                # qdq
        ev(DEV, ops, op("copy.3", "copy"), 400, 50),           # unscoped
        ev(DEV, ops, op("fusion.4"), 450, 150),                # optimizer
        ev(DEV, ops, op("fusion.5"), 850, 100),    # cut at the module's end
        ev(DEV, ops, op("fusion.6"), 920, 20),     # another module
        ev(DEV1, mods, "jit_train_step(12)", 0, 500),
        ev(DEV1, ops, op("fusion.1"), 0, 400),
        ev(DEV1, ops, op("fusion.7"), 400, 100),   # not in the compiled step
    ]


SCOPES = {
    "fusion.1": f"{STEP}/mlp_up/qgemm.fwd/matmul/dot_general",
    "fusion.2": f"{STEP}/mlp_up/qgemm.fwd/quantize/round",
    "copy.3": "",
    "fusion.4": "jit(train_step)/adamw.update/mul",
    "fusion.5": "jit(train_step)/jvp(loss)/exp",
    "fusion.6": "jit(train_step)/adamw.update/mul",
    "while.9": "",
}


def test_reduce_counts_the_step_module_by_category():
    r = ts.reduce(toy(), SCOPES)
    ns = 1e-9 / 2                           # averaged over the two devices
    # TPU:0 200 + 100 + 50 + 150 + 50 (fusion.5 cut at 900); TPU:1 400 + 100
    assert r["op_s"] == pytest.approx(1050 * ns)
    assert sum(r["categories"].values()) == pytest.approx(r["op_s"])
    assert r["categories"] == {
        "qdq": pytest.approx(100 * ns), "matmul": pytest.approx(600 * ns),
        "attention": 0.0, "optimizer": pytest.approx(150 * ns),
        "other": pytest.approx(50 * ns), "unscoped": pytest.approx(150 * ns)}
    assert r["matched_s"] == pytest.approx(950 * ns)
    assert r["paths"]["mlp_up/qgemm.fwd/matmul"] == [
        pytest.approx(600 * ns), 2]
    assert r["paths"]["(unscoped)"] == [pytest.approx(150 * ns), 2]
    assert r["paths"]["loss"] == [pytest.approx(50 * ns), 1]


def test_reduce_clips_to_the_window_and_knows_no_scope_without_a_map():
    r = ts.reduce(toy(), {}, window=(0, 350))
    ns = 1e-9 / 2
    # TPU:0 fusion.1 [100, 300) and fusion.2 [300, 350); TPU:1 [0, 350)
    assert r["op_s"] == pytest.approx(600 * ns)
    assert r["matched_s"] == 0.0
    assert r["categories"]["unscoped"] == pytest.approx(r["op_s"])
    assert ts.reduce([ev(HOST, "python", "bench.window", 0, 10)],
                     SCOPES)["op_s"] == 0.0


def test_table_lists_costliest_paths_first():
    lines = ts.table(ts.reduce(toy(), SCOPES)).splitlines()
    assert lines[0].endswith("mlp_up/qgemm.fwd/matmul")
    assert lines[0].lstrip().startswith("57.14%")


@pytest.mark.parametrize("cell,top,categories", [
    ("qwen1.5-0.5b.train-bf16", "remat/attn.core",
     {"matmul": 0.0014334605, "attention": 0.0021352825,
      "other": 0.000143099, "unscoped": 0.000287376}),
    # the middle of the averis step falls inside one 14 ms operation: the
    # tied head's dx product
    ("qwen3-0.6b.train-averis", "lm_head/qgemm.dx/matmul",
     {"matmul": 0.004}),
])
def test_recorded_chip_excerpt_by_scope(cell, top, categories):
    """4 ms of a train step recorded on one TPU v5 lite with the compiled
    step's op names: every operation is matched, and the categories are
    the clipped durations counted independently."""
    import json
    from collections import defaultdict
    from pathlib import Path

    data = json.loads((Path(__file__).parent / "data" /
                       "trace_excerpt_scoped.json").read_text())
    ex = data["excerpts"][cell]
    w0, w1 = ex["window_ns"]
    r = ts.reduce(ex["events"], ex["op_scopes"], window=(w0, w1))
    want = defaultdict(float)
    for e in ex["events"]:
        if e["line"] != tr.OPS_LINE or tr.op_category(
                e["name"]).split(" ")[0] in tr.CONTAINERS:
            continue
        d = min(e["start_ns"] + e["dur_ns"], w1) - max(e["start_ns"], w0)
        name = ex["op_scopes"][tr.op_name(e["name"])]
        want[ts.category(name)] += max(d, 0.0) * 1e-9
    assert r["matched_s"] == pytest.approx(r["op_s"])
    assert r["op_s"] == pytest.approx(sum(want.values()))
    assert {k: v for k, v in r["categories"].items() if v} == pytest.approx(
        {k: v for k, v in want.items() if v})
    assert {k: v for k, v in r["categories"].items() if v} == pytest.approx(
        categories)
    assert max(r["paths"], key=lambda p: r["paths"][p][0]) == top
