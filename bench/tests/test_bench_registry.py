"""The harness finds its parts by name, and refuses to run without a TPU
or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import registry

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _write(path: Path, text: str) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_added_config_cell_mode_and_metric_are_found_by_name(tmp_path):
    _write(tmp_path / "configs" / "toy-1b.json",
           json.dumps({"name": "toy-1b", "hidden_size": 8}))
    _write(tmp_path / "traffic" / "toy-mix.json",
           json.dumps({"mode": "toy", "rate": 2.5}))
    _write(tmp_path / "cells" / "toy-1b.toy-mix.json",
           json.dumps({"config": "toy-1b", "traffic": "toy-mix",
                       "limits": {"gap": 0.1}}))
    _write(tmp_path / "modes" / "toy.py", "def run(cell, *a, **k):\n"
           "    return cell['job']['rate']\n")
    _write(tmp_path / "metrics" / "toy_share.toy.py",
           "NAME = 'toy_share.toy'\nUNIT = '%'\n"
           "def read(ctx, peaks):\n    return ctx.get('toy')\n")
    cell = registry.cell("toy-1b.toy-mix", root=tmp_path)
    assert cell["model"]["hidden_size"] == 8
    assert cell["job"]["rate"] == 2.5 and cell["limits"] == {"gap": 0.1}
    assert registry.mode(cell["job"]["mode"], root=tmp_path).run(cell) == 2.5
    [metric] = registry.metrics(root=tmp_path)
    assert metric.NAME == "toy_share.toy"
    assert metric.read({"toy": 3.0}, {}) == 3.0
    assert metric.read({}, {}) is None
    with pytest.raises(FileNotFoundError):
        registry.cell("toy-1b.other", root=tmp_path)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_has_its_files(cell):
    c = registry.cell(cell)
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert (c["config"], c["traffic"]) == (w["config"], w["traffic"])
    assert c["chips"] == w["chips"]
    conf = next(x for x in SPEC["configs"] if x["name"] == w["config"])
    assert ROOT / conf["file"] == registry.HERE / "configs" / f"{conf['name']}.json"
    assert c["model"]["source"] == conf["source"]
    assert c["model"]["reduced"] == conf["reduced"]
    assert (registry.HERE / "modes" / f"{c['job']['mode']}.py").is_file()
    numbers = {"loss1", "loss2", "loss3", "gnorm1", "gnorm2", "gnorm3",
               "grad1", "grad1_median", "update", "update_median",
               "update_all"}
    assert c["limits"] and set(c["limits"]) <= numbers


def test_every_per_layer_metric_has_its_reader():
    readers = {m.NAME: m for m in registry.metrics()}
    for m in SPEC["per_layer"]:
        r = readers[m["name"]]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
    assert set(readers) == {m["name"] for m in SPEC["per_layer"]}


def _run(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    res = _run(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
