"""Finds the benchmark's parts by name, each in a file of its own.

* ``cells/<cell>.json``: the configuration, the traffic and the limits of
  the output check.
* ``configs/<config>.json``: the model as it is run.
* ``traffic/<traffic>.json``: the job or the mix; its ``"mode"`` names the
  runner ``modes/<mode>.py`` and its ``"generator"`` the code in
  ``traffic/<generator>.py`` that makes the inputs.
* ``metrics/<metric>.py``: one per-layer metric each.

A new cell, configuration, mix, mode or metric is a new file.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent


def load_json(kind: str, name: str, root: Path = HERE) -> Dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(path: Path) -> ModuleType:
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules and getattr(sys.modules[name], "__file__",
                                       None) == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, root: Path = HERE) -> Dict:
    """A cell with its configuration and traffic filled in."""
    c = load_json("cells", name, root)
    return dict(c, name=name, model=load_json("configs", c["config"], root),
                job=load_json("traffic", c["traffic"], root))


def mode(name: str, root: Path = HERE) -> ModuleType:
    return load_module(root / "modes" / f"{name}.py")


def generator(name: str, root: Path = HERE) -> ModuleType:
    return load_module(root / "traffic" / f"{name}.py")


def metrics(root: Path = HERE) -> List[ModuleType]:
    """Every per-layer metric's reader, in name order."""
    return [load_module(p) for p in sorted((root / "metrics").glob("*.py"))]
