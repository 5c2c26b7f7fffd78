"""Device time of the train step by program scope.

The program names the parts of its train step with ``jax.named_scope``
(``src/repro/obs/scopes.py``): the GeMM site's role, the GeMM
(``qgemm.fwd|dx|dw``, ``qgemm.weights``), the recipe's stages, the GeMM's
product terms, attention, the loss and AdamW. A scope reaches the compiled
program only as the ``op_name`` metadata of its HLO instructions, and the
profiler trace names each device operation by its instruction. So the
reduction takes two inputs: the trace's events (``trace_reduce.load``) and
:func:`op_scopes` of the compiled step's ``as_text()``, the same executable
the trace ran.

A fusion carries the ``op_name`` of its root: a dequantize that XLA fuses
into a GeMM's output fusion counts as the GeMM's. Copies and other
instructions that XLA adds carry no ``op_name`` and read as ``unscoped``,
as do the program's parts that no scope names (norms, RoPE, residual adds).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce as tr

STEP_MODULE = "jit_train_step"

# the program's scope vocabulary and the category each scope stands for;
# a scope with no category (a GeMM site's role, ``qgemm.*``, ``loss``)
# counts as ``other`` where it is the innermost one
CATEGORY = {
    "center": "qdq", "hadamard": "qdq", "quantize": "qdq", "fused": "qdq",
    "matmul": "matmul", "mean_row": "matmul", "rank1": "matmul",
    "attn.core": "attention",
    "adamw.clip": "optimizer", "adamw.update": "optimizer",
}
SCOPES = set(CATEGORY) | {
    "qgemm.fwd", "qgemm.dx", "qgemm.dw", "qgemm.weights", "loss",
    "attn_qkv", "attn_o", "mlp_up", "mlp_down", "lm_head", "moe",
    "ssm_in", "ssm_out", "default"}
CATEGORIES = ("qdq", "matmul", "attention", "optimizer", "other",
              "unscoped")
# the forward pass that ``jax.checkpoint`` recomputes in the backward one
REMAT = "rematted_computation"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of an HLO
    module's text, ``""`` where it carries no ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def components(op_name: str) -> List[str]:
    """The scopes of an ``op_name``, outermost first, with JAX's transform
    wrappers taken off: ``transpose(jvp(lm_head))`` is ``lm_head``. Where
    XLA joined the names of several operations with ``;``, the first one's
    path is read."""
    out = []
    for part in op_name.split(";")[0].split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        out.append(part)
    return out


def category(op_name: str) -> str:
    """The category of the innermost scope that has one: ``qdq``,
    ``matmul``, ``attention`` or ``optimizer``; ``other`` under program
    scopes without one; ``unscoped`` under none."""
    comps = components(op_name)
    for c in reversed(comps):
        if c in CATEGORY:
            return CATEGORY[c]
    return "other" if any(c in SCOPES for c in comps) else "unscoped"


def path(op_name: str) -> str:
    """The program scopes of an ``op_name``, outermost first, joined by
    ``/``; ``remat/`` in front of a recomputed forward pass."""
    comps = components(op_name)
    scoped = [c for c in comps if c in SCOPES]
    if REMAT in comps:
        scoped.insert(0, "remat")
    return "/".join(scoped)


def _window(events: List[Dict], ops: List[Dict]) -> Tuple[float, float]:
    spans = [e for e in events if e["plane"].startswith("/host:")
             and e["name"] == tr.WINDOW]
    if spans:
        return spans[0]["start_ns"], spans[0]["start_ns"] + spans[0]["dur_ns"]
    if ops:
        return (min(e["start_ns"] for e in ops),
                max(e["start_ns"] + e["dur_ns"] for e in ops))
    return 0.0, 0.0


def reduce(events: List[Dict], scopes: Dict[str, str],
           window: Optional[Tuple[float, float]] = None,
           module: str = STEP_MODULE) -> Dict:
    """Device seconds by category and by scope path, averaged over the
    devices, of the operations that ran inside ``module`` in the window.

    ``op_s`` is their time (``while`` loops and other containers left
    out, as in ``trace_reduce``); the categories sum to it. ``matched_s``
    is the part whose instruction ``scopes`` knows. ``paths`` maps each
    scope path to its seconds and its number of events.
    """
    ops = [e for e in events if tr.DEVICE_PLANE.match(e["plane"])
           and e["line"] == tr.OPS_LINE]
    w0, w1 = window if window is not None else _window(events, ops)
    mods: Dict[str, List] = defaultdict(list)
    for e in events:
        if (tr.DEVICE_PLANE.match(e["plane"]) and e["line"] == tr.MODULES_LINE
                and tr._module_name(e["name"]) == module):
            mods[e["plane"]].append((e["start_ns"],
                                     e["start_ns"] + e["dur_ns"]))
    spans = {p: tr._union(ivs) for p, ivs in mods.items()}
    cats = dict.fromkeys(CATEGORIES, 0.0)
    paths: Dict[str, List] = defaultdict(lambda: [0.0, 0])
    total = matched = 0.0
    for e in ops:
        if tr.op_category(e["name"]).split(" ")[0] in tr.CONTAINERS:
            continue
        s, t = e["start_ns"], e["start_ns"] + e["dur_ns"]
        d = sum(max(0.0, min(t, b, w1) - max(s, a, w0))
                for a, b in spans.get(e["plane"], ()))
        if d <= 0:
            continue
        name = tr.op_name(e["name"])
        op_name = scopes.get(name, "")
        total += d
        matched += d if name in scopes else 0.0
        cats[category(op_name)] += d
        p = paths[path(op_name) or "(unscoped)"]
        p[0] += d
        p[1] += 1
    ns = 1e-9 / max(len(spans), 1)
    return {
        "op_s": total * ns,
        "matched_s": matched * ns,
        "categories": {k: v * ns for k, v in cats.items()},
        "paths": {k: [v[0] * ns, v[1]] for k, v in paths.items()},
    }


def table(red: Dict, top: int = 40) -> str:
    """The costliest scope paths as lines of text, with their share of
    ``op_s``."""
    rows = sorted(red["paths"].items(), key=lambda kv: -kv[1][0])[:top]
    op_s = red["op_s"] or 1.0
    return "\n".join(f"{100 * s / op_s:6.2f}% {1e3 * s:9.3f} ms {n:6d}  {p}"
                     for p, (s, n) in rows)
