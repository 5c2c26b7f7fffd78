"""Reduces a profiler trace to device busy time, per-module, per-category
and per-kernel device time, the top device operations and the idle gaps by
what the host was doing.

Events are plain dicts (``plane``, ``line``, ``name``, ``start_ns``,
``dur_ns``): :func:`load` reads them from the ``.xplane.pb`` that
``jax.profiler`` writes, and a test keeps a recorded excerpt in the same
form. Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, each named by its HLO instruction text
(``%fusion.12 = bf16[...] fusion(...), kind=kLoop, ...``); a ``while``
loop's event spans the operations of its body, so loops count towards busy
time but not towards any operation's own time. XLA programs are on the
``XLA Modules`` line. The TPU trace carries no operation category, so the
category here is the HLO opcode and fusion kind. The window is the host
span named ``bench.window`` (the benchmark's own
``jax.profiler.TraceAnnotation``); host spans named ``bench.*`` say what
the host was doing in a device gap.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
TOP = 10


def load(path: str) -> List[Dict]:
    """Events of one ``.xplane.pb`` (device ops, modules and bench spans)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        if not dev and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(HOST_PREFIX):
                    continue
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                out.append(rec)
    return out


def find_xplane(trace_dir: str) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


_HLO = re.compile(r"%(\S+) = .*? ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``fusion.12`` from ``%fusion.12 = bf16[...] fusion(...)``."""
    m = _HLO.match(text)
    return m.group(1) if m else text[:80]


def op_category(text: str) -> str:
    """The HLO opcode, with the fusion kind: ``fusion kLoop``."""
    m = _HLO.match(text)
    if not m:
        return ""
    k = _KIND.search(text)
    return m.group(2) + (f" {k.group(1)}" if k else "")


def reduce(events: List[Dict], window: Optional[Tuple[float, float]] = None
           ) -> Dict:
    """All times in seconds; ``busy_s`` is averaged over the devices."""
    ops = [e for e in events if DEVICE_PLANE.match(e["plane"])
           and e["line"] == OPS_LINE]
    host = [e for e in events if e["plane"].startswith("/host:")
            and e["name"].startswith(HOST_PREFIX)]
    if window is None:
        spans = [e for e in host if e["name"] == WINDOW]
        if spans:
            window = (spans[0]["start_ns"],
                      spans[0]["start_ns"] + spans[0]["dur_ns"])
        elif ops:
            window = (min(e["start_ns"] for e in ops),
                      max(e["start_ns"] + e["dur_ns"] for e in ops))
        else:
            window = (0.0, 0.0)
    w0, w1 = window

    def clip(e):
        s, t = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        return (s, t) if t > s else None

    by_dev: Dict[str, List] = defaultdict(list)
    for e in ops:
        c = clip(e)
        if c:
            by_dev[e["plane"]].append((c, e))
    busy, gaps = {}, []
    for plane, items in by_dev.items():
        merged = _union(c for c, _ in items)
        busy[plane] = sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(by_dev), 1)
    per_op, per_cat, per_kernel = (defaultdict(float) for _ in range(3))
    for items in by_dev.values():
        for (s, t), e in items:
            cat = op_category(e["name"])
            if cat.split(" ")[0] in CONTAINERS:
                continue
            per_op[op_name(e["name"])] += t - s
            per_cat[cat] += t - s
            if cat == "custom-call" or cat == "fusion kCustom":
                per_kernel[op_name(e["name"])] += t - s
    per_module: Dict[str, float] = defaultdict(float)
    for e in events:
        if DEVICE_PLANE.match(e["plane"]) and e["line"] == MODULES_LINE:
            c = clip(e)
            if c:
                per_module[_module_name(e["name"])] += c[1] - c[0]
    spans = sorted((e for e in host if e["name"] != WINDOW),
                   key=lambda e: e["dur_ns"])
    idle: Dict[str, float] = defaultdict(float)
    for s, t in gaps:
        mid = (s + t) / 2
        owner = next((e["name"] for e in spans
                      if e["start_ns"] <= mid <= e["start_ns"] + e["dur_ns"]),
                     "outside any bench span")
        idle[owner] += t - s
    ns = 1e-9
    busy_ops = sum(per_op.values())

    def top(d):
        return [[k, v * ns / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy.values()) * ns / n_dev,
        "devices": len(by_dev),
        "op_s": busy_ops * ns / n_dev,
        "categories": {k: v * ns / n_dev for k, v in per_cat.items()},
        "modules": {k: v * ns / n_dev for k, v in per_module.items()},
        "kernels": {k: v * ns / n_dev for k, v in per_kernel.items()},
        "device_ops": top(per_op),
        "idle_gaps": top(idle),
    }
