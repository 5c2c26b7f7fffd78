"""Chrome-trace (Perfetto JSON) span emitter.

Emits the Trace Event Format that ``chrome://tracing`` and
https://ui.perfetto.dev load directly: complete spans (``ph: "X"``) with
microsecond timestamps, plus instant events (``ph: "i"``) for point
occurrences like prefix-cache pool hits. Spans wrap *host-observed* phases —
callers bracket device work with ``jax.block_until_ready`` so async dispatch
cannot under-report durations (see ``serve/engine.py`` and the
``train.step`` span of ``launch/train.py``).

Every span and instant also enters a ``jax.profiler.TraceAnnotation`` of
the same name, so under a profiler session (``launch/train.py
--profile-dir``) the host spans land in the ``.xplane.pb`` on the device
trace's clock. What runs *inside* a jitted step is named there by the
step's ``jax.named_scope``s (``repro/obs/scopes.py``), which reach the
device operations as their HLO ``op_name``.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Set

import jax


class ChromeTracer:
    """Collects Trace Event Format events; ``save()`` writes the JSON file."""

    def __init__(self, process_name: str = "repro"):
        self.events: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter()
        self.events.append({
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": process_name},
        })

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, cat: str = "engine", tid: int = 0,
             **args: Any):
        """Complete-event span around a ``with`` block."""
        ts = self._now_us()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.events.append({
                "ph": "X", "name": name, "cat": cat, "ts": ts,
                "dur": self._now_us() - ts, "pid": 0, "tid": tid,
                "args": args,
            })

    def instant(self, name: str, cat: str = "engine", tid: int = 0,
                **args: Any) -> None:
        with jax.profiler.TraceAnnotation(name):
            pass
        self.events.append({
            "ph": "i", "s": "t", "name": name, "cat": cat,
            "ts": self._now_us(), "pid": 0, "tid": tid, "args": args,
        })

    def counter(self, name: str, values: Dict[str, float],
                cat: str = "engine") -> None:
        self.events.append({
            "ph": "C", "name": name, "cat": cat, "ts": self._now_us(),
            "pid": 0, "args": {k: float(v) for k, v in values.items()},
        })

    # ---------------------------------------------------------------- output
    def span_names(self) -> Set[str]:
        """Distinct span/instant names recorded (metadata excluded)."""
        return {e["name"] for e in self.events if e["ph"] in ("X", "i")}

    def to_json(self) -> Dict[str, Any]:
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return path
