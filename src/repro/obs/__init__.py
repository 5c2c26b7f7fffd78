"""Observability: quant-health probes, telemetry hub, runtime tracing.

Three layers (README "Observability"):

* :mod:`repro.obs.probes` — in-graph quant-health statistics (the paper's
  §2 diagnostics as per-GeMM-site / per-comm-bucket jit outputs).
* :mod:`repro.obs.telemetry` — host-side counters/gauges/histogram series
  with a JSONL sink (stdlib-only; safe to import from ``repro.core``).
* :mod:`repro.obs.trace` — Chrome-trace (Perfetto JSON) span emitter for
  engine phases and train steps, whose spans also land in a
  ``jax.profiler`` trace.
* :mod:`repro.obs.scopes` — the ``jax.named_scope`` vocabulary that names
  the parts of the train step in its HLO ``op_name``s.

The probe path is **statically gated**: a ``QuantCtx`` without a probe tape
traces the exact pre-probe graph (DESIGN.md — the existing bitwise goldens
are the proof), so telemetry-off runs are byte-identical to a build without
this package.
"""
from .telemetry import JsonlSink, Telemetry, global_hub
from .trace import ChromeTracer

__all__ = [
    "ChromeTracer",
    "JsonlSink",
    "Telemetry",
    "global_hub",
]
