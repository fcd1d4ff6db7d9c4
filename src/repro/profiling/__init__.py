"""Profiling subsystem: capture -> per-op-family breakdown -> PROFILE_*.json.

The paper tunes by *measuring* each architecture; this package is the
measurement half for the jax port.  Three layers:

* :mod:`repro.profiling.tracer` — ``trace(...)`` (a ``jax.profiler`` trace
  scoped to a context manager, strict no-op when disabled),
  ``annotate(...)`` (named host spans with optional args that the serve
  engine / trainer thread through their phases, on the trace timeline's
  clock) and ``recording()`` (whether a capture is taking them);
* :mod:`repro.profiling.breakdown` — a stdlib-only Chrome-trace
  post-processor classifying device time into op families (collective vs
  GEMM vs attention vs host transfer) and counting host syncs, emitting the
  versioned ``PROFILE_*.json`` schema CI validates;
* ``scripts/profile.py`` — the CLI rendering a breakdown next to the
  roofline model (where the time goes vs where it could go).
"""
from repro.profiling.breakdown import (FAMILIES, PROFILE_SCHEMA_VERSION,
                                       build_profile, classify_event_name,
                                       load_trace_events, summarize_events,
                                       validate_profile)
from repro.profiling.tracer import TraceSession, annotate, recording, trace

__all__ = [
    "trace", "annotate", "recording", "TraceSession",
    "load_trace_events", "summarize_events", "build_profile",
    "validate_profile", "classify_event_name",
    "FAMILIES", "PROFILE_SCHEMA_VERSION",
]
