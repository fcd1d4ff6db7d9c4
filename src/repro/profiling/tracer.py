"""Trace capture: a scoped ``jax.profiler`` session + code markers.

``trace(dir)`` wraps ``jax.profiler.start_trace``/``stop_trace`` with the
directory management the post-processor expects; when profiling is off
(``enabled=False`` or no directory) it is a STRICT no-op — no directories
created, no XLA/env state touched, no profiler hooks installed — so it can
stay permanently in the serve/train launchers at zero cost.

``annotate(name)`` is the marker the engine and trainer thread through
their hot paths: a host span (``jax.profiler.TraceAnnotation``) on the
profiler's timeline, on the same clock as the device ops, which is how a
breakdown attributes wall time to e.g. ``serve.chunk``.  It is a host span
only: every call site wraps a call to an already-jitted program, where a
``jax.named_scope`` would reach no HLO.  Code that wants an HLO scope
inside a traced function writes ``jax.named_scope`` there.  A span costs
one context manager when no trace is active, so annotations are
unconditional; their args (ints, shown as strings in the capture) are
built only while :func:`recording` is true.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from typing import Iterator, List, Optional

import jax


@dataclasses.dataclass
class TraceSession:
    """Handle yielded by :func:`trace`: where the capture landed (if on)."""
    dir: Optional[str]
    enabled: bool

    def trace_files(self) -> List[str]:
        """The captured ``*.trace.json.gz`` files (newest capture first).

        ``jax.profiler`` writes ``<dir>/plugins/profile/<timestamp>/`` per
        capture; an engine process may trace more than once into one dir.
        """
        if not self.dir:
            return []
        pattern = os.path.join(self.dir, "plugins", "profile", "*",
                               "*.trace.json.gz")
        return sorted(glob.glob(pattern), key=os.path.getmtime, reverse=True)

    def events(self) -> List[dict]:
        """Parsed Chrome-trace events of the newest capture ([] when off)."""
        from repro.profiling.breakdown import load_trace_events
        if not self.enabled:
            return []
        return load_trace_events(self.dir)


@contextlib.contextmanager
def trace(out_dir: Optional[str] = None, *,
          enabled: bool = True) -> Iterator[TraceSession]:
    """Capture a ``jax.profiler`` trace into ``out_dir`` for the block.

    Disabled (``enabled=False`` or falsy ``out_dir``) it yields an inert
    session and touches nothing.  Enabled, it creates the directory, starts
    the profiler, and guarantees ``stop_trace`` on exit (also on exceptions,
    so a crashed wave still leaves a parseable capture behind).
    """
    if not enabled or not out_dir:
        yield TraceSession(dir=None, enabled=False)
        return
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        yield TraceSession(dir=out_dir, enabled=True)
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` in the trace timeline, with ``args``.

    Used as ``with annotate("serve.chunk") as span:``; a span whose args
    are known only at its end sets them with ``span.set_metadata(...)``.
    """
    return jax.profiler.TraceAnnotation(name, **args)


def recording() -> bool:
    """True while a capture records host spans: the test a call site makes
    before it builds a span's args."""
    return jax.profiler.TraceAnnotation.is_enabled()
