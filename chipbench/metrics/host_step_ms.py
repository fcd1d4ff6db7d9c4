"""Host time per decode chunk (ms): the summed duration of the program's
engine-phase spans in the traced window, all but ``serve.chunk.wait`` (the
wait for the device) and the zero-length ``serve.request`` events, over
the number of ``serve.chunk.wait`` spans there.

A dispatch (``serve.admit``, ``serve.chunk``) can hold the host until a
program already running on device 0 ends: the ``jit_chunk_fn`` call after
an admission returns only once the admission has finished.  That part of
a dispatch is the device's time, not the host's, and is left out: the
overlap of the dispatch span with each program run that began before it."""
PHASES = ("serve.ingest", "serve.admit.plan", "serve.prefix_restore",
          "serve.admit", "serve.prefix_insert", "serve.chunk.plan",
          "serve.chunk", "serve.emit")
DISPATCHES = ("serve.admit", "serve.chunk")


def _waited(span, runs):
    """Microseconds of ``span`` spent while an earlier program ran."""
    end = span.ts + span.dur
    return sum(max(0.0, min(end, r_end) - span.ts)
               for r_start, r_end in runs if r_start < span.ts)


def read(run):
    t = run.trace
    if t is None:
        return None
    chunks = sum(1 for a in t.annotations if a.name == "serve.chunk.wait")
    if not chunks:
        return None
    runs = [(m.ts, m.ts + m.dur) for m in t.modules]
    host_us = sum(a.dur - (_waited(a, runs) if a.name in DISPATCHES else 0)
                  for a in t.annotations if a.name in PHASES)
    return host_us / chunks * 1e-3
