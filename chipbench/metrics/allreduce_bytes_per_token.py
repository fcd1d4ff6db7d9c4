"""Bytes each device's explicit all-reduces moved per output token: the
``allreduce_bytes`` args of the program's ``serve.admit`` spans (one
admission run each) and ``serve.chunk.wait`` spans (one decode-chunk run
each, known once its tokens are on the host) in the traced window, over
the ``tokens`` args of its ``serve.emit`` spans.  The args carry the
engine's ``allreduce_bytes`` counter run by run (docs/PROFILING.md); a
program without the counter leaves them out, and the reader returns
None.  The spans are those of the capture, which the benchmark cuts at
about 1,000,000 events (PERF.md): on four chips that is the head of the
window, where admissions weigh more than in the window as a whole."""
_RUNS = ("serve.admit", "serve.chunk.wait")


def read(run):
    t = run.trace
    if t is None:
        return None
    moved = tokens = 0
    seen = False
    for a in t.annotations:
        if a.name in _RUNS and "allreduce_bytes" in a.args:
            moved += int(a.args["allreduce_bytes"])
            seen = True
        elif a.name == "serve.emit" and "tokens" in a.args:
            tokens += int(a.args["tokens"])
    return moved / tokens if seen and tokens else None
