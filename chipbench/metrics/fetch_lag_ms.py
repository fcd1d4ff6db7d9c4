"""How long a finished decode chunk's result takes to reach the host (ms):
over the program's ``serve.chunk.wait`` spans in the traced window, the
median of the span's end minus the end of the last decode-chunk run
(``jit_chunk_fn``) on device 0 that ended before it, and after the
previous wait span.  The median, since under the profiler a wait now and
then returns 0.04-2 s late, one such wait in a window moving a mean by
tens of ms."""
import bisect
import statistics

from benchlib.trace import module_name


def read(run):
    t = run.trace
    if t is None:
        return None
    ends = sorted(m.ts + m.dur for m in t.modules
                  if module_name(m.name) == "jit_chunk_fn")
    waits = sorted(a.ts + a.dur for a in t.annotations
                   if a.name == "serve.chunk.wait")
    lags, prev = [], float("-inf")
    for end in waits:
        i = bisect.bisect_right(ends, end) - 1
        if i >= 0 and ends[i] > prev:
            lags.append(end - ends[i])
        prev = end
    return statistics.median(lags) * 1e-3 if lags else None
