"""95th percentile, over the requests with two tokens or more inside the
window, of (last token's time - first token's time) / (tokens - 1) in the
window (ms).  Tokens arrive in bursts of a decode chunk, so single gaps
mean little."""
import numpy as np


def read(run):
    per = []
    for r in run.records:
        ts = [t for t in r.times if run.t0 <= t < run.t1]
        if len(ts) >= 2:
            per.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    if not per:
        return None
    return float(np.percentile(np.asarray(per, np.float64), 95))
