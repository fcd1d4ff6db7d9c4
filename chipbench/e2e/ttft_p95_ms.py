"""95th percentile, over every request due in the window, of the time from
when it was due (not when it was submitted) to its first streamed token
(ms).  A request with no token by the window's end counts with the time it
has waited."""
import numpy as np


def read(run):
    waits = [((r.times[0] if r.times and r.times[0] < run.t1 else run.t1)
              - r.due) * 1e3
             for r in run.records if run.t0 <= r.due < run.t1]
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, np.float64), 95))
