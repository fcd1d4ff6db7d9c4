"""95th percentile, over every burst of tokens a request received in the
window, of the time since its previous burst per token in this one (ms).
Tokens arrive in bursts of up to one decode chunk; a burst that waited for
an admission prefill carries that stall."""
import numpy as np

from benchlib.window import bursts


def read(run):
    itl = bursts(run)
    if len(itl) < 20:
        return None
    return float(np.percentile(np.asarray(itl, np.float64), 95))
