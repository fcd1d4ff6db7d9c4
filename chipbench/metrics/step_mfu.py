"""Useful model FLOPs of all prefill and decode work delivered in the
window, over the traced window x chips x the chip's bf16 peak."""
from benchlib.window import window_flops


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    pre, dec = window_flops(run)
    return 100.0 * (pre + dec) / (t.window_s * run.chips
                                  * run.peaks.bf16_flops)
