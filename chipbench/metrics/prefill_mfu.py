"""Useful FLOPs of the prompts prefilled in the window over the summed
device span of the admission program (``jit_admit_fn``) x the bf16 peak."""
from benchlib.window import window_flops


def read(run):
    t = run.trace
    span = t.module_seconds("jit_admit_fn") if t is not None else 0.0
    if span <= 0:
        return None
    pre, _ = window_flops(run)
    return 100.0 * pre / (span * run.peaks.bf16_flops)
