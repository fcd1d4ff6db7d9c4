"""Useful FLOPs of the tokens decoded in the window over the summed device
span of the decode-chunk program (``jit_chunk_fn``) x the bf16 peak."""
from benchlib.window import window_flops


def read(run):
    t = run.trace
    span = t.module_seconds("jit_chunk_fn") if t is not None else 0.0
    if span <= 0:
        return None
    _, dec = window_flops(run)
    return 100.0 * dec / (span * run.peaks.bf16_flops)
