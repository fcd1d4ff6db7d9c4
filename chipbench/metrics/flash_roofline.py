"""The flash-attention kernel (``_flash_kernel``,
kernels/flash_attention.py) against its roofline on device 0: causal
FLOPs and operand bytes from each call's shapes, summed least time over
summed device time.  The kernel computes the masked half of every causal
block too, so its share stays under about half."""
from benchlib.kernels import flash_least, roofline_share


def read(run):
    if run.trace is None:
        return None
    return roofline_share(run.trace.ops, "_flash_kernel", flash_least,
                          run.peaks)
