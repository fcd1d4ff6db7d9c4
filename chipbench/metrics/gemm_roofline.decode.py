"""The GEMM kernel (kernels/gemm.py) against its roofline inside the
decode-chunk program (``jit_chunk_fn``) on device 0: over every call in
the traced window, the sum of least times (the larger of 2mnk at the bf16
peak and operand plus result bytes at the HBM peak, on the shapes the
compiled program passes, padding included) over the sum of device times."""
from benchlib.kernels import gemm_least, roofline_share


def read(run):
    if run.trace is None:
        return None
    return roofline_share(run.trace.ops_in("jit_chunk_fn"), "_gemm_kernel",
                          gemm_least, run.peaks)
