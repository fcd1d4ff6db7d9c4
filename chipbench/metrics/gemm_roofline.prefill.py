"""The GEMM kernel (kernels/gemm.py) against its roofline inside the
admission program (``jit_admit_fn``) on device 0, as
``gemm_roofline.decode`` computes it."""
from benchlib.kernels import gemm_least, roofline_share


def read(run):
    if run.trace is None:
        return None
    return roofline_share(run.trace.ops_in("jit_admit_fn"), "_gemm_kernel",
                          gemm_least, run.peaks)
