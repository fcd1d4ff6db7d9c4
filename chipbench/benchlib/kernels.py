"""The program's Mosaic kernels in a trace, and each call's least time on
the chip, from the operand shapes that the compiled program's HLO gives
each traced op (its ``long_name``).

A Mosaic kernel is an op whose HLO calls ``tpu_custom_call``; the trace
does not carry the kernel's name, so a call is told by its operands.  The
GEMM kernel takes matrices A[m, k] and B[k, n] (and a bias row [1, n]);
the flash kernel takes a vector of row starts (s32) and q, k, v of equal
[batch x heads, sequence, head dim].  These are the program's only two
Mosaic kernels (kernels/gemm.py, kernels/flash_attention.py).
"""
from __future__ import annotations

import math
import re
from typing import List, Optional, Tuple

from benchlib.flops import flash_cost, gemm_cost, least_time

_SHAPE_RE = re.compile(r"\b(bf16|f16|f32|s32|u32|s8|u8|pred)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}

Shape = Tuple[str, Tuple[int, ...]]


def hlo_shapes(text: str) -> Tuple[Optional[Shape], List[Shape]]:
    """(result, operands) of one HLO instruction's text."""
    text = text.split("custom_call_target")[0]
    found = [(t, tuple(int(d) for d in dims.split(",") if d))
             for t, dims in _SHAPE_RE.findall(text)]
    if not found:
        return None, []
    return found[0], found[1:]


def _nbytes(s: Shape) -> int:
    return _BYTES[s[0]] * math.prod(s[1])


def gemm_least(text: str, peaks) -> Optional[float]:
    """C[m, n] = A[m, k] @ B[k, n] (+ a bias row): seconds at the roofline."""
    out, ops = hlo_shapes(text)
    mats = [s for s in ops if len(s[1]) == 2]
    if out is None or len(out[1]) != 2 or len(mats) < 2:
        return None
    (m, k), (k2, n) = mats[0][1], mats[1][1]
    if k != k2 or out[1] != (m, n):
        return None
    flops, _ = gemm_cost(m, n, k)
    nbytes = sum(_nbytes(s) for s in ops) + _nbytes(out)
    return least_time(flops, nbytes, peaks)


def flash_least(text: str, peaks) -> Optional[float]:
    """Causal attention of q[bh, sq, d] over k, v[bh, skv, d]."""
    out, ops = hlo_shapes(text)
    mats = [s for s in ops if len(s[1]) == 3]
    if out is None or len(mats) < 3:
        return None
    (bh, sq, d), (_, skv, _) = mats[0][1], mats[1][1]
    flops, _ = flash_cost(bh, sq, skv, d, causal=True)
    nbytes = sum(_nbytes(s) for s in ops) + _nbytes(out)
    return least_time(flops, nbytes, peaks)


def kernel_kind(op) -> Optional[str]:
    """``_gemm_kernel``, ``_flash_kernel`` or None for one traced op."""
    text = str(op.args.get("long_name", ""))
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    out, ops = hlo_shapes(text)
    dims = [s[1] for s in ops]
    if (len(dims) >= 2 and all(len(d) == 2 for d in dims)
            and dims[0][1] == dims[1][0]):
        return "_gemm_kernel"
    if (len(dims) == 4 and ops[0][0] == "s32" and len(dims[0]) == 1
            and len(dims[1]) == 3 and dims[1] == dims[2] == dims[3]):
        return "_flash_kernel"
    return None


def roofline_share(ops, kernel: str, least_fn, peaks) -> Optional[float]:
    """Sum of least times over sum of device times of one kernel's calls,
    in percent; None where the trace holds no call it can read."""
    least = spent = 0.0
    for op in ops:
        if kernel_kind(op) != kernel:
            continue
        t = least_fn(str(op.args.get("long_name", "")), peaks)
        if t is None or op.dur <= 0:
            continue
        least += t
        spent += op.dur * 1e-6
    return 100.0 * least / spent if spent > 0 else None
