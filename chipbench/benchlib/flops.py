"""Operations and bytes, computed from shapes: the model's useful FLOPs per
token and each kernel's least time on a chip.

Everything here is arithmetic on sizes; nothing reads the program.
"""
from __future__ import annotations

import dataclasses

from benchlib.peaks import Peaks


@dataclasses.dataclass(frozen=True)
class DenseDims:
    """The sizes of a dense decoder that the FLOP count needs."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab


def prefill_flops(dims: DenseDims, n: int, cached: int = 0) -> float:
    """A prompt of ``n`` tokens whose first ``cached`` are served from a
    cache, causal: every matmul once per new token, the attention scores
    and values over ``i`` keys for token ``i``, and the LM head once (only
    the last position's logits are needed)."""
    new = n - cached
    mm = 2.0 * dims.layers * dims.layer_matmul_params * new
    keys = n * (n + 1) / 2 - cached * (cached + 1) / 2
    attn = 4.0 * dims.layers * dims.heads * dims.head_dim * keys
    return mm + attn + 2.0 * dims.head_params


def decode_flops(dims: DenseDims, ctx: int) -> float:
    """One decoded token that attends over ``ctx`` keys (itself included)."""
    mm = 2.0 * (dims.layers * dims.layer_matmul_params + dims.head_params)
    return mm + 4.0 * dims.layers * dims.heads * dims.head_dim * ctx


def least_time(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The larger of compute time at peak and memory time at peak."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)


def gemm_cost(m: int, n: int, k: int, in_bytes: int = 2, out_bytes: int = 2,
              bias: bool = False):
    """(flops, bytes) of C[m, n] = A[m, k] @ B[k, n] (+ bias[n])."""
    flops = 2.0 * m * n * k
    nbytes = (m * k + k * n) * in_bytes + m * n * out_bytes
    if bias:
        nbytes += n * in_bytes
    return flops, float(nbytes)


def flash_cost(bh: int, sq: int, skv: int, d: int, causal: bool,
               in_bytes: int = 2):
    """(flops, bytes) of attention over ``bh`` heads of ``sq`` queries and
    ``skv`` keys of width ``d``; causal counts, for query ``i`` of the last
    ``sq`` positions, the ``skv - sq + i + 1`` keys it may see."""
    if causal:
        keys = sq * (skv - sq) + sq * (sq + 1) / 2
    else:
        keys = float(sq) * skv
    flops = 4.0 * bh * d * keys
    nbytes = bh * d * (2 * sq + 2 * skv) * in_bytes
    return flops, float(nbytes)
