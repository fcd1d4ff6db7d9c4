"""Pure-jnp oracles for every kernel in this package.

These are the ground truth the Pallas kernels are validated against
(``tests/test_gemm_kernel.py`` sweeps shapes/dtypes and asserts allclose).
They intentionally share the *semantics* of the paper's GEMM (Eq. 1):

    C = alpha * A @ B + beta * C      (+ optional bias / activation epilogue)

accumulating in float32 regardless of input dtype, mirroring MXU behaviour.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

ACTIVATIONS = {
    None: lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def apply_epilogue(out_f32, bias=None, activation: Optional[str] = None):
    if bias is not None:
        out_f32 = out_f32 + bias.astype(jnp.float32)
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    return ACTIVATIONS[activation](out_f32)


def gemm_ref(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    alpha: float = 1.0,
    beta: float = 0.0,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    out_dtype=None,
) -> jax.Array:
    """Reference GEMM: ``alpha * A @ B + beta * C`` with f32 accumulation."""
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"gemm_ref expects 2-D operands, got {a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    acc = alpha * acc
    if c is not None:
        acc = acc + beta * c.astype(jnp.float32)
    acc = apply_epilogue(acc, bias=bias, activation=activation)
    return acc.astype(out_dtype)


def batched_gemm_ref(a, b, **kw):
    """Oracle for the batched wrapper: contracts the last dim of ``a`` with
    the second-to-last of ``b`` over shared leading batch dims."""
    fn = lambda x, y: gemm_ref(x, y, **kw)
    for _ in range(a.ndim - 2):
        fn = jax.vmap(fn)
    return fn(a, b)


def gemm_flops(m: int, k: int, n: int, with_beta: bool = False) -> int:
    """Paper Eq. 2 generalized to rectangular operands: 2MKN (+ epilogue)."""
    flops = 2 * m * k * n
    if with_beta:
        flops += 3 * m * n  # alpha scale + beta scale + add, as in 3N^2
    return flops


def attention_ref(q, k, v, *, causal: bool = True, scale=None) -> jax.Array:
    """Naive softmax attention oracle.  q: (B, S, H, d); k, v: (B, T, KV, d)."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = d ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bthd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
