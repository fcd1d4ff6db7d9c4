"""Jit'd wrappers around the single-source Pallas GEMM.

Responsibilities kept OUT of the kernel (so the kernel stays single-source):
  * padding arbitrary operand shapes up to block multiples,
  * backend execution choice (pallas-tpu / pallas-interpret / xla / ref),
  * batching over leading dims.

This is the layer where Alpaka's "back end" concept lives: the same logical
GEMM runs through whichever execution engine the registry selects — exactly
like the paper compiling one source with nvcc / icc / gcc / xlc.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.gemm import gemm_pallas

# Execution backends (paper Tab. 3 analogue).
BACKEND_PALLAS_TPU = "pallas-tpu"          # target hardware path
BACKEND_PALLAS_INTERPRET = "pallas-interpret"  # CPU validation of the kernel
BACKEND_XLA = "xla"                         # vendor-library analogue (cuBLAS/MKL)
BACKEND_REF = "ref"                         # pure-jnp oracle
BACKENDS = (BACKEND_PALLAS_TPU, BACKEND_PALLAS_INTERPRET, BACKEND_XLA, BACKEND_REF)


def _pad_to(x: jax.Array, multiples) -> jax.Array:
    pads = []
    needs = False
    for dim, mult in zip(x.shape, multiples):
        pad = (-dim) % mult
        pads.append((0, pad))
        needs = needs or pad
    return jnp.pad(x, pads) if needs else x


def gemm(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    config=None,            # core.tile_config.TileConfig | None
    backend: str = BACKEND_XLA,
    alpha: float = 1.0,
    beta: float = 0.0,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    out_dtype=None,
    bf16_partials: bool = False,
) -> jax.Array:
    """2-D GEMM with automatic padding to the tile grid.

    ``config`` carries the architecture-tuned block sizes; it is required for
    the pallas backends and ignored by xla/ref (which have no exposed tiles —
    the "vendor library" case of the paper).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == BACKEND_REF:
        return _ref.gemm_ref(a, b, c, alpha=alpha, beta=beta, bias=bias,
                             activation=activation, out_dtype=out_dtype)
    if backend == BACKEND_XLA:
        return _xla_gemm(a, b, c, alpha=alpha, beta=beta, bias=bias,
                         activation=activation, out_dtype=out_dtype,
                         bf16_partials=bf16_partials)

    if config is None:
        raise ValueError("pallas backends need a TileConfig (use core.registry)")
    return _pallas_gemm(a, b, c, bias, config,
                        backend == BACKEND_PALLAS_INTERPRET, alpha, beta,
                        activation, out_dtype)


def _pallas_forward(a, b, c, bias, config, interpret, alpha, beta,
                    activation, out_dtype):
    """Pad to the tile grid, run the kernel, slice the result back."""
    m, _ = a.shape
    _, n = b.shape
    bm, bk, bn = config.bm, config.bk, config.bn
    a_p = _pad_to(a, (bm, bk))
    b_p = _pad_to(b, (bk, bn))
    c_p = _pad_to(c, (bm, bn)) if c is not None else None
    bias_p = _pad_to(bias, (bn,)) if bias is not None else None
    out = gemm_pallas(
        a_p, b_p, c_p,
        bm=bm, bk=bk, bn=bn,
        alpha=alpha, beta=beta, bias=bias_p, activation=activation,
        out_dtype=out_dtype, interpret=interpret,
    )
    if out.shape != (m, n):
        out = out[:m, :n]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _pallas_gemm(a, b, c, bias, config, interpret, alpha, beta, activation,
                 out_dtype):
    return _pallas_forward(a, b, c, bias, config, interpret, alpha, beta,
                           activation, out_dtype)


def _pallas_gemm_fwd(a, b, c, bias, config, interpret, alpha, beta,
                     activation, out_dtype):
    out = _pallas_forward(a, b, c, bias, config, interpret, alpha, beta,
                          activation, out_dtype)
    return out, (a, b, c, bias)


def _pallas_gemm_bwd(config, interpret, alpha, beta, activation, out_dtype,
                     res, g):
    """Backward of ``act(alpha * A @ B + beta * C + bias)`` as two GEMMs
    through the same kernel: ``dA = alpha * g' @ B^T`` and
    ``dB = alpha * A^T @ g'``, where ``g'`` is ``g`` through the
    activation's derivative.  With an activation the pre-activation is
    recomputed by the kernel (a third GEMM) instead of being stored."""
    a, b, c, bias = res
    g = g.astype(jnp.float32)
    if activation is not None:
        z = _pallas_forward(a, b, c, bias, config, interpret, alpha, beta,
                            None, jnp.float32)
        _, act_vjp = jax.vjp(_ref.ACTIVATIONS[activation], z)
        (g,) = act_vjp(g)
    gk = g.astype(jnp.result_type(a.dtype, b.dtype))
    da = _pallas_forward(gk, b.T, None, None, config, interpret, alpha, 0.0,
                         None, a.dtype)
    db = _pallas_forward(a.T, gk, None, None, config, interpret, alpha, 0.0,
                         None, b.dtype)
    dc = (beta * g).astype(c.dtype) if c is not None else None
    dbias = g.sum(axis=0).astype(bias.dtype) if bias is not None else None
    return da, db, dc, dbias


_pallas_gemm.defvjp(_pallas_gemm_fwd, _pallas_gemm_bwd)


def _xla_gemm(a, b, c=None, *, alpha, beta, bias, activation, out_dtype,
              bf16_partials=False):
    """XLA dot path — same semantics, tiling delegated to the XLA compiler.

    This is the baseline the paper calls "vendor library": no exposed tuning
    parameters.  Still forces f32 MXU accumulation for parity (per shard;
    see ExecutionContext.bf16_partials for the cross-shard reduction dtype).
    """
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    pref = jnp.float32
    if bf16_partials and a.dtype.itemsize <= 2 and b.dtype.itemsize <= 2 \
            and bias is None and activation is None and c is None:
        pref = jnp.bfloat16
    acc = jnp.dot(a, b, preferred_element_type=pref)
    if alpha != 1.0:
        acc = alpha * acc
    if c is not None:
        acc = acc + beta * c.astype(jnp.float32)
    acc = _ref.apply_epilogue(acc, bias=bias, activation=activation)
    return acc.astype(out_dtype)


def batched_gemm(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """GEMM over shared leading batch dims via vmap of the single source."""
    if a.ndim != b.ndim:
        raise ValueError(f"rank mismatch {a.shape} vs {b.shape}")
    fn = functools.partial(gemm, **kw)
    for _ in range(a.ndim - 2):
        fn = jax.vmap(fn)
    return fn(a, b)


jit_gemm = jax.jit(gemm, static_argnames=(
    "config", "backend", "alpha", "beta", "activation", "out_dtype"))
