"""Single-source tiled GEMM Pallas kernel (the paper's Fig. 2 algorithm).

This file is the TPU-native re-expression of the Alpaka GEMM of Listing 1.1 /
Fig. 2: one kernel body, *zero* architecture-specific lines.  All tuning
parameters (``bm``, ``bk``, ``bn`` — the generalization of the paper's square
tile size ``T`` — plus grid dimension semantics) arrive from outside via
``core.tile_config.TileConfig`` / ``core.registry``, exactly like Alpaka's
``OptimalVectorSize<T_Acc>`` trait.  Changing hardware never touches this
file.

Mapping of the paper's hierarchy onto Pallas:
  * grid            -> ``pl.pallas_call`` grid (i, j, k) over output tiles
  * block           -> one program instance computing a (bm, bn) C tile
  * thread/element  -> VPU/MXU lanes inside ``jnp.dot`` (the "element layer";
                       on TPU vectorization is structural, not pragma-driven)
  * tile loop over A/B (purple tiles of Fig. 2) -> the ``k`` grid dimension,
    accumulating into a float32 VMEM scratch tile (the orange C tile)

The VMEM working set is (bm*bk + bk*bn + bm*bn) * sizeof(dtype) + bm*bn*4,
the rectangular generalization of the paper's K(S,T) = 2*T^2*S (Eq. 5).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import apply_epilogue


def _gemm_kernel(*refs, n_k: int, alpha: float, beta: float,
                 activation: Optional[str], has_c: bool, has_bias: bool):
    """Kernel body. refs = (a, b[, c][, bias], out, acc_scratch)."""
    idx = 0
    a_ref = refs[idx]; idx += 1
    b_ref = refs[idx]; idx += 1
    c_ref = None
    bias_ref = None
    if has_c:
        c_ref = refs[idx]; idx += 1
    if has_bias:
        bias_ref = refs[idx]; idx += 1
    o_ref = refs[idx]; idx += 1
    acc_ref = refs[idx]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The performance-critical inner tile product (paper Fig. 2, green):
    # MXU matmul with forced f32 accumulation (the TPU analogue of the
    # paper's FMA autovectorization in Listing 1.2).
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...]
        if alpha != 1.0:
            out = alpha * out
        if c_ref is not None:
            out = out + beta * c_ref[...].astype(jnp.float32)
        bias = bias_ref[...] if bias_ref is not None else None
        out = apply_epilogue(out, bias=bias, activation=activation)
        o_ref[...] = out.astype(o_ref.dtype)


def gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    bm: int,
    bk: int,
    bn: int,
    alpha: float = 1.0,
    beta: float = 0.0,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Tiled GEMM ``alpha * A @ B + beta * C`` via ``pl.pallas_call``.

    Operand shapes must be multiples of the block shape — the ``ops.gemm``
    wrapper pads arbitrary shapes before calling this (tiles never straddle
    the matrix edge, as in the paper where N is a multiple of T).
    """
    m, k_dim = a.shape
    k2, n = b.shape
    assert k_dim == k2, (a.shape, b.shape)
    assert m % bm == 0 and k_dim % bk == 0 and n % bn == 0, (
        f"shape {(m, k_dim, n)} not a multiple of block {(bm, bk, bn)}")
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    n_k = k_dim // bk
    grid = (m // bm, n // bn, n_k)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [a, b]
    has_c = c is not None
    if has_c:
        assert c.shape == (m, n), c.shape
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(c)
    has_bias = bias is not None
    if has_bias:
        # A (1, N) row with (1, bn) blocks: Mosaic tiles rank-1 bf16 blocks
        # in 256-lane units, so a rank-1 (bn,) block with bn=128 is refused.
        assert bias.shape == (n,), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))

    kernel = functools.partial(
        _gemm_kernel, n_k=n_k, alpha=alpha, beta=beta,
        activation=activation, has_c=has_c, has_bias=has_bias,
    )

    # Grid iteration order: k innermost (revisits the same C tile) so the
    # accumulator scratch carries across k steps; i/j are parallel.
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
    )(*operands)
