"""Flash attention (online-softmax) Pallas kernel — beyond-paper kernel.

Motivation from the roofline (§Perf): attention-heavy train cells are
memory-term dominated because materialized (S x S) score tensors round-trip
HBM.  This kernel streams KV blocks through VMEM with the online-softmax
recurrence (Dao et al.), so scores never touch HBM: per (bq x d) output tile
the HBM traffic is q + k + v + o — the same "bigger tile => higher arithmetic
intensity" argument as the paper's Eq. 7, applied to attention.

Single-source discipline as for GEMM: block sizes (bq, bk) arrive from
outside — callers get tuned values via
:func:`repro.core.attention_api.flash_attention`, which resolves the
op="flash_attention" entry of the tuning registry; this module never reads
tuning state.  The kernel body is architecture-agnostic.

Ragged / prefill support (the serve-engine path):

* ``kv_start`` — optional per-batch-row ``(B,)`` int32 giving the first
  *valid* KV column of a left-padded ragged batch.  Columns before
  ``kv_start[b]`` are excluded from every softmax, matching the chunked
  reference path (`models/layers._sdpa_chunked`) and the engine's
  right-aligned prompt layout.
* Non-divisible sequence lengths — ``S % bq != 0`` or ``S_kv % bk != 0`` is
  handled by **left-padding** q/k/v up to the next block multiple and
  widening ``kv_start`` by the pad, so padding reuses exactly the ragged
  masking logic; pad query rows are sliced off the output.  Fully-masked
  score blocks contribute exactly zero to the online recurrence (an explicit
  guard keeps ``exp(-inf - -inf)`` from polluting the accumulator), so the
  padded result is numerically identical to the unpadded one.

Validated in interpret mode against ``ref.attention_ref``
(tests/test_flash_attention.py), including ragged and non-divisible cases.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: scores at/below this are treated as masked when guarding exp() — far below
#: any reachable logit, far above NEG_INF
_MASKED_BELOW = -1e28


def _flash_kernel(kvs_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, n_kv: int, scale: float, causal: bool,
                  causal_offset: int, bq: int, bk: int):
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)

    s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(cols <= rows + causal_offset, s, NEG_INF)
    # ragged left-padding: columns before this row's kv_start are invalid
    s = jnp.where(cols >= kvs_ref[bi], s, NEG_INF)

    m_prev = m_scr[...]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # Guard fully-masked prefixes: while every score so far is NEG_INF,
    # m_new == NEG_INF and exp(s - m_new) would be exp(0) = 1 for masked
    # entries — force their contribution to exactly zero instead.
    p = jnp.where(s > _MASKED_BELOW, jnp.exp(s - m_new), 0.0)  # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)              # (bq, 1); 1 while masked

    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        # rows with an empty softmax (pad query rows) would divide by zero;
        # their output is sliced off by the wrapper, any finite value works
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, bq: int = 128, bk: int = 128,
    scale: Optional[float] = None, interpret: bool = False,
    kv_start: Optional[jax.Array] = None,
) -> jax.Array:
    """Head-batched flash attention: q (BH, S, d); k, v (BH, S_kv, d).

    One head-batch per grid row; online softmax over KV blocks (the
    'arbitrary' grid dim).  ``kv_start`` is an optional (BH,) int32 of
    first-valid KV columns (left-padded ragged rows).  Sequence lengths not
    divisible by the block sizes are left-padded internally; see the module
    docstring for why padding is exact.
    """
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    scale = d ** -0.5 if scale is None else scale
    bq = max(1, min(bq, sq))
    bk = max(1, min(bk, skv))

    if kv_start is None:
        kv_start = jnp.zeros((bh,), jnp.int32)
    kv_start = kv_start.astype(jnp.int32)

    # Left-pad to block multiples; the pad columns fold into kv_start.
    pq = (-sq) % bq
    pk = (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (pq, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (pk, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (pk, 0), (0, 0)))
        kv_start = kv_start + pk
    sq_p, skv_p = sq + pq, skv + pk

    n_kv = skv_p // bk
    grid = (bh, sq_p // bq, n_kv)

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, scale=scale, causal=causal,
        causal_offset=skv_p - sq_p, bq=bq, bk=bk)

    # kv_start rides as a scalar-prefetch operand in SMEM: a (1, 1) VMEM
    # block of a (BH, 1) array breaks Mosaic's (8, 128) block tiling.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, kvs: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, kvs: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, kvs: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j, kvs: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_start, q, k, v)
    return out[:, pq:, :] if pq else out


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool = False,
                    kv_start: Optional[jax.Array] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """GQA front end: q (B, S, H, d); k, v (B, S_kv, KV, d) -> (B, S, H, d).

    Grouped KV heads are expanded at this wrapper level (the kernel stays
    pure); ``kv_start`` (B,) marks each row's first valid KV column for
    left-padded ragged batches and is broadcast across heads.
    """
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    if kvh != h:  # expand grouped KV heads (wrapper-level; kernel stays pure)
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    vb = v.transpose(0, 2, 1, 3).reshape(b * h, skv, d)
    ks = None if kv_start is None else jnp.repeat(kv_start.astype(jnp.int32), h)
    out = flash_attention_bhsd(qb, kb, vb, causal=causal, bq=bq, bk=bk,
                               scale=scale, interpret=interpret, kv_start=ks)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
