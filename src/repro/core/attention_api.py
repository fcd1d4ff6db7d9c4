"""The single public flash-attention entry point — models route here.

Mirror of :mod:`repro.core.gemm_api` for the attention kernel family: the
algorithm (``kernels/flash_attention.py``) is written once; *which (bq, bk)
blocks it runs with* is decided here from the ambient
:class:`~repro.core.gemm_api.ExecutionContext` plus the op-keyed tuning
registry.  Model code never mentions block sizes.

Lookup key: ``op="flash_attention"``, shape ``(sq, skv, head_dim)`` — the
same exact → nearest → generic → default resolution order as GEMM tiles,
fed by the committed ``tuned/<hardware>.json`` databases.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.gemm_api import _ctx
from repro.core.registry import GLOBAL_REGISTRY, LookupResult, OP_FLASH_ATTENTION


def flash_tile_lookup(hardware: str, dtype, sq: int, skv: int,
                      d: int) -> LookupResult:
    """Resolve tuned (bq, bk) blocks for one flash-attention problem.

    Thin, named wrapper over the registry so telemetry consumers (e.g.
    ``Engine.stats()``) and the model path share one lookup definition.
    """
    return GLOBAL_REGISTRY.lookup_op(OP_FLASH_ATTENTION, hardware, dtype,
                                     (sq, skv, d))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    kv_start: Optional[jax.Array] = None,
                    bq: Optional[int] = None, bk: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Tuned flash attention over GQA-layout operands.

    Args:
      q: queries, shape ``(B, S, H, d)``.
      k, v: keys/values, shape ``(B, S_kv, KV, d)`` with ``KV`` dividing
        ``H`` (grouped-query attention; KV heads are expanded internally).
      causal: apply the causal mask (queries aligned to the *end* of the KV
        sequence when ``S != S_kv``).
      kv_start: optional ``(B,)`` int32 — first valid KV column per row for
        left-padded ragged batches; earlier columns are masked out of every
        softmax.
      bq, bk: explicit block-size overrides.  When omitted (the normal
        case), the blocks come from the tuning registry's
        ``op="flash_attention"`` entry for ``(S, S_kv, d)`` on the ambient
        context's hardware — exact tuned shape first, then nearest-shape,
        generic, and per-hardware default tiers.
      interpret: force/disable Pallas interpret mode.  By default the
        kernel is compiled by Mosaic under the ``pallas-tpu`` backend and
        on a TPU platform (unless the context asks for
        ``pallas-interpret``), and interpreted elsewhere.

    Returns:
      Attention output, shape ``(B, S, H, d)``, in ``q.dtype``.

    Example::

        from repro.core import execution_context, flash_attention
        from repro.core.hardware import TPU_V5E
        with execution_context(hardware=TPU_V5E.name):
            out = flash_attention(q, k, v, causal=True)   # tuned (bq, bk)
    """
    from repro.distributed.ctx import get_policy
    from repro.kernels import flash_attention as fa_kernel
    from repro.kernels import ops
    b, sq, h, d = q.shape
    skv = k.shape[1]
    ctx = _ctx()
    if bq is None or bk is None:
        cfg = flash_tile_lookup(ctx.resolve_hardware(), q.dtype,
                                sq, skv, d).config
        bq = bq if bq is not None else cfg.bq
        bk = bk if bk is not None else cfg.bk
    if interpret is None:
        backend = ctx.resolve_backend()
        interpret = (backend == ops.BACKEND_PALLAS_INTERPRET
                     or (backend != ops.BACKEND_PALLAS_TPU
                         and jax.default_backend() != "tpu"))

    def kernel(q, k, v, kv_start=None):
        return fa_kernel.flash_attention(q, k, v, causal=causal, bq=bq,
                                         bk=bk, interpret=interpret,
                                         kv_start=kv_start)

    policy = get_policy()
    if policy is None:
        return kernel(q, k, v, kv_start)
    return _per_shard(policy, kernel, q, k, v, kv_start)


def _per_shard(policy, kernel, q, k, v, kv_start):
    """Run ``kernel`` under ``shard_map`` on each shard's local operands:
    Mosaic kernels cannot be partitioned by the compiler.  Rows split over
    the batch axes and heads over the tensor axis; grouped KV heads are
    expanded first when the tensor axis does not divide them."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as sh
    mesh, rules = policy.mesh, policy.rules
    b, _, h, _ = q.shape
    kvh = k.shape[2]
    b_ax = rules.batch_axes
    if b % sh.axis_size(mesh, b_ax):
        b_ax = None
    h_ax = rules.tensor_axis
    if h_ax and h % sh.axis_size(mesh, h_ax):
        h_ax = None
    if h_ax and kvh % sh.axis_size(mesh, h_ax):
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    spec = P(b_ax, None, h_ax, None)
    args, specs = (q, k, v), (spec, spec, spec)
    if kv_start is not None:
        args, specs = args + (kv_start,), specs + (P(b_ax),)
    return jax.shard_map(kernel, mesh=mesh, in_specs=specs, out_specs=spec,
                         check_vma=False)(*args)
