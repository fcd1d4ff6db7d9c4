"""The single public matmul entry point — every model matmul goes here.

This is the framework's enforcement of the paper's thesis: the algorithm
(kernels/gemm.py) is written once; *which execution backend runs it* and
*with which tile parameters* is decided here from ambient context + the
registry.  Model code never mentions tiles or backends.

``ExecutionContext`` plays the role of the paper's build matrix (Tab. 3):
backend x hardware x dtype.  On a real TPU the default context resolves to
the Pallas kernel; on this CPU container it resolves to XLA (for jit/pjit
paths) with pallas-interpret available for kernel validation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hardware as hw
from repro.core.registry import GLOBAL_REGISTRY
from repro.kernels import ops


def _default_backend() -> str:
    platform = jax.default_backend()
    return ops.BACKEND_PALLAS_TPU if platform == "tpu" else ops.BACKEND_XLA


@dataclasses.dataclass
class ExecutionContext:
    backend: Optional[str] = None       # None -> auto by platform
    # Registry/tuner key (target hardware profile).  None resolves through
    # the profile layer: $REPRO_HARDWARE, else jax.devices() detection —
    # an explicit execution_context(hardware=...) override always wins.
    hardware: Optional[str] = None
    capture: Optional[list] = None      # GEMM shape trace
    # When True, each captured entry is a (global, per-shard) shape pair.
    capture_per_shard: bool = False
    # When True, 16-bit matmuls emit 16-bit outputs at the tile level, so
    # cross-shard partial-sum all-reduces run in bf16 instead of f32 (halves
    # the dominant TP collective; MXU still accumulates f32 within a shard).
    bf16_partials: bool = False

    def resolve_backend(self) -> str:
        return self.backend or _default_backend()

    def resolve_hardware(self) -> str:
        return hw.resolve_hardware(self.hardware)


_TLS = threading.local()


def _ctx() -> ExecutionContext:
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        ctx = ExecutionContext()
        _TLS.ctx = ctx
    return ctx


@contextlib.contextmanager
def execution_context(**overrides):
    """Scoped override, e.g. ``with execution_context(backend="pallas-interpret")``."""
    old = _ctx()
    new = dataclasses.replace(old, **overrides)
    _TLS.ctx = new
    try:
        yield new
    finally:
        _TLS.ctx = old


@dataclasses.dataclass
class AllreduceTally:
    """Bytes the explicit cross-shard reductions of a traced program move:
    each adds its per-shard operand (shape x itemsize) once per run."""
    bytes: int = 0


@contextlib.contextmanager
def allreduce_tally():
    """Count, while a program is traced under this scope, the operand
    bytes of every explicit cross-shard reduction in it (the row-parallel
    GEMM's ``psum``).  Counting happens at trace time only: a compiled
    program that is run again adds nothing, so the caller keeps the count
    per compiled program.  Reductions the compiler inserts itself (around
    a sharded embedding or logits) are not counted."""
    tally = AllreduceTally()
    stack = _TLS.__dict__.setdefault("tallies", [])
    stack.append(tally)
    try:
        yield tally
    finally:
        stack.pop()


@contextlib.contextmanager
def allreduce_repeat(n: int):
    """Scale what a loop body traced under this scope adds to the open
    tallies by the loop's trip count ``n`` (a layer ``scan`` traces its
    body once and runs it ``n`` times)."""
    old = getattr(_TLS, "repeat", 1)
    _TLS.repeat = old * n
    try:
        yield
    finally:
        _TLS.repeat = old


def _count_allreduce(x: jax.Array) -> None:
    nbytes = x.size * jnp.dtype(x.dtype).itemsize * getattr(_TLS, "repeat", 1)
    for tally in getattr(_TLS, "tallies", ()):
        tally.bytes += nbytes


def current_hardware() -> str:
    """Resolved registry/tuner hardware key of the ambient execution context.

    Detection order: explicit ``execution_context(hardware=...)`` override,
    then ``$REPRO_HARDWARE``, then :func:`repro.core.hardware.detect_hardware`
    over ``jax.devices()``.
    """
    return _ctx().resolve_hardware()


@contextlib.contextmanager
def capture_gemm_shapes(per_shard: bool = False):
    """Collect every (m, k, n) issued under this scope — feeds the tuner.

    With ``per_shard`` each entry is a ``((m, k, n), (lm, lk, ln))`` pair:
    the shape ``matmul`` was called with and the shape each shard's kernel
    runs under the ambient mesh policy (the same shape without one).
    """
    shapes: List = []
    with execution_context(capture=shapes, capture_per_shard=per_shard):
        yield shapes


# --- bf16-reduction matmul (beyond-paper §Perf option) ---------------------
# Standard AD leaves cotangents in f32 wherever the fwd graph upcast
# (norms, softmax, loss), so the backward TP/FSDP partial-sum all-reduces
# run in f32.  This custom-VJP dot pins BOTH directions to bf16 outputs, so
# every cross-shard reduction of activations/grad-activations/grad-weights
# moves half the bytes.  MXU accumulation within a shard remains f32-backed;
# the cross-shard sum is bf16 (the usual production mixed-precision choice).

@jax.custom_vjp
def _dot_bf16_reduce(x2, w):
    return jax.lax.dot(x2, w, preferred_element_type=jnp.bfloat16)


def _dot_bf16_reduce_fwd(x2, w):
    return _dot_bf16_reduce(x2, w), (x2, w)


def _dot_bf16_reduce_bwd(res, g):
    x2, w = res
    gb = g.astype(jnp.bfloat16)
    dx = jax.lax.dot(gb, w.T, preferred_element_type=jnp.bfloat16)
    dw = jax.lax.dot(x2.T, gb, preferred_element_type=jnp.bfloat16)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_dot_bf16_reduce.defvjp(_dot_bf16_reduce_fwd, _dot_bf16_reduce_bwd)


def matmul(x: jax.Array, w: jax.Array, *, bias: Optional[jax.Array] = None,
           activation: Optional[str] = None, out_dtype=None,
           w_axes: Optional[Tuple[Optional[str], str]] = None) -> jax.Array:
    """``x @ w`` — the only matmul primitive the model zoo uses.

    Leading dims of ``x`` are flattened into the GEMM's M dimension; the
    execution backend and the (bm, bk, bn) tile config are resolved from the
    ambient :class:`ExecutionContext` and the op-keyed tuning registry
    (``op="gemm"``, exact tuned shape first, then nearest-shape, generic and
    per-hardware default tiers).  Fused epilogues (bias, activation) ride on
    the kernel's epilogue so the single source covers the model's hot paths,
    not just plain GEMM.

    Args:
      x: left operand, shape ``(..., K)``.
      w: right operand, shape ``(K, N)``.
      bias: optional ``(N,)`` bias added in f32 before the activation.
      activation: optional fused activation: ``"relu" | "gelu" | "silu" |
        "tanh"``.
      out_dtype: output dtype (default: the operands' result type).
      w_axes: logical axes of ``w`` (as in its ``ParamSpec``); required
        under a mesh, where they say which weight dim the sharding rules
        split for the per-shard kernel.  ``(None, None)`` is a replicated
        weight.

    Returns:
      ``x @ w`` with shape ``(..., N)``, accumulated in float32.

    Example::

        from repro.core import execution_context, matmul
        from repro.core.hardware import TPU_V5E
        with execution_context(backend="pallas-interpret",
                               hardware=TPU_V5E.name):
            y = matmul(x, w, activation="silu")   # tuned tiles, fused SiLU
    """
    ctx = _ctx()
    backend = ctx.resolve_backend()
    k = x.shape[-1]
    if w.shape[0] != k:
        raise ValueError(f"matmul mismatch: {x.shape} @ {w.shape}")
    n = w.shape[1]
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)

    from repro.distributed.ctx import get_policy
    policy = get_policy()
    layout = (_shard_layout(policy, m, k, n, w_axes)
              if policy is not None else None)
    if ctx.capture is not None:
        if ctx.capture_per_shard:
            ctx.capture.append(((m, k, n), layout[1] if layout else (m, k, n)))
        else:
            ctx.capture.append((m, k, n))

    config = None
    if backend in (ops.BACKEND_PALLAS_TPU, ops.BACKEND_PALLAS_INTERPRET):
        if layout is not None:
            out = _per_shard_gemm(policy, layout, ctx.resolve_hardware(),
                                  backend, x2, w, bias, activation, out_dtype)
            return out.reshape(*lead, n)
        # First lookup lazily pulls committed tuned/<hardware>.json DBs into
        # the global registry, so a fresh process serves tuned tiles with no
        # explicit setup; untuned shapes resolve via nearest-shape fallback.
        config = GLOBAL_REGISTRY.lookup(ctx.resolve_hardware(), x.dtype,
                                        m, k, n).config

    if (ctx.bf16_partials and backend == ops.BACKEND_XLA
            and bias is None and activation is None
            and x.dtype == jnp.bfloat16 and w.dtype == jnp.bfloat16):
        out = _dot_bf16_reduce(x2, w)
        if out_dtype is not None:
            out = out.astype(out_dtype)
        return out.reshape(*lead, n)

    out = ops.gemm(x2, w, config=config, backend=backend, bias=bias,
                   activation=activation, out_dtype=out_dtype,
                   bf16_partials=ctx.bf16_partials)
    return out.reshape(*lead, n)


def _shard_layout(policy, m: int, k: int, n: int, w_axes):
    """Where a GEMM splits on the policy's mesh: the mesh axes of its M, K
    and N dims, and the per-shard ``(m, k, n)`` each shard's kernel runs.

    M splits over the batch axes, and the weight keeps the tensor-parallel
    split its sharding rules give it (FSDP shards are gathered first).
    """
    from repro.distributed import sharding as sh
    if w_axes is None:
        raise ValueError(
            f"matmul({m}x{k} @ {k}x{n}) under a mesh needs w_axes, the "
            "weight's logical axes as in its ParamSpec ((None, None) for a "
            "replicated weight); without them a sharded weight would be "
            "gathered whole into every shard")
    mesh, rules = policy.mesh, policy.rules
    k_ax, n_ax = sh.weight_compute_axes(mesh, rules, (k, n), w_axes)
    m_ax = rules.batch_axes
    if m % sh.axis_size(mesh, m_ax):
        m_ax = None
    local = (m // sh.axis_size(mesh, m_ax), k // sh.axis_size(mesh, k_ax),
             n // sh.axis_size(mesh, n_ax))
    return (m_ax, k_ax, n_ax), local


def _per_shard_gemm(policy, layout, hardware: str, backend: str, x2, w,
                    bias, activation, out_dtype) -> jax.Array:
    """The Pallas GEMM on a mesh: Mosaic kernels cannot be partitioned by
    the compiler, so the kernel runs under ``shard_map`` on each shard's
    local operands (split as :func:`_shard_layout` says), with its tiles
    looked up by the *local* shape.

    A weight split along K (row parallel) leaves f32 partial sums that are
    all-reduced before the bias/activation epilogue runs once on the full
    sum.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core.registry import OP_GEMM
    from repro.distributed.sharding import mesh_axis_label
    from repro.kernels.ref import apply_epilogue
    mesh = policy.mesh
    (m_ax, k_ax, n_ax), local = layout
    config = GLOBAL_REGISTRY.lookup_op(OP_GEMM, hardware, x2.dtype, local,
                                       mesh=mesh_axis_label(mesh)).config
    out_dtype = out_dtype or jnp.result_type(x2.dtype, w.dtype)

    def shard_fn(xl, wl, *bl):
        bl = bl[0] if bl else None
        if k_ax is None:
            return ops.gemm(xl, wl, config=config, backend=backend, bias=bl,
                            activation=activation, out_dtype=out_dtype)
        part = ops.gemm(xl, wl, config=config, backend=backend,
                        out_dtype=jnp.float32)
        _count_allreduce(part)
        return apply_epilogue(jax.lax.psum(part, k_ax), bias=bl,
                              activation=activation).astype(out_dtype)

    args, specs = (x2, w), (P(m_ax, k_ax), P(k_ax, n_ax))
    if bias is not None:
        args, specs = args + (bias,), specs + (P(n_ax),)
    return jax.shard_map(shard_fn, mesh=mesh, in_specs=specs,
                         out_specs=P(m_ax, n_ax), check_vma=False)(*args)


def einsum(subscripts: str, *operands, **kw):
    """Thin escape hatch for contractions that are not plain (…,K)x(K,N).

    Routed through XLA dot_general; still subject to the ambient context's
    dtype policy.  Kept in one place so a future Pallas generalization can
    swap in without touching models.
    """
    pref = jnp.float32
    if _ctx().bf16_partials and all(
            jnp.dtype(getattr(o, "dtype", jnp.float32)).itemsize <= 2
            for o in operands):
        pref = jnp.bfloat16
    return jnp.einsum(subscripts, *operands,
                      preferred_element_type=pref, **kw)
