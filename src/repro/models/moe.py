"""Token-choice top-k Mixture-of-Experts layer (OLMoE / Moonlight style).

Capacity-based dispatch (GShard lineage) chosen for SPMD-friendliness:
routing is computed *per sequence group* (the batch dim, which is
data-parallel sharded), so no routing decision crosses a device boundary;
expert weights are expert-parallel ("expert" logical axis -> "model" mesh
axis) and the dispatch/combine contractions lower to the all-to-all pattern
XLA inserts for EP.

Memory: dispatch buffers are (E, C, D) per group with
C = ceil(top_k * S * capacity_factor / E), i.e. ~top_k * cf * tokens * d
total — bounded, scan/remat friendly.  Dropped tokens (over capacity) fall
back to the residual stream, standard for capacity-factor MoE.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import einsum, matmul
from repro.distributed.ctx import constrain
from repro.models.params import ParamSpec

#: logical axes of the router weight: replicated (the expert dim is small)
ROUTER_AXES = ("embed", None)


def moe_template(d_model: int, d_ff: int, num_experts: int):
    e = num_experts
    return {
        "router": ParamSpec((d_model, e), ROUTER_AXES, scale=0.02),
        "w_gate": ParamSpec((e, d_model, d_ff), ("expert", "embed", "ff")),
        "w_up": ParamSpec((e, d_model, d_ff), ("expert", "embed", "ff")),
        "w_down": ParamSpec((e, d_ff, d_model), ("expert", "ff", "embed")),
    }


def capacity(seq_len: int, num_experts: int, top_k: int, cf: float) -> int:
    c = math.ceil(top_k * seq_len * cf / num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _route_group(x, logits, *, top_k: int, num_experts: int, cap: int):
    """Route one sequence group.  x: (S, D), logits: (S, E)."""
    s, d = x.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)              # (S, K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # Slot -> expert one-hot, position within expert buffer via cumsum.
    onehot = jax.nn.one_hot(idx, num_experts, dtype=jnp.int32)   # (S, K, E)
    flat = onehot.reshape(s * top_k, num_experts)
    pos = jnp.cumsum(flat, axis=0) * flat - flat                 # (S*K, E)
    slot_pos = pos.sum(-1)                                       # (S*K,)
    slot_exp = idx.reshape(s * top_k)
    keep = slot_pos < cap

    # Dispatch: scatter tokens (repeated per chosen expert) into (E, C, D).
    xk = jnp.repeat(x, top_k, axis=0)                            # (S*K, D)
    buf = jnp.zeros((num_experts * cap, d), x.dtype)
    tgt = jnp.where(keep, slot_exp * cap + slot_pos, num_experts * cap)
    buf = buf.at[tgt].add(xk * keep[:, None].astype(x.dtype),
                          mode="drop", indices_are_sorted=False)
    return buf.reshape(num_experts, cap, d), (slot_exp, slot_pos, keep,
                                              gate.reshape(s * top_k))


def _combine_group(expert_out, route, s: int, top_k: int, cap: int, dtype):
    slot_exp, slot_pos, keep, gate = route
    e, c, d = expert_out.shape
    flat = expert_out.reshape(e * c, d)
    src = jnp.clip(slot_exp * cap + slot_pos, 0, e * c - 1)
    # Combine in the activation dtype: the gather from the expert-sharded
    # buffer lowers to a masked-select + all-reduce over the EP axis, so
    # keeping it bf16 halves that collective's bytes (gate stays f32 for
    # routing; a k<=8-way weighted sum in bf16 is numerically benign).
    gathered = flat[src]                                          # (S*K, D)
    w = (gate * keep).astype(dtype)[:, None]
    out = (gathered * w).reshape(s, top_k, d).sum(1)
    return out.astype(dtype)


def moe_layer(params, x: jax.Array, *, top_k: int, num_experts: int,
              capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).  Routing vmapped over batch groups."""
    b, s, d = x.shape
    cap = capacity(s, num_experts, top_k, capacity_factor)
    logits = matmul(x, params["router"], w_axes=ROUTER_AXES)      # (B, S, E)

    bufs, routes = jax.vmap(
        lambda xg, lg: _route_group(xg, lg, top_k=top_k,
                                    num_experts=num_experts, cap=cap)
    )(x, logits)                                                   # (B, E, C, D)

    # EP pin: batch-sharded -> expert-sharded transition = all-to-all.
    bufs = constrain(bufs, "moe_dispatch")

    # Expert FFN: grouped GEMMs over the expert axis (EP-sharded).
    h = jax.nn.silu(einsum("becd,edf->becf", bufs, params["w_gate"]))
    h = h * einsum("becd,edf->becf", bufs, params["w_up"])
    out_e = einsum("becf,efd->becd", h.astype(x.dtype), params["w_down"])
    out_e = constrain(out_e, "moe_dispatch")

    out = jax.vmap(
        lambda eo, r: _combine_group(eo, r, s, top_k, cap, x.dtype)
    )(out_e, routes)
    out = constrain(out, "hidden")

    # Load-balance auxiliary loss (Switch-style): E * sum(f_e * p_e).
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    me = probs.mean((0, 1))
    onehot_top1 = jax.nn.one_hot(jnp.argmax(logits, -1), num_experts)
    ce = onehot_top1.mean((0, 1))
    aux = num_experts * jnp.sum(me * ce)
    return out, aux
