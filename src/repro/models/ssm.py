"""Mamba2 (SSD — state-space duality) block, chunked matmul formulation.

The SSD block decomposition (arXiv:2405.21060) recasts the selective-SSM
recurrence as *block matrix multiplications* — intra-chunk dense GEMMs plus
a tiny inter-chunk recurrence — which is exactly the regime the paper's
tunable-GEMM thesis targets (DESIGN.md §4): the hot ops here are the chunked
contractions, lowered through core.einsum / XLA dot and MXU-friendly.

Convention (h = state, per head):
    h_s = exp(dt_s * A) * h_{s-1} + dt_s * B_s * x_s ;   y_l = C_l . h_l + D x_l
n_groups = 1 (B, C shared across heads), as in the released Mamba2 models.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import einsum, matmul
from repro.configs.base import ModelConfig
from repro.models.layers import COL, ROW
from repro.models.params import ParamSpec


def ssm_dims(cfg: ModelConfig):
    d_inner = cfg.d_inner
    n_heads = cfg.ssm_heads
    conv_dim = d_inner + 2 * cfg.ssm_state
    d_in_proj = 2 * d_inner + 2 * cfg.ssm_state + n_heads
    return d_inner, n_heads, conv_dim, d_in_proj


def ssm_template(cfg: ModelConfig):
    d_inner, n_heads, conv_dim, d_in_proj = ssm_dims(cfg)
    return {
        "in_proj": ParamSpec((cfg.d_model, d_in_proj), COL),
        "conv_w": ParamSpec((cfg.ssm_conv, conv_dim), (None, "ff"), scale=0.5),
        "conv_b": ParamSpec((conv_dim,), ("ff",), init="zeros"),
        "A_log": ParamSpec((n_heads,), (None,), init="zeros"),
        "D": ParamSpec((n_heads,), (None,), init="ones"),
        "dt_bias": ParamSpec((n_heads,), (None,), init="zeros"),
        "norm": ParamSpec((d_inner,), ("ff",), init="ones"),
        "out_proj": ParamSpec((d_inner, cfg.d_model), ROW),
    }


def _split_zxbcdt(cfg: ModelConfig, zxbcdt):
    d_inner, n_heads, _, _ = ssm_dims(cfg)
    n = cfg.ssm_state
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * n:]
    return z, xBC, dt


def _gated_norm(scale, y, z, eps):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = (yf * yf).mean(-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale).astype(y.dtype)


def _causal_conv(params, xBC, cfg: ModelConfig):
    """Depthwise causal conv over the sequence: xBC (B, S, C)."""
    k = cfg.ssm_conv
    pad = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * params["conv_w"][i]
              for i in range(k))
    return jax.nn.silu(out + params["conv_b"])


def ssm_block(params, x: jax.Array, cfg: ModelConfig,
              return_state: bool = False, valid_mask=None):
    """Full-sequence SSD forward.  x: (B, S, D) with S % ssm_chunk == 0.

    ``return_state=True`` additionally returns the recurrent state after the
    last position — {"conv", "ssm"} — so prefill can hand off to the
    single-token decode path exactly.

    ``valid_mask`` (B, S) bool marks real tokens in a left-padded ragged
    batch.  Pad columns are zeroed both pre-conv (so early real tokens see
    the same zero conv left-context a lone prompt would) and post-conv (so
    pad positions contribute nothing to the recurrent state — every decay
    span between real tokens covers only real tokens, making the state
    entering the first real token exactly the zero init).
    """
    b, s, _ = x.shape
    d_inner, n_heads, _, _ = ssm_dims(cfg)
    p = cfg.ssm_head_dim
    n = cfg.ssm_state
    # Chunk length: the largest divisor of S not exceeding ssm_chunk, so any
    # sequence length is exact (production shapes are powers of two and use
    # the configured chunk; odd test lengths degrade gracefully).
    l = min(cfg.ssm_chunk, s)
    while s % l:
        l -= 1
    nc = s // l

    z, xBC, dt = _split_zxbcdt(cfg, matmul(x, params["in_proj"], w_axes=COL))
    if valid_mask is not None:
        xBC = jnp.where(valid_mask[..., None], xBC, 0)
    xBC_pre = xBC
    xBC = _causal_conv(params, xBC, cfg)
    xs, bs, cs = xBC[..., :d_inner], xBC[..., d_inner:d_inner + n], xBC[..., d_inner + n:]
    if valid_mask is not None:
        xs = jnp.where(valid_mask[..., None], xs, 0)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])      # (B,S,H)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))                     # (H,)

    xc = xs.reshape(b, nc, l, n_heads, p).astype(jnp.float32)
    bc = bs.reshape(b, nc, l, n).astype(jnp.float32)
    cc = cs.reshape(b, nc, l, n).astype(jnp.float32)
    dtc = dt.reshape(b, nc, l, n_heads)

    da = dtc * a                                                          # (B,nc,L,H)
    cum = jnp.cumsum(da, axis=2)

    # --- intra-chunk (dense GEMM part of SSD) --------------------------
    cb = einsum("bcln,bcsn->bcls", cc, bc)                                # (B,nc,L,L)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]                   # (B,nc,L,S,H)
    causal = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = cb[..., None] * decay * dtc[:, :, None, :, :]                # (B,nc,L,S,H)
    y_diag = einsum("bclsh,bcshp->bclhp", scores, xc)

    # --- chunk boundary states -----------------------------------------
    state_decay = jnp.exp(cum[:, :, -1:, :] - cum)                        # (B,nc,L,H)
    states = einsum("bcln,bclh,bclhp->bchnp", bc, dtc * state_decay, xc)  # (B,nc,H,N,P)

    # --- inter-chunk recurrence (associative scan over chunks) ---------
    chunk_decay = jnp.exp(cum[:, :, -1, :])                               # (B,nc,H)

    def combine(left, right):
        d1, s1 = left
        d2, s2 = right
        return d1 * d2, s1 * d2[..., None, None] + s2

    _, inc = jax.lax.associative_scan(combine, (chunk_decay, states), axis=1)
    prev = jnp.concatenate(
        [jnp.zeros_like(inc[:, :1]), inc[:, :-1]], axis=1)                # states entering chunk c

    y_off = einsum("bcln,bchnp,bclh->bclhp", cc, prev, jnp.exp(cum))
    y = (y_diag + y_off).reshape(b, s, n_heads, p)
    y = y + params["D"].astype(jnp.float32)[None, None, :, None] * xs.reshape(b, s, n_heads, p).astype(jnp.float32)

    y = _gated_norm(params["norm"], y.reshape(b, s, d_inner).astype(x.dtype), z, cfg.norm_eps)
    out = matmul(y, params["out_proj"], w_axes=ROW)
    if not return_state:
        return out
    final_state = {
        "conv": xBC_pre[:, s - (cfg.ssm_conv - 1):, :],   # last K-1 pre-conv inputs
        "ssm": inc[:, -1],                                 # state after position S
    }
    return out, final_state


def ssm_state_init(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d_inner, n_heads, conv_dim, _ = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, n_heads, cfg.ssm_state, cfg.ssm_head_dim),
                         jnp.float32),
    }


def ssm_decode_step(params, x: jax.Array, state, cfg: ModelConfig):
    """Single-token recurrent step.  x: (B, 1, D) -> (y (B,1,D), new state)."""
    b = x.shape[0]
    d_inner, n_heads, conv_dim, _ = ssm_dims(cfg)
    p, n = cfg.ssm_head_dim, cfg.ssm_state

    z, xBC, dt = _split_zxbcdt(cfg, matmul(x[:, 0], params["in_proj"], w_axes=COL))
    window = jnp.concatenate([state["conv"], xBC[:, None, :]], axis=1)    # (B,K,C)
    conv_out = jax.nn.silu((window * params["conv_w"][None]).sum(1) + params["conv_b"])
    new_conv = window[:, 1:]

    xs, bs, cs = conv_out[..., :d_inner], conv_out[..., d_inner:d_inner + n], conv_out[..., d_inner + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])      # (B,H)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    da = jnp.exp(dt * a)                                                  # (B,H)

    xh = xs.reshape(b, n_heads, p).astype(jnp.float32)
    new_ssm = state["ssm"] * da[..., None, None] + einsum(
        "bn,bh,bhp->bhnp", bs.astype(jnp.float32), dt, xh)
    y = einsum("bn,bhnp->bhp", cs.astype(jnp.float32), new_ssm)
    y = y + params["D"].astype(jnp.float32)[None, :, None] * xh

    y = _gated_norm(params["norm"], y.reshape(b, d_inner).astype(x.dtype), z, cfg.norm_eps)
    out = matmul(y, params["out_proj"], w_axes=ROW)[:, None, :]
    return out, {"conv": new_conv, "ssm": new_ssm}
