"""Pure-SSM (Mamba2) and hybrid (Zamba2-style) language models.

Zamba2 topology: units of ``attn_period`` Mamba2 blocks, with ONE
shared-weight attention block applied at the start of every unit (weights
shared across applications, distinct KV per application — so the decode
cache carries a leading 'unit' axis).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import constrain
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.params import ParamSpec


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------

def mamba_lm_template(cfg: ModelConfig):
    return {
        "embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), scale=0.02),
        "blocks": T._stack_template(_mamba_block_template(cfg), cfg.num_layers),
        "ln_f": L.norm_template(cfg.d_model, cfg.norm),
    } | ({} if cfg.tie_embeddings else
         {"lm_head": ParamSpec((cfg.d_model, cfg.vocab_size),
                              T.UNEMBED_AXES)})


def _mamba_block_template(cfg: ModelConfig):
    return {"ln": L.norm_template(cfg.d_model, cfg.norm),
            "ssm": S.ssm_template(cfg)}


def zamba_template(cfg: ModelConfig):
    units = cfg.num_layers // cfg.attn_period
    return {
        "embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), scale=0.02),
        "shared_attn": {   # ONE copy — applied at every unit boundary
            "ln1": L.norm_template(cfg.d_model, cfg.norm),
            "attn": L.attention_template(cfg.d_model, T.attn_dims(cfg)),
            "ln2": L.norm_template(cfg.d_model, cfg.norm),
            "mlp": L.mlp_template(cfg.d_model, cfg.d_ff),
        },
        "units": T._stack_template(
            T._stack_template(_mamba_block_template(cfg), cfg.attn_period),
            units),
        "ln_f": L.norm_template(cfg.d_model, cfg.norm),
        "lm_head": ParamSpec((cfg.d_model, cfg.vocab_size), T.UNEMBED_AXES),
    }


def template(cfg: ModelConfig):
    return zamba_template(cfg) if cfg.family == "hybrid" else mamba_lm_template(cfg)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _mamba_block(cfg, bp, x):
    x = x + S.ssm_block(bp["ssm"], L.apply_norm(bp["ln"], x, eps=cfg.norm_eps), cfg)
    return constrain(x, "hidden")


def _mamba_block_prefill(cfg, bp, x, valid=None):
    y, state = S.ssm_block(bp["ssm"], L.apply_norm(bp["ln"], x, eps=cfg.norm_eps),
                           cfg, return_state=True, valid_mask=valid)
    return constrain(x + y, "hidden"), state


def _mamba_block_step(cfg, bp, x, state):
    y, new_state = S.ssm_decode_step(
        bp["ssm"], L.apply_norm(bp["ln"], x, eps=cfg.norm_eps), state, cfg)
    return x + y, new_state


def _shared_attn_apply(cfg, sp, x, positions, kv_cache=None, cache_offset=None,
                       kv_start=None):
    h, new_cache = L.attention(
        sp["attn"], L.apply_norm(sp["ln1"], x, eps=cfg.norm_eps),
        T.attn_dims(cfg), positions=positions,
        rope_theta=cfg.rope_theta if cfg.use_rope else 0.0,
        kv_cache=kv_cache, cache_offset=cache_offset,
        p_dtype=jnp.dtype(cfg.attn_p_dtype),
        attn_impl=cfg.attention_impl, kv_start=kv_start)
    x = x + h
    x = x + L.mlp(sp["mlp"], L.apply_norm(sp["ln2"], x, eps=cfg.norm_eps))
    return constrain(x, "hidden"), new_cache


# ---------------------------------------------------------------------------
# Forward (train / scoring)
# ---------------------------------------------------------------------------

def forward_hidden(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = T._embed(cfg, params, tokens)

    if cfg.family == "ssm":
        def body(x, bp):
            return _mamba_block(cfg, bp, x), None
        x, _ = T.layer_scan(T._maybe_remat(cfg, body), x, params["blocks"])
    else:
        pos = T._positions(b, s)

        def unit_body(x, unit_params):
            x, _ = _shared_attn_apply(cfg, params["shared_attn"], x, pos)

            def inner(xx, bp):
                return _mamba_block(cfg, bp, xx), None
            x, _ = T.layer_scan(inner, x, unit_params)
            return x, None

        x, _ = T.layer_scan(T._maybe_remat(cfg, unit_body), x, params["units"])
    return x, jnp.float32(0.0)


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    x, aux = forward_hidden(cfg, params, batch)
    return T._unembed(cfg, params, x), aux


# ---------------------------------------------------------------------------
# Decode (recurrent states; hybrid adds shared-attn KV per unit)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = jnp.dtype(dtype or cfg.dtype)
    if cfg.family == "ssm":
        states = S.ssm_state_init(cfg, batch, dtype)
        return {"ssm": jax.tree_util.tree_map(
            lambda z: jnp.broadcast_to(z, (cfg.num_layers,) + z.shape).copy(), states)}
    units = cfg.num_layers // cfg.attn_period
    states = S.ssm_state_init(cfg, batch, dtype)
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "ssm": jax.tree_util.tree_map(
            lambda z: jnp.broadcast_to(z, (units, cfg.attn_period) + z.shape).copy(),
            states),
        "self": (jnp.zeros((units, batch, max_len, kvh, hd), dtype),
                 jnp.zeros((units, batch, max_len, kvh, hd), dtype)),
    }


def prefill(cfg: ModelConfig, params, batch, cache):
    tokens = batch["tokens"]
    b, s = tokens.shape
    kv_start = batch.get("kv_start")
    x = T._embed(cfg, params, tokens)
    pos = (T._positions(b, s) if kv_start is None
           else T._ragged_positions(s, kv_start))
    # Ragged batches: left-pad columns must not perturb the recurrent state.
    # SSD contributions are linear in the (post-conv) inputs, so zeroing the
    # pad columns inside the SSM block makes the state entering the first
    # real token exactly the zero init — see ssm_block(valid_mask=...).
    valid = None if kv_start is None else (
        jnp.arange(s, dtype=jnp.int32)[None, :] >= kv_start[:, None])
    # A Python 0, not a traced one: under jit a jnp scalar is a tracer, and
    # attention routes to the flash kernel only for a static zero offset.
    offset = 0

    if cfg.family == "ssm":
        # Full-sequence SSD pass; the chunked kernel also yields the exact
        # recurrent state after the last position for decode hand-off.
        def body(x, bp):
            x, state = _mamba_block_prefill(cfg, bp, x, valid=valid)
            return x, state
        x, new_states = T.layer_scan(body, x, params["blocks"])
        logits = T._unembed(cfg, params, x[:, -1:, :])[:, 0]
        return logits, {"ssm": jax.tree_util.tree_map(
            lambda old, new: new.astype(old.dtype), cache["ssm"], new_states)}

    def unit_body(carry, xs):
        x = carry
        unit_params, (ck, cv) = xs
        x, new_kv = _shared_attn_apply(cfg, params["shared_attn"], x, pos,
                                       kv_cache=(ck, cv), cache_offset=offset,
                                       kv_start=kv_start)

        def inner(xx, bp):
            return _mamba_block_prefill(cfg, bp, xx, valid=valid)
        x, states = T.layer_scan(inner, x, unit_params)
        return x, (states, new_kv)

    x, (new_states, new_self) = T.layer_scan(
        T._maybe_remat(cfg, unit_body), x, (params["units"], cache["self"]))
    logits = T._unembed(cfg, params, x[:, -1:, :])[:, 0]
    new_states = jax.tree_util.tree_map(
        lambda old, new: new.astype(old.dtype), cache["ssm"], new_states)
    return logits, {"ssm": new_states, "self": new_self}


def decode_step(cfg: ModelConfig, params, tokens, cache, offset, kv_start=None):
    b = tokens.shape[0]
    x = T._embed(cfg, params, tokens)
    if kv_start is None:
        pos = jnp.broadcast_to(offset.astype(jnp.int32), (b, 1))
    else:
        pos = jnp.maximum(offset.astype(jnp.int32) - kv_start, 0)[:, None]

    if cfg.family == "ssm":
        def body(x, xs):
            bp, state = xs
            x, new_state = _mamba_block_step(cfg, bp, x, state)
            return x, new_state
        x, new_states = T.layer_scan(body, x, (params["blocks"], cache["ssm"]))
        logits = T._unembed(cfg, params, x)[:, 0]
        return logits, {"ssm": new_states}

    def unit_body(carry, xs):
        x = carry
        unit_params, states, (ck, cv) = xs
        x, new_kv = _shared_attn_apply(cfg, params["shared_attn"], x, pos,
                                       kv_cache=(ck, cv), cache_offset=offset,
                                       kv_start=kv_start)

        def inner(xx, ys):
            bp, st = ys
            xx, new_st = _mamba_block_step(cfg, bp, xx, st)
            return xx, new_st
        x, new_states = T.layer_scan(inner, x, (unit_params, states))
        return x, (new_states, new_kv)

    x, (new_states, new_self) = T.layer_scan(
        unit_body, x, (params["units"], cache["ssm"], cache["self"]))
    logits = T._unembed(cfg, params, x)[:, 0]
    return logits, {"ssm": new_states, "self": new_self}
