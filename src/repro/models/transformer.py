"""Decoder-only transformer LM (dense / MoE / VLM cross-attn) + Whisper
enc-dec — all built from the shared layers and the single-source GEMM.

Layers are stacked (leading "layer" axis) and executed with ``jax.lax.scan``
(+ optional ``jax.checkpoint``), which keeps compile time flat across the
40-cell dry-run and is the memory-efficient choice on TPU.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import allreduce_repeat, matmul
from repro.distributed.ctx import constrain
from repro.models import layers as L
from repro.models import moe as M
from repro.models.params import ParamSpec


#: logical axes of the unembedding weight (``lm_head``, or the tied
#: embedding table transposed)
UNEMBED_AXES = ("embed", "vocab")


def attn_dims(cfg: ModelConfig) -> L.AttnDims:
    return L.AttnDims(cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim)


def _stack_template(t, n: int):
    """Prepend a 'layer' axis of size n to every ParamSpec in ``t``."""
    def f(spec: ParamSpec):
        return ParamSpec((n,) + spec.shape, ("layer",) + spec.axes,
                         init=spec.init, scale=spec.scale, dtype=spec.dtype)
    return jax.tree_util.tree_map(f, t, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------

def _dense_block_template(cfg: ModelConfig):
    qkv_bias = cfg.name.startswith("chatglm")  # ChatGLM uses QKV bias
    t = {
        "ln1": L.norm_template(cfg.d_model, cfg.norm),
        "attn": L.attention_template(cfg.d_model, attn_dims(cfg), qkv_bias),
        "ln2": L.norm_template(cfg.d_model, cfg.norm),
    }
    if cfg.num_experts:
        t["moe"] = M.moe_template(cfg.d_model, cfg.d_ff, cfg.num_experts)
    else:
        t["mlp"] = L.mlp_template(cfg.d_model, cfg.d_ff)
    return t


def _cross_block_template(cfg: ModelConfig):
    return {
        "ln1": L.norm_template(cfg.d_model, cfg.norm),
        "cross": L.attention_template(cfg.d_model, attn_dims(cfg)),
        "ln2": L.norm_template(cfg.d_model, cfg.norm),
        "mlp": L.mlp_template(cfg.d_model, cfg.d_ff),
    }


def template(cfg: ModelConfig):
    t: Dict[str, Any] = {
        "embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), scale=0.02),
        "ln_f": L.norm_template(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                 UNEMBED_AXES)
    if cfg.family == "vlm":
        units = cfg.num_layers // cfg.cross_attn_period
        per_unit = cfg.cross_attn_period - 1
        t["units"] = {
            "selfs": _stack_template(
                _stack_template(_dense_block_template(cfg), per_unit), units),
            "cross": _stack_template(_cross_block_template(cfg), units),
        }
    elif cfg.family == "audio":
        t["enc_blocks"] = _stack_template(_encoder_block_template(cfg),
                                          cfg.encoder_layers)
        t["enc_ln_f"] = L.norm_template(cfg.d_model, cfg.norm)
        t["dec_blocks"] = _stack_template(_whisper_dec_block_template(cfg),
                                          cfg.num_layers)
        t["pos_emb"] = ParamSpec((cfg.learned_positions, cfg.d_model),
                                 (None, "embed"), scale=0.02)
    else:
        t["blocks"] = _stack_template(_dense_block_template(cfg), cfg.num_layers)
    return t


def _encoder_block_template(cfg: ModelConfig):
    return {
        "ln1": L.norm_template(cfg.d_model, cfg.norm),
        "attn": L.attention_template(cfg.d_model, attn_dims(cfg), qkv_bias=True),
        "ln2": L.norm_template(cfg.d_model, cfg.norm),
        "mlp": L.mlp_gelu_template(cfg.d_model, cfg.d_ff),
    }


def _whisper_dec_block_template(cfg: ModelConfig):
    return {
        "ln1": L.norm_template(cfg.d_model, cfg.norm),
        "attn": L.attention_template(cfg.d_model, attn_dims(cfg), qkv_bias=True),
        "ln_x": L.norm_template(cfg.d_model, cfg.norm),
        "cross": L.attention_template(cfg.d_model, attn_dims(cfg), qkv_bias=True),
        "ln2": L.norm_template(cfg.d_model, cfg.norm),
        "mlp": L.mlp_gelu_template(cfg.d_model, cfg.d_ff),
    }


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _dense_block(cfg: ModelConfig, bp, x, positions, kv_cache=None,
                 cache_offset=None, kv_start=None):
    dims = attn_dims(cfg)
    h, new_cache = L.attention(
        bp["attn"], L.apply_norm(bp["ln1"], x, eps=cfg.norm_eps), dims,
        positions=positions,
        rope_theta=cfg.rope_theta if cfg.use_rope else 0.0,
        rope_fraction=cfg.rope_fraction,
        kv_cache=kv_cache, cache_offset=cache_offset,
        p_dtype=jnp.dtype(cfg.attn_p_dtype),
        attn_impl=cfg.attention_impl, kv_start=kv_start)
    x = x + h
    y_in = L.apply_norm(bp["ln2"], x, eps=cfg.norm_eps)
    if cfg.num_experts:
        y, aux = M.moe_layer(
            bp["moe"], y_in, top_k=cfg.experts_per_token,
            num_experts=cfg.num_experts,
            capacity_factor=cfg.moe_capacity_factor)
    else:
        y, aux = L.mlp(bp["mlp"], y_in), 0.0
    return x + y, new_cache, aux


def _cross_block(cfg: ModelConfig, bp, x, cross_kv_pair):
    dims = attn_dims(cfg)
    h, _ = L.attention(
        bp["cross"], L.apply_norm(bp["ln1"], x, eps=cfg.norm_eps), dims,
        kv_override=cross_kv_pair, p_dtype=jnp.dtype(cfg.attn_p_dtype))
    x = x + h
    y = L.mlp(bp["mlp"], L.apply_norm(bp["ln2"], x, eps=cfg.norm_eps))
    return x + y


def _maybe_remat(cfg: ModelConfig, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        # keep every matmul output resident; recompute only cheap elementwise
        # ops in the backward — trades HBM capacity for HBM traffic.
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def layer_scan(fn, init, xs):
    """``jax.lax.scan`` over a stack's leading layer (or unit) axis.  A
    scan traces its body once and runs it once per layer, so what the
    body's explicit all-reduces add to an open ``allreduce_tally`` is
    scaled by that trip count (nested scans multiply)."""
    with allreduce_repeat(jax.tree_util.tree_leaves(xs)[0].shape[0]):
        return jax.lax.scan(fn, init, xs)


# ---------------------------------------------------------------------------
# Decoder-only stacks (dense / moe)
# ---------------------------------------------------------------------------

def _run_dense_stack(cfg, blocks, x, positions, caches=None, cache_offset=None,
                     kv_start=None):
    """scan over stacked layer params (+ caches).  Returns (x, new_caches, aux)."""
    has_cache = caches is not None

    def body(carry, xs):
        x, aux = carry
        bp = xs[0] if has_cache else xs
        cache = xs[1] if has_cache else None
        x, new_cache, a = _dense_block(cfg, bp, x, positions,
                                       kv_cache=cache, cache_offset=cache_offset,
                                       kv_start=kv_start)
        return (constrain(x, "hidden"), aux + a), new_cache

    xs = (blocks, caches) if has_cache else blocks
    (x, aux), new_caches = layer_scan(_maybe_remat(cfg, body), (x, 0.0), xs)
    return x, (new_caches if has_cache else None), aux


# ---------------------------------------------------------------------------
# Public API per family
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens):
    x = params["embedding"][tokens].astype(jnp.dtype(cfg.dtype))
    return constrain(x, "hidden")


def _unembed(cfg, params, x):
    x = L.apply_norm(params["ln_f"], x, eps=cfg.norm_eps)
    w = params["embedding"].T if cfg.tie_embeddings else params["lm_head"]
    return constrain(matmul(x, w.astype(x.dtype), out_dtype=jnp.float32,
                            w_axes=UNEMBED_AXES), "logits")


def _positions(batch: int, seq: int, offset=0):
    return offset + jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                     (batch, seq))


def _ragged_positions(seq: int, kv_start):
    """Per-row positions for a left-padded ragged batch: the first real token
    of every row sits at position 0 (pad columns clamp to 0 — they're masked
    out of attention anyway)."""
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] - kv_start[:, None]
    return jnp.maximum(pos, 0)


def forward_hidden(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    """Training/scoring trunk -> (final hidden pre-norm (B,S,D), aux_loss)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed(cfg, params, tokens)
    pos = _positions(b, s)
    if cfg.family == "vlm":
        x, _, aux = _run_vlm_stack(cfg, params, x, pos,
                                   image_embeds=batch["image_embeds"])
    elif cfg.family == "audio":
        enc = _run_encoder(cfg, params, batch["encoder_embeds"])
        x = x + params["pos_emb"][:s][None].astype(x.dtype)
        x, _, aux = _run_whisper_decoder(cfg, params, x, pos, enc)
    else:
        x, _, aux = _run_dense_stack(cfg, params["blocks"], x, pos)
    return x, aux


def unembed_weight(cfg: ModelConfig, params):
    return params["embedding"].T if cfg.tie_embeddings else params["lm_head"]


def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array]):
    """Training/scoring forward -> (logits_f32 (B,S,V), aux_loss)."""
    x, aux = forward_hidden(cfg, params, batch)
    return _unembed(cfg, params, x), aux


# -- caches -----------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """KV cache pytree for decode.  Leading 'layer' axis matches the scans."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    kv_plain = lambda n, s: (jnp.zeros((n, batch, s, kvh, hd), dtype),
                             jnp.zeros((n, batch, s, kvh, hd), dtype))
    if cfg.kv_quant:
        def kv(n, s):
            one = {"q": jnp.zeros((n, batch, s, kvh, hd), jnp.int8),
                   "s": jnp.zeros((n, batch, s, kvh), jnp.float32)}
            return (one, jax.tree_util.tree_map(jnp.copy, one))
    else:
        kv = kv_plain
    if cfg.family == "vlm":
        units = cfg.num_layers // cfg.cross_attn_period
        per_unit = cfg.cross_attn_period - 1
        return {
            "self": (jnp.zeros((units, per_unit, batch, max_len, kvh, hd), dtype),
                     jnp.zeros((units, per_unit, batch, max_len, kvh, hd), dtype)),
            # cross caches hold projections recomputed at prefill — plain dtype
            "cross": kv_plain(units, cfg.num_image_tokens),
        }
    if cfg.family == "audio":
        return {"self": kv(cfg.num_layers, max_len),
                "cross": kv_plain(cfg.num_layers, cfg.encoder_len)}
    return {"self": kv(cfg.num_layers, max_len)}


def prefill(cfg: ModelConfig, params, batch, cache):
    """Run the prompt through the model, filling ``cache``.
    Returns (last-token logits (B, V), new_cache).

    ``batch["kv_start"]`` (optional, (B,) int32) marks per-row left-pad
    lengths for ragged batches: pad columns are masked out of attention and
    positions restart at 0 at each row's first real token, so every row
    computes exactly what it would alone (prompts are right-aligned, so the
    shared last column is each row's final prompt token)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    kv_start = batch.get("kv_start")
    x = _embed(cfg, params, tokens)
    pos = _positions(b, s) if kv_start is None else _ragged_positions(s, kv_start)
    # A Python 0, not a traced one: under jit a jnp scalar is a tracer, and
    # attention routes to the flash kernel only for a static zero offset.
    offset = 0
    if cfg.family == "vlm":
        cache = dict(cache)
        cache["cross"] = _vlm_cross_cache(cfg, params, batch["image_embeds"])
        x, new_self, _ = _run_vlm_stack(cfg, params, x, pos,
                                        cross_cache=cache["cross"],
                                        self_caches=cache["self"],
                                        cache_offset=offset,
                                        kv_start=kv_start)
        new_cache = {"self": new_self, "cross": cache["cross"]}
    elif cfg.family == "audio":
        enc = _run_encoder(cfg, params, batch["encoder_embeds"])
        cross = _whisper_cross_cache(cfg, params, enc)
        if kv_start is None:
            x = x + params["pos_emb"][:s][None].astype(x.dtype)
        else:  # per-row shifted learned positions
            x = x + params["pos_emb"][pos].astype(x.dtype)
        x, new_self, _ = _run_whisper_decoder(cfg, params, x, pos,
                                              enc, cross_cache=cross,
                                              self_caches=cache["self"],
                                              cache_offset=offset,
                                              kv_start=kv_start)
        new_cache = {"self": new_self, "cross": cross}
    else:
        x, new_self, _ = _run_dense_stack(cfg, params["blocks"], x, pos,
                                          caches=cache["self"],
                                          cache_offset=offset,
                                          kv_start=kv_start)
        new_cache = {"self": new_self}
    logits = _unembed(cfg, params, x[:, -1:, :])[:, 0]
    return logits, new_cache


def decode_step(cfg: ModelConfig, params, tokens, cache, offset, kv_start=None):
    """One token step.  tokens: (B, 1); offset: scalar int32 = current length.
    ``kv_start``: optional (B,) pad offsets for ragged batches (see prefill).
    Returns (logits (B, V), new_cache)."""
    b = tokens.shape[0]
    x = _embed(cfg, params, tokens)
    if kv_start is None:
        pos = jnp.broadcast_to(offset.astype(jnp.int32), (b, 1))
    else:
        pos = jnp.maximum(offset.astype(jnp.int32) - kv_start, 0)[:, None]
    if cfg.family == "vlm":
        x, new_self, _ = _run_vlm_stack(cfg, params, x, pos,
                                        cross_cache=cache["cross"],
                                        self_caches=cache["self"],
                                        cache_offset=offset,
                                        kv_start=kv_start)
        new_cache = {"self": new_self, "cross": cache["cross"]}
    elif cfg.family == "audio":
        if kv_start is None:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos_emb"], offset, 1, 0)[None].astype(x.dtype)
        else:
            x = x + params["pos_emb"][pos[:, 0]][:, None].astype(x.dtype)
        x, new_self, _ = _run_whisper_decoder(cfg, params, x, pos, None,
                                              cross_cache=cache["cross"],
                                              self_caches=cache["self"],
                                              cache_offset=offset,
                                              kv_start=kv_start)
        new_cache = {"self": new_self, "cross": cache["cross"]}
    else:
        x, new_self, _ = _run_dense_stack(cfg, params["blocks"], x, pos,
                                          caches=cache["self"],
                                          cache_offset=offset,
                                          kv_start=kv_start)
        new_cache = {"self": new_self}
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# VLM (llama-3.2-vision style): units of (P-1 self layers + 1 cross layer)
# ---------------------------------------------------------------------------

def _vlm_cross_cache(cfg, params, image_embeds):
    dims = attn_dims(cfg)
    def per_unit(cp):
        return L.cross_kv(cp["cross"], image_embeds.astype(jnp.dtype(cfg.dtype)), dims)
    ks, vs = jax.lax.map(per_unit, params["units"]["cross"])
    return ks, vs  # (U, B, n_img, kv, hd)


def _run_vlm_stack(cfg, params, x, positions, image_embeds=None,
                   cross_cache=None, self_caches=None, cache_offset=None,
                   kv_start=None):
    dims = attn_dims(cfg)
    if cross_cache is None:
        cross_cache = _vlm_cross_cache(cfg, params, image_embeds)
    has_cache = self_caches is not None

    def unit_body(carry, xs):
        x, aux = carry
        if has_cache:
            selfs, cross_p, ck, cv, scache = xs
        else:
            selfs, cross_p, ck, cv = xs
            scache = None

        def inner(c, ys):
            xx, a = c
            bp = ys[0] if has_cache else ys
            cache = ys[1] if has_cache else None
            xx, nc, da = _dense_block(cfg, bp, xx, positions, kv_cache=cache,
                                      cache_offset=cache_offset,
                                      kv_start=kv_start)
            return (constrain(xx, "hidden"), a + da), nc

        ys = (selfs, scache) if has_cache else selfs
        (x, aux), new_scache = layer_scan(inner, (x, aux), ys)
        x = constrain(_cross_block(cfg, cross_p, x, (ck, cv)), "hidden")
        out = new_scache if has_cache else 0.0
        return (x, aux), out

    u = params["units"]
    ks, vs = cross_cache
    xs = (u["selfs"], u["cross"], ks, vs) + ((self_caches,) if has_cache else ())
    (x, aux), new_caches = layer_scan(_maybe_remat(cfg, unit_body), (x, 0.0), xs)
    return x, (new_caches if has_cache else None), aux


# ---------------------------------------------------------------------------
# Whisper enc-dec
# ---------------------------------------------------------------------------

def _run_encoder(cfg, params, encoder_embeds):
    """encoder_embeds: (B, enc_len, D) — the conv-frontend STUB output."""
    x = encoder_embeds.astype(jnp.dtype(cfg.dtype))
    dims = attn_dims(cfg)

    def body(x, bp):
        h, _ = L.attention(bp["attn"], L.apply_norm(bp["ln1"], x, eps=cfg.norm_eps),
                           dims, causal=False,
                           p_dtype=jnp.dtype(cfg.attn_p_dtype),
                           attn_impl=cfg.attention_impl)
        x = x + h
        x = x + L.mlp_gelu(bp["mlp"], L.apply_norm(bp["ln2"], x, eps=cfg.norm_eps))
        return constrain(x, "hidden"), None

    x, _ = layer_scan(_maybe_remat(cfg, body), x, params["enc_blocks"])
    return L.apply_norm(params["enc_ln_f"], x, eps=cfg.norm_eps)


def _whisper_cross_cache(cfg, params, enc):
    dims = attn_dims(cfg)
    ks, vs = jax.lax.map(lambda bp: L.cross_kv(bp["cross"], enc, dims),
                         params["dec_blocks"])
    return ks, vs


def _run_whisper_decoder(cfg, params, x, positions, enc, cross_cache=None,
                         self_caches=None, cache_offset=None, kv_start=None):
    dims = attn_dims(cfg)
    if cross_cache is None:
        cross_cache = _whisper_cross_cache(cfg, params, enc)
    has_cache = self_caches is not None

    def body(carry, xs):
        x, aux = carry
        if has_cache:
            bp, ck, cv, cache = xs
        else:
            bp, ck, cv = xs
            cache = None
        h, new_cache = L.attention(
            bp["attn"], L.apply_norm(bp["ln1"], x, eps=cfg.norm_eps), dims,
            positions=positions, kv_cache=cache, cache_offset=cache_offset,
            p_dtype=jnp.dtype(cfg.attn_p_dtype),
            attn_impl=cfg.attention_impl, kv_start=kv_start)
        x = x + h
        h, _ = L.attention(bp["cross"],
                           L.apply_norm(bp["ln_x"], x, eps=cfg.norm_eps),
                           dims, kv_override=(ck, cv),
                           p_dtype=jnp.dtype(cfg.attn_p_dtype))
        x = x + h
        x = x + L.mlp_gelu(bp["mlp"], L.apply_norm(bp["ln2"], x, eps=cfg.norm_eps))
        return (constrain(x, "hidden"), aux), (new_cache if has_cache else 0.0)

    ks, vs = cross_cache
    xs = (params["dec_blocks"], ks, vs) + ((self_caches,) if has_cache else ())
    (x, aux), new_caches = layer_scan(_maybe_remat(cfg, body), (x, 0.0), xs)
    return x, (new_caches if has_cache else None), aux
