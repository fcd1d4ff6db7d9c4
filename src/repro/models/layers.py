"""Shared neural-net layers: norms, RoPE, GQA attention (chunked), MLP.

Every dense projection routes through ``core.matmul`` — the paper's
single-source GEMM — so per-architecture tile tuning applies to the whole
model zoo without touching this file.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import einsum, matmul
from repro.models.params import ParamSpec

logger = logging.getLogger(__name__)

#: logical axes of the two matmul weight layouts: column parallel (the
#: output dim splits over the tensor axis) and row parallel (the contracted
#: dim splits).  Templates declare them and matmul calls pass them on, so a
#: per-shard kernel on a mesh knows each weight's split.
COL = ("embed", "ff")
ROW = ("ff", "embed")

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_template(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), init="ones")}
    if kind == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), init="ones"),
                "bias": ParamSpec((d,), ("embed",), init="zeros")}
    raise ValueError(kind)


def apply_norm(params, x, *, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        xf = xf - mu
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (with partial-dim fraction, as in ChatGLM / StableLM)
# ---------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < d else out


# ---------------------------------------------------------------------------
# Attention (GQA, query-chunked for O(S * chunk) score memory, KV cache)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    num_heads: int
    num_kv_heads: int
    head_dim: int

    @property
    def group(self) -> int:
        return self.num_heads // self.num_kv_heads


def attention_template(d_model: int, dims: AttnDims, qkv_bias: bool = False):
    h, kv, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
    t = {
        "wq": ParamSpec((d_model, h * hd), COL),
        "wk": ParamSpec((d_model, kv * hd), COL),
        "wv": ParamSpec((d_model, kv * hd), COL),
        "wo": ParamSpec((h * hd, d_model), ROW),
    }
    if qkv_bias:
        t["bq"] = ParamSpec((h * hd,), ("ff",), init="zeros")
        t["bk"] = ParamSpec((kv * hd,), ("ff",), init="zeros")
        t["bv"] = ParamSpec((kv * hd,), ("ff",), init="zeros")
    return t


def _sdpa_chunked(q, k, v, *, causal: bool, q_offset, kv_len: Optional[jax.Array],
                  chunk: int = 1024, p_dtype=jnp.float32,
                  kv_start: Optional[jax.Array] = None) -> jax.Array:
    """Grouped scaled-dot-product attention, chunked over queries.

    q: (B, Sq, KV, G, hd);  k, v: (B, Skv, KV, hd)
    q_offset: scalar int — absolute position of q[0] (decode: cache length).
    kv_len: optional scalar — number of valid cache entries (<= Skv).
    kv_start: optional (B,) int32 — first valid cache column per row, for
      left-padded ragged batches (columns < kv_start[b] are pad and masked).
    """
    b, sq, kvh, g, hd = q.shape
    skv = k.shape[1]
    scale = hd ** -0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(p_dtype)
    col_ids = jnp.arange(skv)

    def one_chunk(q_c, row0):
        # q_c: (B, C, KV, G, hd)
        s = einsum("bqkgd,btkd->bqkgt", q_c.astype(jnp.float32) * scale, kf)
        mask = jnp.ones((q_c.shape[1], skv), jnp.bool_)
        if causal:
            rows = row0 + q_offset + jnp.arange(q_c.shape[1])
            mask &= col_ids[None, :] <= rows[:, None]
        if kv_len is not None:
            mask &= col_ids[None, :] < kv_len
        if kv_start is None:
            s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        else:  # per-row pad mask -> (B, C, Skv)
            maskb = mask[None] & (col_ids[None, None, :] >= kv_start[:, None, None])
            s = jnp.where(maskb[:, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(p_dtype)
        return einsum("bqkgt,btkd->bqkgd", p, vf).astype(q.dtype)

    if sq <= chunk:
        return one_chunk(q, 0)
    while sq % chunk:  # largest divisor <= chunk (e.g. whisper enc_len=1500)
        chunk -= 1
    n = sq // chunk
    qs = q.reshape(b, n, chunk, kvh, g, hd).swapaxes(0, 1)
    row0s = jnp.arange(n) * chunk
    out = jax.lax.map(lambda args: one_chunk(*args), (qs, row0s))
    return out.swapaxes(0, 1).reshape(b, sq, kvh, g, hd)


def kv_quantize(x: jax.Array):
    """Per-(token, head) symmetric int8 quantization of a (B,S,KV,hd) slab."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale[..., 0]


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# Attention-impl routing (chunked jnp vs Pallas flash kernel)
# ---------------------------------------------------------------------------

#: fallback reasons already logged this process (each is logged once)
_FLASH_FALLBACKS_LOGGED = set()


def _is_static_zero(x) -> bool:
    """True iff ``x`` is a compile-time-known zero (None counts).

    Traced values (tracers) raise on ``int()`` — broad except because the
    exact error type varies across JAX versions — and are treated as
    not-statically-zero.
    """
    if x is None:
        return True
    try:
        return int(x) == 0
    except Exception:
        return False


def flash_fallback_reason(*, causal: bool, seq_len: int,
                          cross_attention: bool,
                          cache_offset_static_zero: bool = True
                          ) -> Optional[str]:
    """Why a flash-requested attention call must use the chunked path.

    Returns ``None`` when the flash kernel applies.  The documented
    fallbacks (each logged once per process by :func:`attention`):

    * ``cross-attention`` — precomputed non-causal KV (``kv_override``);
      the flash kernel covers causal self-attention.
    * ``non-causal``      — e.g. encoder self-attention.
    * ``decode-step``     — single-query steps read the whole KV cache; the
      chunked path's cache-masked softmax is the decode kernel.
    * ``cached-continuation`` — multi-token step into a cache at an offset
      not statically known to be zero: it must attend the whole cache
      prefix, which the flash path (fresh prefill columns only) does not
      cover.

    Note what is *not* here: ``kv_cache is not None`` alone.  Prefill runs
    with a cache to fill (at offset 0), but attends over exactly the tokens
    it just projected — the flash kernel handles it (ragged rows included
    via ``kv_start``).  The old routing silently fell back whenever a cache
    was present, which excluded serving prefill entirely.
    """
    if cross_attention:
        return "cross-attention"
    if not causal:
        return "non-causal"
    if seq_len == 1:
        return "decode-step"
    if not cache_offset_static_zero:
        return "cached-continuation"
    return None


def _log_flash_fallback(reason: str) -> None:
    if reason not in _FLASH_FALLBACKS_LOGGED:
        _FLASH_FALLBACKS_LOGGED.add(reason)
        logger.info("flash attention requested but falling back to the "
                    "chunked path: %s (logged once)", reason)


def cross_kv(params, src: jax.Array, dims: AttnDims):
    """Project encoder/image embeddings to the (static) cross K/V once."""
    b = src.shape[0]
    k = matmul(src, params["wk"], bias=params.get("bk"), w_axes=COL
               ).reshape(b, -1, dims.num_kv_heads, dims.head_dim)
    v = matmul(src, params["wv"], bias=params.get("bv"), w_axes=COL
               ).reshape(b, -1, dims.num_kv_heads, dims.head_dim)
    return k, v


def attention(
    params,
    x: jax.Array,
    dims: AttnDims,
    *,
    positions: Optional[jax.Array] = None,
    rope_theta: float = 0.0,
    rope_fraction: float = 1.0,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_offset: Optional[jax.Array] = None,
    causal: bool = True,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
    q_chunk: int = 1024,
    p_dtype=jnp.float32,
    attn_impl: str = "chunked",
    kv_start: Optional[jax.Array] = None,
):
    """Returns (out, new_kv_cache_or_None).

    * self-attention: KV projected from ``x``; if ``kv_cache`` is given the
      new KV is written at ``cache_offset`` and attention runs on the cache.
    * cross-attention: pass precomputed ``kv_override`` (from ``cross_kv``);
      non-causal, cache untouched.
    * ragged batches: ``kv_start`` (B,) marks the first non-pad column per
      row (left padding); pad columns are excluded from every softmax.
    * ``attn_impl="flash"`` routes every eligible call — causal
      self-attention with more than one query, i.e. training forwards AND
      serving/scoring prefill (cache present, ragged rows included) —
      through the tuned Pallas flash kernel
      (:func:`repro.core.flash_attention`).  Ineligible calls fall back to
      the chunked path with the reason logged once
      (:func:`flash_fallback_reason`).
    """
    b, s, _ = x.shape
    h, kvh, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim

    use_flash = False
    if attn_impl == "flash":
        reason = flash_fallback_reason(
            causal=causal, seq_len=s,
            cross_attention=kv_override is not None,
            cache_offset_static_zero=(kv_cache is None
                                      or _is_static_zero(cache_offset)))
        if reason is None:
            use_flash = True
        else:
            _log_flash_fallback(reason)

    q = matmul(x, params["wq"], bias=params.get("bq"), w_axes=COL)
    q = q.reshape(b, s, h, hd)

    if kv_override is not None:
        k, v = kv_override
        qg = q.reshape(b, s, kvh, dims.group, hd)
        out = _sdpa_chunked(qg, k, v, causal=False, q_offset=0,
                            kv_len=None, chunk=q_chunk, p_dtype=p_dtype)
        return matmul(out.reshape(b, s, h * hd), params["wo"],
                      w_axes=ROW), None

    k = matmul(x, params["wk"], bias=params.get("bk"), w_axes=COL
               ).reshape(b, s, kvh, hd)
    v = matmul(x, params["wv"], bias=params.get("bv"), w_axes=COL
               ).reshape(b, s, kvh, hd)
    if rope_theta:
        q = apply_rope(q, positions, theta=rope_theta, fraction=rope_fraction)
        k = apply_rope(k, positions, theta=rope_theta, fraction=rope_fraction)

    new_cache = None
    kv_len = None
    q_offset = 0
    if kv_cache is not None:
        ck, cv = kv_cache
        if isinstance(ck, dict):   # int8-quantized cache: {"q": i8, "s": f32}
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            ck = {"q": jax.lax.dynamic_update_slice(ck["q"], kq, (0, cache_offset, 0, 0)),
                  "s": jax.lax.dynamic_update_slice(ck["s"], ks, (0, cache_offset, 0))}
            cv = {"q": jax.lax.dynamic_update_slice(cv["q"], vq, (0, cache_offset, 0, 0)),
                  "s": jax.lax.dynamic_update_slice(cv["s"], vs, (0, cache_offset, 0))}
            k = kv_dequantize(ck["q"], ck["s"], k.dtype)
            v = kv_dequantize(cv["q"], cv["s"], v.dtype)
        else:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_offset, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_offset, 0, 0))
            k, v = ck, cv
        q_offset = cache_offset
        kv_len = cache_offset + s
        new_cache = (ck, cv)

    if use_flash:
        # Tuned Pallas flash kernel (training forward or prefill).  With a
        # cache present the routing above guarantees cache_offset is a
        # static 0 (prefill): attend over exactly the s freshly-written
        # columns — sliced from the cache so a quantized cache's
        # dequantization round-trip matches the chunked path bit-for-bit.
        # Ragged left-padded rows mask via kv_start.
        kf, vf = (k[:, :s], v[:, :s]) if kv_cache is not None else (k, v)
        from repro.core import flash_attention as tuned_flash
        out = tuned_flash(q, kf, vf, causal=causal, kv_start=kv_start)
        return (matmul(out.reshape(b, s, h * hd), params["wo"], w_axes=ROW),
                new_cache)

    qg = q.reshape(b, s, kvh, dims.group, hd)
    out = _sdpa_chunked(qg, k, v, causal=causal, q_offset=q_offset,
                        kv_len=kv_len, chunk=q_chunk, p_dtype=p_dtype,
                        kv_start=kv_start)
    out = out.reshape(b, s, h * hd)
    return matmul(out, params["wo"], w_axes=ROW), new_cache


# ---------------------------------------------------------------------------
# Gated MLP (llama-style SwiGLU) — fused activation epilogues via the kernel
# ---------------------------------------------------------------------------

def mlp_template(d_model: int, d_ff: int):
    return {
        "w_gate": ParamSpec((d_model, d_ff), COL),
        "w_up": ParamSpec((d_model, d_ff), COL),
        "w_down": ParamSpec((d_ff, d_model), ROW),
    }


def mlp(params, x: jax.Array) -> jax.Array:
    gate = matmul(x, params["w_gate"], activation="silu", w_axes=COL)
    up = matmul(x, params["w_up"], w_axes=COL)
    return matmul(gate * up, params["w_down"], w_axes=ROW)


def mlp_gelu_template(d_model: int, d_ff: int):
    """Whisper-style 2-matrix GELU MLP (with biases)."""
    return {
        "w_up": ParamSpec((d_model, d_ff), COL),
        "b_up": ParamSpec((d_ff,), ("ff",), init="zeros"),
        "w_down": ParamSpec((d_ff, d_model), ROW),
        "b_down": ParamSpec((d_model,), ("embed",), init="zeros"),
    }


def mlp_gelu(params, x: jax.Array) -> jax.Array:
    h = matmul(x, params["w_up"], bias=params["b_up"], activation="gelu",
               w_axes=COL)
    return matmul(h, params["w_down"], bias=params["b_down"], w_axes=ROW)
