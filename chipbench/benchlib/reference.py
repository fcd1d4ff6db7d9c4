"""Plain reference of a dense decoder: float32, highest matmul precision,
one sequence at a time, layer by layer, queries in blocks.

It follows the published architecture (RMSNorm, grouped-query attention
with rotary positions on the first ``rope_dims`` of each head, optional
QKV bias, SwiGLU feed-forward, untied LM head) and imports nothing of the
program.  One departure, stated in the configuration files: the rotary
pairs are (i, i + rope_dims/2), not chatglm's interleaved (2i, 2i+1).  The
two differ by a fixed permutation of the query and key columns, which
random weights make immaterial.

``control=True`` computes the same forward with every matmul's operands
rounded to float8 (e4m3, one scale per row of activations and per column of
weights): the step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: queries per attention block, and the multiple sequences are padded to
Q_BLOCK = 512
PAD_TO = 1024


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control: bool):
    """x (S, K) @ w (K, N) in float32."""
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta, rot):
    """Rotate pairs (i, i + rot/2) of the first ``rot`` dims of x (S, H, d)."""
    half = rot // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs        # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _f32(a):
    return a.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _layer(x, blocks, layer, *, arch, control):
    """One decoder layer on x (S, D) float32."""
    a = dict(arch)
    s = x.shape[0]
    h, kvh, hd = a["heads"], a["kv_heads"], a["head_dim"]
    w = jax.tree_util.tree_map(lambda t: _f32(t[layer]), blocks)
    at = w["attn"]
    y = _rms(x, w["ln1"]["scale"], a["norm_eps"])
    q, k, v = (_mm(y, at[n], control) for n in ("wq", "wk", "wv"))
    if a["qkv_bias"]:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    pos = jnp.arange(s)
    q = _rope(q.reshape(s, h, hd), pos, a["rope_theta"], a["rope_dims"])
    k = _rope(k.reshape(s, kvh, hd), pos, a["rope_theta"], a["rope_dims"])
    v = v.reshape(s, kvh, hd)
    g = h // kvh
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, kvh, g, hd)

    def block(args):
        i, qi = args                                # qi (Q_BLOCK, kvh, g, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HIGHEST)
        sc = sc / np.sqrt(hd)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, :] <= rows[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb)).reshape(s, h * hd)
    x = x + _mm(o, at["wo"], control)
    y = _rms(x, w["ln2"]["scale"], a["norm_eps"])
    m = w["mlp"]
    f = jax.nn.silu(_mm(y, m["w_gate"], control)) * _mm(y, m["w_up"], control)
    return x + _mm(f, m["w_down"], control)


@functools.partial(jax.jit, static_argnames=("arch", "control"))
def _head(x, ln_f, lm_head, rows, *, arch, control):
    a = dict(arch)
    y = _rms(x[rows], _f32(ln_f), a["norm_eps"])
    return _mm(y, _f32(lm_head), control)


def logits_at(weights, arch: dict, tokens, rows, control: bool = False):
    """Reference logits (len(rows), V) float32 at sequence positions
    ``rows`` of ``tokens``."""
    n = len(tokens)
    s = -(-n // PAD_TO) * PAD_TO          # trailing pad: causal, never seen
    toks = np.zeros((s,), np.int32)
    toks[:n] = tokens
    key = tuple(sorted(arch.items()))
    x = _f32(weights["embedding"][jnp.asarray(toks)])
    for layer in range(arch["layers"]):
        x = _layer(x, weights["blocks"], layer, arch=key, control=control)
    return _head(x, weights["ln_f"]["scale"], weights["lm_head"],
                 jnp.asarray(np.asarray(rows, np.int32)), arch=key,
                 control=control)


def token_gaps(weights, arch: dict, prompt, served, control: bool = False):
    """Per served token, how far its reference logit lies below the
    reference's best, in standard deviations of that position's reference
    logits.  With ``control``, the token scored at each position is the one
    the float8 forward puts first, not the served one."""
    tokens = list(prompt) + list(served)
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    ref = logits_at(weights, arch, tokens, rows)
    if control:
        pick = jnp.argmax(logits_at(weights, arch, tokens, rows, True), -1)
    else:
        pick = jnp.asarray(np.asarray(served, np.int32))
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    gap = (ref.max(-1) - got) / ref.std(-1)
    return np.asarray(jax.device_get(gap), np.float64)
