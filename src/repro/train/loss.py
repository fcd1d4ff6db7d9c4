"""Cross-entropy loss with token-chunked unembedding.

For the large-vocab archs (moonshot: 163 840), materializing full
(B, S, V) f32 logits dominates activation memory.  ``chunked_ce`` streams
the unembed GEMM + CE over sequence chunks under ``jax.checkpoint``, so peak
logits memory is (B, chunk, V) in both fwd and bwd — a memory-roofline
optimization recorded in §Perf.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import matmul
from repro.distributed.ctx import constrain
from repro.models.transformer import UNEMBED_AXES

Z_LOSS_WEIGHT = 1e-4
MOE_AUX_WEIGHT = 1e-2


def _ce_block(x, w, labels):
    """x: (B, C, D) final-normed hidden; w: (D, V); labels: (B, C)."""
    logits = constrain(matmul(x, w.astype(x.dtype), out_dtype=jnp.float32,
                              w_axes=UNEMBED_AXES), "logits")
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (lse - picked).sum()
    z = jnp.square(lse).sum()
    return ce, z


def chunked_ce(x, w, labels, *, chunk: int = 0) -> Tuple[jax.Array, jax.Array]:
    """-> (sum CE over tokens, sum z-loss).  chunk=0 -> single pass."""
    b, s, d = x.shape
    if chunk <= 0 or s <= chunk or s % chunk != 0:
        return _ce_block(x, w, labels)
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, chunk).swapaxes(0, 1)

    def body(carry, xs_t):
        ce_acc, z_acc = carry
        xc, lc = xs_t
        ce, z = jax.checkpoint(_ce_block)(xc, w, lc)
        return (ce_acc + ce, z_acc + z), None

    (ce, z), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (xs, ls))
    return ce, z


def lm_loss(model, params, batch, *, logit_chunk: Optional[int] = None):
    """Next-token LM loss.  batch['tokens'] (B, S), batch['labels'] (B, S).

    -> (loss scalar, metrics dict)."""
    hidden, aux = model.forward_hidden(params, batch)
    hidden = model.final_norm(params, hidden)
    w = model.unembed_weight(params)
    chunk = model.cfg.logit_chunk if logit_chunk is None else logit_chunk
    ce_sum, z_sum = chunked_ce(hidden, w, batch["labels"], chunk=chunk)
    ntok = batch["labels"].size
    ce = ce_sum / ntok
    z = z_sum / ntok
    loss = ce + Z_LOSS_WEIGHT * z + MOE_AUX_WEIGHT * aux
    return loss, {"ce": ce, "z_loss": z, "moe_aux": aux,
                  "perplexity": jnp.exp(ce)}
