"""Random weights of a dense decoder, made by the benchmark from the seed.

The weights are drawn on the device in one jitted call, leaf by leaf from
``fold_in(key, leaf)`` and layer by layer inside stacked leaves, in the
dtype they are served in.  They are the benchmark's: the plain reference
reads the same arrays, and nothing the program makes.

The tree has the names and shapes of the program's parameter tree; the
benchmark checks that layout against :data:`DENSE_LEAVES` before it draws
anything, so a program whose layout moved fails here and not in the
comparison.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

#: every leaf of a dense decoder with QKV bias (chatglm3) or without (yi):
#: name -> shape, in the published sizes (L layers, D model width, Q query
#: width, KV key/value width, F feed-forward width, V vocabulary)
DENSE_LEAVES = {
    "embedding": ("V", "D"),
    "ln_f/scale": ("D",),
    "lm_head": ("D", "V"),
    "blocks/ln1/scale": ("L", "D"),
    "blocks/attn/wq": ("L", "D", "Q"),
    "blocks/attn/wk": ("L", "D", "KV"),
    "blocks/attn/wv": ("L", "D", "KV"),
    "blocks/attn/wo": ("L", "Q", "D"),
    "blocks/attn/bq": ("L", "Q"),
    "blocks/attn/bk": ("L", "KV"),
    "blocks/attn/bv": ("L", "KV"),
    "blocks/ln2/scale": ("L", "D"),
    "blocks/mlp/w_gate": ("L", "D", "F"),
    "blocks/mlp/w_up": ("L", "D", "F"),
    "blocks/mlp/w_down": ("L", "F", "D"),
}
BIAS_LEAVES = ("blocks/attn/bq", "blocks/attn/bk", "blocks/attn/bv")


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def expected_shapes(arch: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf name -> shape, from the published sizes of a configuration."""
    sizes = {"L": arch["layers"], "D": arch["d_model"],
             "Q": arch["heads"] * arch["head_dim"],
             "KV": arch["kv_heads"] * arch["head_dim"],
             "F": arch["d_ff"], "V": arch["vocab"]}
    leaves = dict(DENSE_LEAVES)
    if not arch["qkv_bias"]:
        for name in BIAS_LEAVES:
            leaves.pop(name)
    return {k: tuple(sizes[s] for s in v) for k, v in leaves.items()}


def check_layout(abstract, arch: dict) -> None:
    """Raise unless the program's parameter tree is the published layout."""
    got = {leaf_name(p): tuple(x.shape) for p, x in
           jax.tree_util.tree_flatten_with_path(abstract)[0]}
    want = expected_shapes(arch)
    if got != want:
        raise SystemExit(f"chipbench: parameter layout differs from the "
                         f"published sizes: program {got}, expected {want}")


def _std(name: str, shape) -> Tuple[float, float]:
    """(mean, std) of one leaf: matrices N(0, 1/fan_in), the embedding
    N(0, 1), norm scales N(1, 0.1^2), biases N(0, 0.1^2)."""
    if name.endswith("scale"):
        return 1.0, 0.1
    if name in BIAS_LEAVES:
        return 0.0, 0.1
    if name == "embedding":
        return 0.0, 1.0
    return 0.0, float(shape[-2]) ** -0.5


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make_weights(abstract, seed: int, dtype, shardings=None):
    """Draw every leaf of ``abstract`` in ``dtype``, in one jitted call: on
    the default device, or placed by ``shardings`` (a tree like
    ``abstract``), each chip drawing its own shards."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [leaf_name(p) for p, _ in flat]
    shapes = [tuple(x.shape) for _, x in flat]
    dtype = jnp.dtype(dtype)

    def draw(key, name, shape):
        mean, std = _std(name, shape)

        def one(k, shp):
            return (mean + std * jax.random.normal(k, shp, jnp.float32)
                    ).astype(dtype)

        if name.startswith("blocks/"):
            # stacked layers: one layer at a time keeps the f32 draw small
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(lambda k: one(k, shape[1:]), keys)
        return one(key, shape)

    out = (None if shardings is None
           else jax.tree_util.tree_leaves(shardings))

    @functools.partial(jax.jit, out_shardings=out)
    def make(key):
        return [draw(jax.random.fold_in(key, i), n, s)
                for i, (n, s) in enumerate(zip(names, shapes))]

    leaves = make(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
