"""Parameter templates: single source of truth for shapes, shardings, init.

Every model module builds a pytree of ``ParamSpec`` (shape + logical axes +
init rule).  From that one template we derive
  * randomly initialized parameters        (``init_params``)
  * ``jax.ShapeDtypeStruct`` stand-ins     (``abstract_params`` — dry-run)
  * ``PartitionSpec`` sharding pytrees     (``distributed.sharding``)
so shapes/shardings can never drift apart.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis name per dim (or None)
    init: str = "normal"                 # normal | zeros | ones
    scale: Optional[float] = None        # stddev; None -> 1/sqrt(fan_in)
    dtype: Optional[str] = None          # override model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _leaf_key(key: jax.Array, path: str) -> jax.Array:
    digest = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "big")
    return jax.random.fold_in(key, digest)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def init_params(template, key: jax.Array, default_dtype: str = "float32",
                shardings=None):
    """Materialize random parameters from a template pytree.

    One jitted program draws every leaf.  ``shardings`` (a pytree of
    ``NamedSharding`` aligned with the template, e.g. from
    ``distributed.sharding.param_shardings``) become its output shardings:
    every device draws only its own shards, so a model larger than one
    device's memory never lands whole on device 0.  Values are bit-identical
    to the unsharded init, only the layout differs, which is what keeps
    1-device vs N-device runs token-for-token comparable.
    """
    def build(key):
        def init_leaf(path, spec: ParamSpec):
            dtype = jnp.dtype(spec.dtype or default_dtype)
            if spec.init == "zeros":
                return jnp.zeros(spec.shape, dtype)
            if spec.init == "ones":
                return jnp.ones(spec.shape, dtype)
            fan_in = (spec.shape[-2] if len(spec.shape) >= 2
                      else max(spec.shape[-1], 1))
            scale = (spec.scale if spec.scale is not None
                     else 1.0 / math.sqrt(fan_in))
            k = _leaf_key(key, _path_str(path))
            return (scale * jax.random.normal(k, spec.shape, jnp.float32)
                    ).astype(dtype)

        return jax.tree_util.tree_map_with_path(init_leaf, template,
                                                is_leaf=is_spec)

    return jax.jit(build, out_shardings=shardings)(key)


def abstract_params(template, default_dtype: str = "float32"):
    """ShapeDtypeStruct pytree (no allocation) — dry-run input."""
    def leaf(spec: ParamSpec):
        return jax.ShapeDtypeStruct(spec.shape, jnp.dtype(spec.dtype or default_dtype))
    return jax.tree_util.tree_map(leaf, template, is_leaf=is_spec)


def param_count(template) -> int:
    leaves = jax.tree_util.tree_leaves(template, is_leaf=is_spec)
    return sum(math.prod(l.shape) for l in leaves)


# ---------------------------------------------------------------------------
# Logical-axis vocabulary used across the model zoo (consumed by
# distributed/sharding.py):
#   "vocab"   embedding / logits vocabulary dim  -> tensor-parallel
#   "embed"   residual-stream d_model dim        -> FSDP ("data") when enabled
#   "ff"      hidden dims that want TP (ffn hidden, q/kv head dim products)
#   "expert"  MoE expert dim                     -> expert-parallel
#   "layer"   stacked-layer leading dim          -> never sharded
#   None      replicated
# ---------------------------------------------------------------------------
