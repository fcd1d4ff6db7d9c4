"""Logical-axis -> mesh-axis sharding rules (DP/FSDP/TP/EP/SP).

Parameters carry logical axis names in their ``ParamSpec`` (models/params.py);
this module maps them to ``PartitionSpec`` for a given mesh.  The mapping is
the framework-level counterpart of the paper's per-architecture tuning table:
a small set of knobs, applied outside the model code, adapts the same model
source to any mesh.

Rules of thumb implemented here:
  * "vocab" / "ff" / "expert"  -> "model"  (tensor / expert parallel)
  * "embed" (d_model dims)     -> "data"   (FSDP) when enabled
  * 1-D params (norm scales, biases) are replicated
  * a mesh axis is used at most once per spec (first dim wins)
  * dims not divisible by the axis size fall back to replicated
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import ParamSpec, is_spec


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """The tuning knobs of the distribution layer."""
    tensor_axis: Optional[str] = "model"     # TP/EP target axis
    fsdp_axis: Optional[str] = "data"        # weight-shard axis (None = pure DP)
    batch_axes: Tuple[str, ...] = ("data",)  # activation batch axes
    sequence_axis: Optional[str] = None      # SP: shard activation seq dim

    def logical_map(self):
        return {
            "vocab": self.tensor_axis,
            "ff": self.tensor_axis,
            "expert": self.tensor_axis,
            "embed": self.fsdp_axis,
            "layer": None,
            None: None,
        }


def mesh_axis_label(mesh: Optional[Mesh]) -> Optional[str]:
    """Compact topology label for keys/filenames: ``"data4xmodel2"``.

    This is the mesh coordinate of mesh-keyed tuned entries
    (``registry.mesh_hardware_key``) and of the per-mesh benchmark baseline
    filenames, so the same string means the same topology everywhere.
    None (no mesh) stays None.
    """
    if mesh is None:
        return None
    return "x".join(f"{name}{int(mesh.shape[name])}" for name in mesh.axis_names)


def rules_for_mesh(mesh: Mesh, *, fsdp: bool = True,
                   sequence_parallel: bool = False) -> ShardingRules:
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes) or (axes[0],)
    return ShardingRules(
        tensor_axis="model" if "model" in axes else None,
        fsdp_axis="data" if (fsdp and "data" in axes) else None,
        batch_axes=batch_axes,
        sequence_axis="model" if (sequence_parallel and "model" in axes) else None,
    )


def serving_rules(mesh: Mesh) -> ShardingRules:
    """Inference rules: tensor parallelism only, no FSDP.

    Training shards weights over the data axes and re-gathers them per
    step, amortized over a big batch.  Decode GEMMs are tiny (B x 1
    tokens), so per-step weight all-gathers would serialize the loop:
    serving replicates weights over the data axes and shards them only
    over the tensor axis.
    """
    return rules_for_mesh(mesh, fsdp=False)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


#: public alias — consumers (serve engine telemetry) need the same
#: axis-or-axes size resolution the spec builders use
axis_size = _axis_size


def mesh_axes_for(mesh: Mesh, rules: ShardingRules, shape, axes) -> list:
    """Mesh axis (or None) per dim of an array with logical ``axes``."""
    mapping = rules.logical_map()
    used = set()
    out = []
    for dim, axis_name in zip(shape, axes):
        mesh_axis = mapping.get(axis_name)
        if (mesh_axis is None or mesh_axis in used
                or dim % _axis_size(mesh, mesh_axis) != 0):
            out.append(None)
        else:
            out.append(mesh_axis)
            used.add(mesh_axis)
    return out


def spec_for_param(mesh: Mesh, rules: ShardingRules, spec: ParamSpec) -> P:
    if len(spec.shape) <= 1:
        return P()
    return P(*mesh_axes_for(mesh, rules, spec.shape, spec.axes))


def weight_compute_axes(mesh: Mesh, rules: ShardingRules, shape,
                        axes) -> Tuple[Optional[str], ...]:
    """Mesh axis per dim of a matmul weight as a per-shard kernel consumes
    it: the tensor-parallel split of :func:`spec_for_param`, with weight
    shards over the batch axes (FSDP) gathered first."""
    batch = set(rules.batch_axes)
    return tuple(None if a in batch else a
                 for a in mesh_axes_for(mesh, rules, shape, axes))


def param_specs(mesh: Mesh, rules: ShardingRules, template):
    return jax.tree_util.tree_map(
        lambda s: spec_for_param(mesh, rules, s), template, is_leaf=is_spec)


def param_shardings(mesh: Mesh, rules: ShardingRules, template):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_for_param(mesh, rules, s)),
        template, is_leaf=is_spec)


def shard_params(params, mesh: Mesh, rules: ShardingRules, template):
    """Place an (already materialized) param pytree by the rules.

    ``jax.device_put`` reshards committed arrays in place, so this works both
    for fresh ``init_params`` output and for checkpoint-restored params.
    """
    return jax.device_put(params, param_shardings(mesh, rules, template))


def sharding_summary(mesh: Mesh, rules: ShardingRules, template) -> dict:
    """JSON-friendly provenance: how many param leaves each spec shape got.

    e.g. ``{"('data', 'model')": 9, "()": 14}`` — surfaced by
    ``Engine.stats()["sharding"]`` next to the rules' axis mapping.
    """
    counts: dict = {}
    for spec in jax.tree_util.tree_leaves(template, is_leaf=is_spec):
        key = str(tuple(spec_for_param(mesh, rules, spec)))
        counts[key] = counts.get(key, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# Activation / batch / cache specs
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh, rules: ShardingRules, batch_size: int, rank: int) -> P:
    """Spec for a (B, ...) activation-like array."""
    ba = rules.batch_axes
    if batch_size % _axis_size(mesh, ba) == 0:
        return P(ba, *([None] * (rank - 1)))
    # try fewer axes (e.g. B=1 long-context: replicate batch dim)
    for sub in (ba[:1],):
        if batch_size % _axis_size(mesh, sub) == 0:
            return P(sub, *([None] * (rank - 1)))
    return P(*([None] * rank))


def batch_shardings(mesh: Mesh, rules: ShardingRules, batch_abstract):
    def leaf(x):
        return NamedSharding(mesh, batch_spec(mesh, rules, x.shape[0], x.ndim))
    return jax.tree_util.tree_map(leaf, batch_abstract)


def cache_spec(mesh: Mesh, rules: ShardingRules, shape: Tuple[int, ...],
               batch_dim: int, seq_dim: Optional[int] = None,
               head_dim: Optional[int] = None) -> P:
    """Spec for KV caches / recurrent states with a leading layer axis.

    Prefer sharding batch over the DP axes; if the batch dim is too small
    (long-context B=1), shard the sequence dim instead.  Heads go on the
    tensor axis when divisible.
    """
    out = [None] * len(shape)
    ba = rules.batch_axes
    if shape[batch_dim] % _axis_size(mesh, ba) == 0:
        out[batch_dim] = ba
    elif seq_dim is not None and shape[seq_dim] % _axis_size(mesh, ba) == 0:
        out[seq_dim] = ba
    ta = rules.tensor_axis
    if ta:
        if (head_dim is not None
                and shape[head_dim] % _axis_size(mesh, ta) == 0):
            out[head_dim] = ta
        elif (seq_dim is not None and out[seq_dim] is None
                and shape[seq_dim] % _axis_size(mesh, ta) == 0):
            # few KV heads (GQA kv < model axis): shard cache sequence on the
            # tensor axis instead — softmax/contractions over the sharded seq
            # lower to the standard partial-reduce + all-reduce pattern.
            out[seq_dim] = ta
    return P(*out)


def cache_shardings(mesh: Mesh, rules: ShardingRules, cache_abstract):
    """Heuristic spec derivation for the whole cache pytree.

    Leaves are one of:
      KV cache       (L..., B, S, KV, hd)   rank >= 5
      ssm state      (L..., B, H, N, P)     rank >= 5 (no seq dim)
      conv state     (L..., B, K-1, C)      rank >= 4
    We identify the batch dim as the first dim matching the cache batch size
    recorded by the caller via closure — instead we use the structure: leaves
    under key "self"/"cross" are KV; under "ssm" are states.
    """
    def walk(tree, kind=None):
        if isinstance(tree, dict):
            return {k: walk(v, {"self": "kv", "cross": "kv",
                                "ssm": "ssm", "conv": "conv",
                                "q": kind, "s": "kv_scale"}.get(k, kind))
                    for k, v in tree.items()}
        if isinstance(tree, (tuple, list)):
            t = type(tree)
            return t(walk(v, kind) for v in tree)
        shape = tree.shape
        if kind == "kv_scale":
            # int8-quant scale slab (L..., B, S, KV): batch=-3, seq=-2, heads=-1
            sp = cache_spec(mesh, rules, shape, len(shape) - 3,
                            seq_dim=len(shape) - 2, head_dim=len(shape) - 1)
        elif kind == "kv":
            # (L..., B, S, KV, hd): batch = -4, seq = -3, heads = -2
            sp = cache_spec(mesh, rules, shape, len(shape) - 4,
                            seq_dim=len(shape) - 3, head_dim=len(shape) - 2)
        elif kind == "conv":
            # (L..., B, K-1, C): batch = -3, channels = -1
            sp = cache_spec(mesh, rules, shape, len(shape) - 3,
                            head_dim=len(shape) - 1)
        else:
            # ssm state (L..., B, H, N, P): batch = -4, heads = -3
            sp = cache_spec(mesh, rules, shape, len(shape) - 4,
                            head_dim=len(shape) - 3)
        return NamedSharding(mesh, sp)

    return walk(cache_abstract)
