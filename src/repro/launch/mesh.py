"""Mesh topology construction — the distribution layer's `--mesh` knob.

Functions (not module-level constants) so importing this module never
touches jax device state — required for the XLA_FLAGS trick in dryrun.py.

``build_mesh`` is the single entry point every launcher/engine/benchmark
uses to turn a ``--mesh`` flag into a :class:`jax.sharding.Mesh`:

  * ``"data=4,model=2"``  — explicit axis sizes (the paper's tuning-table
    discipline applied to topology: one spec string, zero model edits);
  * ``"auto"``            — all visible devices on the ``data`` axis;
  * ``None`` / ``""``     — no mesh (single-device execution).

CI exercises multi-device meshes on a CPU host via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

from repro.distributed.sharding import mesh_axis_label

#: axis names the sharding rules understand (distributed/sharding.py)
MESH_AXES = ("pod", "data", "model")


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """``"data=4,model=2"`` -> ``{"data": 4, "model": 2}`` (order kept).

    Axis names must come from :data:`MESH_AXES` (the vocabulary
    ``rules_for_mesh`` maps logical axes onto); sizes must be >= 1.
    """
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected axis=size, got {part!r}")
        name, _, size_s = part.partition("=")
        name = name.strip()
        if name not in MESH_AXES:
            raise ValueError(
                f"bad mesh spec {spec!r}: unknown axis {name!r} "
                f"(choose from {', '.join(MESH_AXES)})")
        if name in out:
            raise ValueError(f"bad mesh spec {spec!r}: duplicate axis {name!r}")
        try:
            size = int(size_s)
        except ValueError:
            raise ValueError(
                f"bad mesh spec {spec!r}: size of {name!r} is not an int")
        if size < 1:
            raise ValueError(
                f"bad mesh spec {spec!r}: size of {name!r} must be >= 1")
        out[name] = size
    if not out:
        raise ValueError(f"bad mesh spec {spec!r}: no axes")
    return out


def build_mesh(spec: Optional[str], *,
               devices=None) -> Optional[jax.sharding.Mesh]:
    """Build a Mesh from a ``--mesh`` spec string (None/"" -> no mesh).

    ``"auto"`` puts every visible device on the ``data`` axis.  An explicit
    spec may use a *subset* of the visible devices (the first ``prod(sizes)``
    in ``jax.devices()`` order), so ``data=2`` works on an 8-device host.
    """
    if not spec:
        return None
    devices = list(devices if devices is not None else jax.devices())
    if spec.strip() == "auto":
        sizes = {"data": len(devices)}
    else:
        sizes = parse_mesh_spec(spec)
    n = int(np.prod(list(sizes.values())))
    if n > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {n} devices, only {len(devices)} visible "
            f"(hint: XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"forces a CPU host to expose {n})")
    dev_array = np.array(devices[:n]).reshape(tuple(sizes.values()))
    return jax.sharding.Mesh(dev_array, tuple(sizes))


def describe_mesh(mesh: Optional[jax.sharding.Mesh]) -> Dict[str, object]:
    """JSON-friendly mesh provenance for stats()/bench artifacts."""
    if mesh is None:
        return {"devices": 1, "axes": None, "label": None}
    return {"devices": int(mesh.size),
            "axes": {name: int(mesh.shape[name]) for name in mesh.axis_names},
            "label": mesh_axis_label(mesh)}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model) — the 'pod' axis is
    pure data parallelism across the slow inter-pod (DCN) domain."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small CPU mesh for in-process smoke tests (requires the host platform
    to expose data*model devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int):
    """Auto axis types: ``jax.make_mesh`` defaults to Explicit axes, which
    ``with_sharding_constraint`` (``distributed.ctx.constrain``) rejects."""
    return (jax.sharding.AxisType.Auto,) * n
