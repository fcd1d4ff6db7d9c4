"""Hardware profiles: one record per backend the single source runs on.

The paper's Tables 1/2 list one column per architecture (P100, KNL, Haswell,
Power8); here each column is a :class:`HardwareProfile` — peak FLOPS and HBM
bandwidth for the cost/roofline models, tile-alignment constraints for the
candidate spaces, and the seeded default blocks the registry serves before
any tuning ran.  The roofline analysis, the analytic tile cost model, the
tuner, the registry's default tier, and the serve engine all read from these
— never from constants scattered in code.

Three profiles ship registered (the paper's build matrix, Tab. 3):

* ``tpu-v5e``       — the TPU target (platform ``tpu``); tuned via the
  analytic cost model on any host, measured on real TPUs.
* ``gpu-generic``   — an A100-class target (platform ``gpu``); defines the
  lowering/tiling constraints (16-wide tensor-core tiles, SM shared-memory
  budget) so a GPU runner can ``tune.py sweep --mode measure`` without any
  code change.
* ``cpu-interpret`` — the pallas-interpret backend on the host CPU
  (platform ``cpu-interpret``); the measurable backend of this container,
  with its own committed ``tuned/cpu-interpret.json``.

Resolution order for "which hardware am I tuning/serving for":

1. explicit ``execution_context(hardware=...)`` / ``--hardware`` flag;
2. the ``REPRO_HARDWARE`` environment variable (how the CI backend matrix
   pins each job's profile);
3. auto-detection from ``jax.devices()`` (:func:`detect_hardware`).

``host-cpu`` is kept as a legacy alias of ``cpu-interpret`` so pre-profile
tuning DBs and call sites keep resolving.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Iterable, Optional, Tuple

import jax.numpy as jnp

#: platform kinds — the coarse backend families a profile belongs to
PLATFORM_TPU = "tpu"
PLATFORM_GPU = "gpu"
PLATFORM_CPU_INTERPRET = "cpu-interpret"
PLATFORMS = (PLATFORM_TPU, PLATFORM_GPU, PLATFORM_CPU_INTERPRET)

#: env var pinning the hardware profile for a whole process (CI matrix knob)
HARDWARE_ENV = "REPRO_HARDWARE"


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """One tuning/serving target: cost-model numbers + tiling constraints.

    ``mxu_dim``/``sublane`` drive the candidate-space alignment predicates
    (:meth:`repro.core.tile_config.TileConfig.aligned`); ``vmem_bytes`` is
    the on-chip budget of the feasibility predicate (paper Eq. 5) — VMEM on
    TPU, SM shared memory on GPU, an L2/L3 proxy for the interpreted CPU
    path.  ``hbm_bytes`` is the per-chip main-memory *capacity* (HBM on
    TPU/GPU, a host-RAM proxy on the interpreted CPU) that the IR memory
    check (IR003, ``analyze.py ir``) budgets each compiled program's
    live-buffer peak against.  ``gemm_block``/``flash_block`` seed the
    registry's default tier (the paper's ``#define GPU_ELEM_NUM`` analogue)
    before any sweep ran.
    """
    name: str
    # peak FLOP/s per chip, keyed by dtype name (paper Tab. 1/2 "theoretical peak")
    peak_flops: Dict[str, float]
    hbm_bandwidth: float          # bytes/s per chip
    vmem_bytes: int               # software-managed on-chip memory (the "cache")
    ici_link_bandwidth: float     # bytes/s per link (inter-chip)
    hbm_bytes: int = 16 * 1024**3  # per-chip main-memory capacity
    mxu_dim: int = 128            # native minor-dim tile (MXU / tensor core)
    sublane: int = 8              # native second-minor tiling for f32
    platform: str = PLATFORM_TPU
    default_backend: str = "pallas-tpu"   # kernels.ops backend string
    gemm_block: Tuple[int, int, int] = (128, 128, 128)   # seeded default tier
    flash_block: Tuple[int, int] = (128, 128)
    #: Compiler flags that let collectives overlap compute on this backend
    #: (async collectives, latency-hiding scheduling).  Set by
    #: ``launch.common.apply_latency_hiding_flags`` before the backend
    #: starts, for mesh runs.  Empty where the runtime has no such scheduler
    #: (interpret CPU).
    xla_latency_flags: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.platform not in PLATFORMS:
            raise ValueError(
                f"unknown platform {self.platform!r}; known: {PLATFORMS}")

    def peak_for(self, dtype) -> float:
        return self.peak_flops[jnp.dtype(dtype).name]

    def default_block(self, op: str) -> Optional[Tuple[int, ...]]:
        """Seeded default block tuple for an op family (None if unknown)."""
        return {"gemm": self.gemm_block,
                "flash_attention": self.flash_block}.get(op)


#: legacy alias — pre-profile code constructed/annotated ``HardwareSpec``
HardwareSpec = HardwareProfile


TPU_V5E = HardwareProfile(
    name="tpu-v5e",
    platform=PLATFORM_TPU,
    peak_flops={
        "bfloat16": 197e12,   # task-spec constant: 197 TFLOP/s bf16
        "float32": 98.5e12,   # MXU f32 ~ half bf16 throughput
    },
    hbm_bandwidth=819e9,      # 819 GB/s
    hbm_bytes=16 * 1024**3,   # 16 GiB HBM per chip
    vmem_bytes=128 * 1024 * 1024 // 8,  # ~16 MiB usable VMEM per core
    ici_link_bandwidth=50e9,  # ~50 GB/s per ICI link
    default_backend="pallas-tpu",
    gemm_block=(128, 128, 128),
    flash_block=(128, 128),
    # TPU collectives already run on dedicated ICI hardware; only ask the
    # scheduler to fuse/overlap all-gathers with the compute stream.
    xla_latency_flags=(
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    ),
)

GPU_GENERIC = HardwareProfile(
    name="gpu-generic",
    platform=PLATFORM_GPU,
    peak_flops={
        "bfloat16": 312e12,   # A100-class tensor-core bf16
        "float32": 19.5e12,   # CUDA-core f32
    },
    hbm_bandwidth=1555e9,     # HBM2e
    hbm_bytes=40 * 1024**3,   # A100-40GB HBM2e stack
    vmem_bytes=192 * 1024,    # SM shared memory (the GEMM tile budget)
    ici_link_bandwidth=600e9 / 12,  # NVLink per-link
    mxu_dim=16,               # tensor-core fragment minor dim
    sublane=4,                # warp-level row granularity for f32
    default_backend="xla",    # vendor-library path until a Triton lowering lands
    gemm_block=(64, 128, 128),
    flash_block=(64, 64),
    # The standard GPU latency-hiding set: async collectives on their own
    # high-priority stream, scheduled to overlap with compute.
    xla_latency_flags=(
        "--xla_gpu_enable_async_collectives=true",
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_gpu_enable_highest_priority_async_stream=true",
    ),
)

# The pallas-interpret backend on this host: the one we can actually measure.
CPU_INTERPRET = HardwareProfile(
    name="cpu-interpret",
    platform=PLATFORM_CPU_INTERPRET,
    peak_flops={"bfloat16": 1e11, "float32": 2e11},
    hbm_bandwidth=50e9,
    hbm_bytes=8 * 1024**3,         # host-RAM slice the CI runner can commit
    vmem_bytes=32 * 1024 * 1024,   # L2+L3-ish proxy
    ici_link_bandwidth=10e9,
    mxu_dim=16,                    # SIMD width proxy — relaxes alignment
    sublane=1,
    default_backend="pallas-interpret",
    gemm_block=(32, 32, 32),
    flash_block=(32, 32),
)

#: legacy name for the host-measurement profile (pre-profile code imports it)
HOST_CPU = CPU_INTERPRET

HARDWARE: Dict[str, HardwareProfile] = {}
PROFILES = HARDWARE   # the profile registry's preferred name

#: legacy hardware names -> canonical profile names
ALIASES: Dict[str, str] = {"host-cpu": CPU_INTERPRET.name}


def register_profile(profile: HardwareProfile) -> HardwareProfile:
    """Register (or replace) a profile; returns it for chaining."""
    HARDWARE[profile.name] = profile
    return profile


for _p in (TPU_V5E, GPU_GENERIC, CPU_INTERPRET):
    register_profile(_p)


def canonical_name(name: str) -> str:
    return ALIASES.get(name, name)


def find_profile(name: str) -> Optional[HardwareProfile]:
    """Profile for ``name`` (alias-aware), or None when unregistered."""
    return HARDWARE.get(canonical_name(name))


def get_profile(name: str) -> HardwareProfile:
    prof = find_profile(name)
    if prof is None:
        raise KeyError(f"unknown hardware {name!r}; known: {sorted(HARDWARE)}"
                       f" (aliases: {sorted(ALIASES)})")
    return prof


#: legacy accessor name
get_hardware = get_profile


# ---------------------------------------------------------------------------
# Detection: env pin > jax.devices()
# ---------------------------------------------------------------------------

#: TPU ``device_kind`` (as JAX reports it) -> registered profile name.  A TPU
#: whose kind is not listed is an error: its peaks and tiles are unknown.
TPU_DEVICE_KINDS: Dict[str, str] = {"TPU v5 lite": TPU_V5E.name}

#: jax platform strings of the GPU backends
GPU_PLATFORMS = ("gpu", "cuda", "rocm")


def detect_hardware(devices: Optional[Iterable] = None) -> str:
    """Profile name for this process: ``$REPRO_HARDWARE`` if set, else the
    profile of ``jax.devices()`` (``devices`` is injectable for tests).

    An accelerator wins over the host CPU.  A TPU is keyed on its
    ``device_kind``; an unknown kind raises instead of borrowing another
    chip's peaks and tiles.
    """
    env = os.environ.get(HARDWARE_ENV)
    if env:
        return canonical_name(env)
    if devices is None:
        import jax
        devices = jax.devices()
    devices = list(devices)
    tpus = [d for d in devices if d.platform == "tpu"]
    if tpus:
        kind = tpus[0].device_kind
        if kind not in TPU_DEVICE_KINDS:
            raise RuntimeError(
                f"unknown TPU device_kind {kind!r}; known: "
                f"{sorted(TPU_DEVICE_KINDS)} (register a profile for it, or "
                f"pin one with ${HARDWARE_ENV})")
        return TPU_DEVICE_KINDS[kind]
    if any(d.platform in GPU_PLATFORMS for d in devices):
        return GPU_GENERIC.name
    return CPU_INTERPRET.name


def resolve_hardware(name: Optional[str] = None) -> str:
    """Canonical hardware name for an optional explicit override.

    Explicit ``name`` (alias-resolved) wins; ``None`` falls back to
    :func:`detect_hardware`.  Unregistered names pass through untouched —
    the registry's default tier handles them with a warning, so a typo'd
    target degrades loudly instead of crashing mid-serve.
    """
    if name:
        return canonical_name(name)
    return detect_hardware()


def resolve_profile(hardware=None,
                    default: Optional[HardwareProfile] = None
                    ) -> HardwareProfile:
    """Like :func:`resolve_hardware` but returns the profile object;
    accepts a profile, a name, or None.  ``None`` resolves to ``default``
    when given (how the benchmark suites pin the TPU target for direct
    calls), else to the detected host profile."""
    if isinstance(hardware, HardwareProfile):
        return hardware
    if hardware is None and default is not None:
        return default
    name = resolve_hardware(hardware)
    prof = find_profile(name)
    if prof is None:
        raise KeyError(f"unknown hardware {name!r}; known: {sorted(HARDWARE)}")
    return prof
