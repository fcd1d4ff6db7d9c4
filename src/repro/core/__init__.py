"""Core: the paper's contribution — single-source tunable kernel machinery.

One architecture-agnostic kernel source per op (GEMM, flash attention), with
every tuning knob — block sizes, backend, dtype policy — carried *outside*
the kernel in an op-keyed registry fed by a persistent tuning database.
"""
from repro.core.attention_api import flash_attention  # noqa: F401
from repro.core.gemm_api import (  # noqa: F401
    ExecutionContext, allreduce_repeat, allreduce_tally, capture_gemm_shapes,
    current_hardware, einsum, execution_context, matmul,
)
from repro.core.hardware import (  # noqa: F401
    CPU_INTERPRET, GPU_GENERIC, HARDWARE, HOST_CPU, HardwareProfile,
    HardwareSpec, PLATFORM_CPU_INTERPRET, PLATFORM_GPU, PLATFORM_TPU,
    PROFILES, TPU_V5E, detect_hardware, get_hardware, get_profile,
    register_profile, resolve_hardware, resolve_profile,
)
from repro.core.registry import (  # noqa: F401
    GLOBAL_REGISTRY, KNOWN_OPS, LookupResult, OP_DECODE_LOOP,
    OP_FLASH_ATTENTION, OP_GEMM, TileRegistry, get_tile_config,
    mesh_hardware_key,
)
from repro.core.tile_config import (  # noqa: F401
    FLASH_INTERPRET_SPACE, DecodeLoopConfig, DecodeLoopTuningSpace,
    FlashAttentionConfig, FlashTuningSpace, INTERPRET_SPACE, TileConfig,
    TuningSpace, square,
)
from repro.core.tuner import (  # noqa: F401
    SEARCH_EXHAUSTIVE, SEARCH_GUIDED, SweepResult, sweep_flash_attention,
    sweep_gemm, sweep_shapes, tune_model_gemms,
)
from repro.core.tuning_db import (  # noqa: F401
    TuningDB, TuningDBError, TuningRecord, db_from_sweeps, load_all,
)
