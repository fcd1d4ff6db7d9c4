"""Core tuning machinery: tile feasibility invariants, cost-model behaviour
(paper Eqs. 5-7), tuner sweeps, registry persistence."""
import os

import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, strategies as st

from repro.core import (GLOBAL_REGISTRY, HOST_CPU, INTERPRET_SPACE, TPU_V5E,
                        TileConfig, TileRegistry, TuningSpace, sweep_gemm)
from repro.core.cost_model import gemm_cost, ratio_model
from repro.core.tile_config import square


def test_vmem_working_set_matches_paper_eq5_for_square_tiles():
    """K(S,T) = 2 T^2 S for the A/B tiles (paper Eq. 5)."""
    for t in (64, 128, 256):
        cfg = square(t)
        s = 4  # f32
        ab_bytes = (cfg.bm * cfg.bk + cfg.bk * cfg.bn) * s
        assert ab_bytes == 2 * t * t * s


def test_candidates_all_fit_vmem():
    space = TuningSpace()
    for cfg in space.candidates(TPU_V5E, jnp.bfloat16):
        assert cfg.fits(TPU_V5E, jnp.bfloat16)
        assert cfg.aligned(TPU_V5E, jnp.bfloat16)


def test_candidate_space_nonempty_for_all_dtypes():
    for dt in (jnp.bfloat16, jnp.float32):
        assert len(list(TuningSpace().candidates(TPU_V5E, dt))) > 0


@settings(max_examples=20, deadline=None)
@given(t=st.sampled_from([128, 256, 512]), n=st.integers(1024, 20480))
def test_ratio_model_monotone_in_t(t, n):
    """Paper Eq. 7: R(N, T) grows with T and approaches T for large N."""
    assert ratio_model(n, 2 * t) > ratio_model(n, t)
    assert ratio_model(n, t) < t


def test_cost_model_prefers_larger_tiles_until_vmem():
    """The paper's headline tuning curve: bigger T -> fewer HBM bytes."""
    n = 8192
    costs = [gemm_cost(n, n, n, square(t), TPU_V5E, jnp.bfloat16)
             for t in (128, 256, 512)]
    for a, b in zip(costs, costs[1:]):
        assert b.hbm_bytes < a.hbm_bytes


def test_cost_model_arithmetic_intensity_tracks_eq7():
    """Measured AI of the tiled GEMM ~ R(N,T) = 2NT/(2N+T) (square tiles,
    equal in/out dtype) up to the f32-accumulator/output constant."""
    n, t = 4096, 256
    c = gemm_cost(n, n, n, square(t), TPU_V5E, jnp.float32)
    # model AI in flops/element: R(N,T); convert to bytes (4 B/elem)
    want = ratio_model(n, t) / 4.0
    assert 0.5 * want < c.arithmetic_intensity < 2.0 * want


def test_sweep_model_mode_records_registry():
    reg = TileRegistry()
    res = sweep_gemm(2048, 2048, 2048, dtype=jnp.bfloat16, mode="model",
                     registry=reg)
    assert len(res.points) > 4
    best = res.best.config
    assert reg.get("tpu-v5e", jnp.bfloat16, 2048, 2048, 2048) == best


def test_sweep_measure_mode_runs():
    res = sweep_gemm(32, 32, 32, dtype=jnp.float32, mode="measure",
                     space=INTERPRET_SPACE, hardware=HOST_CPU, repeats=1,
                     record=False)
    assert all(p.seconds > 0 for p in res.points)


@pytest.mark.parametrize("hardware,backend,interpret,n,d", [
    ("cpu-interpret", "pallas-interpret", True, 32, 8),
    ("tpu-v5e", "pallas-tpu", False, 256, 64),
])
def test_measure_mode_times_the_profiles_backend(monkeypatch, hardware,
                                                 backend, interpret, n, d):
    """Measure mode runs the profile's default backend: interpreted on
    cpu-interpret, the compiled kernel on a TPU.  The kernels are stubbed
    with the XLA path so the choice is observable on this host."""
    from repro.core import FLASH_INTERPRET_SPACE, sweep_flash_attention
    from repro.kernels import flash_attention as fa
    from repro.kernels import ops
    from repro.kernels.ref import attention_ref
    seen = []
    real_gemm = ops.gemm
    monkeypatch.setattr(ops, "gemm", lambda a, b, *, config, backend: (
        seen.append(backend), real_gemm(a, b, backend="xla"))[1])
    monkeypatch.setattr(fa, "flash_attention", lambda q, k, v, *, causal,
                        bq, bk, interpret: (seen.append(interpret),
                                            attention_ref(q, k, v,
                                                          causal=causal))[1])
    cpu = hardware == "cpu-interpret"
    sweep_gemm(n, n, n, dtype=jnp.float32, mode="measure",
               space=INTERPRET_SPACE if cpu else None, hardware=hardware,
               top_k=1, repeats=1, record=False)
    sweep_flash_attention(n, n, d, dtype=jnp.float32, mode="measure",
                          space=FLASH_INTERPRET_SPACE if cpu else None,
                          hardware=hardware, top_k=1, repeats=1,
                          record=False)
    assert seen and set(seen) == {backend, interpret}


def test_registry_persistence_roundtrip(tmp_path):
    path = os.path.join(tmp_path, "tuned.json")
    reg = TileRegistry()
    cfg = TileConfig(256, 512, 256)
    reg.put(cfg, "tpu-v5e", jnp.bfloat16, 1024, 1024, 1024)
    reg.put(TileConfig(64, 128, 128), "tpu-v5e", jnp.bfloat16)
    reg.save(path)
    reg2 = TileRegistry(path)
    assert reg2.get("tpu-v5e", jnp.bfloat16, 1024, 1024, 1024) == cfg
    # shape-specific beats hardware-default; unknown shape falls back
    assert reg2.get("tpu-v5e", jnp.bfloat16, 7, 7, 7) == TileConfig(64, 128, 128)


def test_registry_fallback_default():
    reg = TileRegistry()
    cfg = reg.get("tpu-v5e", jnp.bfloat16)
    assert isinstance(cfg, TileConfig)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(128, 8192), k=st.integers(128, 8192),
       n=st.integers(128, 8192))
def test_property_cost_model_positive_and_flops_exact(m, k, n):
    c = gemm_cost(m, k, n, TileConfig(128, 128, 128), TPU_V5E, jnp.bfloat16)
    assert c.flops == 2 * m * k * n
    assert c.total_s > 0
    assert c.hbm_bytes > 0
