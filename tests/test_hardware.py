"""Hardware-profile layer: detection order, per-backend registry seeding,
unknown-hardware fallback, cross-backend DB isolation, engine provenance,
and the bench-trend gate."""
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import (CPU_INTERPRET, GPU_GENERIC, TPU_V5E, TuningDB,
                        TuningRecord, current_hardware, execution_context,
                        register_profile, sweep_gemm)
from repro.core import hardware as hw
from repro.core import registry as registry_mod
from repro.core.registry import OP_FLASH_ATTENTION, OP_GEMM, TileRegistry
from repro.core.tile_config import (FlashAttentionConfig, FlashTuningSpace,
                                    TileConfig, TuningSpace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


class _FakeDev:
    def __init__(self, platform, device_kind="cpu"):
        self.platform = platform
        self.device_kind = device_kind


# ---------------------------------------------------------------------------
# Detection order: explicit override > $REPRO_HARDWARE > jax.devices()
# ---------------------------------------------------------------------------

def test_cpu_only_devices_detect_cpu_interpret(monkeypatch):
    monkeypatch.delenv(hw.HARDWARE_ENV, raising=False)
    # genuine path on this CPU-only container...
    assert jax.default_backend() == "cpu"
    assert hw.detect_hardware() == CPU_INTERPRET.name
    # ...and via the injectable device list
    assert hw.detect_hardware([_FakeDev("cpu")]) == CPU_INTERPRET.name
    assert hw.detect_hardware([_FakeDev("cpu"), _FakeDev("gpu")]) == \
        GPU_GENERIC.name
    assert hw.detect_hardware([_FakeDev("tpu", "TPU v5 lite")]) == \
        TPU_V5E.name


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    """A TPU is keyed on its device_kind: an unknown kind fails loudly
    instead of serving with another chip's peaks and tiles."""
    monkeypatch.delenv(hw.HARDWARE_ENV, raising=False)
    with pytest.raises(RuntimeError, match="unknown TPU device_kind"):
        hw.detect_hardware([_FakeDev("tpu", "TPU v9 imaginary")])
    # the env pin still names the profile explicitly
    monkeypatch.setenv(hw.HARDWARE_ENV, TPU_V5E.name)
    assert hw.detect_hardware([_FakeDev("tpu", "TPU v9 imaginary")]) == \
        TPU_V5E.name


def test_env_pin_beats_detection(monkeypatch):
    monkeypatch.setenv(hw.HARDWARE_ENV, TPU_V5E.name)
    assert hw.detect_hardware() == TPU_V5E.name
    assert current_hardware() == TPU_V5E.name
    # aliases resolve through the env pin too
    monkeypatch.setenv(hw.HARDWARE_ENV, "host-cpu")
    assert hw.detect_hardware() == CPU_INTERPRET.name


def test_explicit_execution_context_override_wins(monkeypatch):
    monkeypatch.setenv(hw.HARDWARE_ENV, CPU_INTERPRET.name)
    with execution_context(hardware=TPU_V5E.name):
        assert current_hardware() == TPU_V5E.name
        with execution_context(hardware=GPU_GENERIC.name):
            assert current_hardware() == GPU_GENERIC.name
        assert current_hardware() == TPU_V5E.name
    assert current_hardware() == CPU_INTERPRET.name


def test_host_cpu_alias_resolves_to_cpu_interpret():
    assert hw.resolve_hardware("host-cpu") == CPU_INTERPRET.name
    assert hw.get_profile("host-cpu") is CPU_INTERPRET
    assert hw.get_hardware(CPU_INTERPRET.name) is CPU_INTERPRET
    with pytest.raises(KeyError, match="unknown hardware"):
        hw.get_profile("knights-landing")


# ---------------------------------------------------------------------------
# Registry seeding from profiles + the unknown-hardware fallback bugfix
# ---------------------------------------------------------------------------

def test_registry_defaults_seeded_from_profiles():
    reg = TileRegistry()
    for prof in (TPU_V5E, GPU_GENERIC, CPU_INTERPRET):
        g = reg.lookup_op(OP_GEMM, prof.name, jnp.bfloat16)
        assert g.source == "default"
        assert g.config == TileConfig(*prof.gemm_block)
        f = reg.lookup_op(OP_FLASH_ATTENTION, prof.name, jnp.bfloat16)
        assert f.source == "default"
        assert f.config == FlashAttentionConfig(*prof.flash_block)


def test_unknown_hardware_warns_once_and_serves_seeded_defaults(monkeypatch):
    """Satellite bugfix: an unknown hardware name used to escape as a bare
    KeyError from deep inside registry.py; it must fall back to the detected
    profile's seeded defaults with a once-per-process warning."""
    monkeypatch.delenv(hw.HARDWARE_ENV, raising=False)
    monkeypatch.setattr(registry_mod, "_WARNED_UNKNOWN_HARDWARE", set())
    reg = TileRegistry()
    detected = hw.get_profile(hw.detect_hardware())
    with pytest.warns(UserWarning, match="unknown hardware 'knl-7250'"):
        res = reg.lookup("knl-7250", jnp.bfloat16, 64, 64, 64)
    assert res.source == "fallback"
    assert res.config == TileConfig(*detected.gemm_block)
    # flash lookups fall back the same way; the warning fires only once
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res2 = reg.lookup_op(OP_FLASH_ATTENTION, "knl-7250", jnp.float32)
        reg.lookup("knl-7250", jnp.float32, 8, 8, 8)
    assert res2.config == FlashAttentionConfig(*detected.flash_block)
    assert not [w for w in caught if "unknown hardware" in str(w.message)]


def test_register_profile_gives_new_backend_a_default_tier():
    name = "test-exotic-accel"
    prof = register_profile(hw.HardwareProfile(
        name=name, platform=hw.PLATFORM_GPU,
        peak_flops={"bfloat16": 1e12, "float32": 5e11},
        hbm_bandwidth=100e9, vmem_bytes=1 << 20, ici_link_bandwidth=1e9,
        mxu_dim=16, sublane=2, gemm_block=(16, 32, 32), flash_block=(16, 16)))
    try:
        reg = TileRegistry()
        res = reg.lookup(name, jnp.bfloat16, 128, 128, 128)
        assert res.source == "default"
        assert res.config == TileConfig(16, 32, 32)
    finally:
        hw.HARDWARE.pop(name, None)
    assert prof.default_block("gemm") == (16, 32, 32)


def test_gpu_generic_constraints_admit_a_tuning_space():
    """The gpu-generic profile must define feasible, aligned candidate
    spaces so a GPU runner can tune with zero code changes."""
    gemm_cands = list(TuningSpace().candidates(GPU_GENERIC, jnp.bfloat16))
    assert gemm_cands
    for cfg in gemm_cands:
        assert cfg.fits(GPU_GENERIC, jnp.bfloat16)
        assert cfg.aligned(GPU_GENERIC, jnp.bfloat16)
    flash_cands = list(FlashTuningSpace().candidates(GPU_GENERIC,
                                                     jnp.bfloat16, d=64))
    assert flash_cands
    # and the tuner accepts the profile BY NAME (string), end to end
    res = sweep_gemm(512, 512, 512, dtype=jnp.bfloat16, mode="model",
                     hardware=GPU_GENERIC.name, record=False)
    assert res.hardware == GPU_GENERIC.name
    assert res.points


# ---------------------------------------------------------------------------
# TuningDB isolation across hardware names
# ---------------------------------------------------------------------------

def test_tuning_db_roundtrip_two_hardware_no_cross_contamination(tmp_path):
    def rec(bm):
        return TuningRecord.gemm("bfloat16", 1024, 1024, 1024, bm, bm, bm)

    db_a = TuningDB(TPU_V5E.name)
    db_a.add(rec(512))
    db_b = TuningDB(CPU_INTERPRET.name)
    db_b.add(rec(32))
    path_a = str(tmp_path / f"{TPU_V5E.name}.json")
    path_b = str(tmp_path / f"{CPU_INTERPRET.name}.json")
    db_a.save(path_a)
    db_b.save(path_b)

    from repro.core.tuning_db import load_all
    reg = TileRegistry()
    loaded = load_all(reg, str(tmp_path))
    assert loaded == {path_a: 1, path_b: 1}
    a = reg.lookup(TPU_V5E.name, jnp.bfloat16, 1024, 1024, 1024)
    b = reg.lookup(CPU_INTERPRET.name, jnp.bfloat16, 1024, 1024, 1024)
    assert (a.source, a.config) == ("exact", TileConfig(512, 512, 512))
    assert (b.source, b.config) == ("exact", TileConfig(32, 32, 32))
    # a third backend sees NEITHER: nearest never crosses hardware buckets
    c = reg.lookup(GPU_GENERIC.name, jnp.bfloat16, 1024, 1024, 1024)
    assert c.source == "default"
    assert c.config == TileConfig(*GPU_GENERIC.gemm_block)


def test_legacy_host_cpu_db_reachable_from_cpu_interpret_lookups(tmp_path):
    """A pre-profile tuned/host-cpu.json must keep resolving: entries are
    canonicalized to cpu-interpret on registry write, so lookups under the
    new name (and the alias) both hit them."""
    db = TuningDB("host-cpu")
    db.add(TuningRecord.gemm("float32", 64, 64, 64, 16, 32, 32,
                             source="measure", seconds=1e-4))
    db.save(str(tmp_path / "host-cpu.json"))
    from repro.core.tuning_db import load_all
    reg = TileRegistry()
    load_all(reg, str(tmp_path))
    for name in (CPU_INTERPRET.name, "host-cpu"):
        res = reg.lookup(name, jnp.float32, 64, 64, 64)
        assert (res.source, res.config) == ("exact", TileConfig(16, 32, 32))


def test_committed_cpu_interpret_db_exists_and_loads():
    """Acceptance: tuned/cpu-interpret.json is committed and loads under the
    cpu-interpret profile (kernel ops plus the mesh-keyed decode unroll and
    the paged-KV page size)."""
    path = os.path.join(REPO, "tuned", f"{CPU_INTERPRET.name}.json")
    assert os.path.exists(path), "tuned/cpu-interpret.json must be committed"
    db = TuningDB.from_file(path)
    assert db.hardware == CPU_INTERPRET.name
    assert set(db.ops()) == {"gemm", "flash_attention", "decode_loop",
                             "paged_attn"}
    reg = TileRegistry()
    from repro.core.tuning_db import load_into_registry
    assert load_into_registry(reg, path) == len(db) > 0
    rec = db.records("gemm")[0]
    res = reg.lookup(CPU_INTERPRET.name, rec.dtype, *rec.shape)
    assert res.source == "exact"


# ---------------------------------------------------------------------------
# Engine provenance
# ---------------------------------------------------------------------------

def test_engine_stats_carry_hardware_provenance():
    from repro.configs.catalog import get_config
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    cfg = get_config("llama3.2-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 ServeConfig(max_batch=2, max_len=64,
                             hardware=CPU_INTERPRET.name))
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
    st = eng.stats()
    assert st["hardware"] == CPU_INTERPRET.name
    assert st["hardware_platform"] == hw.PLATFORM_CPU_INTERPRET
    assert st["decode_tile_lookups"], "decode tile provenance missing"
    # the legacy alias resolves to the same profile at engine construction
    eng2 = Engine(model, params,
                  ServeConfig(max_batch=2, max_len=64, hardware="host-cpu"))
    assert eng2.hardware == CPU_INTERPRET.name


# ---------------------------------------------------------------------------
# Bench-trend gate (scripts/bench_compare.py)
# ---------------------------------------------------------------------------

def _bench_blob(rows, **extra):
    blob = {"smoke": True, "hardware": CPU_INTERPRET.name,
            "suites": ["gemm_tuning"],
            "rows": [{"name": n, "us_per_call": u, "derived": d}
                     for n, u, d in rows]}
    blob.update(extra)
    return blob


def _run_compare(tmp_path, fresh_rows, base_rows, extra_args=(),
                 tolerances=None):
    base = _bench_blob(base_rows)
    if tolerances is not None:
        base["tolerances"] = tolerances
    bdir = tmp_path / "baselines"
    bdir.mkdir(exist_ok=True)
    name = "BENCH_gemm_tuning__cpu-interpret.json"
    (bdir / name).write_text(json.dumps(base))
    fresh = tmp_path / name
    fresh.write_text(json.dumps(_bench_blob(fresh_rows)))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_compare.py"),
         str(fresh), "--baseline-dir", str(bdir), *extra_args],
        capture_output=True, text=True, timeout=120)
    return proc


def test_bench_compare_passes_within_tolerance(tmp_path):
    base = [("gemm_tune/cpu-interpret/bf16/N512/128x128x128", 10.0, 100.0)]
    fresh = [("gemm_tune/cpu-interpret/bf16/N512/256x256x256", 11.0, 80.0)]
    proc = _run_compare(tmp_path, fresh, base)     # -20% < 30% tolerance;
    assert proc.returncode == 0, proc.stdout       # tile label normalized
    assert "PASS" in proc.stdout


def test_bench_compare_fails_on_30pct_regression(tmp_path):
    base = [("gemm_tune/cpu-interpret/bf16/N512/128x128x128", 10.0, 100.0)]
    fresh = [("gemm_tune/cpu-interpret/bf16/N512/128x128x128", 30.0, 60.0)]
    proc = _run_compare(tmp_path, fresh, base)
    assert proc.returncode == 1, proc.stdout
    assert "REGRESSION" in proc.stdout
    # ...unless the per-family tolerance in the baseline JSON allows it
    proc = _run_compare(tmp_path, fresh, base,
                        tolerances={"gemm_tune/": 0.5})
    assert proc.returncode == 0, proc.stdout
    # ...or the CLI-wide override knob is loosened
    proc = _run_compare(tmp_path, fresh, base, extra_args=["--tolerance", ".6"])
    assert proc.returncode == 0, proc.stdout


def test_bench_compare_fails_on_missing_family(tmp_path):
    base = [("serving/llama3.2-1b/prefill_tok_s/B8xP16", 10.0, 100.0)]
    proc = _run_compare(tmp_path, [], base)
    assert proc.returncode == 1
    assert "missing from fresh run" in proc.stdout


def test_bench_compare_zero_baseline_warns_instead_of_silently_passing(tmp_path):
    """Satellite bugfix: a 0.0 baseline used to skip the comparison without
    a word.  It must now warn explicitly (and stay neutral — a zero cannot
    anchor a relative gate)."""
    base = [("serving/llama3.2-1b/prefill_tok_s/B8xP16", 10.0, 0.0)]
    fresh = [("serving/llama3.2-1b/prefill_tok_s/B8xP16", 10.0, 0.0)]
    proc = _run_compare(tmp_path, fresh, base)
    assert proc.returncode == 0, proc.stdout
    assert "warn" in proc.stdout and "zero baseline" in proc.stdout


def test_bench_compare_fails_when_nonzero_family_drops_to_zero(tmp_path):
    """A previously-nonzero family reporting 0.0 is a dead metric — fail
    regardless of how loose the family's tolerance is."""
    base = [("serving/llama3.2-1b/prefill_tok_s/B8xP16", 10.0, 100.0)]
    fresh = [("serving/llama3.2-1b/prefill_tok_s/B8xP16", 10.0, 0.0)]
    proc = _run_compare(tmp_path, fresh, base,
                        tolerances={"serving/": 0.99})
    assert proc.returncode == 1, proc.stdout
    assert "went dead" in proc.stdout


SPEEDUP_FAMILY = "serving/llama3.2-1b/decode_speedup_fused_vs_sync"


def test_bench_compare_require_improvement_gate(tmp_path):
    """Absolute gate for ratio metrics: >= 1.0 means the fused path wins,
    whatever the committed baseline says — a blessed-in regression cannot
    silently return."""
    winning = [(f"{SPEEDUP_FAMILY}-1.07x", 0.0, 1.07)]
    losing = [(f"{SPEEDUP_FAMILY}-0.54x", 0.0, 0.54)]
    # pass: family present and >= 1.0 (the -1.07x suffix normalizes away)
    proc = _run_compare(tmp_path, winning, winning,
                        extra_args=["--require-improvement", SPEEDUP_FAMILY])
    assert proc.returncode == 0, proc.stdout
    assert "required improvement holds" in proc.stdout
    # fail: present but < 1.0 — even though the relative trend gate passes
    proc = _run_compare(tmp_path, losing, losing,
                        extra_args=["--require-improvement", SPEEDUP_FAMILY])
    assert proc.returncode == 1, proc.stdout
    assert "REQUIRED IMPROVEMENT FAILED" in proc.stdout
    # fail: family missing entirely
    other = [("serving/llama3.2-1b/prefill_tok_s/B8xP16", 1.0, 10.0)]
    proc = _run_compare(tmp_path, other, other,
                        extra_args=["--require-improvement", SPEEDUP_FAMILY])
    assert proc.returncode == 1, proc.stdout
    assert "family missing" in proc.stdout


def test_bench_compare_refuses_to_bless_failing_requirement(tmp_path):
    """--write-baseline must not capture a file that fails the absolute
    gate: losing runs cannot become the new normal."""
    losing = [(f"{SPEEDUP_FAMILY}-0.54x", 0.0, 0.54)]
    name = "BENCH_gemm_tuning__cpu-interpret.json"
    bdir = tmp_path / "baselines"
    proc = _run_compare(tmp_path, losing, losing,
                        extra_args=["--require-improvement", SPEEDUP_FAMILY,
                                    "--write-baseline"])
    # _run_compare pre-writes the baseline file; blessing would REWRITE it
    # with the fresh (losing) rows — verify it still holds the old blob
    assert proc.returncode == 1, proc.stdout
    assert "refusing to bless" in proc.stdout
    base = json.loads((bdir / name).read_text())
    assert base["rows"][0]["derived"] == 0.54   # pre-written, not re-blessed
    assert "tolerances" not in base             # bless would have added them


def test_committed_bench_baselines_exist():
    bdir = os.path.join(REPO, "benchmarks", "baselines")
    for suite in ("gemm_tuning", "attention_tuning", "serving"):
        path = os.path.join(bdir,
                            f"BENCH_{suite}__{CPU_INTERPRET.name}.json")
        assert os.path.exists(path), f"missing committed baseline {path}"
        blob = json.load(open(path))
        assert blob["hardware"] == CPU_INTERPRET.name
        assert blob["rows"] and blob["tolerances"]
