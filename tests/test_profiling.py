"""Profiling layer: strict no-op when off, real captures parse into the
per-op-family breakdown, the classifier/summarizer handle synthetic events,
PROFILE schema validation, and the engine's one-device_get-per-wave
invariant with tracing annotations enabled."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.catalog import ARCHITECTURES
from repro.models import build_model
from repro.profiling import (FAMILIES, PROFILE_SCHEMA_VERSION, annotate,
                             build_profile, classify_event_name,
                             load_trace_events, summarize_events, trace,
                             validate_profile)
from repro.serve import Engine, ServeConfig


# ---------------------------------------------------------------------------
# trace(...) capture
# ---------------------------------------------------------------------------

def test_trace_disabled_is_strict_noop(tmp_path):
    """Off = OFF: no directory creation, no env mutation, inert session.
    This is what lets the launchers keep trace(...) permanently in the
    serve/train hot paths."""
    target = tmp_path / "never-created"
    env_before = dict(os.environ)
    with trace(str(target), enabled=False) as s:
        jnp.square(jnp.arange(4.0)).block_until_ready()
    assert not s.enabled and s.dir is None
    assert s.trace_files() == [] and s.events() == []
    assert not target.exists()
    # falsy dir disables too, even with enabled=True
    with trace(None) as s:
        pass
    assert not s.enabled
    assert dict(os.environ) == env_before   # XLA_FLAGS & friends untouched


def test_trace_captures_parseable_breakdown(tmp_path):
    """A real (tiny) capture round-trips: gzipped Chrome-trace files appear
    under the session dir, parse with the stdlib loader, and roll up into a
    schema-valid PROFILE blob with the annotated span present."""
    target = tmp_path / "cap"
    x = jnp.ones((64, 64), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()               # compile outside the trace
    with trace(str(target)) as s:
        with annotate("serve.unit_test_span"):
            jax.device_get(f(x))
    assert s.enabled and s.trace_files(), "no capture written"
    events = load_trace_events(str(target))
    assert events
    blob = build_profile("serving", events=events, hardware="cpu-interpret")
    validate_profile(blob)                 # raises on any schema violation
    assert blob["schema_version"] == PROFILE_SCHEMA_VERSION
    assert set(blob["families"]) == set(FAMILIES)
    assert blob["totals"]["op_us"] > 0
    assert "serve.unit_test_span" in blob["annotations"]
    # the blob is JSON-serializable as written by scripts/profile.py
    json.dumps(blob)


def test_load_trace_events_raises_on_empty_dir(tmp_path):
    """CI's "the profiler actually ran" check: an empty trace dir is an
    error, not an empty (and trivially green) breakdown."""
    with pytest.raises(FileNotFoundError):
        load_trace_events(str(tmp_path))


# ---------------------------------------------------------------------------
# classifier + summarizer on synthetic events
# ---------------------------------------------------------------------------

def test_classify_event_name_families():
    assert classify_event_name("all-reduce.7") == "collective"
    assert classify_event_name("all-gather-start.2") == "collective"
    assert classify_event_name("reduce-scatter") == "collective"
    assert classify_event_name("dot.30") == "gemm"
    assert classify_event_name("convolution.1") == "gemm"
    assert classify_event_name("softmax_fusion") == "attention"
    assert classify_event_name("fusion.12") == "other"
    assert classify_event_name("dynamic-update-slice.4") == "other"


def _ev(name, dur, ts=0, hlo=None):
    ev = {"ph": "X", "name": name, "dur": dur, "ts": ts}
    if hlo:
        ev["args"] = {"hlo_op": hlo}
    return ev


def test_summarize_events_synthetic():
    events = [
        _ev("xla-op", 100.0, ts=0, hlo="all-reduce.1"),
        _ev("xla-op", 50.0, ts=100, hlo="all-reduce.2"),
        _ev("xla-op", 30.0, ts=150, hlo="dot.5"),
        # container op: covers the leaves above, must NOT double-count
        _ev("xla-op", 500.0, ts=0, hlo="while.3"),
        # host fetch: runtime event, no hlo_op
        _ev("np.asarray(jax.Array)", 20.0, ts=200),
        # annotate(...) marker
        _ev("serve.decode_wave", 400.0, ts=0),
        # non-duration events are ignored
        {"ph": "M", "name": "process_name"},
    ]
    s = summarize_events(events)
    assert s["families"]["collective"]["us"] == 150.0
    assert s["families"]["collective"]["count"] == 2
    assert s["families"]["gemm"]["us"] == 30.0
    assert s["families"]["host_transfer"]["us"] == 20.0
    assert s["host_syncs"] == 1
    assert s["families"]["other"]["us"] == 0.0    # while.3 excluded
    assert s["totals"]["op_us"] == 180.0          # device ops, no transfers
    # SSA numbering folds: two all-reduce events -> one top op
    assert s["top_ops"][0] == {"name": "all-reduce", "us": 150.0, "count": 2}
    assert s["annotations"] == {
        "serve.decode_wave": {"us": 400.0, "count": 1}}
    assert s["totals"]["wall_us"] == 500.0        # ts 0 .. 100+400


def test_summarize_fractions_sum_to_one():
    events = [_ev("x", 75.0, hlo="dot.1"), _ev("x", 25.0, hlo="add.2")]
    s = summarize_events(events)
    assert sum(e["fraction"] for e in s["families"].values()) == pytest.approx(1.0)
    assert s["families"]["gemm"]["fraction"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# PROFILE schema validation
# ---------------------------------------------------------------------------

def _valid_blob():
    return build_profile("serving", events=[
        _ev("x", 10.0, hlo="dot.1"), _ev("np.asarray(jax.Array)", 1.0)])


def test_validate_profile_accepts_and_returns_blob():
    blob = _valid_blob()
    assert validate_profile(blob) is blob


def test_validate_profile_lists_every_violation():
    blob = _valid_blob()
    blob["schema_version"] = 99
    del blob["families"]["gemm"]
    blob["host_syncs"] = -1
    with pytest.raises(ValueError) as e:
        validate_profile(blob)
    msg = str(e.value)
    assert "schema_version" in msg
    assert "families['gemm'] missing" in msg
    assert "host_syncs" in msg


def test_validate_profile_rejects_empty_capture():
    """A trace that captured nothing (zero totals) must fail — that is the
    CI profiling leg's guard against a silently-dead profiler."""
    blob = build_profile("serving", events=[])
    with pytest.raises(ValueError) as e:
        validate_profile(blob)
    assert "op_us" in str(e.value) and "wall_us" in str(e.value)


# ---------------------------------------------------------------------------
# engine invariant under tracing
# ---------------------------------------------------------------------------

def test_fused_decode_one_device_get_per_wave_under_tracing(
        tmp_path, monkeypatch):
    """The annotate(...) markers in the decode path must not change the
    execution model: with a trace ACTIVE, the fused loop still performs
    exactly one jax.device_get per wave, and the capture shows the
    serve.prefill_wave/serve.decode_wave spans per wave."""
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    # wave pinned: the wave-specific annotation names and the one-get-per-
    # wave contract are what this test is about; the continuous scheduler's
    # one-get-per-chunk contract lives in test_recompile_count.py
    eng = Engine(model, params, ServeConfig(max_batch=2, max_len=64,
                                            scheduler="wave"))
    prompts = [[5, 9, 2], [1, 3, 3], [2, 4, 6]]      # 3 prompts, 2 slots
    eng.generate(prompts, 4)                          # compile outside count
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda *a, **k: (
        calls.append(1), real(*a, **k))[1])
    waves0 = eng.stats()["waves"]
    with trace(str(tmp_path / "cap")) as s:
        eng.generate(prompts, 4)
    waves = eng.stats()["waves"] - waves0
    assert waves == 2
    assert len(calls) == waves                        # one fetch per wave
    ann = summarize_events(s.events())["annotations"]
    assert ann.get("serve.prefill_wave", {}).get("count") == waves
    assert ann.get("serve.decode_wave", {}).get("count") == waves


# ---------------------------------------------------------------------------
# scripts/profile.py end to end on a mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cmd,extra", [
    ("serve", ["--plen", "4", "--max-new", "2", "--max-len", "32"]),
    ("train", ["--steps", "1", "--seq-len", "8"]),
])
def test_profile_script_on_a_mesh(tmp_path, cmd, extra):
    """The profiler's serve and train commands build their --mesh (forcing
    the host device count themselves), capture a trace and write a valid
    PROFILE with the roofline of the same step."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = tmp_path / "PROFILE.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "profile.py"), cmd,
         "--mesh", "data=2,model=2", "--batch", "4", "--out", str(out),
         "--trace-dir", str(tmp_path / "trace")] + extra,
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(out) as f:
        blob = validate_profile(json.load(f))
    assert blob["mesh"] == "data2xmodel2"
    assert blob["roofline"] and blob["roofline"]["chips"] == 4, proc.stdout
