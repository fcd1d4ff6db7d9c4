"""Consolidated launcher CLI: one declaration of the shared flags, a
serving argument group, and warn-and-forward semantics for retired flags.

Host-side argparse only — no engine or device work.  Guards the contract
that ``launch/serve.py`` and ``launch/train.py`` expose identical common
flags (so the copies can never drift again) and that old command lines
keep working one release while printing their migration path.
"""
import argparse

import pytest

from repro.launch.common import (add_common_args, add_serving_args,
                                 deprecated_flag, forward_deprecated)

COMMON_FLAGS = ["--hardware", "--mesh", "--stats", "--tuned-dir",
                "--trace-dir"]
SERVING_FLAGS = ["--scheduler", "--page-size", "--capacity-tokens",
                 "--decode-chunk", "--no-prefix-cache"]


def _option_strings(ap):
    return {s for a in ap._actions for s in a.option_strings}


def test_common_args_single_declaration():
    ap = argparse.ArgumentParser()
    add_common_args(ap)
    assert set(COMMON_FLAGS) <= _option_strings(ap)
    args = ap.parse_args(["--mesh", "data=2,model=2", "--stats"])
    assert args.mesh == "data=2,model=2" and args.stats is True
    assert args.hardware is None and args.tuned_dir is None


def test_serving_args_group_and_defaults():
    ap = argparse.ArgumentParser()
    add_serving_args(ap)
    assert set(SERVING_FLAGS) <= _option_strings(ap)
    assert any(g.title == "serving" for g in ap._action_groups)
    args = ap.parse_args([])
    assert args.scheduler == "continuous" and args.decode_chunk == 8
    assert args.page_size is None and not args.no_prefix_cache
    with pytest.raises(SystemExit):
        ap.parse_args(["--scheduler", "bogus"])


def test_both_launchers_expose_the_same_common_flags():
    """The drift this module exists to prevent: serve.py and train.py must
    agree flag-for-flag on the shared surface."""
    from repro.launch import serve, train
    surfaces = []
    for mod in (serve, train):
        ap = argparse.ArgumentParser()
        add_common_args(ap)
        surfaces.append(_option_strings(ap) & set(COMMON_FLAGS))
        # and the modules import the shared declaration, not a copy
        assert mod.add_common_args is add_common_args
    assert surfaces[0] == surfaces[1] == set(COMMON_FLAGS)


def test_deprecated_flag_warns_and_forwards():
    ap = argparse.ArgumentParser()
    add_common_args(ap)
    deprecated_flag(ap, "--mesh-data", "--mesh", type=int)
    with pytest.warns(DeprecationWarning, match="--mesh-data is deprecated"):
        args = ap.parse_args(["--mesh-data", "4"])
    assert args.mesh_data == 4
    assert args._deprecated_used == {"mesh_data"}
    forward_deprecated(args, {"mesh_data": ("mesh", lambda v: f"data={v}")})
    assert args.mesh == "data=4"


def test_deprecated_flag_loses_to_the_modern_flag():
    ap = argparse.ArgumentParser()
    add_common_args(ap)
    deprecated_flag(ap, "--mesh-data", "--mesh", type=int)
    with pytest.warns(DeprecationWarning):
        args = ap.parse_args(["--mesh-data", "4", "--mesh", "data=8"])
    forward_deprecated(args, {"mesh_data": ("mesh", lambda v: f"data={v}")})
    assert args.mesh == "data=8"          # explicit modern flag wins


def test_deprecated_flag_hidden_and_inert_when_unused():
    ap = argparse.ArgumentParser()
    add_common_args(ap)
    deprecated_flag(ap, "--mesh-data", "--mesh", type=int)
    args = ap.parse_args([])              # no warning, no _deprecated_used
    assert getattr(args, "_deprecated_used", set()) == set()
    forward_deprecated(args, {"mesh_data": ("mesh", lambda v: f"data={v}")})
    assert args.mesh is None
    # retired flags stay out of --help
    assert "--mesh-data" not in ap.format_help()


def test_train_legacy_mesh_pair_builds_a_mesh_spec():
    """The real train.py composition: --mesh-data/--mesh-model warn and
    combine into one 'data=N,model=M' spec unless --mesh was given."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1)
    add_common_args(ap)
    deprecated_flag(ap, "--mesh-data", "--mesh", type=int)
    deprecated_flag(ap, "--mesh-model", "--mesh", type=int)
    with pytest.warns(DeprecationWarning):
        args = ap.parse_args(["--mesh-data", "4", "--mesh-model", "2"])
    used = getattr(args, "_deprecated_used", set())
    assert used == {"mesh_data", "mesh_model"}
    if {"mesh_data", "mesh_model"} & used and not args.mesh:
        args.mesh = f"data={args.mesh_data or 1},model={args.mesh_model or 1}"
    assert args.mesh == "data=4,model=2"


@pytest.mark.parametrize("hardware,env", [
    ("tpu-v5e", "LIBTPU_INIT_ARGS"),   # XLA_FLAGS aborts on xla_tpu_* flags
    ("gpu-generic", "XLA_FLAGS"),
])
def test_latency_hiding_flags_reach_the_runtime(monkeypatch, hardware, env):
    """A mesh launcher named a profile sets its collective-overlap flags
    where that runtime reads them, keeps a flag the caller set, and adds
    nothing twice."""
    from repro.core.hardware import get_profile
    from repro.launch.common import apply_latency_hiding_flags
    flags = get_profile(hardware).xla_latency_flags
    assert flags
    other = {"LIBTPU_INIT_ARGS", "XLA_FLAGS"} - {env}
    monkeypatch.delenv("REPRO_HARDWARE", raising=False)
    for name in other:
        monkeypatch.delenv(name, raising=False)
    user = flags[0].split("=")[0] + "=false"
    monkeypatch.setenv(env, user)
    assert apply_latency_hiding_flags(hardware) == list(flags[1:])
    import os
    assert os.environ[env].split() == [user] + list(flags[1:])
    assert not any(os.environ.get(name) for name in other)
    assert apply_latency_hiding_flags(hardware) == []
    # $REPRO_HARDWARE names the profile too; no name and the host CPU set
    # nothing (detecting the profile would start the backend)
    monkeypatch.delenv(env)
    monkeypatch.setenv("REPRO_HARDWARE", hardware)
    assert apply_latency_hiding_flags(None) == list(flags)
    monkeypatch.delenv(env)
    monkeypatch.delenv("REPRO_HARDWARE")
    assert apply_latency_hiding_flags(None) == []
    assert apply_latency_hiding_flags("cpu-interpret") == []
    assert env not in os.environ
