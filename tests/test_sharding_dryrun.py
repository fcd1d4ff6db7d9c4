"""Sharding-rule unit tests + an in-subprocess mini dry-run on an 8-device
host mesh (subprocess isolates XLA_FLAGS from the 1-device test session)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.models.params import ParamSpec


def test_param_spec_rules_small_mesh():
    """Verify the logical->mesh mapping rules without building a mesh, via a
    stub mesh object."""
    from repro.distributed.sharding import ShardingRules, spec_for_param

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    rules = ShardingRules(tensor_axis="model", fsdp_axis="data",
                          batch_axes=("data",))
    mesh = FakeMesh()

    # embedding (vocab, embed) -> (model, data)
    sp = spec_for_param(mesh, rules, ParamSpec((128, 64), ("vocab", "embed")))
    assert tuple(sp) == ("model", "data")
    # attention wq (embed, ff) -> (data, model)
    sp = spec_for_param(mesh, rules, ParamSpec((64, 128), ("embed", "ff")))
    assert tuple(sp) == ("data", "model")
    # expert weights (expert, embed, ff): model used once (expert wins)
    sp = spec_for_param(mesh, rules,
                        ParamSpec((8, 64, 128), ("expert", "embed", "ff")))
    assert tuple(sp) == ("model", "data", None)
    # non-divisible dim falls back to replicated
    sp = spec_for_param(mesh, rules, ParamSpec((63, 128), ("vocab", "ff")))
    assert tuple(sp) == (None, "model")
    # 1-D params replicated
    sp = spec_for_param(mesh, rules, ParamSpec((64,), ("embed",)))
    assert tuple(sp) == ()
    # stacked layer axis never sharded
    sp = spec_for_param(mesh, rules,
                        ParamSpec((4, 64, 128), ("layer", "embed", "ff")))
    assert tuple(sp) == (None, "data", "model")


class _FakeMesh:
    def __init__(self, **shape):
        self.axis_names = tuple(shape)
        self.shape = shape


def test_rules_for_mesh_axis_presence():
    """rules_for_mesh degrades gracefully with whatever axes the mesh has."""
    from repro.distributed.sharding import rules_for_mesh

    r = rules_for_mesh(_FakeMesh(data=4, model=2))
    assert (r.tensor_axis, r.fsdp_axis, r.batch_axes) == ("model", "data",
                                                          ("data",))
    assert r.sequence_axis is None
    # data-only mesh: no tensor axis to map TP onto
    r = rules_for_mesh(_FakeMesh(data=8))
    assert r.tensor_axis is None and r.fsdp_axis == "data"
    # model-only mesh: batch falls back to the first axis
    r = rules_for_mesh(_FakeMesh(model=8))
    assert r.tensor_axis == "model" and r.fsdp_axis is None
    assert r.batch_axes == ("model",)
    # multi-pod: batch spans the pod AND data axes, in that order
    r = rules_for_mesh(_FakeMesh(pod=2, data=4, model=2))
    assert r.batch_axes == ("pod", "data")
    # knobs: FSDP off, sequence parallelism on
    r = rules_for_mesh(_FakeMesh(data=4, model=2), fsdp=False,
                       sequence_parallel=True)
    assert r.fsdp_axis is None and r.sequence_axis == "model"
    # sequence parallelism needs a model axis to land on
    r = rules_for_mesh(_FakeMesh(data=8), sequence_parallel=True)
    assert r.sequence_axis is None


def test_rules_for_mesh_spec_edge_cases():
    """Edge cases threaded end-to-end through rules_for_mesh -> specs:
    non-divisible dims replicate, 1-D params replicate, and a mesh axis is
    used at most once per spec."""
    from repro.distributed.sharding import rules_for_mesh, spec_for_param

    mesh = _FakeMesh(data=4, model=2)
    rules = rules_for_mesh(mesh)
    # dims not divisible by their target axis size fall back to replicated
    sp = spec_for_param(mesh, rules, ParamSpec((63, 128), ("vocab", "ff")))
    assert tuple(sp) == (None, "model")
    sp = spec_for_param(mesh, rules, ParamSpec((64, 125), ("embed", "ff")))
    assert tuple(sp) == ("data", None)
    # 1-D params (norm scales, biases) always replicate
    for axes in (("embed",), ("vocab",), (None,)):
        assert tuple(spec_for_param(mesh, rules, ParamSpec((64,), axes))) == ()
    # a mesh axis is used at most once per spec (first dim wins)
    sp = spec_for_param(mesh, rules,
                        ParamSpec((8, 64, 128), ("expert", "embed", "ff")))
    assert tuple(sp) == ("model", "data", None)
    sp = spec_for_param(mesh, rules, ParamSpec((128, 64), ("vocab", "ff")))
    assert tuple(sp) == ("model", None)


def test_gemm_shard_layout():
    """The per-shard GEMM split matmul runs on a mesh — and reports to the
    serve engine's tile lookups — from the weight's logical axes: TP splits
    kept, FSDP shards gathered, non-divisible dims replicated."""
    from repro.core.gemm_api import _shard_layout
    from repro.distributed.ctx import ActivationPolicy
    from repro.distributed.sharding import mesh_axis_label, rules_for_mesh

    mesh = _FakeMesh(data=4, model=2)
    fsdp = ActivationPolicy(mesh, rules_for_mesh(mesh))
    col, row = ("embed", "ff"), ("ff", "embed")
    # column parallel: N on the model axis; the FSDP split of K is gathered
    assert _shard_layout(fsdp, 8, 64, 128, col) == (
        (("data",), None, "model"), (2, 64, 64))
    # row parallel: K on the model axis (partial sums all-reduced)
    assert _shard_layout(fsdp, 8, 128, 64, row) == (
        (("data",), "model", None), (2, 64, 64))
    # a square (K, N) splits both ways, by its axes, not its shape
    assert _shard_layout(fsdp, 8, 64, 64, col)[1] == (2, 64, 32)
    assert _shard_layout(fsdp, 8, 64, 64, row)[1] == (2, 32, 64)
    # M not divisible by the batch axes, N not by the model axis: replicated
    assert _shard_layout(fsdp, 3, 64, 125, col) == (
        (None, None, None), (3, 64, 125))
    assert _shard_layout(fsdp, 8, 64, 128, (None, None))[1] == (2, 64, 128)
    with pytest.raises(ValueError, match="w_axes"):
        _shard_layout(fsdp, 8, 64, 128, None)
    assert mesh_axis_label(mesh) == "data4xmodel2"
    assert mesh_axis_label(None) is None


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax
    from repro.configs.catalog import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.dryrun import lower_cell
    from repro.launch.mesh import make_host_mesh
    from repro.distributed import sharding as sh

    cfg = get_config("{arch}").reduced()
    cfg = dataclasses.replace(cfg, dtype="float32")
    shape = ShapeSpec("t", seq_len=16, global_batch=8, kind="{kind}")
    mesh = make_host_mesh(data=4, model=2)
    rules = sh.rules_for_mesh(mesh)
    lowered, meta = lower_cell(cfg, shape, mesh, rules)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    print("RESULT " + json.dumps({{"flops": float(cost["flops"]),
                                   "kind": meta["kind"]}}))
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch,kind", [
    ("llama3.2-1b", "train"),
    ("olmoe-1b-7b", "train"),
    ("mamba2-130m", "decode"),
    ("zamba2-2.7b", "prefill"),
    ("whisper-large-v3", "decode"),
    ("llama-3.2-vision-11b", "train"),
])
def test_mini_dryrun_compiles_on_8dev_mesh(arch, kind):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROC.format(arch=arch, kind=kind)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["flops"] > 0
    assert rec["kind"] == kind
