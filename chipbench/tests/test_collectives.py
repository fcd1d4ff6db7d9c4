"""The four-chip tensor-parallel cell and its collective metrics: the
``yi-9b-tp4`` configuration against the program, a yi-layout cell served
on a data=1,model=4 mesh of four CPU devices and checked by the
reference, and the readers of ``collective_exposed_share`` and
``allreduce_bytes_per_token`` on hand-built traces."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import types

import pytest

from benchlib import cells, trace

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
NAMES = ("collective_exposed_share", "allreduce_bytes_per_token")
READ = {k: v.read for k, v in cells.metric_readers(
    [{"name": n} for n in NAMES]).items()}


def test_yi_configuration_is_the_program():
    """The cell's file states the program's sizes and the published
    parameter layout (no QKV bias), on four chips, with both collective
    metrics and no one-chip metric beyond the end-to-end ones."""
    import run as run_mod
    from benchlib import weights
    cell = cells.load_cell("yi-9b-tp4.mixed_long")
    assert cell.chips == 4 and cell.config["serve"]["mesh"] == "data=1,model=4"
    model = run_mod.program_model(cell.config)
    weights.check_layout(model.abstract(), cell.config["arch"])
    assert [m["name"] for m in cell.per_layer] == list(NAMES)
    assert [m["name"] for m in cell.end_to_end] == ["output_tok_s", "setup_s"]


def test_yi_layout_cell_on_four_devices_is_correct(tmp_path):
    """A cell of yi's layout at toy widths on a data=1,model=4 mesh, from
    new files alone: drawn with the serving shardings, served by the
    engine on the mesh and judged correct by the reference.  Four CPU
    devices need a process of their own."""
    cb = tmp_path / "chipbench"
    for d in ("traffic", "metrics", "e2e"):
        shutil.copytree(BENCH / d, cb / d)
    (cb / "configs").mkdir()
    shutil.copy(HERE / "data" / "tiny_yi_tp4.json",
                cb / "configs" / "tiny_yi_tp4.json")
    shutil.copy(HERE / "data" / "tiny_backlog.json",
                cb / "traffic" / "tiny_backlog.json")
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "tiny_yi_tp4.backlog", "config": "tiny_yi_tp4",
        "traffic": "tiny_backlog", "chips": 4,
        "why": "yi's layout on a model=4 mesh at toy widths"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (
        "import argparse, pathlib, sys\n"
        f"sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / 'src')!r}]\n"
        "import run\n"
        "from benchlib import cells\n"
        f"root = pathlib.Path({str(tmp_path)!r})\n"
        "cell = cells.load_cell('tiny_yi_tp4.backlog', "
        "bench_file=root / 'BENCHMARK.json', root=root / 'chipbench')\n"
        "args = argparse.Namespace(seed=2 ** 33 + 17, seconds=3.0, "
        "trace=0, control=0)\n"
        "sys.exit(run.run_cell(cell, args, require_tpu=False))\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0


def _meta(pid, name, threads):
    ev = [{"ph": "M", "name": "process_name", "pid": pid,
           "args": {"name": name}}]
    ev += [{"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": t}} for tid, t in threads.items()]
    return ev


def _x(pid, tid, name, ts, dur, **args):
    # the profiler writes args as strings
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": {k: str(v) for k, v in args.items()}}


def _run(ev):
    return types.SimpleNamespace(trace=trace.reduce_events(ev))


def _devices():
    ev = []
    for d in range(2):
        ev += _meta(d + 1, f"/device:TPU:{d}", {1: "XLA Ops",
                                                2: "XLA Modules"})
    return ev + _meta(9, "/host:CPU", {5: "python3"})


def _hlo(name, opcode, shape="bf16[16,4096]{1,0:T(8,128)(2,1)}"):
    # an op's long_name as the profiler writes it: instruction, shape,
    # opcode, operands with their shapes
    return (f"%{name} = {shape} {opcode}({shape} %shard_map.3), "
            "channel_id=1, replica_groups={{0,1,2,3}}")


def collective_events():
    """Device 0 busy 0-21 and 30-60 us: a synchronous row-parallel psum
    (10-14) between two GEMMs, an async all-reduce whose start (20-21) and
    done (40-44) halves frame a fusion, and an all-gather (50-56) that a
    copy (52-54) overlaps.  A fusion that reads an all-reduce's result is
    no collective; device 1's collectives do not count."""
    ev = _devices()
    ev += [
        _x(1, 1, "fusion.1", 0, 10, long_name=_hlo("fusion.1", "fusion")),
        _x(1, 1, "psum.64", 10, 4,
           long_name=_hlo("psum.64", "all-reduce")),         # exposed 4
        _x(1, 1, "fusion.2", 14, 6, long_name=(
            "%fusion.2 = bf16[16,4096]{1,0} fusion(bf16[16,4096]{1,0} "
            "%psum.64, s32[4]{0} %all-reduce.11), kind=kLoop")),
        _x(1, 1, "all-reduce-start.3", 20, 1,
           long_name=_hlo("all-reduce-start.3",
                          "all-reduce-start")),              # exposed 1
        _x(1, 1, "fusion.3", 30, 10),
        _x(1, 1, "all-reduce-done.3", 40, 4),                # exposed 4
        _x(1, 1, "fusion.4", 44, 6),
        _x(1, 1, "all-gather.8", 50, 6),                     # exposed 4 of 6
        _x(1, 1, "copy.2", 52, 2),
        _x(1, 1, "while.1", 0, 60, hlo_category="while"),   # a container
        _x(1, 1, "fusion.5", 56, 4),
        _x(2, 1, "psum.64", 0, 60, long_name=_hlo("psum.64", "all-reduce")),
    ]
    return ev


def test_collective_exposed_share_by_hand():
    # exposed 4 + 1 + 4 + 4 = 13 us of a busy union 0-21, 30-60 = 51 us
    assert READ["collective_exposed_share"](_run(collective_events())) == \
        pytest.approx(13 / 51)


def test_collective_exposed_share_without_collectives_is_none():
    ev = _devices() + [_x(1, 1, "fusion.1", 0, 10),
                       _x(1, 1, "psum.3", 10, 5,
                          long_name=_hlo("psum.3", "fusion"))]
    assert READ["collective_exposed_share"](_run(ev)) is None
    assert READ["collective_exposed_share"](
        types.SimpleNamespace(trace=None)) is None


def span_events(with_counter=True):
    """Two admissions and two decode chunks as the engine's spans carry
    them; the parent's program leaves the counter out."""
    def host(name, ts, dur, **args):
        if not with_counter:
            args.pop("allreduce_bytes", None)
        return _x(9, 5, name, ts, dur, **args)

    ev = _devices() + [_x(1, 1, "fusion.1", 0, 1)]
    ev += [
        host("serve.admit", 0, 5, admit_id=0, rows=1, batch=1, bucket=512,
             prompt_tokens=400, cached_tokens=0,
             allreduce_bytes=512 * 786432),
        host("serve.admit", 5, 5, admit_id=0, rows=1, batch=1, bucket=32,
             prompt_tokens=20, cached_tokens=0, allreduce_bytes=32 * 786432),
        host("serve.chunk", 10, 2, chunk_id=0, rows=2, width=1024),
        host("serve.chunk.wait", 12, 30, chunk_id=0,
             allreduce_bytes=8 * 16 * 786432),
        host("serve.emit", 42, 1, tokens=16),
        host("serve.chunk", 43, 2, chunk_id=1, rows=2, width=1024),
        host("serve.chunk.wait", 45, 30, chunk_id=1,
             allreduce_bytes=5 * 16 * 786432),
        host("serve.emit", 75, 1, tokens=9),
    ]
    return ev


def test_allreduce_bytes_per_token_by_hand():
    # (512 + 32 + 13 x 16) row-steps of 786432 bytes over 16 + 9 tokens
    want = (512 + 32 + 13 * 16) * 786432 / 25
    assert READ["allreduce_bytes_per_token"](_run(span_events())) == \
        pytest.approx(want)


def test_allreduce_bytes_per_token_absent_counter_is_none():
    """The parent's program puts no counter on its spans: no number, no
    error; nor without a trace."""
    assert READ["allreduce_bytes_per_token"](
        _run(span_events(with_counter=False))) is None
    assert READ["allreduce_bytes_per_token"](
        types.SimpleNamespace(trace=None)) is None
