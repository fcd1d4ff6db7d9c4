"""The harness end to end on the CPU, at toy widths: cells made of new
files alone (a backlog, an open loop, a mesh of four devices), the
comparison that decides ``correct``, its control and a fault planted where
tokens are produced."""
import argparse
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchlib import cells

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent

PROBE = '''"""A metric a later change adds: the requests the window submitted."""


def read(run):
    return float(len(run.records))
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose tiny cell, traffic mix and one metric exist only as
    new files: no file the benchmark has is edited."""
    root = tmp_path_factory.mktemp("checkout")
    cb = root / "chipbench"
    for d in ("traffic", "metrics", "e2e"):
        shutil.copytree(BENCH / d, cb / d)
    (cb / "configs").mkdir()
    for name in ("tiny", "tiny_tp4"):
        shutil.copy(HERE / "data" / f"{name}.json",
                    cb / "configs" / f"{name}.json")
    for name in ("tiny_backlog", "tiny_open"):
        shutil.copy(HERE / "data" / f"{name}.json",
                    cb / "traffic" / f"{name}.json")
    (cb / "metrics" / "submitted_probe.py").write_text(PROBE)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"] += [
        {"name": "tiny.backlog", "config": "tiny", "traffic": "tiny_backlog",
         "chips": 1, "why": "toy widths on the CPU"},
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
         "chips": 1, "why": "open loop at toy widths on the CPU"},
        {"name": "tiny_tp4.backlog", "config": "tiny_tp4",
         "traffic": "tiny_backlog", "chips": 4,
         "why": "model=4 mesh at toy widths on four CPU devices"}]
    bench["end_to_end"].append({
        "name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["tiny.open"]})
    bench["per_layer"].append({
        "name": "submitted_probe", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "front end",
        "moves": "output_tok_s", "workloads": ["tiny.backlog"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, capsys, *, seed=2 ** 33 + 3, control=0, name="tiny.backlog"):
    import run as run_mod
    cell = cells.load_cell(name, bench_file=root / "BENCHMARK.json",
                           root=root / "chipbench")
    args = argparse.Namespace(seed=seed, seconds=3.0, trace=0,
                              control=control)
    assert run_mod.run_cell(cell, args, require_tpu=False) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_new_cell_found_by_name(root):
    cell = cells.load_cell("tiny.backlog", bench_file=root / "BENCHMARK.json",
                           root=root / "chipbench")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["kind"] == "backlog"
    names = [m["name"] for m in cell.per_layer]
    assert names == ["submitted_probe"]
    readers = cells.metric_readers(cell.per_layer, root=root / "chipbench")
    assert readers["submitted_probe"].read(
        argparse.Namespace(records=[1, 2])) == 2.0
    assert [m["name"] for m in cell.end_to_end] == ["output_tok_s",
                                                    "setup_s"]


def test_run_is_correct_and_prints_the_contract(root, capsys):
    res, err = _run(root, capsys)
    assert res["correct"] is True, res
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["device"]["count"] == 1 and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["checks"]["token_gap"]["value"] <= \
        res["checks"]["token_gap"]["limit"]
    assert err.strip().splitlines()[-1].startswith("check ")


def test_control_is_not_correct(root, capsys):
    """The float8 control in the program's place, scored on the same
    prompts and tokens, makes the run not correct; the program itself, on
    the same seed, is."""
    res, err = _run(root, capsys, seed=41, control=1)
    assert res["correct"] is False
    gap = res["checks"]["token_gap"]
    assert gap["value"] > gap["limit"]
    program = float(err.split("program token_gap ")[1].split()[0])
    assert program <= gap["limit"]


def test_open_loop_cell_from_files(root, capsys):
    """An open-loop cell: requests sent on schedule from the generator's
    own thread, timed from when they were due."""
    res, err = _run(root, capsys, seed=2 ** 32 + 11, name="tiny.open")
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"output_tok_s", "setup_s", "ttft_p95_ms"}
    assert res["metrics"]["ttft_p95_ms"]["value"] > 0
    late = err.split("generator lateness max ")[1]
    assert "over 0 timed sends" not in late


def test_mesh_cell_from_files(root):
    """A cell on a data=1,model=4 mesh, from a configuration file alone:
    weights drawn with the serving shardings, served by the engine on the
    mesh and checked by the reference.  Four CPU devices need a process
    of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (
        "import argparse, json, pathlib, sys\n"
        f"sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent / 'src')!r}]\n"
        "import run\n"
        "from benchlib import cells\n"
        f"root = pathlib.Path({str(root)!r})\n"
        "cell = cells.load_cell('tiny_tp4.backlog', "
        "bench_file=root / 'BENCHMARK.json', root=root / 'chipbench')\n"
        "args = argparse.Namespace(seed=2 ** 31 + 5, seconds=3.0, trace=0, "
        "control=0)\n"
        "sys.exit(run.run_cell(cell, args, require_tpu=False))\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["device"]["count"] == 4
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_token_altered_where_produced_is_not_correct(root, capsys,
                                                     monkeypatch):
    """A fault in the timed path: each chunk's first token of every slot
    is replaced as the engine copies the chunk to the host."""
    from repro.serve.engine import Engine
    real = Engine._run_chunk

    def broken(self, key):
        key, buf, lens = real(self, key)
        buf = buf.copy()
        buf[:, 0] = (buf[:, 0] + 1) % self.model.cfg.vocab_size
        return key, buf, lens

    monkeypatch.setattr(Engine, "_run_chunk", broken)
    res, _ = _run(root, capsys, seed=43)
    assert res["correct"] is False
    assert res["checks"]["token_gap"]["value"] > \
        res["checks"]["token_gap"]["limit"]


def test_decode_state_left_unchanged_is_not_correct(root, capsys,
                                                    monkeypatch):
    """A fault in the timed path: each decode chunk hands back the KV
    pools it was given, so what it decoded is never in the cache."""
    from repro.serve.engine import Engine
    real = Engine._run_chunk

    def broken(self, key):
        pools = self._pools
        out = real(self, key)
        self._pools = pools
        return out

    monkeypatch.setattr(Engine, "_run_chunk", broken)
    res, _ = _run(root, capsys, seed=47)
    assert res["correct"] is False
    assert res["checks"]["token_gap"]["value"] > \
        res["checks"]["token_gap"]["limit"]


def test_no_tpu_exits_nonzero(root):
    import run as run_mod
    cell = cells.load_cell("tiny.backlog", bench_file=root / "BENCHMARK.json",
                           root=root / "chipbench")
    with pytest.raises(SystemExit) as e:
        run_mod.devices_for(cell.chips)
    assert e.value.code != 0


def test_unknown_device_kind_exits_nonzero():
    from benchlib.peaks import peaks_for
    with pytest.raises(SystemExit):
        peaks_for("TPU v9 imaginary")
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
