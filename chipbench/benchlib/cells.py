"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``; its configuration lives in
``configs/<config>.json``, its traffic in ``traffic/<traffic>.json`` (whose
``kind`` names the generator ``traffic/<kind>.py``), each end-to-end
metric in ``e2e/<name>.py`` and each per-layer metric in
``metrics/<name>.py``.  Adding any of them is adding files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List, Optional

#: the benchmark's own directory (``chipbench/``)
HERE = pathlib.Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the cell's end-to-end metric entries
    per_layer: List[dict]       # the cell's per-layer metric entries


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Optional[pathlib.Path] = None,
              root: pathlib.Path = HERE) -> Cell:
    bench_file = bench_file or root.parent / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    config = json.loads((root / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((root / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    # a per-layer metric without a cell list goes to every cell that
    # reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def traffic_generator(traffic: dict, root: pathlib.Path = HERE):
    return load_module(root / "traffic" / f"{traffic['kind']}.py",
                       f"chipbench_traffic_{traffic['kind']}")


def metric_readers(entries: List[dict], kind: str = "metrics",
                   root: pathlib.Path = HERE) -> Dict[str, object]:
    """``read(run)`` of each metric entry: ``kind`` is ``metrics`` for the
    per-layer ones, ``e2e`` for the end-to-end ones."""
    return {m["name"]: load_module(root / kind / f"{m['name']}.py",
                                   f"chipbench_{kind}_" + m["name"]
                                   .replace(".", "_").replace("-", "_"))
            for m in entries}
