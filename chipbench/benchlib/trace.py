"""Reduce one profiler capture to the numbers the per-layer metrics read.

``jax.profiler`` writes a Chrome-trace file, ``*.trace.json.gz``, under
``<dir>/plugins/profile/<time>/``.  Its processes are named: a device is
``/device:TPU:<n>``, with threads ``XLA Ops`` (one event per HLO op run on
the chip) and ``XLA Modules`` (one per program run); the host's threads
carry the program's ``serve.*`` annotations.  Host and device events share
one clock, in microseconds.

This module keeps to the standard library and numpy; the reading of names
follows ``repro.profiling.breakdown`` (gzip + json, SSA numbers folded),
with device time taken as the union of op intervals on one device.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

_DEVICE_RE = re.compile(r"/device:TPU:(\d+)")
_ANNOTATION_RE = re.compile(r"^serve\.[\w.]+$")
_SSA_RE = re.compile(r"\.\d+$")
_MODULE_RE = re.compile(r"^(jit_\w+?)(\(\d+\))?$")
#: control-flow ops: their span holds the ops of their bodies
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str
    ts: float          # microseconds
    dur: float
    args: dict


def load_events(trace_dir: str) -> List[dict]:
    """All Chrome-trace events of the newest capture under ``trace_dir``."""
    caps = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*")), key=os.path.getmtime)
    if not caps:
        raise FileNotFoundError(f"no profiler capture under {trace_dir}")
    events: List[dict] = []
    for path in sorted(glob.glob(os.path.join(caps[-1], "*.trace.json.gz"))):
        with gzip.open(path, "rt") as f:
            events.extend(json.load(f).get("traceEvents", []))
    return events


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Reduction:
    """One device's ops and modules, the host's annotations, and the
    traced window."""
    ops: List[Op]                       # device 0's XLA ops
    modules: List[Op]                   # device 0's program runs
    annotations: List[Op]               # host serve.* spans
    devices: int
    busy_per_device: List[float]        # seconds
    window_s: float
    t_lo: float                         # microseconds, trace clock
    t_hi: float

    @property
    def busy_s(self) -> float:
        return sum(self.busy_per_device) / len(self.busy_per_device)

    def module_seconds(self, prefix: str) -> float:
        """Summed device span of the runs of one program (``jit_<fn>``)."""
        return sum(m.dur for m in self.modules
                   if module_name(m.name) == prefix) * 1e-6

    def ops_in(self, prefix: str) -> List[Op]:
        """Device ops that ran inside a run of program ``prefix``."""
        spans = sorted((m.ts, m.ts + m.dur) for m in self.modules
                       if module_name(m.name) == prefix)
        out, i = [], 0
        for op in sorted(self.ops, key=lambda o: o.ts):
            while i < len(spans) and spans[i][1] < op.ts:
                i += 1
            if i < len(spans) and spans[i][0] <= op.ts <= spans[i][1]:
                out.append(op)
        return out

    def idle_gaps(self, top: int = 10) -> List[list]:
        """The longest gaps between device-0 ops, each named by the host
        annotation that covers most of it (``host.none`` where none)."""
        busy = merged([(o.ts, o.ts + o.dur) for o in self.ops])
        gaps = [(b[1], c[0]) for b, c in zip(busy, busy[1:]) if c[0] > b[1]]
        if busy:
            gaps += [(self.t_lo, busy[0][0]), (busy[-1][1], self.t_hi)]
        gaps = sorted((g for g in gaps if g[1] > g[0]),
                      key=lambda g: g[0] - g[1])[:top]
        out = []
        for a, b in gaps:
            best, name = 0.0, "host.none"
            for ann in self.annotations:
                ov = min(b, ann.ts + ann.dur) - max(a, ann.ts)
                if ov > best:
                    best, name = ov, ann.name
            out.append([name, (b - a) * 1e-6])
        return out

    def top_ops(self, top: int = 10) -> List[list]:
        tot: Dict[str, float] = {}
        for o in self.ops:
            key = op_label(o)
            tot[key] = tot.get(key, 0.0) + o.dur
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v * 1e-6] for k, v in best]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def module_name(name: str) -> str:
    m = _MODULE_RE.match(name)
    return m.group(1) if m else name


def op_label(op: Op) -> str:
    """An op's name with SSA numbering folded; a Mosaic kernel by its kind
    and operand shape."""
    from benchlib.kernels import kernel_kind, hlo_shapes
    kind = kernel_kind(op)
    if kind is None:
        return _SSA_RE.sub("", op.name)
    _, ops = hlo_shapes(op.args.get("long_name", ""))
    dims = [s[1] for s in ops if len(s[1]) >= 2][:2]
    return f"{kind}{list(dims)}".replace(" ", "")


def reduce_events(events: List[dict], window_s: Optional[float] = None
                  ) -> Reduction:
    """Split the events into device 0's ops and modules and the host's
    annotations; busy time per device is the union of its op intervals."""
    pname: Dict[int, str] = {}
    tname: Dict[Tuple[int, int], str] = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pname[ev["pid"]] = ev.get("args", {}).get("name", "")
        elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tname[(ev["pid"], ev.get("tid"))] = ev.get("args", {}).get(
                "name", "")
    dev_pid: Dict[int, int] = {}
    for pid, name in pname.items():
        m = _DEVICE_RE.search(name)
        if m:
            dev_pid[pid] = int(m.group(1))
    ops: Dict[int, List[Op]] = {d: [] for d in dev_pid.values()}
    modules: List[Op] = []
    annotations: List[Op] = []
    lo, hi = float("inf"), float("-inf")
    for ev in events:
        if ev.get("ph") != "X":
            continue
        ts, dur = float(ev.get("ts", 0.0)), float(ev.get("dur", 0.0))
        lo, hi = min(lo, ts), max(hi, ts + dur)
        pid = ev.get("pid")
        op = Op(ev.get("name", ""), ts, dur, ev.get("args") or {})
        if pid in dev_pid:
            thread = tname.get((pid, ev.get("tid")), "")
            if op.args.get("hlo_category") in _CONTAINERS:
                continue        # its duration covers the ops it runs
            if thread == "XLA Ops":
                ops[dev_pid[pid]].append(op)
            elif thread == "XLA Modules" and dev_pid[pid] == min(ops):
                modules.append(op)
        elif _ANNOTATION_RE.match(op.name):
            annotations.append(op)
    if not ops:
        raise ValueError("no device in the trace")
    d0 = min(ops)
    busy = [union_length([(o.ts, o.ts + o.dur) for o in ops[d]]) * 1e-6
            for d in sorted(ops)]
    if window_s is None:
        window_s = (hi - lo) * 1e-6
    return Reduction(ops=ops[d0], modules=modules, annotations=annotations,
                     devices=len(ops), busy_per_device=busy,
                     window_s=window_s, t_lo=lo, t_hi=hi)


def reduce_dir(trace_dir: str, window_s: Optional[float] = None
               ) -> Reduction:
    return reduce_events(load_events(trace_dir), window_s)
