"""The readers of the engine's phase spans (``prefill_pad_share``,
``host_step_ms``, ``fetch_lag_ms``) on hand-built events and on one chunk
boundary with an admission recorded on the chip."""
import gzip
import json
import pathlib
import types

import pytest

from benchlib import cells, trace

NAMES = ("prefill_pad_share", "host_step_ms", "fetch_lag_ms")
READ = {k: v.read for k, v in cells.metric_readers(
    [{"name": n} for n in NAMES]).items()}


def _meta(pid, name, threads):
    ev = [{"ph": "M", "name": "process_name", "pid": pid,
           "args": {"name": name}}]
    ev += [{"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": t}} for tid, t in threads.items()]
    return ev


def _x(pid, tid, name, ts, dur, **args):
    # the profiler writes span args as strings
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": {k: str(v) for k, v in args.items()}}


def _host(name, ts, dur, **args):
    return _x(9, 5, name, ts, dur, **args)


def events():
    """Two decode chunks with an admission between them, the second
    chunk's dispatch held until the admission ends on the device, then a
    second admission (two partial hits) and a wait with no chunk run
    behind it."""
    ev = _meta(1, "/device:TPU:0", {1: "XLA Ops", 2: "XLA Modules"})
    ev += _meta(9, "/host:CPU", {5: "python3"})
    ev += [
        _x(1, 2, "jit_chunk_fn(3)", 2, 98),          # ends 100
        _x(1, 1, "fusion.1", 2, 98),
        _x(1, 2, "jit_admit_fn(4)", 120, 40),
        _x(1, 1, "fusion.2", 120, 40),
        _x(1, 2, "jit_chunk_fn(3)", 162, 118),       # ends 280
        _x(1, 1, "fusion.1", 162, 118),
        _host("serve.chunk", 0, 4, chunk_id=0, rows=8, width=512),
        _host("serve.chunk.wait", 4, 102, chunk_id=0),         # ends 106
        _host("serve.emit", 106, 3, tokens=64),
        _host("serve.ingest", 109, 1, n=1),
        _host("serve.admit.plan", 110, 6),
        _host("serve.request", 112, 0, rid=9, admit_id=0, front_us=40,
              queue_us=300, hit=0),
        _host("serve.admit", 116, 10, admit_id=0, rows=1, batch=8,
              bucket=512, prompt_tokens=300, cached_tokens=0),
        _host("serve.prefix_insert", 126, 2),
        _host("serve.chunk.plan", 128, 4),
        _host("serve.chunk", 132, 30, chunk_id=1, rows=8, width=512),
        _host("serve.chunk.wait", 162, 125, chunk_id=1),       # ends 287
        _host("serve.emit", 287, 2, tokens=64),
        _host("serve.admit", 289, 8, admit_id=1, rows=2, batch=8,
              bucket=2048, prompt_tokens=2200, cached_tokens=2048),
        _host("serve.chunk.wait", 297, 3, chunk_id=2),         # no run
    ]
    return ev


def _run(ev):
    return types.SimpleNamespace(trace=trace.reduce_events(ev))


def test_prefill_pad_share_by_hand():
    # useful 300 + (2200 - 2048) = 452 of 8 x 512 + 8 x 2048 = 20480 slots
    assert READ["prefill_pad_share"](_run(events())) == pytest.approx(
        1 - 452 / 20480)


def test_host_step_ms_by_hand():
    # chunk 4 + emit 3 + ingest 1 + admit.plan 6 + admit 10 + insert 2 +
    # chunk.plan 4 + chunk 30 + emit 2 + admit 8 = 70 us, less the 28 us
    # (132 to 160) the second chunk's dispatch waits out the admission
    # that began before it: 42 us over 3 waits.  The first admission's
    # dispatch overlaps its own program, which began after it: kept.
    assert READ["host_step_ms"](_run(events())) == pytest.approx(14e-3)


def test_fetch_lag_ms_by_hand():
    # wait ends 106 after the run that ended at 100; 287 after 280; the
    # wait ending at 300 has no run that ended after the previous wait
    assert READ["fetch_lag_ms"](_run(events())) == pytest.approx(6.5e-3)


def test_fetch_lag_ms_is_the_median_wait():
    """One wait that returns late does not move the reading."""
    ev = _meta(1, "/device:TPU:0", {1: "XLA Ops", 2: "XLA Modules"})
    ev += _meta(9, "/host:CPU", {5: "python3"})
    for k, (run_end, lag) in enumerate([(100, 2), (300, 3), (500, 2000)]):
        ev += [_x(1, 2, "jit_chunk_fn(3)", run_end - 90, 90),
               _x(1, 1, "fusion.1", run_end - 90, 90),
               _host("serve.chunk.wait", run_end - 95, 95 + lag,
                     chunk_id=k)]
    assert READ["fetch_lag_ms"](_run(ev)) == pytest.approx(3e-3)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(name):
    """No trace, or a trace from a program without the phase spans: the
    reader returns None."""
    assert READ[name](types.SimpleNamespace(trace=None)) is None
    old = _meta(1, "/device:TPU:0", {1: "XLA Ops", 2: "XLA Modules"})
    old += _meta(9, "/host:CPU", {5: "python3"})
    old += [_x(1, 2, "jit_chunk_fn(3)", 2, 98), _x(1, 1, "fusion.1", 2, 98),
            _host("serve.decode_chunk", 0, 110),
            _host("serve.prefill_admit", 110, 20)]
    assert READ[name](_run(old)) is None


def test_recorded_admission_boundary():
    """Two decode chunks of chatglm3-6b at 8 rows with an admission between
    them (one row, a partial prefix hit), recorded on a TPU v5e
    (``fewshot_batch``, ``--trace 1``): the engine's spans and device 0's
    program runs whole, its XLA ops only within 20 ms of each program
    boundary.  The second chunk's dispatch waits out the 1.66-s
    admission."""
    path = pathlib.Path(__file__).parent / "data" / \
        "admit_boundary.trace.json.gz"
    with gzip.open(path, "rt") as f:
        run = _run(json.load(f)["traceEvents"])
    t = run.trace
    names = [a.name for a in t.annotations]
    assert "serve.decode_chunk" not in names
    assert "serve.prefill_admit" not in names
    assert names.count("serve.chunk.wait") == 2
    (admit,) = [a for a in t.annotations if a.name == "serve.admit"]
    assert {k: int(v) for k, v in admit.args.items()} == {
        "admit_id": 4, "rows": 1, "batch": 8, "bucket": 2048,
        "prompt_tokens": 1187, "cached_tokens": 1024}
    # 1187 - 1024 new tokens of 8 x 2048 slots
    assert READ["prefill_pad_share"](run) == pytest.approx(
        1 - 163 / 16384)
    # the dispatch after the admission holds the host 1656 ms
    chunk = [a.dur for a in t.annotations if a.name == "serve.chunk"]
    assert chunk[1] == pytest.approx(1656028, rel=1e-6)
    # host work over the two chunks: every phase but the waits, less the
    # 1652.7 ms of that dispatch spent behind the admission on device 0
    (admit_run,) = [m for m in t.modules
                    if trace.module_name(m.name) == "jit_admit_fn"]
    blocked = admit_run.ts + admit_run.dur - [
        a.ts for a in t.annotations if a.name == "serve.chunk"][1]
    assert blocked == pytest.approx(1652732, rel=1e-6)
    phases = sum(a.dur for a in t.annotations
                 if a.name not in ("serve.chunk.wait", "serve.request"))
    assert READ["host_step_ms"](run) == pytest.approx(
        (phases - blocked) / 2 * 1e-3)
    assert 10.5 < READ["host_step_ms"](run) < 10.7
    # each wait ends 1.63 and 1.83 ms after its chunk's run
    assert READ["fetch_lag_ms"](run) == pytest.approx(1.728, abs=1e-3)
