"""The continuous engine's phase spans and per-request events.

Each pass of the continuous scheduling loop is a run of flat host spans
(``serve.ingest`` ... ``serve.emit``, docs/PROFILING.md) on the profiler's
clock, with one zero-length ``serve.request`` event per admitted request;
their args are built only while a capture records.  ``Server.submit``
stamps the submit time, so a request's ``ttft_s`` counts its wait in the
server's queue.
"""
import threading
import time

import jax
import pytest

from repro import profiling
from repro.configs.catalog import ARCHITECTURES
from repro.models import build_model
from repro.serve import Engine, Request, ServeConfig, Server
from repro.serve.engine import _bucket_len

#: the phases of one pass of the continuous loop, in loop order
PHASES = ["serve.ingest", "serve.admit.plan", "serve.prefix_restore",
          "serve.admit", "serve.prefix_insert", "serve.chunk.plan",
          "serve.chunk", "serve.chunk.wait", "serve.emit"]

PROMPT = list(range(1, 21))


@pytest.fixture(scope="module")
def engine():
    cfg = ARCHITECTURES["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = Engine(model, params, ServeConfig(max_batch=3, max_len=64))
    # compile the buckets, the chunk widths and the prefix-hit copy once
    eng.generate([PROMPT, PROMPT[:17] + [40, 41, 42]], 6)
    eng.generate([PROMPT], 6)
    eng.clear_prefix_cache()
    return eng


def _spans(events):
    out = [e for e in events
           if e.get("ph") == "X" and e.get("name", "").startswith("serve.")]
    return sorted(out, key=lambda e: e["ts"])


def _iargs(e):
    return {k: int(v) for k, v in (e.get("args") or {}).items()}


def test_phases_in_loop_order_with_one_event_per_admission(engine, tmp_path):
    engine.clear_prefix_cache()
    page = engine.stats()["page_size"]
    assert page <= 16
    chunks0 = engine.stats()["chunks"]
    first = [PROMPT, [3, 4, 5]]
    # a full hit on the first prompt, a partial hit on its first page
    second = [PROMPT, PROMPT[:16] + [7, 7, 7], [9, 8, 7, 6]]
    with profiling.trace(str(tmp_path / "cap")) as s:
        with Server(engine) as srv:
            res = [h.result(timeout=300) for h in
                   [srv.submit(Request(prompt=p, max_new_tokens=6))
                    for p in first]]
            res += [h.result(timeout=300) for h in
                    [srv.submit(Request(prompt=p, max_new_tokens=10))
                     for p in second]]
    chunks = engine.stats()["chunks"] - chunks0
    spans = _spans(s.events())
    names = [e["name"] for e in spans]
    assert "serve.prefill_admit" not in names
    assert "serve.decode_chunk" not in names
    assert len({e["tid"] for e in spans}) == 1          # the worker thread

    phases = [e for e in spans if e["name"] != "serve.request"]
    assert {e["name"] for e in phases} == set(PHASES)
    rank = [PHASES.index(e["name"]) for e in phases]
    for a, b in zip(rank, rank[1:]):
        # forward through the pass, or back to the next pass's ingest;
        # serve.admit once per prefill call; serve.chunk.plan twice (pages
        # and preemption, then the chunk's indices)
        assert b > a or b == 0 or (a == b and PHASES[a] in (
            "serve.admit", "serve.chunk.plan")), (PHASES[a], PHASES[b])
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3     # flat: no nesting
        if a["name"] == "serve.chunk":
            assert b["name"] == "serve.chunk.wait"
        if a["name"] == "serve.chunk.wait":
            assert b["name"] == "serve.emit"
    waits = [e for e in phases if e["name"] == "serve.chunk.wait"]
    assert len(waits) == chunks
    ids = [_iargs(e)["chunk_id"] for e in waits]
    assert ids == list(range(ids[0], ids[0] + chunks))
    assert sum(_iargs(e)["tokens"] for e in phases
               if e["name"] == "serve.emit") == sum(len(r.tokens)
                                                    for r in res)
    assert sum(_iargs(e)["n"] for e in phases
               if e["name"] == "serve.ingest") == len(res)

    # one serve.request per admitted request, inside serve.admit.plan
    reqs = [e for e in spans if e["name"] == "serve.request"]
    assert sorted(_iargs(e)["rid"] for e in reqs) == \
        sorted(r.request_id for r in res)
    plans = [e for e in phases if e["name"] == "serve.admit.plan"]
    for e in reqs:
        assert any(p["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= p["ts"] + p["dur"] + 1e-3 for p in plans)
        a = _iargs(e)
        assert a["front_us"] >= 0 and a["queue_us"] >= 0
    by_rid = {r.request_id: r for r in res}
    hit_code = {None: 0, "partial": 1, "full": 2}
    for e in reqs:
        a = _iargs(e)
        assert a["hit"] == hit_code[by_rid[a["rid"]].prefix_hit]
    assert sorted(_iargs(e)["hit"] for e in reqs) == [0, 0, 0, 1, 2]

    # one serve.admit per prefilled request (one row per call on a single
    # device), at its own prompt's bucket; a pass's calls share its
    # admit_id and together carry its admitted (prefilled) prompts
    admits = [_iargs(e) for e in phases if e["name"] == "serve.admit"]
    assert admits
    for a in admits:
        assert a["rows"] == a["batch"] == 1
        assert a["bucket"] == _bucket_len(a["prompt_tokens"])
    for admit_id in {a["admit_id"] for a in admits}:
        calls = [a for a in admits if a["admit_id"] == admit_id]
        mine = [by_rid[_iargs(e)["rid"]] for e in reqs
                if _iargs(e)["admit_id"] == admit_id
                and _iargs(e)["hit"] != 2]
        assert len(calls) == len(mine)
        assert sorted(a["prompt_tokens"] for a in calls) == \
            sorted(r.prompt_len for r in mine)
        assert sum(a["cached_tokens"] for a in calls) == sum(
            r.cached_prefix_tokens for r in mine if r.prefix_hit == "partial")
    assert sum(a["cached_tokens"] for a in admits) == 16


def test_one_admit_span_per_prefill_call(engine, tmp_path):
    """A pass that admits k prompts of different lengths on one device
    makes k prefill calls of one row, each at its own prompt's bucket,
    under one admit_id; their prompt tokens sum to the pass's prompts."""
    engine.clear_prefix_cache()
    calls0 = engine.stats()["admission_prefills"]
    prompts = [[4] * 40, [5] * 3, [6] * 20]
    with profiling.trace(str(tmp_path / "cap")) as s:
        handles = [engine.submit(Request(prompt=p, max_new_tokens=4))
                   for p in prompts]
        engine.run()
    assert all(h.result(timeout=0).finish_reason for h in handles)
    admits = [_iargs(e) for e in _spans(s.events())
              if e["name"] == "serve.admit"]
    assert len(admits) == len(prompts)
    assert engine.stats()["admission_prefills"] - calls0 == len(prompts)
    assert len({a["admit_id"] for a in admits}) == 1
    assert [(a["rows"], a["batch"]) for a in admits] == [(1, 1)] * 3
    # shortest first: a call's rows are sorted by prompt length
    assert [(a["prompt_tokens"], a["bucket"]) for a in admits] == \
        [(3, 8), (20, 32), (40, 64)]
    assert sum(a["prompt_tokens"] for a in admits) == sum(map(len, prompts))
    assert all(a["cached_tokens"] == 0 for a in admits)


class _Span:
    """Stands in for a TraceAnnotation: records its name, args and any
    metadata set on it."""

    def __init__(self, log, name, args):
        self.log, self.name, self.args = log, name, dict(args)
        log.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        self.args.update(kw)


@pytest.mark.parametrize("recording", [False, True])
def test_span_args_built_only_while_recording(engine, monkeypatch,
                                              recording):
    log = []
    monkeypatch.setattr(profiling, "annotate",
                        lambda name, **args: _Span(log, name, args))
    monkeypatch.setattr(profiling, "recording", lambda: recording)
    engine.clear_prefix_cache()             # a miss, so a prefill runs
    with Server(engine) as srv:
        h = srv.submit(Request(prompt=[2, 7, 1, 8], max_new_tokens=9))
        h.result(timeout=300)
    names = {sp.name for sp in log}
    assert {"serve.ingest", "serve.admit.plan", "serve.admit",
            "serve.chunk.plan", "serve.chunk", "serve.chunk.wait",
            "serve.emit"} <= names
    if recording:
        assert "serve.request" in names
        by = {sp.name: sp.args for sp in log}
        assert set(by["serve.admit"]) == {"admit_id", "rows", "batch",
                                          "bucket", "prompt_tokens",
                                          "cached_tokens", "allreduce_bytes"}
        assert set(by["serve.chunk"]) == {"chunk_id", "rows", "width"}
        assert set(by["serve.chunk.wait"]) == {"chunk_id", "allreduce_bytes"}
        # one device: no all-reduce in either program
        assert by["serve.admit"]["allreduce_bytes"] == 0
        assert by["serve.chunk.wait"]["allreduce_bytes"] == 0
        assert set(by["serve.request"]) == {"rid", "admit_id", "front_us",
                                            "queue_us", "hit"}
    else:
        assert "serve.request" not in names
        assert all(sp.args == {} for sp in log)


def test_annotate_is_a_host_span_only():
    """A function traced inside annotate(...) carries no scope of that
    name in its HLO: annotate is the timeline span alone."""
    def f(x):
        with profiling.annotate("serve.inner_probe"):
            return x * 2

    with profiling.annotate("serve.outer_probe") as span:
        assert isinstance(span, jax.profiler.TraceAnnotation)
        text = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert "inner_probe" not in text and "outer_probe" not in text
    assert not profiling.recording()


def test_server_ttft_counts_the_wait_before_ingest(engine):
    """A request that waits out one slow chunk boundary in the server's
    queue reports a time to first token that includes the wait."""
    stall = 1.0
    in_callback = threading.Event()

    def slow(ev):
        if not ev.finished and ev.index == 0:
            in_callback.set()
            time.sleep(stall)

    lat0 = engine.stats()["latency"]["count"]
    with Server(engine) as srv:
        a = srv.submit(Request(prompt=[5, 9, 2, 7], max_new_tokens=6,
                               stream=slow))
        assert in_callback.wait(300)
        t_sub = time.perf_counter()
        b = srv.submit(Request(prompt=[1, 3, 3], max_new_tokens=6))
        rb = b.result(timeout=300)
        t_done = time.perf_counter()
        a.result(timeout=300)
    # b reached the engine only after a's callback returned
    assert rb.ttft_s >= 0.8 * stall
    assert rb.ttft_s <= rb.total_s <= t_done - t_sub
    st = engine.stats()["latency"]
    assert st["count"] == lat0 + 2
    assert st["ttft_s"]["p99"] >= 0.8 * stall
