"""Drive the program's serving entry point for one measured window.

Requests go through ``repro.serve.Server.submit`` into one ``Engine``;
token times are taken in each ``Request.stream`` callback with the host's
monotonic clock.  When and how requests are sent is the traffic kind's
(``traffic/<kind>.py``, ``drive``).  When the window closes the client
cancels what is still in flight: the next stream event raises
:class:`Cancelled`, and the engine fails the rows it was serving.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Iterator, List, Optional


class Cancelled(Exception):
    """Raised from a stream callback once the window has closed."""


@dataclasses.dataclass
class Record:
    """One request as the client saw it."""
    prompt: list
    max_new: int
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    due: float = 0.0
    result: object = None
    error: Optional[BaseException] = None
    handle: object = None


def bucket(n: int) -> int:
    """The engine's power-of-two length bucket (at least 8)."""
    b = 8
    while b < n:
        b *= 2
    return b


def warmup_plan(shortest: int, longest: int, total: int, chunk: int):
    """Requests, as (prompt length, new tokens), that compile every
    admission bucket of prompts in [shortest, longest] and every decode
    width a row of [shortest, total] tokens needs.  A width beyond the
    first chunk of the longest prompt is reached by decoding that prompt
    until its row needs it.  Returns (plan, buckets, widths)."""
    need = set()
    b = bucket(shortest)
    while b <= bucket(longest):
        need.add(b)
        b *= 2
    widths = set()
    n = shortest
    while n <= total:
        widths.add(bucket(n + chunk))
        n = bucket(n + chunk) - chunk + 1
    plan = {}
    for w in widths:
        p = min(longest, w - chunk)
        grow = max(0, w // 2 - chunk + 1 - p)    # tokens until width w
        plan[p] = max(plan.get(p, chunk), -(-grow // chunk) * chunk + chunk)
    for a in need - {bucket(p) for p in plan}:
        plan.setdefault(min(a, longest), chunk)
    return sorted(plan.items()), sorted(need), sorted(widths)


class Client:
    """The load side of one run: submits, times and, where the traffic
    kind asks for it, keeps a backlog topped up.  A traffic kind's
    ``drive(client, spec)`` starts the window (:meth:`start`), the server
    and whatever sends the requests; :meth:`close` ends it."""

    def __init__(self, server, engine, reqs: Iterator, *, seconds: float,
                 seed: int):
        self.server = server
        self.engine = engine
        self.reqs = reqs
        self.seconds = seconds
        self.seed = seed
        self.records: List[Record] = []
        self.threads: List[threading.Thread] = []
        self.lateness: List[float] = []
        self.lock = threading.Lock()
        self.refill = False
        self.open = False
        self.cancelled = False
        self.t0 = 0.0
        self.t_end = float("inf")

    def start(self):
        """Open the window: it starts now and lasts ``seconds``."""
        self.t0 = time.monotonic()
        self.t_end = self.t0 + self.seconds
        self.open = True

    def _stream(self, rec: Record):
        def on_event(ev):
            if self.cancelled:
                raise Cancelled("the window has closed")
            now = time.monotonic()
            if ev.finished:
                if self.refill and self.open and now < self.t_end:
                    self.submit(next(self.reqs))
                return
            if ev.index < len(rec.tokens):        # restarted after preemption
                del rec.tokens[ev.index:], rec.times[ev.index:]
            rec.tokens.append(ev.token)
            rec.times.append(now)
        return on_event

    def submit(self, r, *, due: Optional[float] = None, direct=False):
        """Submit request ``r`` (``prompt``, ``max_new``), due at ``due``
        (now where not given), through the server or, ``direct``, into
        the engine's own queue before the server starts."""
        from repro.serve import Request
        now = time.monotonic()
        rec = Record(prompt=r.prompt, max_new=r.max_new,
                     due=now if due is None else due)
        with self.lock:
            self.records.append(rec)
            if due is not None:
                self.lateness.append(now - due)
        submit = self.engine.submit if direct else self.server.submit
        rec.handle = submit(Request(prompt=r.prompt, max_new_tokens=r.max_new,
                                    stream=self._stream(rec)))

    def close(self, timeout: float = 120.0):
        """End the window: submit nothing more, cancel what is in flight
        and stop the server.  Each request keeps its result, or the error
        it failed with inside the window; one that the closing cancelled
        or left queued keeps neither."""
        self._settle(keep_errors=True)
        with self.lock:
            self.open = False
            self.cancelled = True
        for th in self.threads:
            th.join(timeout)
        self.server.stop(drain=False, timeout=timeout)
        self._settle(keep_errors=False)

    def _settle(self, keep_errors: bool):
        for rec in self.records:
            if rec.result is not None or rec.error is not None \
                    or not rec.handle.done:
                continue
            try:
                rec.result = rec.handle.result(timeout=0)
            except Exception as e:      # the engine failed this request
                if keep_errors:
                    rec.error = e


def warm(engine, plan, vocab: int, rng, prompts=()):
    """Serve the plan's requests one at a time, then as many requests as
    there are slots at the longest length, so every program and every
    per-slot host-side op the window will use is compiled now; then empty
    the prefix cache and serve ``prompts`` (what the traffic keeps cached,
    such as its shared prefixes) together."""
    from repro.serve import Request
    chunk = engine.cfg.decode_chunk
    for n, new in plan:
        engine.submit(Request(prompt=rng.integers(0, vocab, n).tolist(),
                              max_new_tokens=new))
        engine.run()
    for _ in range(engine.cfg.max_batch):
        engine.submit(Request(
            prompt=rng.integers(0, vocab, plan[-1][0]).tolist(),
            max_new_tokens=chunk))
    engine.run()
    engine.clear_prefix_cache()
    for p in prompts:
        engine.submit(Request(prompt=p, max_new_tokens=chunk))
    if prompts:
        engine.run()


def counters(stats: dict) -> Dict[str, float]:
    """The engine counters the per-layer metrics read, flattened."""
    pc = stats["prefix_cache"]
    return {
        "tokens_generated": stats["tokens_generated"],
        "chunks": stats["chunks"],
        "admission_prefills": stats["admission_prefills"],
        "admissions": stats.get("admissions", 0),
        "preemptions": stats.get("preemptions", 0),
        "cached_tokens_served": pc["cached_tokens_served"],
        "prefill_tokens_computed": pc["prefill_tokens_computed"],
        "prefill_tokens_saved": pc["prefill_tokens_saved"],
        "decode_chunk": stats.get("decode_chunk", 0),
        "page_size": stats.get("page_size") or 1,
        "slots": stats["slots"],
    }
