"""Offline backlog: every request is due at the window's start, more than
the window drains.

Parameters (``traffic/<name>.json``): ``"kind": "backlog"``,
``"queue_depth"`` (requests kept queued beyond the engine's slots) and the
request mix of ``benchlib.mix``.  The engine's queue is kept topped up from
the stream callback itself: each request that finishes inside the window
submits the next, so the backlog never drains.  The first ``max_batch +
queue_depth - 1`` requests are queued in the engine before its server
starts, so the first admission takes them in order on every run; the
last, through the server, starts it.  Set-up serves each shared prefix
once, so the window starts with them in the prefix cache.
"""
from __future__ import annotations

from benchlib.mix import cached_prompts, requests, shapes  # noqa: F401


def drive(client, spec: dict) -> None:
    depth = client.engine.cfg.max_batch + int(spec["queue_depth"])
    client.refill = True
    client.start()
    for _ in range(depth - 1):
        client.submit(next(client.reqs), direct=True)
    client.server.start()
    client.submit(next(client.reqs))
