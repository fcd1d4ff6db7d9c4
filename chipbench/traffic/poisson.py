"""Open loop: requests arrive at a fixed mean rate, whatever the system
does, and each is timed from when it was due.

Parameters (``traffic/<name>.json``): ``"kind": "poisson"``, ``"rate"``
(requests per second) and the request mix of ``benchlib.mix``.  The gaps
between arrivals are exponential with mean ``1 / rate``; each block of
``block`` requests holds the same gaps (the stratified quantiles), in an
order the seed draws, so every seed offers the same load.  A thread of
its own submits each request through the server when it is due and
records how late it ran.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchlib import sizes
from benchlib.mix import cached_prompts, requests, shapes  # noqa: F401


def arrivals(spec: dict, seed: int):
    """Offsets in seconds from the window's start, one per request."""
    gaps = sizes.stratified({"exponential": {"mean": 1.0 / spec["rate"]}},
                            int(spec["block"]), integer=False)
    rng = np.random.default_rng([seed, 4])
    t = 0.0
    while True:
        for i in rng.permutation(len(gaps)):
            t += gaps[i]
            yield t


def drive(client, spec: dict) -> None:
    client.start()
    client.server.start()
    due = arrivals(spec, client.seed)

    def send():
        for at in due:
            t = client.t0 + at
            if t >= client.t_end:
                return
            while True:
                wait = t - time.monotonic()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
                if not client.open:
                    return
            if not client.open:
                return
            client.submit(next(client.reqs), due=t)

    th = threading.Thread(target=send, name="chipbench-arrivals",
                          daemon=True)
    client.threads.append(th)
    th.start()
