"""What every metric reader gets (:class:`Run`), and the arithmetic over
the client's records that several of them share.

A token counts for the window when it reached the client inside it; a
prefill counts when its request's first token did.  Useful FLOPs leave
out padding rows and columns and the prompt tokens a prefix-cache hit
recomputes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from benchlib.flops import decode_flops, prefill_flops


@dataclasses.dataclass
class Run:
    """One run as the readers see it: the cell and its sizes, the set-up
    time, the engine counters at the window's ends, the client's records
    (in the order they were submitted), the prompts set-up left in the
    prefix cache, and, traced, the trace reduction."""
    cell: object
    dims: object
    peaks: object
    chips: int
    seconds: float
    setup_s: float
    t0: float
    st0: dict
    st1: dict
    records: list
    warm_prompts: list
    trace: Optional[object] = None

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    def delta(self, key: str) -> float:
        return self.st1[key] - self.st0[key]


def tokens_in_window(run) -> int:
    return sum(1 for r in run.records for t in r.times
               if run.t0 <= t < run.t1)


def bursts(run) -> List[float]:
    """Per burst of tokens a request received inside the window: the time
    since its previous burst over the tokens in this one (ms/token).  The
    tokens of one chunk reach the client within a millisecond of each
    other; a burst is a run of tokens with no longer gap inside it."""
    out = []
    for r in run.records:
        starts, sizes, last = [], [], None
        for t in r.times:
            if not run.t0 <= t < run.t1:
                continue
            if last is not None and t - last < 1e-3:
                sizes[-1] += 1
            else:
                starts.append(t)
                sizes.append(1)
            last = t
        out += [(b - a) / n * 1e3
                for a, b, n in zip(starts, starts[1:], sizes[1:])]
    return out


def _common_prefix(a: np.ndarray, b: np.ndarray) -> int:
    m = min(len(a), len(b))
    ne = np.flatnonzero(a[:m] != b[:m])
    return int(ne[0]) if len(ne) else m


def cached_tokens(run) -> List[int]:
    """Per record, the prompt tokens the prefix cache served it.  A
    finished request carries the engine's own count.  For one that the
    window's close cancelled it is the most a prefix cache could have
    served: the whole prompt where an earlier prompt was the same, else the
    longest page-aligned prefix it shares with an earlier one (set-up's
    included).  So a cancelled request never counts a cached prefix as
    prefill work."""
    page = int(run.st1.get("page_size") or 1)
    earlier = [np.asarray(p) for p in run.warm_prompts]
    out = []
    for r in run.records:
        mine = np.asarray(r.prompt)
        if r.result is not None:
            out.append(int(r.result.cached_prefix_tokens))
        else:
            best = 0
            for q in earlier:
                k = _common_prefix(mine, q)
                if k == len(mine) == len(q):
                    best = k
                    break
                best = max(best, k // page * page)
            out.append(min(best, len(mine)))
        earlier.append(mine)
    return out


def window_flops(run):
    """(prefill FLOPs, decode FLOPs) of the window."""
    pre = dec = 0.0
    for r, cached in zip(run.records, cached_tokens(run)):
        if not r.times:
            continue
        n = len(r.prompt)
        if run.t0 <= r.times[0] < run.t1:
            pre += prefill_flops(run.dims, n, cached)
        for j, t in enumerate(r.times):
            if j >= 1 and run.t0 <= t < run.t1:
                dec += decode_flops(run.dims, n + j)
    return pre, dec
