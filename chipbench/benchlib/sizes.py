"""Lengths and gaps that every seed shares: the seed only orders them.

A distribution is a small dict, e.g. ``{"lognormal": {"median": 384,
"sigma": 0.8}, "min": 32, "max": 2048}`` or ``{"uniform": {"low": 3072,
"high": 6144}}`` or ``{"exponential": {"mean": 0.4}}``.  ``stratified``
takes ``m`` values at the quantiles ``(i + 0.5) / m``, so a block of ``m``
requests always holds the same multiset of sizes; a seed permutes it.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np


def quantile(dist: dict, u: float) -> float:
    if "lognormal" in dist:
        p = dist["lognormal"]
        x = p["median"] * math.exp(p["sigma"] * NormalDist().inv_cdf(u))
    elif "uniform" in dist:
        p = dist["uniform"]
        x = p["low"] + u * (p["high"] - p["low"])
    elif "exponential" in dist:
        x = -dist["exponential"]["mean"] * math.log(1.0 - u)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    return min(max(x, lo), hi)


def stratified(dist: dict, m: int, integer: bool = True) -> List[float]:
    vals = [quantile(dist, (i + 0.5) / m) for i in range(m)]
    return [int(round(v)) for v in vals] if integer else vals


def tokens(rng: np.random.Generator, vocab: int, n: int) -> List[int]:
    return rng.integers(0, vocab, n).tolist()
