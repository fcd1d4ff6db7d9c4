"""The requests of a traffic mix: lengths that every seed shares, in an
order and with token ids that the seed draws.

Parameters, read from ``traffic/<name>.json`` by every generator kind::

    "block": 40,                    # requests per block of shared sizes
    "prompt": <distribution>,       # prompt length (or question length
                                    # when "prefixes" is given)
    "long": {"share": 0.2, "prompt": <distribution>},   # optional class
    "prefixes": {"count": 8, "length": 1024},           # optional
    "output": <distribution>,
    "max_total": 3072               # prompt + output, output cut to fit

Distributions are those of ``benchlib.sizes``.  Each block of ``block``
requests holds the same multiset of (prompt, output) pairs, in an order
the seed draws, so every seed asks for the same work.  With ``prefixes``,
request ``i`` of a block opens with prefix ``i % count`` (page aligned for
any power-of-two page up to its length), then a question drawn fresh.
"""
from __future__ import annotations

import itertools
from typing import Iterator, List, NamedTuple

import numpy as np

from benchlib import sizes


class Req(NamedTuple):
    prompt: list
    max_new: int


def block_shapes(spec: dict):
    """(prompt or question length, output length) pairs of one block; the
    pairing is fixed, so every seed asks for the same pairs."""
    m = int(spec["block"])
    long = spec.get("long")
    n_long = int(round(m * long["share"])) if long else 0
    prompts = sizes.stratified(spec["prompt"], m - n_long)
    if n_long:
        prompts += sizes.stratified(long["prompt"], n_long)
    outputs = sizes.stratified(spec["output"], m)
    order = np.random.default_rng(0).permutation(m)
    return [(prompts[i], outputs[j]) for i, j in zip(range(m), order)]


def prefixes(spec: dict, seed: int, vocab: int) -> List[list]:
    pre = spec.get("prefixes")
    rng = np.random.default_rng([seed, 1])
    return ([sizes.tokens(rng, vocab, int(pre["length"]))
             for _ in range(int(pre["count"]))] if pre else [])


def cached_prompts(spec: dict, seed: int, vocab: int) -> List[list]:
    """One prompt per shared prefix, served in set-up: the steady state of
    the cell has every prefix in the prefix cache."""
    rng = np.random.default_rng([seed, 3])
    q = int(sizes.quantile(spec["prompt"], 0.5))
    return [h + sizes.tokens(rng, vocab, q)
            for h in prefixes(spec, seed, vocab)]


def requests(spec: dict, seed: int, vocab: int) -> Iterator[Req]:
    """The endless stream of requests, in order."""
    rng = np.random.default_rng([seed, 2])
    pairs = block_shapes(spec)
    heads = prefixes(spec, seed, vocab)
    cap = int(spec["max_total"])
    for _ in itertools.count():
        for i, k in enumerate(rng.permutation(len(pairs))):
            n_prompt, n_out = pairs[k]
            head = heads[i % len(heads)] if heads else []
            prompt = head + sizes.tokens(rng, vocab, n_prompt)
            yield Req(prompt, max(1, min(n_out, cap - len(prompt))))


def shapes(spec: dict):
    """(shortest prompt, longest prompt, longest prompt + output): the
    lengths warm-up must cover."""
    pairs = block_shapes(spec)
    head = int(spec["prefixes"]["length"]) if spec.get("prefixes") else 0
    total = max(min(int(spec["max_total"]), head + p + o) for p, o in pairs)
    return (head + min(p for p, _ in pairs), head + max(p for p, _ in pairs),
            total)
