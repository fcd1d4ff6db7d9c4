"""The traffic generators: the seed orders a fixed set of sizes."""
import json
import pathlib
from collections import Counter
from itertools import islice

import pytest

from benchlib import cells, sizes

HERE = pathlib.Path(__file__).resolve().parent
TRAFFIC = HERE.parent / "traffic"


def _spec(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _take(name, seed, n, vocab=65024):
    spec = _spec(name)
    gen = cells.traffic_generator(spec)
    return list(islice(gen.requests(spec, seed, vocab), n))


def test_same_seed_same_requests():
    a = _take("mixed_long", 2 ** 33 + 5, 80)
    b = _take("mixed_long", 2 ** 33 + 5, 80)
    assert [(r.prompt, r.max_new) for r in a] == \
        [(r.prompt, r.max_new) for r in b]
    c = _take("mixed_long", 6, 80)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_every_seed_asks_for_the_same_sizes():
    spec = _spec("mixed_long")
    m = spec["block"]
    shapes = [Counter((len(r.prompt), r.max_new) for r in
                      _take("mixed_long", seed, 3 * m)) for seed in (1, 99)]
    assert shapes[0] == shapes[1]
    lens = [Counter(len(r.prompt) for r in _take("mixed_long", seed, m))
            for seed in (3, 4)]
    assert lens[0] == lens[1]


def test_mixed_long_bounds_and_long_share():
    spec = _spec("mixed_long")
    reqs = _take("mixed_long", 17, 4 * spec["block"])
    lo, hi = spec["long"]["prompt"]["uniform"]["low"], \
        spec["long"]["prompt"]["uniform"]["high"]
    for r in reqs:
        assert spec["prompt"]["min"] <= len(r.prompt) <= hi
        assert 1 <= r.max_new <= spec["output"]["max"]
        assert len(r.prompt) + r.max_new <= spec["max_total"]
        assert all(0 <= t < 65024 for t in r.prompt)
    # the long class: exactly its share of every block, drawn from its range
    n_long = round(spec["block"] * spec["long"]["share"])
    per_block = [sum(1 for r in reqs[i:i + spec["block"]]
                     if len(r.prompt) >= lo)
                 for i in range(0, len(reqs), spec["block"])]
    short_max = sizes.stratified(spec["prompt"],
                                 spec["block"] - n_long)[-1]
    assert short_max < lo
    assert per_block == [n_long] * 4


def test_fewshot_prefixes_are_shared_and_page_aligned():
    spec = _spec("fewshot_batch")
    reqs = _take("fewshot_batch", 23, 2 * spec["block"])
    plen = spec["prefixes"]["length"]
    assert plen % 128 == 0          # aligned for any page size up to 128
    heads = Counter(tuple(r.prompt[:plen]) for r in reqs)
    assert len(heads) == spec["prefixes"]["count"]
    assert set(heads.values()) == {len(reqs) // spec["prefixes"]["count"]}
    q = spec["prompt"]
    for r in reqs:
        assert q["min"] <= len(r.prompt) - plen <= q["max"]
        assert spec["output"]["min"] <= r.max_new <= spec["output"]["max"]
        assert len(r.prompt) + r.max_new <= spec["max_total"]


def test_stratified_quantiles():
    d = {"lognormal": {"median": 100, "sigma": 0.5}, "min": 10, "max": 1000}
    v = sizes.stratified(d, 9)
    assert v == sorted(v) and v[4] == 100
    assert sizes.stratified({"uniform": {"low": 0, "high": 10}}, 5) == \
        [1, 3, 5, 7, 9]


def test_open_loop_offers_the_same_gaps_to_every_seed():
    """Each block of arrivals holds the same gaps, in a seed's order; the
    mean gap is 1 / rate."""
    spec = json.loads((HERE / "data" / "tiny_open.json").read_text())
    gen = cells.traffic_generator(spec)
    m = spec["block"]

    def gaps(seed):
        at = list(islice(gen.arrivals(spec, seed), 2 * m))
        return [b - a for a, b in zip([0.0] + at, at)]

    a, b = gaps(2 ** 33 + 1), gaps(7)
    assert a != b
    for k in (0, m):
        assert sorted(a[k:k + m]) == pytest.approx(sorted(b[k:k + m]))
    assert abs(sum(a[:m]) / m - 1.0 / spec["rate"]) < 0.1 / spec["rate"]
    assert a == gaps(2 ** 33 + 1)
