"""The traffic generator: deterministic per seed, the same work for every
seed, and the shapes its mix file states."""
import json

import numpy as np
import pytest

import spec
import traffic

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))


def load(name):
    return json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())


def lens(reqs):
    return [len(r.prompt) for r in reqs], [r.max_new for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a = traffic.generate(load(mix), 2**31 + 977, 45, 1000)
    b = traffic.generate(load(mix), 2**31 + 977, 45, 1000)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes_and_gaps(mix):
    a = traffic.generate(load(mix), 1, 45, 1000)
    b = traffic.generate(load(mix), 5_000_000_017, 45, 1000)
    assert sorted(lens(a)[0]) == sorted(lens(b)[0])
    assert sorted(lens(a)[1]) == sorted(lens(b)[1])
    ga = np.diff([r.due for r in a])
    gb = np.diff([r.due for r in b])
    assert len(ga) == len(gb)
    assert lens(a) != lens(b)            # another order
    if load(mix)["arrivals"]["kind"] == "poisson":
        assert sum(ga) < 45 and sum(gb) < 45
        assert not np.allclose(ga, gb)


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_within_the_stated_bounds(mix):
    m = load(mix)
    reqs = traffic.generate(m, 3, 45, 1000)
    p, o = lens(reqs)
    assert m["prompt"]["min"] <= min(p) and max(p) <= m["prompt"]["max"]
    assert m["output"]["min"] <= min(o) and max(o) <= m["output"]["max"]
    assert abs(np.median(p) - m["prompt"]["median"]) \
        < 0.1 * m["prompt"]["median"]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)


def test_every_block_spans_the_distribution():
    m = load("batch")
    reqs = traffic.generate(m, 9, 45, 1000)
    p = np.array(lens(reqs)[0])
    blk = m["block"]
    whole = np.sort(p)
    for b in range(0, len(p), blk):
        part = np.sort(p[b:b + blk])
        # each block holds one size from every stratum of the whole set
        lo = whole[np.arange(len(part)) * (len(p) // len(part))]
        hi = whole[np.arange(1, len(part) + 1) * (len(p) // len(part)) - 1]
        assert np.all(part >= lo) and np.all(part <= hi)


def test_poisson_count_follows_rate_and_window():
    m = dict(load("chat"), arrivals={"kind": "poisson", "rate_per_s": 2.5})
    assert len(traffic.generate(m, 1, 40, 100)) == 100
