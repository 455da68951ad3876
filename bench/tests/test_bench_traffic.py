"""The traffic generator: deterministic in the seed, the same work for
every seed, and the stated distributions."""
import json
import math

import numpy as np
import pytest

from bench.lib import traffic
from bench.tests.tiny import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_inputs(name):
    a = traffic.generate(mix(name), 50272, BIG_SEED)
    b = traffic.generate(mix(name), 50272, BIG_SEED)
    assert len(a) == mix(name)["requests"]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    spec = mix(name)
    m = spec["stratum"]
    a = traffic.generate(spec, 50272, 1)
    b = traffic.generate(spec, 50272, BIG_SEED)
    assert [(len(x.prompt), x.max_new, x.due_s) for x in a] \
        == [(len(x.prompt), x.max_new, x.due_s) for x in b]
    first = sorted(len(x.prompt) for x in a[:m])
    for k in range(m, len(a) - m + 1, m):
        assert sorted(len(x.prompt) for x in a[k:k + m]) == first
    # a stratum is not in sorted order, and the seed draws the tokens
    assert [len(x.prompt) for x in a[:m]] != first
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_stated_distributions(name):
    spec = mix(name)
    items = traffic.generate(spec, 50272, 7)
    for key, got in (("prompt", [len(x.prompt) for x in items]),
                     ("output", [x.max_new for x in items])):
        d = spec[key]
        got = np.asarray(got)
        assert got.min() >= d["min"] and got.max() <= d["max"]
        if d["dist"] == "lognormal":
            # quartiles of a lognormal: median * exp(-+0.6745 sigma)
            for q, z in ((25, -0.6745), (50, 0.0), (75, 0.6745)):
                want = d["median"] * math.exp(z * d["sigma"])
                want = min(max(want, d["min"]), d["max"])
                assert abs(np.percentile(got, q) / want - 1) < 0.04
    for x in items:
        assert x.prompt.dtype == np.int32 and x.prompt.max() < 50272


@pytest.mark.parametrize("name", MIXES)
def test_backlog_is_all_due_at_once(name):
    assert {x.due_s for x in traffic.generate(mix(name), 100, 3)} == {0.0}


@pytest.mark.parametrize("key,value", [
    ("arrival", {"pattern": "poisson", "rate": 8.0}),
    ("prompt", {"dist": "uniform", "min": 1, "max": 10})])
def test_unknown_patterns_are_refused(key, value):
    with pytest.raises(ValueError, match="unknown"):
        traffic.generate(dict(mix(MIXES[0]), **{key: value}), 100, 3)


def test_fixed_lengths():
    spec = dict(mix(MIXES[0]), prompt={"dist": "fixed", "value": 7},
                requests=16, stratum=8)
    assert {len(x.prompt) for x in traffic.generate(spec, 100, 3)} == {7}


def test_lognormal_quantiles():
    got = traffic.lengths({"dist": "lognormal", "median": 100, "sigma": 0.5,
                           "min": 1, "max": 10 ** 6}, 1000)
    assert abs(np.median(got) - 100) <= 1
    assert abs(np.std(np.log(got)) - 0.5) < 0.02
    assert math.isclose(np.mean(np.log(got)), math.log(100), abs_tol=0.01)
