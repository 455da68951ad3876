"""The one traffic generator: every mix is a JSON file of parameters.

A mix states the request count, the length distributions of prompts and
outputs, the arrival pattern and the sampling. Lengths are the midpoint
quantiles of their distributions over a *stratum* of ``stratum``
requests; every stratum holds the same sizes, in an order drawn once
from a fixed stream. The seed draws the token ids
(and, in the harness, the weights). Greedy requests decoded to their
length budget cost the same whatever their tokens, so every seed offers
the same work, and the spread between runs of different seeds is the
system's, not the traffic's.

Keys of a mix file:

    requests        total requests (a backlog must outlast set-up and window)
    stratum         requests per stratum
    prompt, output  {"dist": "lognormal", "median", "sigma", "min", "max"}
                    or {"dist": "fixed", "value"}
    arrival         {"pattern": "backlog"}: all due at t=0
    sampling        {"temperature": 0, "ignore_eos": true}
    window          {"opens_after_finished": n | "max_batch"}
    check_requests  finished requests the correctness check compares

The length arithmetic follows the program's own generator
(``serving/workload.py``: ``sharegpt_like``).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Item:
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due_s: float                # seconds after the traffic starts


def _quantiles(m: int) -> np.ndarray:
    return (np.arange(m) + 0.5) / m


def lengths(spec: Dict, m: int) -> np.ndarray:
    """The stratum's lengths: midpoint quantiles of the distribution."""
    if spec["dist"] == "fixed":
        return np.full(m, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(m)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


ORDER = 0           # the fixed stream the strata's order is drawn from


def generate(spec: Dict, vocab: int, seed: int) -> List[Item]:
    if spec["arrival"]["pattern"] != "backlog":
        raise ValueError(f"unknown arrival pattern "
                         f"{spec['arrival']['pattern']!r}")
    m, n = int(spec["stratum"]), int(spec["requests"])
    base_p = lengths(spec["prompt"], m)
    base_o = lengths(spec["output"], m)
    order = _rng(ORDER, 0)
    p, o = [], []
    for _ in range(-(-n // m)):
        p.append(base_p[order.permutation(m)])
        o.append(base_o[order.permutation(m)])
    p, o = np.concatenate(p)[:n], np.concatenate(o)[:n]
    tokens = _rng(seed, 1)
    return [Item(tokens.integers(0, vocab, size=int(p[i])).astype(np.int32),
                 int(o[i]), 0.0) for i in range(n)]


def warm_tokens(vocab: int, seed: int, n: int) -> np.ndarray:
    """Token ids for warm-up prompts (a stream of their own)."""
    return _rng(seed, 2).integers(0, vocab, size=n).astype(np.int32)
