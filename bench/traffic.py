"""The one traffic generator. A traffic file (`bench/traffic/<name>.json`)
holds parameters only; this module turns them and a seed into requests.

Every seed gets the same multiset of prompt lengths, output lengths,
gaps between arrivals and tenants - the quantiles of the stated
distributions at (i + 0.5) / n - in each segment of the run (lead-in,
window, drain), in an order drawn from the seed. So two seeds offer the
same work in the window, and differ in its order and in the prompt
tokens, which are uniform over the vocabulary from token id 10 up.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

FIRST_TOKEN_ID = 10


@dataclass
class Planned:
    due_s: float          # offset from the start of the arrivals
    prompt: np.ndarray    # (S,) int32
    max_new_tokens: int
    tenant: int           # adapter-bank row


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles of a clipped lognormal (median, sigma)
    or of a uniform range (min, max)."""
    u = quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int) -> np.ndarray:
    """n gaps between arrivals: exponential quantiles (a Poisson process)
    at the stated rate."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return -np.log1p(-quantiles(n)) / spec["rate_rps"]


def tenants(spec: dict, n: int) -> np.ndarray:
    """n bank rows at the quantiles of a Zipf law (exponent s) over the
    stated number of tenants; row 0 is the most popular."""
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown tenant distribution {spec['dist']!r}")
    w = 1.0 / np.arange(1, spec["n"] + 1) ** spec["s"]
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, quantiles(n)), spec["n"] - 1)


def open_loop(traffic: dict, seed: int, segments, vocab: int) -> list:
    """Requests arriving over consecutive segments of the given lengths in
    seconds (lead-in, window, drain), ordered by due time. Each segment
    holds its own fixed multiset of rate x length requests, with its gaps
    scaled to fill it, so every seed puts the same work in the window."""
    rng = np.random.default_rng(seed)
    out, start = [], 0.0
    for length in segments:
        n = int(math.ceil(traffic["arrivals"]["rate_rps"] * length))
        g = gaps(traffic["arrivals"], n)
        g = rng.permutation(g * (length / g.sum()))
        due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        S = rng.permutation(lengths(traffic["prompt_len"], n))
        out_len = rng.permutation(lengths(traffic["output_len"], n))
        row = rng.permutation(tenants(traffic["tenants"], n))
        out += [Planned(float(due[i]),
                        rng.integers(FIRST_TOKEN_ID, vocab, size=int(S[i]),
                                     dtype=np.int32),
                        int(out_len[i]), int(row[i]))
                for i in range(n)]
        start += length
    return out
