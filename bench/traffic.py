"""The one request generator: a traffic file's parameters + a seed ->
requests.

Every seed gets the same sizes, in another order: each block of ``block``
consecutive requests holds the block's quantiles of each distribution
(output lengths, adapters), and the seed permutes them. The arrival
pattern (the inter-arrival gaps and the prompt lengths, the same quantiles
permuted) is drawn from the traffic file's ``arrival_seed``, so every run
of a cell offers load at the same moments; the run's seed draws what is
asked. Prompt tokens are uniform over the vocabulary.

Parameters (``bench/traffic/<mix>.json``):

    loop           "open" (arrivals on a schedule) | "closed" (a new
                   request as soon as one finishes)
    rate_per_s     open loop: Poisson arrival rate
    outstanding    closed loop: requests kept in the system
    prompt         {"dist": "lognormal", "median", "sigma", "buckets"}: each
                   draw becomes the bucket nearest to it on a log scale (the
                   engine compiles one admission program per prompt length)
    output         {"dist": "lognormal", "median", "sigma", "min", "max"}
                   or {"dist": "uniform", "min", "max"} (integers)
    adapters       {"dist": "zipf", "alpha"} over the configuration's
                   adapters, or {"dist": "uniform"}
    block          stratification block (requests)
    arrival_seed   seed of the arrival pattern
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Req:
    rid: str
    arrival_s: float  # open loop: scheduled time from the loop's start
    prompt: np.ndarray  # int32 tokens
    adapter: int
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(spec: dict, q: np.ndarray) -> np.ndarray:
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(float(v)) for v in q])
    return spec["median"] * np.exp(spec["sigma"] * z)


def _prompt_lens(spec: dict, q: np.ndarray) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown prompt distribution {spec['dist']!r}")
    b = np.asarray(spec["buckets"], np.int64)
    gap = np.abs(np.log(_lognormal(spec, q))[:, None] - np.log(b)[None, :])
    return b[np.argmin(gap, 1)]


def _out_lens(spec: dict, q: np.ndarray) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        v = _lognormal(spec, q)
    elif spec["dist"] == "uniform":
        v = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    return np.clip(np.round(v), lo, hi).astype(np.int64)


def _adapters(spec: dict, q: np.ndarray, n_adapters: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        return np.minimum((q * n_adapters).astype(np.int64), n_adapters - 1)
    if spec["dist"] == "zipf":
        w = 1.0 / np.arange(1, n_adapters + 1) ** spec["alpha"]
        cdf = np.cumsum(w) / w.sum()
        return np.minimum(np.searchsorted(cdf, q), n_adapters - 1)
    raise ValueError(f"unknown adapter distribution {spec['dist']!r}")


def generate(traffic: dict, seed: int, n: int, vocab: int,
             n_adapters: int) -> list[Req]:
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    pattern = np.random.default_rng([int(traffic["arrival_seed"]), 0xA77])
    block = int(traffic["block"])
    out, ad, gap, plen = [], [], [], []
    for b0 in range(0, n, block):
        m = min(block, n - b0)
        q = _quantiles(m)
        out.append(rng.permutation(_out_lens(traffic["output"], q)))
        ad.append(rng.permutation(_adapters(traffic["adapters"], q,
                                            n_adapters)))
        gap.append(pattern.permutation(-np.log1p(-q)))  # unit-rate exp.
        plen.append(pattern.permutation(_prompt_lens(traffic["prompt"], q)))
    out, ad, plen = (np.concatenate(x) for x in (out, ad, plen))
    gap = np.concatenate(gap)
    if traffic["loop"] == "open":
        arrival = np.cumsum(gap / float(traffic["rate_per_s"]))
    else:
        arrival = np.zeros(n)
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(plen[i])).astype(np.int32)
        reqs.append(Req(f"r{i}", float(arrival[i]), prompt, int(ad[i]),
                        int(out[i])))
    return reqs


def count_needed(traffic: dict, seconds: float) -> int:
    """Requests to generate so that the loop never runs dry."""
    horizon = float(traffic.get("warmup_s", 0.0)) + seconds
    if traffic["loop"] == "open":
        return int(math.ceil(traffic["rate_per_s"] * horizon * 1.5)) + 64
    return int(traffic["outstanding"]) + int(
        math.ceil(horizon * traffic["max_completions_per_s"])) + 64


def prompt_lens(traffic: dict) -> list[int]:
    """Every prompt length the traffic can send (one admission program
    each)."""
    return [int(b) for b in traffic["prompt"]["buckets"]]


def max_len(traffic: dict) -> int:
    """Cache positions a request can need: the longest prompt and output,
    and one spare (the engine retires a row one short of its cache)."""
    return max(prompt_lens(traffic)) + int(traffic["output"]["max"]) + 2
