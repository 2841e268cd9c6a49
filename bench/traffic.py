"""One general traffic generator, driven by a mix file ``bench/traffic/<mix>.json``.

A mix fixes the arrival kind, the length distributions, the share of hard
requests and the concurrency or the rate.  The work is the same for every
seed: requests come in blocks whose (prompt length, output budget, hard)
triples are a fixed set, taken at evenly spaced quantiles of the
distributions, paired and ordered by fixed permutations.  A closed loop
draws blocks of ``block`` requests; an open loop's window is one block of
exactly ``int(rate * seconds)`` requests, arriving after gaps that are
likewise a fixed set of quantiles of the exponential distribution in a
fixed order.  The seed draws the prompt tokens (and the weights): runs on
different seeds replay the same schedule of sizes and arrivals, so they
differ in values, not in work or order.  On the chip, reordering the
schedule by the seed moved an open loop's answer p90 by 14% between
seeds while two runs of one seed agreed within 2% (PERF.md).

Easy prompts draw their tokens from ``easy_ids``, hard prompts from
``hard_ids``: in a cascade configuration these are the marker ids whose
embedding rows differ between tier 0's members (``bench/weights.py``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

KINDS = ("closed", "poisson")
KEYS = {
    "arrival", "prompt", "output", "hard_share", "block", "easy_ids",
    "hard_ids", "outstanding_per_slot", "rate_per_s", "source",
}


@dataclass(frozen=True)
class Item:
    """One request as generated: prompt tokens, output budget, hard flag."""

    tokens: np.ndarray
    max_new_tokens: int
    hard: bool


def validate(mix: dict) -> dict:
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    if mix["arrival"] not in KINDS:
        raise ValueError(f"arrival {mix['arrival']!r} not in {KINDS}")
    if mix["arrival"] == "closed" and mix.get("outstanding_per_slot", 0) <= 0:
        raise ValueError("a closed loop needs outstanding_per_slot > 0")
    if mix["arrival"] == "poisson" and mix.get("rate_per_s", 0) <= 0:
        raise ValueError("an open loop needs rate_per_s > 0")
    if mix["arrival"] == "closed" and mix.get("block", 0) <= 0:
        raise ValueError("a closed loop needs a block size")
    if not 0.0 <= mix["hard_share"] <= 1.0:
        raise ValueError(f"hard_share {mix['hard_share']} outside [0, 1]")
    return mix


def lognormal_quantiles(d: dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoints of n equal-probability bins of a
    lognormal (median, sigma), rounded and clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(d["median"] * np.exp(d["sigma"] * z)).astype(np.int64)
    return np.clip(x, d["min"], d["max"])


def block_shapes(mix: dict, n: int = 0):
    """The fixed (prompt_len, max_new, hard) triples of one block of ``n``
    requests (``block`` by default)."""
    n = n or int(mix["block"])
    prompt = lognormal_quantiles(mix["prompt"], n)
    output = lognormal_quantiles(mix["output"], n)
    # a fixed pairing, the same for every seed
    output = output[np.random.default_rng(0).permutation(n)]
    n_hard = int(round(mix["hard_share"] * n))
    hard = np.zeros(n, bool)
    hard[np.random.default_rng(1).permutation(n)[:n_hard]] = True
    return prompt, output, hard


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed, *salt])


def requests(mix: dict, seed: int, block: int = 0) -> Iterator[Item]:
    """An endless stream of requests, block by block."""
    prompt, output, hard = block_shapes(mix, block)
    e0, e1 = mix["easy_ids"]
    h0, h1 = mix["hard_ids"]
    b = 0
    while True:
        rng = _rng(seed, 0, b)
        for i in _rng(0, 0, b).permutation(len(prompt)):
            lo, hi = (h0, h1) if hard[i] else (e0, e1)
            toks = rng.integers(lo, hi, int(prompt[i])).astype(np.int32)
            yield Item(toks, int(output[i]), bool(hard[i]))
        b += 1


def arrival_times(mix: dict, seconds: float) -> List[float]:
    """The ``int(rate * seconds)`` scheduled arrival times of an open loop,
    all in [0, seconds): the exponential's gaps at evenly spaced quantiles,
    in a fixed order, scaled to span the window."""
    n = int(float(mix["rate_per_s"]) * seconds)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    t = np.cumsum(gaps[_rng(0, 1).permutation(n)])
    return (t * seconds * n / ((n + 1) * t[-1])).tolist()


def outstanding(mix: dict, n_slots: int) -> int:
    """Requests a closed loop keeps in the system."""
    return int(mix["outstanding_per_slot"] * n_slots)
