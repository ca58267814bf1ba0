"""Traffic that offers every seed the same work.

Lengths and gaps are not drawn independently. For n requests each
quantity is the (i + 0.5) / n quantiles of its distribution; a generator
lays them out in an order and the seed only changes that order (and picks
token ids and weights elsewhere). So the multiset of requests, the
offered tokens and the offered rate are the same for every seed, and
what differs between runs is the system.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, List

_NORMAL = NormalDist()


def quantile(dist: Dict, u: float) -> float:
    """The u-quantile (0 < u < 1) of a distribution given as data."""
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["lo"] + u * (dist["hi"] - dist["lo"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "exponential":
        x = -dist["mean"] * math.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "clip" in dist:
        lo, hi = dist["clip"]
        x = min(max(x, lo), hi)
    return x


def quantiles(dist: Dict, n: int) -> List[float]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def int_quantiles(dist: Dict, n: int) -> List[int]:
    return [int(round(x)) for x in quantiles(dist, n)]


def gaps_summing_to(dist: Dict, n: int, total: float) -> List[float]:
    """n inter-arrival gaps with the distribution's shape, scaled so that
    they sum to `total` exactly: the offered rate is n / total for every
    seed."""
    raw = quantiles(dist, n)
    scale = total / sum(raw)
    return [g * scale for g in raw]


def permuted(values: List, rng: random.Random) -> List:
    out = list(values)
    rng.shuffle(out)
    return out


def paired_lengths(
    prompt: Dict, output: Dict, n: int, pairing_seed: int
) -> List[tuple]:
    """n (prompt_len, output_len) pairs. Which prompt quantile meets
    which output quantile is fixed by `pairing_seed`, a number in the
    traffic file, not by the run's seed: the multiset of pairs is the
    same in every run."""
    prompts = int_quantiles(prompt, n)
    outputs = permuted(int_quantiles(output, n), random.Random(pairing_seed))
    return list(zip(prompts, outputs))
