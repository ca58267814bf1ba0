"""Open-loop request traffic from a data file.

Three segments, each with its own quantile-sampled requests: a lead-in
(driven, not counted), the window (counted), and a drain (driven, not
counted, so that the last counted requests finish under load and not in
an emptying system). Inside a segment the prompt lengths, output lengths
and inter-arrival gaps are the (i + 0.5) / n quantiles of their
distributions, laid out in one cycle whose order the traffic file fixes
(`pairing_seed`). The run's seed chooses where in the cycle the segment
starts, and the token ids. So every seed offers the same requests with
the same gaps between the same neighbours, in another order: the
window's multiset of (prompt, output) pairs, its offered tokens, its
offered rate and its bursts are the same for every seed. A tail such as
a 90th percentile of the time to first token is made by the few requests
that arrive behind a burst; with a free permutation two seeds read 328
and 898 ms on the same code (my chip runs, PR 23).
"""

from __future__ import annotations

import random
from dataclasses import dataclass
from typing import List

import numpy as np

from benchmarks.lib import sampler


@dataclass
class Planned:
    index: int
    due_s: float  # seconds after the generator starts
    prompt: List[int]
    max_new_tokens: int
    segment: str  # "lead_in" | "window" | "drain"


def _segment(traffic, name, start_s, length_s, rng, ids, vocab):
    n = int(round(traffic["rate_per_s"] * length_s))
    if n <= 0 or length_s <= 0:
        return []
    fixed = int(traffic.get("pairing_seed", 0))
    pairs = sampler.paired_lengths(
        traffic["prompt_len"], traffic["output_len"], n, fixed
    )
    cycle = random.Random(f"{fixed}/{name}")
    pairs = sampler.permuted(pairs, cycle)
    gaps = sampler.permuted(
        sampler.gaps_summing_to(traffic["gap"], n, length_s), cycle
    )
    k = rng.randrange(n)  # the seed's place in the cycle
    pairs, gaps = pairs[k:] + pairs[:k], gaps[k:] + gaps[:k]
    out, at = [], start_s
    for (plen, olen), gap in zip(pairs, gaps):
        prompt = ids.integers(1, vocab, size=plen).tolist()
        out.append(Planned(0, at, prompt, olen, name))
        at += gap
    return out


def generate(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    rng = random.Random(int(seed))
    ids = np.random.Generator(np.random.PCG64([int(seed), 1]))
    lead = float(traffic.get("lead_in_s", 0.0))
    drain = float(traffic.get("drain_s", 0.0))
    plan = (
        _segment(traffic, "lead_in", 0.0, lead, rng, ids, vocab)
        + _segment(traffic, "window", lead, float(seconds), rng, ids, vocab)
        + _segment(traffic, "drain", lead + seconds, drain, rng, ids, vocab)
    )
    for i, p in enumerate(plan):
        p.index = i
    return {
        "plan": plan,
        "window": (lead, lead + float(seconds)),
        "end_of_offer_s": lead + float(seconds) + drain,
        "lengths": sorted({len(p.prompt) for p in plan}),
    }
