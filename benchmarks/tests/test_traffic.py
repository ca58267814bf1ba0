"""Every seed offers the same work: the quantile sampler and the
open-loop generator."""

import collections

import pytest

from benchmarks.lib import loading, sampler
from benchmarks.generators import open_loop


def test_quantiles_are_the_distribution():
    u = sampler.quantiles({"dist": "uniform", "lo": 10, "hi": 20}, 5)
    assert u == pytest.approx([11, 13, 15, 17, 19])
    ln = sampler.int_quantiles(
        {"dist": "lognormal", "median": 128, "sigma": 0.7, "clip": [16, 640]}, 101)
    assert ln[50] == 128 and min(ln) >= 16 and max(ln) <= 640
    assert ln == sorted(ln)
    gaps = sampler.gaps_summing_to({"dist": "exponential", "mean": 1.0}, 153, 51.0)
    assert sum(gaps) == pytest.approx(51.0) and min(gaps) > 0


@pytest.mark.parametrize("name", ["chat", "docs"])
def test_two_seeds_same_multiset_other_order(name):
    traffic = loading.with_rehearsal(loading.load_traffic(name), False)
    a = open_loop.generate(traffic, 2147483649, 51.0, 50257)
    b = open_loop.generate(traffic, 7, 51.0, 50257)
    ends = {"lead_in": a["window"][0], "window": a["window"][1],
            "drain": a["end_of_offer_s"]}
    for segment in ("lead_in", "window", "drain"):
        pa = [p for p in a["plan"] if p.segment == segment]
        pb = [p for p in b["plan"] if p.segment == segment]
        pairs = lambda plan: collections.Counter(
            (len(p.prompt), p.max_new_tokens) for p in plan)
        assert pairs(pa) == pairs(pb)
        gaps = lambda plan: sorted(
            y - x for x, y in zip([p.due_s for p in plan],
                                  [p.due_s for p in plan[1:]] + [ends[segment]]))
        assert gaps(pa) == pytest.approx(gaps(pb))
    wa = [p for p in a["plan"] if p.segment == "window"]
    wb = [p for p in b["plan"] if p.segment == "window"]
    assert len(wa) == round(traffic["rate_per_s"] * 51.0)
    la, lb = [len(p.prompt) for p in wa], [len(p.prompt) for p in wb]
    assert la != lb
    # another order, the same cycle: one is a rotation of the other, so
    # the same neighbours meet the same gaps
    assert any(la[k:] + la[:k] == lb for k in range(len(la)))
    assert wa[0].prompt != wb[0].prompt  # the seed picks the token ids
    lo, hi = a["window"]
    assert all(lo <= p.due_s < hi for p in wa)
    assert a["lengths"] == b["lengths"]


def test_same_seed_same_traffic():
    traffic = loading.load_traffic("chat")
    a = open_loop.generate(traffic, 2**31 + 5, 10.0, 50257)
    b = open_loop.generate(traffic, 2**31 + 5, 10.0, 50257)
    assert [(p.due_s, p.prompt, p.max_new_tokens) for p in a["plan"]] == [
        (p.due_s, p.prompt, p.max_new_tokens) for p in b["plan"]]
