"""The traffic generators are pure functions of the seed and offer every
seed the same work: the same multiset of lengths and gaps, the same request
count, the same class shares. The seed decides only order and token ids."""

import glob
import json
import os

import numpy as np
import pytest

from benchmark import manifest
from benchmark.traffic import dist
from benchmark.traffic.kinds import (closed_callers, closed_sessions,
                                     open_stratified)

SEEDS = (0, 1, 7, 2 ** 31 + 5)
VOCAB = 32000

OPEN = {"rate_rps": 3.7, "time_blocks": 8, "classes": [
    {"name": "short", "share": 0.8, "judged": True,
     "prompt_tokens": {"dist": "loguniform", "lo": 64, "hi": 512},
     "output_tokens": {"dist": "loguniform", "lo": 64, "hi": 192}},
    {"name": "long", "share": 0.2, "judged": False,
     "prompt_tokens": {"dist": "uniform", "lo": 2048, "hi": 3840},
     "output_tokens": {"dist": "uniform", "lo": 32, "hi": 64}}]}
CALLERS = {"callers": 16, "requests_per_caller": 3, "ramp_s": 2.0, "classes": [
    {"name": "batch", "share": 1.0, "judged": True,
     "prompt_tokens": {"dist": "loguniform", "lo": 128, "hi": 1024},
     "output_tokens": {"dist": "fixed", "value": 256}}]}
SESSIONS = {"sessions": 4, "max_turns": 5, "classes": [
    {"name": "doc", "share": 1.0, "judged": True,
     "context_tokens": {"dist": "uniform", "lo": 512, "hi": 1024},
     "turn_tokens": {"dist": "loguniform", "lo": 16, "hi": 64},
     "output_tokens": {"dist": "fixed", "value": 32}}]}


def open_work(plan):
    reqs = plan["requests"]
    due = np.asarray([r["due_s"] for r in reqs])
    return {"n": len(reqs),
            "prompts": sorted((r["class"], r["prompt_len"]) for r in reqs),
            "outputs": sorted((r["class"], r["max_new_tokens"]) for r in reqs),
            "gaps": np.sort(np.round(np.diff(due), 9)).tolist(),
            "judged": sum(r["judged"] for r in reqs)}


def callers_work(plan):
    reqs = [r for q in plan["callers"] for r in q]
    return {"n": len(reqs), "per_caller": sorted(len(q) for q in plan["callers"]),
            "prompts": sorted(r["prompt_len"] for r in reqs),
            "outputs": sorted(r["max_new_tokens"] for r in reqs)}


def sessions_work(plan):
    ss = plan["sessions"]
    return {"n": len(ss), "docs": sorted(len(s["document"]) for s in ss),
            "new": sorted(len(t["new"]) for s in ss for t in s["turns"]),
            "out": sorted(t["max_new_tokens"] for s in ss for t in s["turns"]),
            "turns": sorted(len(s["turns"]) for s in ss)}


KINDS = {"open_stratified": (open_stratified, OPEN, open_work),
         "closed_callers": (closed_callers, CALLERS, callers_work),
         "closed_sessions": (closed_sessions, SESSIONS, sessions_work)}


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("seed", SEEDS)
def test_plan_is_a_pure_function_of_the_seed(kind, seed):
    module, params, _ = KINDS[kind]
    assert module.plan(params, seed, 30, VOCAB) == \
        module.plan(json.loads(json.dumps(params)), seed, 30, VOCAB)


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("seed", SEEDS[1:])
def test_every_seed_offers_the_same_work(kind, seed):
    module, params, work = KINDS[kind]
    assert work(module.plan(params, seed, 30, VOCAB)) == \
        work(module.plan(params, SEEDS[0], 30, VOCAB))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_seed_changes_order_and_tokens(kind):
    module, params, _ = KINDS[kind]
    assert module.plan(params, 1, 30, VOCAB) != module.plan(params, 2, 30, VOCAB)


@pytest.mark.parametrize("seconds,rate", [(10, 3.7), (45, 3.7), (45, 4.8),
                                          (30, 0.5)])
def test_open_loop_count_shares_and_span_are_exact(seconds, rate):
    plan = open_stratified.plan(dict(OPEN, rate_rps=rate), 3, seconds, VOCAB)
    n = round(rate * seconds)
    reqs = plan["requests"]
    assert len(reqs) == n
    assert sum(r["class"] == "short" for r in reqs) == round(0.8 * n)
    assert sum(r["judged"] for r in reqs) == round(0.8 * n)
    due = [r["due_s"] for r in reqs]
    assert due[0] == 0.0 and due == sorted(due)
    assert due[-1] == pytest.approx((n - 1) / rate)
    assert all(len(r["prompt"]) == r["prompt_len"] for r in reqs)
    assert all(3 <= t < VOCAB for r in reqs for t in r["prompt"])


def test_a_fixed_arrangement_is_only_rotated_by_the_seed():
    params = dict(OPEN, arrangement_seed=23)
    a = open_stratified.plan(params, 0, 45, VOCAB)["requests"]
    b = open_stratified.plan(params, 5, 45, VOCAB)["requests"]
    n = len(a)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    lengths = [(r["class"], r["prompt_len"], r["max_new_tokens"]) for r in a]
    assert [(r["class"], r["prompt_len"], r["max_new_tokens"]) for r in b] == \
        lengths[5:] + lengths[:5]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert open_work(a and {"requests": a}) == open_work({"requests": b})
    c = open_stratified.plan(params, 5 + n, 45, VOCAB)["requests"]
    assert [r["prompt_len"] for r in c] == [r["prompt_len"] for r in b]


def test_open_loop_spreads_the_long_class_over_the_window():
    """No seed piles its documents into one half of the window."""
    for seed in SEEDS:
        reqs = open_stratified.plan(OPEN, seed, 45, VOCAB)["requests"]
        first = sum(r["class"] == "long" for r in reqs[:len(reqs) // 2])
        total = sum(r["class"] == "long" for r in reqs)
        assert abs(first - total / 2) <= 2


@pytest.mark.parametrize("spec,lo,hi,mean", [
    ({"dist": "fixed", "value": 256}, 256, 256, 256.0),
    ({"dist": "uniform", "lo": 2048, "hi": 3840}, 2048, 3840, 2944.0),
    ({"dist": "loguniform", "lo": 64, "hi": 1024}, 64, 1024,
     (1024 - 64) / np.log(1024 / 64))])
def test_quantile_midpoints_stay_in_range_and_match_the_mean(spec, lo, hi, mean):
    values = dist.quantiles(spec, 400)
    assert values == sorted(values) and lo <= values[0] and values[-1] <= hi
    assert np.mean(values) == pytest.approx(mean, rel=0.01)


def test_exponential_midpoints_have_the_stated_rate():
    gaps = dist.quantiles({"dist": "exponential", "rate": 4.0}, 2000)
    assert np.mean(gaps) == pytest.approx(0.25, rel=0.01)


@pytest.mark.parametrize("n,blocks", [(166, 8), (40, 12), (7, 8), (96, 6),
                                      (5, 1)])
def test_stratify_puts_one_of_every_neighbour_group_into_each_stretch(n, blocks):
    rng = np.random.default_rng(n)
    stretches = dist.stratify(rng, n, 2, blocks)
    for column in (0, 1):
        seen = sorted(row[column] for rows in stretches for row in rows)
        assert seen == list(range(n))          # a permutation: same multiset
    b = len(stretches)
    for rows in stretches:
        groups = [row[0] // b for row in rows]
        assert len(set(groups)) == len(groups)  # one value per group


@pytest.mark.parametrize("shares,n", [((0.8, 0.2), 166), ((0.8, 0.2), 7),
                                      ((1.0,), 33), ((0.5, 0.3, 0.2), 101)])
def test_class_counts_sum_exactly(shares, n):
    counts = dist.class_counts([{"share": s} for s in shares], n)
    assert sum(counts) == n
    assert all(abs(c - s * n) < 1 for c, s in zip(counts, shares))


def _traffic_files():
    return sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(manifest.HERE, "traffic", "*.json")))


@pytest.mark.parametrize("name", _traffic_files())
def test_every_traffic_file_plans_at_the_run_length(name):
    traffic = manifest.load_traffic(name)
    kind = manifest.traffic_kind(traffic["kind"])
    seconds = manifest.load()["run_seconds"]
    a, b = (kind.plan(traffic, s, seconds, VOCAB) for s in (11, 2 ** 31 + 11))
    assert a != b
    for fn in ("plan", "prepare", "drive"):
        assert callable(getattr(kind, fn))
    shares = [c["share"] for c in traffic["classes"]]
    assert sum(shares) == pytest.approx(1.0)
    assert any(c["judged"] for c in traffic["classes"])
