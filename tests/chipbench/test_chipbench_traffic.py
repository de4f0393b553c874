"""The stratified generator: every seed offers the same work in
another order, and the window opens on a population Little's law
would leave in flight."""

import json
from collections import Counter
from pathlib import Path

import pytest

from chipbench import traffic as tg

TRAFFIC = sorted((Path(__file__).parents[2] / "chipbench/traffic").glob("*.json"))


def _load(path):
    return json.loads(path.read_text())


def _longest(traffic):
    """The mix's own longest request: the cap its templates fit under."""
    return int(traffic["prompt"]["max"] + traffic["output"]["max"])


@pytest.mark.parametrize("dist", [
    {"dist": "uniform", "min": 10, "max": 50},
    {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 32, "max": 512},
    {"dist": "exponential", "mean": 0.5},
    {"dist": "fixed", "value": 7},
])
def test_quantiles_are_sorted_bounded_and_seedless(dist):
    q = tg.quantiles(dist, 16)
    assert q == sorted(q) and len(q) == 16
    if "min" in dist:
        assert dist["min"] <= q[0] and q[-1] <= dist["max"]
    assert q == tg.quantiles(dist, 16)


def test_exponential_block_lasts_exactly_its_mean_times_n():
    q = tg.quantiles({"dist": "exponential", "mean": 0.4}, 16)
    assert sum(q) == pytest.approx(16 * 0.4, rel=1e-12)


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_every_seed_walks_the_same_multiset_in_another_order(path):
    traffic = _load(path)
    pairs = tg.templates(traffic, _longest(traffic))
    n = len(pairs)
    walks = []
    for seed in (1, 2, 4_000_000_123):
        s = tg.template_stream(pairs, seed)
        walk = [next(s) for _ in range(2 * n)]
        assert Counter(walk[:n]) == Counter(pairs) == Counter(walk[n:])
        walks.append(walk)
    assert walks[0] != walks[1] != walks[2]


def test_open_schedule_same_gaps_and_count_for_every_seed():
    t = _load(Path(__file__).parents[2] / "chipbench/traffic/chat.json")
    pairs = tg.templates(t, _longest(t))
    block_s = t["gap_block"] / t["rate_per_s"]
    runs = [tg.open_schedule(t, pairs, seed, 3 * block_s + 1e-9)
            for seed in (7, 8, 2**31 + 5)]
    assert len({len(r) for r in runs}) == 1
    assert len(runs[0]) == 3 * t["gap_block"]

    def gaps(run):
        due = [0.0] + [r.due_s for r in run]
        return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))

    assert gaps(runs[0]) == gaps(runs[1]) == gaps(runs[2])
    assert [r.due_s for r in runs[0]] != [r.due_s for r in runs[1]]
    # Offered work: whole cycles of the same templates.
    assert (sum(r.out_len for r in runs[0][: len(pairs)])
            == sum(r.out_len for r in runs[1][: len(pairs)]))


def test_standing_population_has_littles_law_ages_and_lengths():
    pairs = [(100, 50)] * 8 + [(100, 150)] * 8
    pop = tg.standing_population(pairs, 40)
    assert pop == tg.standing_population(pairs, 40)  # no seed
    # In flight in proportion to output length: 150 / (50 + 150).
    long = [r for r in pop if r.prompt_len + r.out_len == 250]
    assert len(long) == 30
    # Ages spread evenly: the mean progress is half the output, and
    # every request has at least one token left to emit.
    done = [r.prompt_len - 100 for r in pop]
    total = [250 - 100 if r in long else 50 for r in pop]
    frac = [d / t for d, t in zip(done, total)]
    assert all(r.out_len >= 1 for r in pop)
    assert 0.45 < sum(frac) / len(frac) < 0.55
    quarters = Counter(min(3, int(f * 4)) for f in frac)
    assert all(8 <= quarters[k] <= 12 for k in range(4))


def test_token_ids_take_large_seeds_and_differ_by_request():
    a = tg.token_ids(2**31 + 17, 0, 64, 50257)
    b = tg.token_ids(2**31 + 17, 1, 64, 50257)
    assert a.min() >= 0 and a.max() < 50257
    assert (a != b).any()
    assert (a == tg.token_ids(2**31 + 17, 0, 64, 50257)).all()
