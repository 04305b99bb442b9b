"""The stratified length draw: the same multiset for every seed, another
order and other gaps."""
import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest as M, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = {"chat-backlog": json.load(open(os.path.join(M.BENCH_DIR, "traffic", "chat-backlog.json"))),
         "chat": json.load(open(os.path.join(HERE, "fixtures", "chat", "bench", "traffic",
                                             "chat-open.json")))}
OPT = {"arrival_rate_rps": 2.5}


def _lens(reqs):
    return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_carries_the_same_multiset_in_another_order(mix):
    a = traffic.schedule(MIXES[mix], OPT, 45, 1, 1000)
    b = traffic.schedule(MIXES[mix], OPT, 45, 3_000_000_019, 1000)
    for phase in ("ramp", "window"):
        assert _lens(a[phase]) == _lens(b[phase])
        assert [len(r["prompt"]) for r in a[phase]] != [len(r["prompt"]) for r in b[phase]]
    assert _lens(a["ramp"]) != _lens(a["window"])[:len(a["ramp"])]   # a multiset of its own


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_schedule(mix):
    a = traffic.schedule(MIXES[mix], OPT, 45, 77, 1000)
    b = traffic.schedule(MIXES[mix], OPT, 45, 77, 1000)
    assert [r["due"] for r in a["window"]] == [r["due"] for r in b["window"]]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a["window"], b["window"]))


def test_poisson_arrivals_have_a_fixed_count_and_differ_by_seed():
    a = traffic.schedule(MIXES["chat"], OPT, 45, 1, 1000)["window"]
    b = traffic.schedule(MIXES["chat"], OPT, 45, 2, 1000)["window"]
    assert len(a) == len(b) == traffic.n_window_requests(MIXES["chat"], OPT, 45) == 112
    assert all(0 <= r["due"] < 45 for r in a)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    assert [r["due"] for r in a] != [r["due"] for r in b]
    # the same multiset of gaps between arrivals in every run, in another order
    gaps = lambda reqs: np.sort(np.diff([r["due"] for r in reqs]))
    np.testing.assert_allclose(gaps(a)[:-1], gaps(b)[:-1], rtol=0.2)
    assert a[0]["due"] == 0.0 and 0.3 < np.std(np.diff([r["due"] for r in a])) / np.mean(
        np.diff([r["due"] for r in a])) < 1.3        # exponential-like, not a metronome
    ramp = traffic.schedule(MIXES["chat"], OPT, 45, 1, 1000)["ramp"]
    assert all(-MIXES["chat"]["ramp_seconds"] <= r["due"] < 0 for r in ramp)


def test_backlog_is_all_due_when_its_phase_opens():
    s = traffic.schedule(MIXES["chat-backlog"], {}, 45, 5, 1000)
    assert {r["due"] for r in s["window"]} == {0.0}
    assert {r["due"] for r in s["ramp"]} == {-MIXES["chat-backlog"]["ramp_seconds"]}
    assert len(s["window"]) == MIXES["chat-backlog"]["backlog_requests"]


def test_lengths_are_the_stated_distribution_and_fit_the_engine():
    mix = MIXES["chat"]
    groups = traffic.multiset(mix, 512)
    p = np.array([a for g in groups for a, _ in g])
    o = np.array([b for g in groups for _, b in g])
    assert p.min() >= 64 and p.max() <= 3072 and o.min() >= 16 and o.max() <= 768
    assert 480 <= np.median(p) <= 545 and 150 <= np.median(o) <= 170
    assert 680 <= p.mean() <= 780 and 190 <= o.mean() <= 215
    assert (p.max() + o.max()) <= 4096
    # every group is a stratified sample: their sums stay close
    sums = np.array([sum(a for a, _ in g) for g in groups], float)
    assert sums.std() / sums.mean() < 0.08


def test_quantiles_are_never_a_random_draw():
    q1 = traffic.lognormal_quantiles(MIXES["chat"]["prompt_tokens"], 64)
    q2 = traffic.lognormal_quantiles(MIXES["chat"]["prompt_tokens"], 64)
    assert (q1 == q2).all() and (np.diff(q1) >= 0).all()
