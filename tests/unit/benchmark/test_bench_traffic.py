"""The traffic generator: arrivals are drawn from the seed; lengths are the
same pairs under every seed, in an order the seed decides."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import traffic

from benchmarks.lib.harness import REPO_ROOT as REPO


def mix(name):
    with open(os.path.join(REPO, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


SERVE_MIXES = ["chat", "docs-sat", "test-chat", "test-docs"]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.serve_schedule(mix(name), 50257, 2 ** 31 + 12345, 60)
    b = traffic.serve_schedule(mix(name), 50257, 2 ** 31 + 12345, 60)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_seeds_reorder_the_same_lengths(name):
    m = mix(name)
    a = traffic.serve_schedule(m, 50257, 1, 60)
    b = traffic.serve_schedule(m, 50257, 2, 60)
    n = min(len(a), len(b)) // m["block"] * m["block"]     # whole blocks both schedules hold
    assert n > 0
    pairs = lambda reqs: sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)  # noqa: E731
    assert pairs(a[:n]) == pairs(b[:n])
    assert pairs(a[:m["block"]]) == pairs(b[:m["block"]])     # block by block
    assert [len(r["prompt"]) for r in a[:n]] != [len(r["prompt"]) for r in b[:n]]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_lengths_stay_inside_their_cuts(name):
    m = mix(name)
    for r in traffic.serve_schedule(m, 50257, 3, 60):
        assert m["prompt_len"]["min"] <= len(r["prompt"]) <= m["prompt_len"]["max"]
        assert 1 <= r["max_new_tokens"] <= m["output_len"]["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= m["max_total"]
        assert r["prompt"].dtype == np.int32 and 0 <= r["prompt"].min() and r["prompt"].max() < 50257


def test_docs_sat_is_a_backlog_under_the_queue_limit():
    reqs = traffic.serve_schedule(mix("docs-sat"), 50257, 5, 60)
    assert len(reqs) == 512 < 1024
    assert all(r["due"] == 0.0 for r in reqs)
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs) <= 1024


def test_poisson_arrivals_are_independent_exponential_gaps():
    """A Poisson process, not a smoothed one: gaps with the exponential's
    mean and standard deviation, no memory from one to the next, and a
    count per window that varies from seed to seed as a Poisson count does."""
    m = mix("chat")
    rate = m["arrivals"]["rate_rps"]
    reqs = traffic.serve_schedule(m, 50257, 7, 2000)
    dues = np.array([r["due"] for r in reqs])
    assert dues[0] == 0.0 and (np.diff(dues) > 0).all() and 1990 < dues[-1] <= 2000
    gaps = np.diff(dues)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    assert gaps.std() == pytest.approx(1 / rate, rel=0.08)
    assert abs(np.corrcoef(gaps[:-1], gaps[1:])[0, 1]) < 0.05
    counts = [len(traffic.serve_schedule(m, 50257, seed, 45)) for seed in range(40)]
    assert np.mean(counts) == pytest.approx(rate * 45, rel=0.05)
    assert 0.6 < np.var(counts) / np.mean(counts) < 1.6       # a Poisson count: variance = mean


def test_a_rate_scales_the_same_arrivals():
    """One seed at two rates gives the same bursts, compressed: what lets a
    sweep over rates compare like with like."""
    m = mix("chat")
    slow = [r["due"] for r in traffic.serve_schedule(m, 50257, 11, 30)]
    fast = [r["due"] for r in traffic.serve_schedule(
        dict(m, arrivals={"process": "poisson", "rate_rps": 2 * m["arrivals"]["rate_rps"]}),
        50257, 11, 15)]
    n = min(len(slow), len(fast))
    assert n > 30 and np.allclose(np.array(slow[:n]) / 2, fast[:n])


def test_chat_medians_are_the_files():
    reqs = traffic.serve_schedule(mix("chat"), 50257, 9, 200)
    assert np.median([len(r["prompt"]) for r in reqs]) == pytest.approx(96, rel=0.1)
    assert np.median([r["max_new_tokens"] for r in reqs]) == pytest.approx(48, rel=0.1)


def test_train_ring_is_distinct_seeded_batches():
    m = mix("pretrain-seq1k")
    a = traffic.train_ring(m, 50304, 2 ** 31 + 1, chips=4)
    b = traffic.train_ring(m, 50304, 2 ** 31 + 1, chips=4)
    assert len(a) == m["ring"]
    assert all(x["input_ids"].shape == (32, 1024) and x["input_ids"].dtype == np.int32 for x in a)
    assert all(np.array_equal(x["input_ids"], y["input_ids"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])
    assert a[0]["input_ids"].max() < 50304


def test_long_prompts_meet_short_and_long_outputs_alike():
    reqs = traffic.serve_schedule(mix("docs-sat"), 50257, 1, 60)
    p, o = zip(*((len(r["prompt"]), r["max_new_tokens"]) for r in reqs[:32]))
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.3


def test_an_unknown_distribution_or_process_is_an_error():
    m = mix("test-docs")
    with pytest.raises(ValueError):
        traffic.serve_schedule(dict(m, prompt_len={"dist": "fixed", "value": 3}), 50257, 1, 60)
    with pytest.raises(ValueError):
        traffic.serve_schedule(dict(m, arrivals={"process": "bursty"}), 50257, 1, 60)
