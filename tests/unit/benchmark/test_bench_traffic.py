"""The traffic generator: arrival times are drawn from the seed and their
count is not; lengths are the same pairs under every seed, in an order the
seed decides."""

import hashlib
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
    # blocks of lengths begin with the periods of a process, after the pre-roll's own
    first = m["preroll_s"] if m["arrivals"]["process"] == "poisson_fixed_count" else 0.0
    a = [r for r in traffic.serve_schedule(m, 50257, 1, 60) if r["due"] >= first]
    b = [r for r in traffic.serve_schedule(m, 50257, 2, 60) if r["due"] >= first]
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


MIXES = sorted(name[:-len(".json")]
               for name in os.listdir(os.path.join(REPO, "benchmarks", "traffic")))
BACKLOGS = [name for name in MIXES
            if mix(name).get("arrivals", {}).get("process") == "all_at_zero"]


@pytest.mark.parametrize("name", BACKLOGS)
def test_a_backlog_is_no_more_than_the_queue_limit_of_its_cells(name):
    """Every ``all_at_zero`` mix hands the server its ``count`` in one tick:
    each cell's configuration has to let the queue hold it (``serve.max_queue``,
    the server's default where the configuration gives none)."""
    from deepspeed_tpu.inference.serving import ServingConfig

    m = mix(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    cells = [w for w in manifest["workloads"] if w["traffic"] == name]
    limits = [ServingConfig.model_fields["max_queue"].default]   # a mix of the tests: no cell
    for cell in cells:
        with open(os.path.join(REPO, files[cell["config"]])) as f:
            serve = json.load(f)["serve"]
        limits.append(serve.get("max_queue", limits[0]))
    limit = min(limits[1:] or limits)
    reqs = traffic.serve_schedule(m, 50257, 5, 60)
    assert len(reqs) == m["arrivals"]["count"] <= limit
    assert all(r["due"] == 0.0 for r in reqs)
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs) <= m["max_total"]


def test_the_two_deepened_backlogs_outlast_a_server_twice_as_fast():
    """Tokens a backlog asks for: over three times what a pre-roll and a
    window take at TWICE the rate the ledger reads (PR 53: 4,922 and 5,392
    tokens/s), so such a server still closes its window on a deep queue."""
    for name, rate, count in (("docs-sat", 4922, 4096), ("agent-sat", 5392, 2048)):
        m = mix(name)
        reqs = traffic.serve_schedule(m, 50257, 1, 60)
        asked = sum(len(r["prompt"]) + r["max_new_tokens"] for r in reqs)
        assert len(reqs) == count and asked > 3 * 2 * rate * (m["preroll_s"] + 51)


def dues(m, seed, horizon_s):
    return np.array([r["due"] for r in traffic.serve_schedule(m, 50257, seed, horizon_s)])


def test_fixed_count_arrivals_hold_a_block_in_every_period():
    """A Poisson process conditioned on its count: ``block`` arrivals in
    every period of ``block / rate`` seconds from the window's opening on,
    the pre-roll's own ``rate * preroll_s`` before it, in order of time."""
    m = mix("chat")
    rate, block, preroll = m["arrivals"]["rate_rps"], m["block"], m["preroll_s"]
    period = block / rate
    for seed in (1, 2 ** 31 + 7, 4600000311):
        due = dues(m, seed, 200)
        assert (np.diff(due) >= 0).all() and due[0] >= 0
        assert (due < preroll).sum() == round(rate * preroll) == 20
        inside = np.floor((due[due >= preroll] - preroll) / period).astype(int)
        assert (np.bincount(inside) == block).all()
        assert preroll + period * len(np.bincount(inside)) >= 200      # whole periods past the end


def test_every_seed_offers_the_window_the_same_requests_and_tokens():
    """What the check's spread asked for: under 20 seeds the 51 s window
    holds the same four whole periods, so the same number of requests but for
    the last 0.2 s of the fourth period (0.5 requests in expectation), and
    the same tokens asked for: the seed decides when each comes and which is
    long."""
    m = mix("chat")
    preroll, block = m["preroll_s"], m["block"]
    counts, whole, tokens, head = set(), set(), set(), set()
    for seed in range(20):
        reqs = traffic.serve_schedule(m, 50257, 2 ** 31 + seed, preroll + 51 + m["drain_s"] + 1)
        due = np.array([r["due"] for r in reqs])
        counts.add(int(((due >= preroll) & (due < preroll + 51)).sum()))
        periods = [r for r in reqs if preroll <= r["due"] < preroll + 4 * 12.8]
        whole.add(len(periods))
        tokens.add((sum(len(r["prompt"]) for r in periods),
                    sum(r["max_new_tokens"] for r in periods)))
        head.add(tuple(sorted((len(r["prompt"]), r["max_new_tokens"])
                              for r in reqs if r["due"] < preroll)))
    assert whole == {4 * block} and len(tokens) == 1 and len(head) == 1
    assert counts <= {126, 127, 128} and max(counts) - min(counts) <= 2


def test_bursts_and_lulls_stay_inside_a_period():
    """Not a smoothed process: the gaps have an exponential's spread (the
    standard deviation near the mean, less the little the fixed count takes),
    no memory from one to the next, and two seeds give different times."""
    m = mix("chat")
    rate = m["arrivals"]["rate_rps"]
    due = dues(m, 7, 2000)
    due = due[due >= m["preroll_s"]]
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.01)
    assert 0.85 / rate < gaps.std() < 1.1 / rate
    assert abs(np.corrcoef(gaps[:-1], gaps[1:])[0, 1]) < 0.08
    assert gaps.max() > 4 / rate and np.sort(gaps)[len(gaps) // 10] < 0.15 / rate
    other = dues(m, 8, 2000)
    assert not np.allclose(due[:64], other[other >= m["preroll_s"]][:64])


def test_a_rate_scales_the_same_arrivals():
    """One seed at two rates gives the same bursts, compressed: what lets a
    sweep over rates compare like with like."""
    m = mix("chat")
    preroll, rate = m["preroll_s"], m["arrivals"]["rate_rps"]
    slow = dues(m, 11, preroll + 60)
    fast = dues(dict(m, arrivals=dict(m["arrivals"], rate_rps=2 * rate)), 11, preroll + 30)
    slow, fast = slow[slow >= preroll] - preroll, fast[fast >= preroll] - preroll
    n = min(len(slow), len(fast))
    assert n >= 128 and np.allclose(slow[:n] / 2, fast[:n])


# the five saturating mixes this PR's files do not touch: (requests, sha256 of
# every due time, output length and prompt) at the parent, PR 53's commit
UNTOUCHED = {"reason-sat": (960, "4dbfeec40200a19f"), "longdoc-sat": (512, "e10863b47c2f60e8"),
             "longctx-sat": (512, "f968374ec0e43c89"), "mixedlen-sat": (960, "0ea5ed830c441d33"),
             "mathword-sat": (960, "f6de0be6d10f6f19")}


@pytest.mark.parametrize("name", list(UNTOUCHED))
def test_an_untouched_mix_keeps_its_schedule_byte_for_byte(name):
    reqs = traffic.serve_schedule(mix(name), 50257, 2 ** 31 + 55, 60)
    digest = hashlib.sha256()
    for r in reqs:
        digest.update(np.float64(r["due"]).tobytes())
        digest.update(np.int64(r["max_new_tokens"]).tobytes())
        digest.update(r["prompt"].tobytes())
    assert (len(reqs), digest.hexdigest()[:16]) == UNTOUCHED[name]


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
