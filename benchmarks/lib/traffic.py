"""The one traffic generator. A traffic mix is a data file of parameters
(``benchmarks/traffic/<name>.json``); this module turns it and ``--seed``
into the inputs of a run. The program under test receives only what is
generated here.

Arrivals are a process of the mix: ``all_at_zero`` is a backlog of
``count`` requests; ``poisson_fixed_count`` is a Poisson process conditioned
on its count (given how many arrivals an interval holds, a Poisson process
puts them there uniformly and independently): every period of ``block /
rate_rps`` seconds holds exactly ``block`` arrivals, at times the seed draws
uniformly inside it, so a window holds bursts and lulls as an open service
sees them and the same number of requests under every seed.

Lengths are *not* drawn independently, and every mix's file and ``why``
says so: each run of ``block`` requests holds the same ``block`` (prompt,
output) pairs under every seed, the quantiles of the two distributions at
(i + 0.5) / block, paired by a fixed stride, and the seed decides their
order. Two seeds differ in which request is long and when it comes, not in
how many tokens a block of requests asks for; under ``poisson_fixed_count``
a block of lengths is a period of arrivals, so not in how many tokens a
period asks for either. Token ids are drawn freely.
"""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantile(spec, u):
    dist = spec["dist"]
    if dist == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if dist == "lognormal":
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * _NORMAL.inv_cdf(u))
        return min(max(x, spec["min"]), spec["max"])
    raise ValueError(f"unknown distribution {dist!r}")


def length_pairs(traffic, n, block, rng):
    """Prompt and output lengths of ``n`` requests. The i-th prompt quantile
    always goes with the same output quantile (a fixed stride through them,
    so long prompts meet short and long outputs alike); the seed orders the
    pairs inside each block."""
    stride = next(k for k in range(block // 2 + 1, 2 * block) if np.gcd(k, block) == 1)
    prompt = np.array([_quantile(traffic["prompt_len"], (i + 0.5) / block) for i in range(block)])
    output = np.array([_quantile(traffic["output_len"], ((i * stride) % block + 0.5) / block)
                       for i in range(block)])
    order = np.concatenate([rng.permutation(block) for _ in range(-(-n // block))])[:n]
    return prompt[order], output[order]


def fixed_count_arrivals(rate, block, preroll_s, horizon_s, rng):
    """Arrival times of a Poisson process conditioned on its count, as
    ``[(times, size of the blocks their lengths come in)]``. Periods of
    ``block / rate`` seconds begin where the window opens, at ``preroll_s``,
    and go on past ``horizon_s``; each holds exactly ``block`` arrivals at
    uniform times. The pre-roll before them holds its own ``round(rate *
    preroll_s)``, one block of that size: the same pairs under every seed,
    and the periods' blocks begin with the periods. The periods are drawn
    first and as fractions of a period, so one seed at two rates gives the
    same bursts and lulls, compressed."""
    period = block / rate
    n_periods = max(1, math.ceil((horizon_s - preroll_s) / period))
    inside = np.sort(rng.random((n_periods, block)), axis=1)
    periods = preroll_s + period * (np.arange(n_periods)[:, None] + inside).ravel()
    head = preroll_s * np.sort(rng.random(int(round(rate * preroll_s))))
    return [(head, len(head)), (periods, block)]


def serve_schedule(traffic, vocab_size, seed, horizon_s):
    """Requests of a serving mix: a list of dicts ``due`` (seconds from the
    start of the schedule), ``prompt`` (int32 ids) and ``max_new_tokens``,
    in order of ``due``. ``horizon_s`` is how long the schedule has to
    last where arrivals are a process; a fixed ``count`` ignores it."""
    rng = np.random.default_rng(seed)
    block = int(traffic["block"])
    arrivals = traffic["arrivals"]
    if arrivals["process"] == "all_at_zero":
        groups = [(np.zeros(int(arrivals["count"])), block)]
    elif arrivals["process"] == "poisson_fixed_count":
        groups = fixed_count_arrivals(float(arrivals["rate_rps"]), block,
                                      float(traffic["preroll_s"]), horizon_s, rng)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    groups = [(times, size) for times, size in groups if len(times)]
    due = np.concatenate([times for times, _ in groups])
    n = len(due)
    pairs = [length_pairs(traffic, len(times), size, rng) for times, size in groups]
    prompts, outputs = (np.concatenate(part) for part in zip(*pairs))
    prompts, outputs = np.rint(prompts).astype(int), np.rint(outputs).astype(int)
    max_total = int(traffic["max_total"])
    prompts = np.minimum(prompts, max_total - 1)
    outputs = np.maximum(1, np.minimum(outputs, max_total - prompts))
    return [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab_size, (int(prompts[i]),)).astype(np.int32),
             "max_new_tokens": int(outputs[i])} for i in range(n)]


def train_ring(traffic, vocab_size, seed, chips):
    """``ring`` distinct batches of ``seqs_per_chip * chips`` sequences of
    ``seq_len`` token ids, made once; a window cycles through them."""
    rng = np.random.default_rng(seed)
    shape = (int(traffic["seqs_per_chip"]) * chips, int(traffic["seq_len"]))
    return [{"input_ids": rng.integers(0, vocab_size, shape).astype(np.int32)}
            for _ in range(int(traffic["ring"]))]
