"""The metric arithmetic: percentiles, the spread, operation counts, MFU."""

import statistics

import numpy as np
import pytest

from benchmarks.lib import opcounts, peaks, stats


@pytest.mark.parametrize("p", [0, 5, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_matches_numpy(p, n):
    values = list(np.random.default_rng(n).normal(size=n))
    assert stats.percentile(values, p) == pytest.approx(np.percentile(values, p))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None


def test_spread_is_interquartile_share_of_median():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 110.0]
    q = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q[2] - q[0]) / 102.5)


def test_gpt2_medium_parameter_count():
    # 24 blocks of 12E^2 + 13E, the tied head of 50304 rows, the final norm
    n = opcounts.gpt2_matmul_params(1024, 24, 50304)
    assert n == 24 * (12 * 1024 ** 2 + 13 * 1024) + 50304 * 1024 + 2048
    assert 353e6 < n < 355e6


def test_model_flops_per_token_adds_the_causal_attention_term():
    n = 1000
    assert opcounts.model_flops_per_token(n) == 6 * n
    assert opcounts.model_flops_per_token(n, 2, 64, 128, causal=False) == 6 * n + 12 * 2 * 64 * 128
    assert opcounts.model_flops_per_token(n, 2, 64, 128, causal=True) == 6 * n + 6 * 2 * 64 * 128


def test_attention_flops_agree_with_the_per_token_term():
    # one layer, forward + backward, causal: per token 6 * seq * hidden
    batch, heads, seq, dim = 8, 16, 1024, 64
    per_token = opcounts.model_flops_per_token(0, 1, heads * dim, seq, causal=True)
    assert opcounts.attention_flops(batch, heads, seq, dim) == per_token * batch * seq
    assert opcounts.attention_flops(batch, heads, seq, dim, backward=False) * 3 == \
        opcounts.attention_flops(batch, heads, seq, dim)


def test_attention_bytes_count_operands_and_results_once():
    tensor = 2 * 4 * 128 * 64 * 2
    assert opcounts.attention_bytes(2, 4, 128, 64, backward=False) == 4 * tensor
    assert opcounts.attention_bytes(2, 4, 128, 64) == 12 * tensor


def test_roofline_names_the_binding_limit():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert opcounts.roofline_seconds(197e12, 1.0, v5e) == (1.0, "compute")
    assert opcounts.roofline_seconds(1.0, 819e9, v5e) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def test_mfu_of_pr22s_reading():
    # 35.0k tokens/s/chip on GPT-2 medium at 1,024 positions read 40.5% in PR 22
    n = opcounts.gpt2_matmul_params(1024, 24, 50304)
    per_token = opcounts.model_flops_per_token(n, 24, 1024, 1024)
    mfu = 35019.0 * per_token / peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    assert 0.39 < mfu < 0.42
