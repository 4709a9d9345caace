"""Pin the benchmark's FLOP accounting
(``benchmarks/lib/opcounts.model_flops_per_token``).

The reference's published TFLOPS numbers use the standard parameter-matmul
estimate; the benchmark adds the attention-score term that estimate omits
(PaLM-appendix accounting) so long-context cells report true model FLOPs
(the bare 6N model understated seq-8k MFU by ~36%).
"""
from benchmarks.lib.opcounts import model_flops_per_token


def test_no_attention_term_degenerates_to_6n():
    assert model_flops_per_token(1_000_000) == 6e6
    assert model_flops_per_token(1_000_000, 0, 0, 0) == 6e6


def test_causal_attention_term_exact():
    # per layer fwd: QK^T + AV = 4*s*h FLOPs/token; x3 fwd+bwd; /2 causal
    n, L, h, s = 354_800_000, 24, 1024, 8192
    expected_attn = 12.0 * L * h * s / 2.0
    assert model_flops_per_token(n, L, h, s, causal=True) == 6.0 * n + expected_attn
    # at 350M/seq-8k the attention term is ~36% of the total — the
    # magnitude the 6N model was missing
    frac = expected_attn / model_flops_per_token(n, L, h, s, causal=True)
    assert 0.30 < frac < 0.42


def test_bidirectional_is_twice_causal_attention():
    n, L, h, s = 100, 2, 64, 128
    c = model_flops_per_token(n, L, h, s, causal=True) - 6.0 * n
    b = model_flops_per_token(n, L, h, s, causal=False) - 6.0 * n
    assert b == 2 * c
