"""Retry/backoff policy (runtime/resilience/retry.py): classification of
the installed runtime's failure text, bounded attempts, deterministic jitter,
evidence-row history."""

import pytest

from deepspeed_tpu.runtime.resilience.faults import FlakyCall
from deepspeed_tpu.runtime.resilience.retry import (BACKEND_UNAVAILABLE, CONNECTION_FLAKE,
                                                    RetryPolicy, classify_failure, is_transient)


def test_classifier_matches_real_backend_unavailable_message():
    # the exact text jax 0.9.0 / libtpu 0.0.34 raise while another process
    # holds the chip
    exc = RuntimeError("Unable to initialize backend 'tpu': ABORTED: Internal error "
                       "when accessing libtpu multi-process lockfile.")
    assert classify_failure(exc) == BACKEND_UNAVAILABLE
    assert is_transient(exc)


def test_classifier_connection_and_unknown():
    assert classify_failure(OSError("Connection refused")) == CONNECTION_FLAKE
    assert classify_failure(ValueError("shapes do not match")) is None
    assert not is_transient(ValueError("shapes do not match"))


def test_transient_failures_retried_then_succeed():
    flaky = FlakyCall(lambda: 42, fails=2)
    sleeps = []
    policy = RetryPolicy(max_attempts=3, base_delay=1.0, jitter=0.5, seed=7,
                         sleep=sleeps.append)
    assert policy.call(flaky) == 42
    assert flaky.calls == 3
    assert len(sleeps) == 2
    ev = policy.evidence()
    assert ev["retries"] == 2
    assert [a["attempt"] for a in ev["retry_history"]] == [1, 2]
    assert all(a["error_class"] == BACKEND_UNAVAILABLE for a in ev["retry_history"])


def test_attempts_bounded_and_history_survives_failure():
    flaky = FlakyCall(lambda: "never", fails=99)
    policy = RetryPolicy(max_attempts=3, base_delay=0.1, sleep=lambda s: None, seed=0)
    with pytest.raises(RuntimeError, match="libtpu multi-process lockfile"):
        policy.call(flaky)
    assert flaky.calls == 3
    assert policy.evidence()["retries"] == 3
    # the terminal attempt slept 0 (there was no next attempt)
    assert policy.evidence()["retry_history"][-1]["delay_s"] == 0.0


def test_non_transient_raises_immediately():
    calls = []

    def bad():
        calls.append(1)
        raise ValueError("deterministic bug")

    policy = RetryPolicy(max_attempts=5, sleep=lambda s: None)
    with pytest.raises(ValueError):
        policy.call(bad)
    assert len(calls) == 1


def test_backoff_grows_exponentially_with_deterministic_jitter():
    p1 = RetryPolicy(base_delay=2.0, max_delay=100.0, multiplier=2.0, jitter=0.25, seed=3)
    p2 = RetryPolicy(base_delay=2.0, max_delay=100.0, multiplier=2.0, jitter=0.25, seed=3)
    d1 = [p1.delay_for(n) for n in (1, 2, 3)]
    assert d1 == [p2.delay_for(n) for n in (1, 2, 3)]  # seeded = reproducible
    for n, d in zip((1, 2, 3), d1):
        base = 2.0 * 2.0 ** (n - 1)
        assert base <= d <= base * 1.25
    # cap: delay never exceeds max_delay * (1 + jitter)
    assert RetryPolicy(base_delay=2.0, max_delay=5.0, seed=0).delay_for(10) <= 5.0 * 1.25


def test_before_attempt_sees_running_history():
    seen = []
    flaky = FlakyCall(lambda: "ok", fails=1)
    policy = RetryPolicy(max_attempts=2, base_delay=0.01, sleep=lambda s: None, seed=0)
    policy.call(flaky, before_attempt=lambda i, hist: seen.append((i, len(hist))))
    assert seen == [(1, 0), (2, 1)]


def test_clean_call_has_empty_evidence():
    policy = RetryPolicy(max_attempts=3, sleep=lambda s: None)
    assert policy.call(lambda: "fine") == "fine"
    assert policy.evidence() == {}  # clean rows stay clean
