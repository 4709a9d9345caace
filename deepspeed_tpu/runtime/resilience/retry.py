"""Shared retry/backoff policy for flaky infrastructure.

A measurement that dies on a failure which a second attempt would have
survived is the canonical transient-vs-terminal triage failure. This
module is the one place that policy lives: exponential backoff with
deterministic jitter, a bounded attempt budget, a failure *classifier*
(so a structured ``blocked: backend_unavailable`` evidence row replaces a
bare traceback),
and a per-attempt history the caller logs into the rung's evidence row —
banked numbers show their retry history.

Kept dependency-free above the stdlib so launcher-level supervisors can
import it without touching an accelerator backend.
"""

import random
import time
from typing import Callable, List, Optional

# failure classes recognized by the classifier; `blocked:` evidence rows
# carry one of these instead of a bare exception string
BACKEND_UNAVAILABLE = "backend_unavailable"
CONNECTION_FLAKE = "connection_flake"
CHECKPOINT_CORRUPT = "checkpoint_corrupt"

_BACKEND_MARKS = ("unable to initialize backend", "libtpu multi-process lockfile")
_CONNECTION_MARKS = ("connection refused", "connection reset", "broken pipe",
                     "timed out", "temporarily unavailable")


def classify_failure(exc: BaseException) -> Optional[str]:
    """Map an exception to a known failure class (None = unrecognized).
    String-matched on purpose: a backend that cannot start arrives as a
    ``RuntimeError`` whose only structure is its message (``Unable to
    initialize backend 'tpu': ABORTED: Internal error when accessing
    libtpu multi-process lockfile`` while another process still holds the
    chip — what jax 0.9.0 with libtpu 0.0.34 raises)."""
    text = f"{type(exc).__name__}: {exc}".lower()
    if any(m in text for m in _BACKEND_MARKS):
        return BACKEND_UNAVAILABLE
    if "checkpointcorrupt" in text:
        return CHECKPOINT_CORRUPT
    if any(m in text for m in _CONNECTION_MARKS):
        return CONNECTION_FLAKE
    return None


def is_transient(exc: BaseException) -> bool:
    """Default retry predicate: a backend another process still holds and
    connection flakes are worth re-attempting (the holder exits, peers
    come back); corruption and everything unrecognized are not."""
    return classify_failure(exc) in (BACKEND_UNAVAILABLE, CONNECTION_FLAKE)


class RetryPolicy:
    """Bounded exponential backoff with jitter.

    ``delay(n) = min(max_delay, base_delay * multiplier**n) * (1 + jitter*u)``
    with ``u ~ U[0,1)`` from a seedable stream — deterministic in tests,
    decorrelated in a fleet (synchronized retries against a just-restarted
    helper re-kill it).

    Args:
        max_attempts: total attempts including the first (1 = no retry).
        retry_on: predicate deciding whether an exception is retryable;
            non-retryable exceptions propagate immediately.
        sleep: injection point for tests / heartbeat-aware waits (a
            supervised tool sleeps in slices that touch the heartbeat so
            backoff is not mistaken for a hang).
        seed: seeds the jitter stream (None = nondeterministic).

    After ``call``, ``self.attempts`` holds one dict per failed attempt —
    ``{attempt, error, error_class, delay_s}`` — the evidence-row payload.
    """

    def __init__(self, max_attempts: int = 3, base_delay: float = 1.0,
                 max_delay: float = 120.0, multiplier: float = 2.0,
                 jitter: float = 0.25,
                 retry_on: Callable[[BaseException], bool] = is_transient,
                 sleep: Callable[[float], None] = time.sleep,
                 seed: Optional[int] = None):
        assert max_attempts >= 1
        self.max_attempts = int(max_attempts)
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.multiplier = float(multiplier)
        self.jitter = float(jitter)
        self.retry_on = retry_on
        self._sleep = sleep
        self._rng = random.Random(seed)
        self.attempts: List[dict] = []

    def delay_for(self, failed_attempts: int) -> float:
        base = min(self.max_delay, self.base_delay * self.multiplier ** (failed_attempts - 1))
        return base * (1.0 + self.jitter * self._rng.random())

    def evidence(self) -> dict:
        """Evidence-row fragment for the attempt history so far (empty when
        the first attempt succeeded — clean rows stay clean)."""
        if not self.attempts:
            return {}
        return {"retries": len(self.attempts),
                "retry_history": [dict(a) for a in self.attempts]}

    def call(self, fn: Callable, *args, before_attempt: Optional[Callable[[int, List[dict]], None]] = None,
             **kwargs):
        """Run ``fn`` under the policy. ``before_attempt(attempt_index,
        attempts_so_far)`` fires before every attempt (first included) so
        callers can refresh evidence that must survive a final failure."""
        self.attempts = []
        for attempt in range(1, self.max_attempts + 1):
            if before_attempt is not None:
                before_attempt(attempt, self.attempts)
            try:
                return fn(*args, **kwargs)
            except BaseException as e:  # noqa: BLE001 — classified and re-raised below
                record = {"attempt": attempt,
                          "error": f"{type(e).__name__}: {str(e)[:240]}",
                          "error_class": classify_failure(e)}
                retryable = self.retry_on(e) and attempt < self.max_attempts
                record["delay_s"] = round(self.delay_for(attempt), 2) if retryable else 0.0
                self.attempts.append(record)
                if not retryable:
                    raise
                self._sleep(record["delay_s"])
        raise AssertionError("unreachable")


def heartbeat_sleep(slice_s: float = 5.0):
    """A ``sleep`` implementation for supervised tools: naps in slices and
    touches the elastic-agent heartbeat between them, so a multi-minute
    backoff under ``DSElasticAgent`` reads as alive-and-waiting, not hung."""
    def _sleep(total: float) -> None:
        from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat
        remaining = float(total)
        while remaining > 0:
            nap = min(slice_s, remaining)
            time.sleep(nap)
            remaining -= nap
            touch_heartbeat()
    return _sleep
