"""Injected-fault matrix on CPU (tools/fault_bench.py scenarios run
in-process): each documented failure class must produce its documented
recovery — verified fallback for corruption, fail-fast for poisoned
numerics, retry-with-evidence for transient 500s, flag-then-boundary
checkpoint for preemption."""

import os
import signal
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
for p in (REPO, os.path.join(REPO, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

import fault_bench  # noqa: E402 — tools/fault_bench.py (scenarios shared with the CLI)


# ---------------------------------------------------------------------------
# corruption classes → verified fallback
# ---------------------------------------------------------------------------

def test_truncated_checkpoint_falls_back(tmp_path):
    row = fault_bench.scenario_corrupt_checkpoint(str(tmp_path), "truncate")
    assert row["ok"], row


def test_bitflipped_checkpoint_falls_back(tmp_path):
    row = fault_bench.scenario_corrupt_checkpoint(str(tmp_path), "bitflip")
    assert row["ok"], row


def test_all_tags_corrupt_is_loud(tmp_path):
    row = fault_bench.scenario_all_corrupt(str(tmp_path))
    assert row["ok"], row


def test_explicit_tag_fallback_never_falls_forward(tmp_path):
    """A corrupt explicitly-requested tag falls back to an OLDER intact tag
    — never forward to a newer one (the caller may be rolling back past a
    divergence; resolving to the newer state would defeat the rollback)."""
    from deepspeed_tpu.runtime.resilience.faults import corrupt_checkpoint
    from deepspeed_tpu.runtime.resilience.manifest import CheckpointCorruptError
    ckpt = str(tmp_path / "ck")
    engine, batch = fault_bench._tiny_engine()
    for tag in ("t1", "t2", "t3"):
        engine.train_batch(batch)
        engine.save_checkpoint(ckpt, tag=tag)
    corrupt_checkpoint(ckpt, "t2", mode="truncate")
    fresh, _ = fault_bench._tiny_engine()
    fresh.initialize_state(batch)
    fresh.load_checkpoint(ckpt, tag="t2")
    assert fresh._loaded_checkpoint_tag == "t1", fresh._loaded_checkpoint_tag
    # with no older tag intact, the explicit request fails loudly rather
    # than resolving forward to t3
    corrupt_checkpoint(ckpt, "t1", mode="truncate")
    strict, _ = fault_bench._tiny_engine()
    strict.initialize_state(batch)
    with pytest.raises(CheckpointCorruptError):
        strict.load_checkpoint(ckpt, tag="t2")
    # an explicitly-requested tag so torn it is UNLISTED has unknown
    # position: fallback is refused outright (never risk falling forward)
    import shutil
    shutil.rmtree(os.path.join(ckpt, "t1"))
    strict2, _ = fault_bench._tiny_engine()
    strict2.initialize_state(batch)
    with pytest.raises(CheckpointCorruptError):
        strict2.load_checkpoint(ckpt, tag="t1")
    assert not hasattr(strict2, "_loaded_checkpoint_tag")


def test_fallback_disabled_raises(tmp_path):
    """With resilience.fallback_on_corruption=false a corrupt requested tag
    raises instead of silently time-traveling to an older tag."""
    from deepspeed_tpu.runtime.resilience.faults import corrupt_checkpoint
    from deepspeed_tpu.runtime.resilience.manifest import CheckpointCorruptError
    ckpt = str(tmp_path / "ck")
    engine, batch = fault_bench._tiny_engine()
    engine.train_batch(batch)
    engine.save_checkpoint(ckpt, tag="t1")
    engine.train_batch(batch)
    engine.save_checkpoint(ckpt, tag="t2")
    corrupt_checkpoint(ckpt, "t2", mode="truncate")
    strict, _ = fault_bench._tiny_engine(
        ds_extra={"resilience": {"fallback_on_corruption": False}})
    strict.initialize_state(batch)
    with pytest.raises(CheckpointCorruptError):
        strict.load_checkpoint(ckpt)


def test_torn_save_invisible_and_recoverable(tmp_path):
    """SIGKILL between staging and the atomic rename: the partial tag is
    invisible, 'latest' still names the previous tag, resume works, and the
    next save sweeps the stale staging dir."""
    row = fault_bench.scenario_torn_save(str(tmp_path))
    assert row["ok"], row


# ---------------------------------------------------------------------------
# poisoned numerics → fail fast
# ---------------------------------------------------------------------------

def test_persistent_overflow_aborts_after_k(tmp_path):
    row = fault_bench.scenario_overflow_abort(str(tmp_path))
    assert row["ok"], row


def test_overflow_streak_spans_fused_dispatches(tmp_path):
    """The abort-after-K guard must see fused train_batches stacks exactly
    as per-dispatch steps: a streak built across two dispatches trips the
    guard, and the stack's synthetic final-step metrics must not reset it."""
    import jax

    from deepspeed_tpu.runtime.fp16.loss_scaler import OverflowAbort
    from deepspeed_tpu.runtime.resilience.faults import overflow_injected_loss, poison_batch
    engine, batch = fault_bench._tiny_engine(
        ds_extra={"resilience": {"max_consecutive_overflows": 4}},
        loss_fn=overflow_injected_loss())
    poisoned = poison_batch(batch)
    stack = jax.tree.map(lambda x: np.broadcast_to(np.asarray(x), (2,) + np.shape(x)),
                         poisoned)
    engine.train_batches(stack)  # streak = 2
    with pytest.raises(OverflowAbort, match="4 consecutive"):
        engine.train_batches(stack)  # steps 3 and 4 of the streak


def test_overflow_watcher_events_and_streaks():
    from deepspeed_tpu.runtime.fp16.loss_scaler import OverflowAbort, OverflowWatcher
    w = OverflowWatcher(abort_after=3)
    assert w.record(1, False, 65536.0) == []
    ev = w.record(2, True, 32768.0)  # skip + scale cut
    assert ("Train/consecutive_overflow_skips", 1, 2) in ev
    assert ("Train/loss_scale_cut", 32768.0, 2) in ev
    ev = w.record(3, True, 32768.0)  # hysteresis held the scale: no cut event
    assert ev == [("Train/consecutive_overflow_skips", 2, 3)]
    ev = w.record(4, False, 32768.0)  # recovery closes the streak series
    assert ev == [("Train/consecutive_overflow_skips", 0, 4)]
    assert w.consecutive == 0 and w.total_skipped == 2 and w.longest_streak == 2
    w.record(5, True, 16384.0)
    w.record(6, True, 8192.0)
    with pytest.raises(OverflowAbort, match="3 consecutive"):
        w.record(7, True, 4096.0)


# ---------------------------------------------------------------------------
# transient infrastructure → retried, evidence recorded
# ---------------------------------------------------------------------------

def test_http500_retry_matrix(tmp_path):
    row = fault_bench.scenario_http500_retry(str(tmp_path))
    assert row["ok"], row


# ---------------------------------------------------------------------------
# preemption → flag, then boundary checkpoint
# ---------------------------------------------------------------------------

@pytest.fixture
def _restore_signals():
    prev = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    yield
    for s, h in prev.items():
        signal.signal(s, h)


def test_sigterm_checkpoints_at_next_boundary(tmp_path, _restore_signals):
    engine, batch = fault_bench._tiny_engine()
    ckpt = str(tmp_path / "preempt")
    guard = engine.enable_preemption_checkpoint(ckpt, exit_after_save=False)
    engine.train_batch(batch)
    assert not os.path.exists(ckpt)  # nothing saved without a signal
    os.kill(os.getpid(), signal.SIGTERM)
    assert guard.requested  # handler only flags — no work in signal context
    assert not os.path.exists(ckpt)
    loss = engine.train_batch(batch)  # the boundary honors the request
    assert np.isfinite(float(loss))
    assert not guard.requested
    assert open(os.path.join(ckpt, "latest")).read() == "global_step2"
    # the saved checkpoint is verified and resumable
    fresh, _ = fault_bench._tiny_engine()
    fresh.initialize_state(batch)
    tag, _ = fresh.resume(ckpt)
    assert tag == "global_step2" and fresh.global_steps == 2


def test_preempt_exit_code_distinguishes_from_success(tmp_path, _restore_signals):
    """exit_after_save exits 143, so a supervisor relaunches instead of
    reading the preempted run as finished."""
    engine, batch = fault_bench._tiny_engine()
    engine.enable_preemption_checkpoint(str(tmp_path / "p"), exit_after_save=True)
    engine.train_batch(batch)
    os.kill(os.getpid(), signal.SIGTERM)
    with pytest.raises(SystemExit) as e:
        engine.train_batch(batch)
    assert e.value.code == 143
    assert os.path.exists(tmp_path / "p" / "latest")  # durable BEFORE the exit


def test_second_sigint_escalates_to_keyboard_interrupt(_restore_signals):
    """Ctrl-C twice always gets you out: with a request already pending
    (the boundary never came — stuck compile), the second SIGINT restores
    the previous handlers and raises KeyboardInterrupt immediately."""
    import time

    from deepspeed_tpu.runtime.resilience.signals import PreemptionGuard
    guard = PreemptionGuard(signals=["SIGINT"]).install()
    try:
        os.kill(os.getpid(), signal.SIGINT)
        time.sleep(0.01)  # let the handler run at the next checkpoint
        assert guard.requested  # first Ctrl-C: flag only
        with pytest.raises(KeyboardInterrupt):
            os.kill(os.getpid(), signal.SIGINT)
            time.sleep(0.5)
        assert not guard.installed  # handlers restored by the escalation
    finally:
        guard.uninstall()


def test_preempt_save_dir_config_arms_at_init(tmp_path, _restore_signals):
    ckpt = str(tmp_path / "auto")
    engine, batch = fault_bench._tiny_engine(
        ds_extra={"resilience": {"preempt_save_dir": ckpt,
                                 "exit_after_preempt_save": False}})
    assert engine._preemption is not None and engine._preemption.installed
    engine._preemption.request("test")
    engine.train_batch(batch)
    assert os.path.exists(os.path.join(ckpt, "latest"))


def test_sigterm_mid_serve_drains_and_exits_143(tmp_path):
    """graft-serve drain contract under a REAL SIGTERM (subprocess): every
    in-flight request finishes its full budget, the queue is terminally
    refused, no KV block leaks, exit code is 143."""
    row = fault_bench.scenario_serve_drain(str(tmp_path))
    assert row["ok"], row


def test_rlhf_sigterm_drains_and_stitches(tmp_path):
    """graft-rlhf preemption contract under a REAL SIGTERM (subprocess):
    in-flight rollouts drain to full budget and are banked (zero dropped),
    the learner checkpoints at one step boundary with the loop cursors in
    client_state, and the resumed life finishes with a stitched loss curve
    inside RLHF_STITCH_LOSS_RTOL of an uninterrupted reference."""
    row = fault_bench.scenario_rlhf_sigterm(str(tmp_path))
    assert row["ok"], row


def test_replica_sigterm_migrates_inflight_kv(tmp_path):
    """graft-fleet SIGTERM contract: every in-flight request's KV moves
    to the peer through a digest-verified bundle, nothing is dropped,
    and the migrated continuations are bit-identical (greedy parity) to
    an uninterrupted run."""
    row = fault_bench.scenario_replica_sigterm_migrate(str(tmp_path))
    assert row["ok"], row


def test_replica_sigkill_readmits_at_most_once(tmp_path):
    """graft-fleet SIGKILL contract: the router's liveness sweep
    re-admits orphaned requests on the surviving replica, delivery stays
    at-most-once, zero dropped, TTFT spike bounded."""
    row = fault_bench.scenario_replica_sigkill_readmit(str(tmp_path))
    assert row["ok"], row


# ---------------------------------------------------------------------------
# heartbeat cadence (satellite: wired + off the hot path)
# ---------------------------------------------------------------------------

def test_heartbeat_throttle(tmp_path):
    from deepspeed_tpu.elasticity.elastic_agent import touch_heartbeat
    hb = str(tmp_path / "hb")
    touch_heartbeat(hb, min_interval=30.0)
    os.utime(hb, (0, 0))  # pretend the file is ancient
    touch_heartbeat(hb, min_interval=30.0)  # throttled: within the interval
    assert os.path.getmtime(hb) == 0.0
    touch_heartbeat(hb)  # unthrottled call always touches
    assert os.path.getmtime(hb) > 0.0


def test_engine_step_touches_heartbeat(tmp_path, monkeypatch):
    """The train loop feeds the elastic agent's liveness signal (cadenced
    via resilience.heartbeat_interval) — the hang detector has a pulse."""
    hb = str(tmp_path / "hb")
    monkeypatch.setenv("DS_ELASTIC_HEARTBEAT_FILE", hb)
    engine, batch = fault_bench._tiny_engine(
        ds_extra={"resilience": {"heartbeat_interval": 0.0}})
    engine.train_batch(batch)
    assert os.path.exists(hb)
    os.utime(hb, (0, 0))
    engine.train_batch(batch)
    assert os.path.getmtime(hb) > 0.0  # refreshed by _post_step


def test_heartbeat_payload_roundtrip(tmp_path):
    """The heartbeat file carries a JSON payload (pid + clocks + caller
    fields) readable via read_heartbeat — progress, not just liveness."""
    from deepspeed_tpu.elasticity.elastic_agent import read_heartbeat, touch_heartbeat
    hb = str(tmp_path / "hb")
    assert read_heartbeat(hb) is None  # missing file: no crash
    touch_heartbeat(hb, payload={"global_step": 7, "last_span": "dispatch"})
    data = read_heartbeat(hb)
    assert data["global_step"] == 7 and data["last_span"] == "dispatch"
    assert data["pid"] == os.getpid() and data["monotonic"] > 0
    # pre-payload / torn writers degrade to None, never crash a supervisor
    with open(hb, "w") as fh:
        fh.write('{"torn')
    assert read_heartbeat(hb) is None
    # unserializable caller fields degrade to the base payload
    touch_heartbeat(hb, payload={"bad": object()})
    assert read_heartbeat(hb)["pid"] == os.getpid()


def test_engine_heartbeat_reports_progress(tmp_path, monkeypatch):
    """The engine's per-step heartbeat stamps global_step + the last
    telemetry span, so a supervisor reports how far a child got."""
    from deepspeed_tpu.elasticity.elastic_agent import read_heartbeat
    hb = str(tmp_path / "hb")
    monkeypatch.setenv("DS_ELASTIC_HEARTBEAT_FILE", hb)
    engine, batch = fault_bench._tiny_engine(
        ds_extra={"resilience": {"heartbeat_interval": 0.0}})
    engine.train_batch(batch)
    engine.train_batch(batch)
    data = read_heartbeat(hb)
    assert data["global_step"] == 2
