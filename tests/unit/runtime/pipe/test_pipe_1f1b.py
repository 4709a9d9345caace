"""1F1B schedule tests (ROADMAP-2 / PR 11 acceptance).

Everything here runs on pipe-ONLY meshes (pipe=2 or pipe=4 with every
other axis size 1); the pipe x data x fsdp compositions are in
test_pipe.py.

Three claims are pinned:

* the static schedule table (``schedule.one_f_one_b_table``) has the
  1F1B phase structure — warmup fwd-only, steady interleave, cooldown
  bwd-only — with the documented constant-in-M stash bound;
* the manual-vjp backward computes the SAME gradients as autodiff
  through the differentiable scan (the strongest internal-consistency
  check available: two independent derivations of d loss/d params);
* ``train_batch`` under 1f1b / chunked / gpipe produces equivalent
  losses and parameter trajectories. Tolerance note: the schedules
  reduce microbatch losses and gradients in different orders, so
  equality is pinned at fp32 reduction-order precision (measured
  <=1 ulp on the loss, <=2e-5 absolute on params after 4 steps), not
  bit-identity — the documented pinned-precision envelope.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import get_gpt2_config
from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.runtime.pipe import schedule as sched
from deepspeed_tpu.runtime.pipe.module import PipelineModule


@pytest.fixture(autouse=True)
def _clear():
    os.environ.pop("DS_PIPE_ACT_BUDGET_MB", None)
    set_topology(None)
    yield
    os.environ.pop("DS_PIPE_ACT_BUDGET_MB", None)
    set_topology(None)


# ---------------------------------------------------------------------------
# static schedule table: warmup / steady / cooldown tick pattern
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,S", [(4, 2), (6, 3), (16, 4), (3, 4), (4, 1)])
def test_one_f_one_b_table_phases(M, S):
    table = sched.one_f_one_b_table(M, S)
    assert len(table) == M + 2 * S - 2
    for t, row in enumerate(table):
        fwds = [f for f, _ in row if f is not None]
        bwds = [b for _, b in row if b is not None]
        if t < S - 1:  # warmup: forward-only ticks
            assert fwds and not bwds, (t, row)
        elif t >= M + S - 1:  # cooldown: backward-only ticks
            assert bwds and not fwds, (t, row)
        else:  # steady 1F1B: both directions live every tick
            assert fwds and bwds, (t, row)
    # per stage: M forwards + M backwards, forward strictly before backward
    for s in range(S):
        fwd_ticks = {table[t][s][0]: t for t in range(len(table))
                     if table[t][s][0] is not None}
        bwd_ticks = {table[t][s][1]: t for t in range(len(table))
                     if table[t][s][1] is not None}
        assert sorted(fwd_ticks) == list(range(M))
        assert sorted(bwd_ticks) == list(range(M))
        for m in range(M):
            if s == S - 1:  # last stage: fwd and bwd of m share the tick
                assert fwd_ticks[m] == bwd_ticks[m]
            else:
                assert fwd_ticks[m] < bwd_ticks[m]
        # constant-in-M in-flight bound: end-of-tick stash occupancy never
        # exceeds 2(S-1-s) — attained at stage 0, the engine's ring size
        live = set()
        peak = 0
        for t in range(len(table)):
            f, b = table[t][s]
            if f is not None:
                live.add(f)
            if b is not None:  # last stage consumes its own-tick forward
                live.discard(b)
            peak = max(peak, len(live))
        assert peak <= max(1, 2 * (S - 1 - s)), (s, peak)


def test_table_matches_reference_schedule_instruction_counts():
    """The combined-tick table and the reference even/odd TrainSchedule
    agree on the per-stage instruction multiset (M fwd + M bwd) and on
    the tick algebra: one combined tick = two reference half-ticks."""
    M, S = 8, 4
    table = sched.one_f_one_b_table(M, S)
    for stage in range(S):
        ref = sched.TrainSchedule(micro_batches=M, stages=S, stage_id=stage)
        steps = list(ref.steps())
        ref_fwd = sum(1 for cmds in steps for c in cmds
                      if isinstance(c, sched.ForwardPass))
        ref_bwd = sum(1 for cmds in steps for c in cmds
                      if isinstance(c, sched.BackwardPass))
        fwd = sum(1 for row in table if row[stage][0] is not None)
        bwd = sum(1 for row in table if row[stage][1] is not None)
        assert (fwd, bwd) == (ref_fwd, ref_bwd) == (M, M)
        # 2(M+S-1) half-ticks, one op each vs M+2S-2 combined ticks, up
        # to two ops each: both schedules finish 2M ops per stage
        assert len(steps) == 2 * (M + S - 1)
    assert len(table) == M + 2 * S - 2


# ---------------------------------------------------------------------------
# engine construction: schedule knob resolution
# ---------------------------------------------------------------------------
def _pipe_engine(schedule=None, chunk=0, gas=4, bs=8, extra_ds=None, n_layer=2,
                 stages=2):
    set_topology(None)
    cfg = get_gpt2_config("test", n_layer=n_layer)
    topo = MeshTopology(pipe=stages, data=1, devices=jax.devices()[:stages])
    pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
    ds = {"train_batch_size": bs, "gradient_accumulation_steps": gas,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
    pcfg = {}
    if schedule:
        pcfg["schedule"] = schedule
    if chunk:
        pcfg["chunk_microbatches"] = chunk
    if pcfg:
        ds["pipeline"] = pcfg
    ds.update(extra_ds or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=pipe, topology=topo, config=ds)
    rng = np.random.default_rng(7)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (bs, 32)).astype(np.int32)}
    return engine, batch, cfg


def test_schedule_knob_resolution():
    e, _, _ = _pipe_engine()
    assert e.pipe_schedule == "1f1b" and e.pipe_chunk == 0
    assert e.stash_slots == 2  # S=2: one stash awaiting bwd + one in transit
    e, _, _ = _pipe_engine(chunk=2)
    assert e.pipe_schedule == "chunked" and e.pipe_chunk == 2
    e, _, _ = _pipe_engine(schedule="gpipe")
    assert e.pipe_schedule == "gpipe"
    # chunked without an explicit chunk size defaults to C=S waves...
    e, _, _ = _pipe_engine(schedule="chunked")
    assert e.pipe_schedule == "chunked" and e.pipe_chunk == 2
    # ...and refuses (rather than silently degrading to gpipe's O(M)
    # liveness) when S does not divide M
    with pytest.raises(ValueError, match="chunk_microbatches"):
        _pipe_engine(schedule="chunked", gas=3, bs=6)
    with pytest.raises(ValueError, match="pipeline.schedule"):
        _pipe_engine(schedule="interleaved")
    # chunk under a non-chunked schedule is ignored with a warning
    e, _, _ = _pipe_engine(schedule="1f1b", chunk=2)
    assert e.pipe_schedule == "1f1b" and e.pipe_chunk == 0


# ---------------------------------------------------------------------------
# manual-vjp backward == autodiff through the differentiable scan
# ---------------------------------------------------------------------------
def test_1f1b_grads_match_autodiff():
    engine, batch, cfg = _pipe_engine()
    engine.initialize_state(batch)
    ids = jnp.asarray(batch["input_ids"]).reshape(4, 2, 32)
    params = jax.device_get(engine.state.params)

    gfn = engine._pipeline_1f1b_grads_fn()
    lfn = engine._pipeline_loss_fn()
    with engine.mesh:
        loss_m, grads_m = jax.jit(gfn)(params, ids, ids, jnp.float32(1.0))
        loss_a, grads_a = jax.jit(
            jax.value_and_grad(lambda p: lfn(p, ids, ids)))(params)
    # the loss reductions agree bit-for-bit on this shape; grads agree to
    # fp32 reduction order (measured worst relative diff ~6e-7)
    assert float(loss_m) == pytest.approx(float(loss_a), abs=1e-6)
    for gm, ga in zip(jax.tree.leaves(grads_m), jax.tree.leaves(grads_a)):
        gm = np.asarray(gm, np.float32)
        ga = np.asarray(ga, np.float32)
        np.testing.assert_allclose(gm, ga, atol=2e-6,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# schedule equivalence: train_batch parity across 1f1b / chunked / gpipe
# ---------------------------------------------------------------------------
def test_schedule_equivalence_train_batch():
    """The three schedules are the same math in different tick orders:
    per-step losses agree to fp32 reduction-order precision and the
    parameter trajectories stay together."""
    e1, batch, _ = _pipe_engine()
    ec, _, _ = _pipe_engine(chunk=2)
    eg, _, _ = _pipe_engine(schedule="gpipe")
    assert (e1.pipe_schedule, ec.pipe_schedule, eg.pipe_schedule) == (
        "1f1b", "chunked", "gpipe")
    for step in range(3):
        l1 = float(e1.train_batch(batch))
        lc = float(ec.train_batch(batch))
        lg = float(eg.train_batch(batch))
        np.testing.assert_allclose(l1, lc, rtol=2e-6, err_msg=f"step {step}")
        np.testing.assert_allclose(l1, lg, rtol=2e-6, err_msg=f"step {step}")
    for p1, pc, pg in zip(jax.tree.leaves(e1.state.params),
                          jax.tree.leaves(ec.state.params),
                          jax.tree.leaves(eg.state.params)):
        np.testing.assert_allclose(np.asarray(p1), np.asarray(pc), atol=5e-5)
        np.testing.assert_allclose(np.asarray(p1), np.asarray(pg), atol=5e-5)


def test_1f1b_trains_and_eval_matches():
    """Loss falls under the 1F1B schedule and eval_batch (the forward
    scan) scores the trained params — the two programs share weights."""
    engine, batch, _ = _pipe_engine()
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert np.isfinite(float(engine.eval_batch(batch)))


def test_1f1b_fp16_overflow_skips_step():
    """The loss-scale seed threads the manual backward: an absurd initial
    scale overflows fp16 grads, the step is skipped (params frozen) and
    the dynamic scale cuts — through the REAL loss-scaler path."""
    engine, batch, _ = _pipe_engine(extra_ds={
        "fp16": {"enabled": True, "loss_scale": 0, "initial_scale_power": 40,
                 "hysteresis": 1}})
    engine.initialize_state(batch)
    before = np.asarray(jax.device_get(engine.state.params["tied_embed"]["wte"]))
    scale_before = float(engine.state.loss_scale.loss_scale)
    engine.train_batch(batch)
    after = np.asarray(jax.device_get(engine.state.params["tied_embed"]["wte"]))
    assert float(engine.state.loss_scale.loss_scale) < scale_before
    np.testing.assert_array_equal(before, after)
    assert engine.skipped_steps == 1
