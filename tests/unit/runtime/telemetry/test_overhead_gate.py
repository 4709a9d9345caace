"""The two hard telemetry constraints, as tier-1 gates (ISSUE 13):

1. **Program identity** — the telemetry-on engine's traced step is
   eqn-identical to the telemetry-off twin (R015) and carries no host
   callbacks (R003): instrumentation can never silently enter the
   compiled program.
2. **Overhead** — what the recorder's spans and the sink's window flush
   add to a ``train_batch`` step is within 2% of the step: counted (spans
   a step, flushes a window) times the measured cost of one.
"""

import time

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config


def _engine(tmp_path, telemetry: bool, seq=64):
    cfg = get_gpt2_config("test")
    config = {"train_batch_size": 8,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 0}}
    if telemetry:
        config["telemetry"] = {"enabled": True, "output_path": str(tmp_path),
                               "job_name": f"overhead_{telemetry}"}
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config=config)
    batch = {"input_ids": np.arange(8 * seq, dtype=np.int32).reshape(8, seq)
             % cfg.vocab_size}
    return engine, batch


def test_telemetry_program_identity(tmp_path):
    """Same engine config ± the telemetry block → identical jaxpr eqn
    counts, R003/R015 clean on the telemetry-on program; a seeded
    mismatch trips R015."""
    from deepspeed_tpu.analysis import check_program
    from deepspeed_tpu.analysis.program import ProgramAnalyzer, ProgramInfo

    off_engine, batch = _engine(tmp_path, telemetry=False)
    on_engine, _ = _engine(tmp_path, telemetry=True)
    off = off_engine.traced_programs(batch, lower=False)["train_step"]
    on = on_engine.traced_programs(batch, lower=False)["train_step"]

    def eqns(step):
        return len(ProgramAnalyzer(ProgramInfo(
            name="x", jaxpr=step["jaxpr"], kind="train_step")).records())

    n_off, n_on = eqns(off), eqns(on)
    assert n_on == n_off, (f"telemetry changed the traced program: "
                           f"{n_on} vs {n_off} eqns")
    # R003 (host callbacks) + R015 (identity vs the off twin) stay clean
    findings = check_program(on["jaxpr"], rules=["R003", "R015"],
                             metadata={"expect_eqn_count": n_off},
                             kind="train_step")
    assert not findings, [f.message for f in findings]
    # seeded regression: a wrong expectation must trip R015 as ERROR
    seeded = check_program(on["jaxpr"], rules=["R015"],
                           metadata={"expect_eqn_count": n_off + 1},
                           kind="train_step")
    assert len(seeded) == 1 and seeded[0].rule == "R015"


def _least_of_three(fn):
    """Seconds of the quickest of three calls: a shared core can stall any one."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def test_telemetry_overhead_within_2pct(tmp_path):
    """Acceptance gate: what the recorder and the sink add to a step is
    within 2% of the step, held by what can be counted. A wall-clock A/B of
    two engines cannot resolve 2% of this toy's 15 ms step on shared cores
    (alone on the machine its rounds read -4% to +5%), and both arms open
    the same spans anyway: the recorder is always on. So:

    * the recorder: spans opened a step (counted in the ring) times the
      measured cost of one span;
    * the sink: one window flush every ``flush_every`` steps at its
      measured cost, and the step's ``begin_step``/``end_step`` pair;

    each timing the least of three, against the median of the step's own
    ``train_batch`` spans over 30 warm steps."""
    from deepspeed_tpu.utils import trace

    engine, batch = _engine(tmp_path, telemetry=True)
    for _ in range(4):  # compile + settle (incl. the price trace)
        engine.train_batch(batch)
    warm = engine.global_steps
    for _ in range(30):
        engine.train_batch(batch)
    tel = engine.telemetry
    records = [r for r in trace.recorder().records(tel.source) if (r.uid or 0) > warm]
    steps = [r.dur for r in records if r.name == "train_batch"]
    assert len(steps) == 30
    step_s = float(np.median(steps))
    spans_a_step = len(records) / len(steps)
    assert 6 <= spans_a_step <= 8, spans_a_step  # the step and its five phases (+ the cadenced flush)

    scratch = trace.Recorder()

    def ten_thousand_spans():
        for i in range(10_000):
            with scratch.span("phase", i, "engine#x"):
                pass

    span_s = _least_of_three(ten_thousand_spans) / 10_000

    def one_window():   # ten steps of records drained, reduced and written
        for _ in range(tel.flush_every):
            engine.train_batch(batch)
        t0 = time.perf_counter()
        tel.flush_window(engine.global_steps)
        return time.perf_counter() - t0

    flush_s = min(one_window() for _ in range(3))

    def ten_thousand_steps():
        for i in range(10_000):
            tel.begin_step(i)
            tel._step_t0 = None     # as end_step leaves it, without the cadenced flush
    pair_s = _least_of_three(ten_thousand_steps) / 10_000

    recorder_s = spans_a_step * span_s
    sink_s = flush_s / tel.flush_every + 2 * pair_s
    assert recorder_s + sink_s <= 0.02 * step_s, (
        f"recorder {spans_a_step:.1f} spans x {span_s * 1e6:.2f} us = {recorder_s * 1e6:.1f} us, "
        f"sink {flush_s * 1e3:.3f} ms a window / {tel.flush_every} steps + "
        f"{2 * pair_s * 1e6:.2f} us = {sink_s * 1e6:.1f} us, "
        f"against a step of {step_s * 1e3:.3f} ms (2% = {0.02 * step_s * 1e6:.0f} us)")
