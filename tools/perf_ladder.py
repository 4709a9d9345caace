"""Bench the config ladder's larger rungs on the real chip, one process.
The script checks an INTERNAL deadline between rungs and exits cleanly; a
rung whose compile is in flight is allowed to finish. Each rung is try/except-isolated; results
print as they land. Measurement methodology is shared with the other
perf tools via bench_core.

Run: python tools/perf_ladder.py            (background it; poll stdout)
Env: LADDER=760m_mb4,760m_mb8,xl_offload_mb1  (comma list; default 760m)
     LADDER_DEADLINE=3600  (seconds; checked between rungs only)
     LADDER_FUSED=10       (steps per fused dispatch; lower = faster compile)
     LADDER_RETRIES=3      (attempts per rung on transient failures —
                            resilience/retry.py classes; backoff base
                            LADDER_RETRY_BASE=15s, heartbeat-aware)
     LADDER_TELEMETRY=1    (graft-trace evidence: per-phase span medians +
                            drift ratios on every rung row; 0 opts out.
                            JSONLs land under LADDER_TELEMETRY_DIR, default
                            /tmp/ds_tpu_ladder_telemetry/<tag>)

Transient-failure policy (resilience/retry.py): a rung that dies with a
backend that another process still holds / a connection flake is retried with backoff+jitter; the
attempt history rides the rung's evidence row (``retries`` +
``retry_history``) so banked numbers show what they survived. A rung whose
retries exhaust emits a STRUCTURED row — ``blocked: backend_unavailable``
with the full history — instead of a bare error (PERF.md §PR9 envelope).
"""
import json
import os
import sys
import time
import traceback

# multi-device CPU smoke (pipe rungs need a pipe mesh): LADDER_DEVICES=N
# forces a virtual host-device count, same contract as GRAFT_LINT_DEVICES.
# Must land in XLA_FLAGS before bench_core imports jax.
_n_dev = os.environ.get("LADDER_DEVICES")
if _n_dev and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n_dev}").strip()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_core import (build_engine, enable_compile_cache, report,
                        time_fused, time_per_dispatch)

SEQ = 1024


def run_rung(tag, model_name, mb, offload=False, steps=None, seq=None,
             fused_xent=False, ds=None, cfg_overrides=None, pipe_stages=0,
             retry_evidence=None, retry_evidence_extra=None):
    ds_overrides = dict(ds or {})
    if offload:
        # full ZeRO-Infinity single-chip recipe: params rest pinned-host and
        # stream through the step (offload_param), masters + moments on the
        # host C++ Adam (offload_optimizer) — runtime/zero/param_offload.py
        ds_overrides["zero_optimization"] = {
            "stage": 3,
            "offload_param": {"device": "cpu", "pin_memory": True},
            "offload_optimizer": {"device": "cpu", "pin_memory": True},
        }
    if model_name == "bert_test":  # smoke rung: keep the tiny test vocab
        overrides = {}
    elif model_name.startswith("bert_"):
        # lane-aligned vocab (30522 → 30592, x128); BERT has no causal LM
        # head so the GPT-2 fused-xent/onehot knobs don't apply
        overrides = {"vocab_size": 30592}
    elif model_name == "test":  # smoke rungs: keep the tiny 256 vocab
        overrides = {}
    else:
        overrides = {"vocab_size": 50304, "embed_onehot_grad": True}
        if fused_xent:
            overrides["fused_head_loss_chunk"] = 1024
    overrides.update(cfg_overrides or {})  # rung-specific model-config knobs (MoE, ...)
    if os.environ.get("LADDER_TELEMETRY", "1") == "1":
        # graft-trace evidence: span timeline + drift ratios for the rung's
        # own steps (run header carries the static price). ≤2% overhead by
        # the tier-1 gate; LADDER_TELEMETRY=0 opts out for A/B paranoia.
        ds_overrides.setdefault("telemetry", {
            "enabled": True,
            "output_path": os.environ.get("LADDER_TELEMETRY_DIR",
                                          "/tmp/ds_tpu_ladder_telemetry"),
            "job_name": tag})
    engine, batch, n_params, cfg = build_engine(
        model_name, mb, seq or SEQ, ds_overrides=ds_overrides,
        pipe_stages=pipe_stages, **overrides)
    if offload:
        # host-driven schedule: per-step dispatch is the real path here
        n_steps, dt, compile_s = time_per_dispatch(engine, batch, steps or 3)
    else:
        fused = int(os.environ.get("LADDER_FUSED", "10"))
        n_steps, dt, compile_s = time_fused(engine, batch, fused=fused)
    # ONE trace shared by both static-evidence paths (tracing a real
    # model's step costs seconds; lint and cost must not each pay it)
    programs = _traced_programs_evidence(engine, batch)
    report(tag, mb, seq or SEQ, n_params, n_steps, dt, compile_s, cfg=cfg,
           **attn_geometry_evidence(cfg, mb, seq or SEQ),
           **moe_route_evidence(cfg),
           **lint_evidence(engine, batch, programs),
           **cost_evidence(engine, batch, programs),
           **telemetry_evidence(engine),
           **calibration_evidence(programs),
           **(retry_evidence_extra or {}),
           **(retry_evidence or {}))


def _traced_programs_evidence(engine, batch):
    """The engine's traced step, computed once for every evidence helper
    that needs it; None (with the evidence paths degrading to their own
    error rows) when tracing itself fails or both paths are opted out."""
    if (os.environ.get("LADDER_LINT", "1") != "1"
            and os.environ.get("LADDER_COST", "1") != "1"):
        return None
    try:
        return engine.traced_programs(batch)
    except Exception:  # each evidence helper reports its own error row
        return None


def attn_geometry_evidence(cfg, mb, seq):
    """Which flash-attention geometry this rung ran, and which resolution
    layer picked it (explicit/cache/default) — rows regenerate
    the PERF.md long-context table, so the chosen partitioning must ride
    next to the TFLOPS it produced."""
    if getattr(cfg, "attention_backend", None) != "flash":
        return {}
    try:
        import jax.numpy as jnp

        from deepspeed_tpu.ops.pallas.attention_geometry import (parse_spec,
                                                                 resolve_geometry)
        heads = getattr(cfg, "n_head", None) or getattr(cfg, "num_attention_heads", 1)
        causal = hasattr(cfg, "n_layer") or hasattr(cfg, "rope_theta")
        # mirror the kernel's resolution exactly: a per-model
        # attention_blocks pin is the highest-precedence (clamped) layer
        spec = getattr(cfg, "attention_blocks", None)
        geom, src = resolve_geometry(seq, seq, cfg.head_dim, heads, mb, causal,
                                     jnp.dtype(cfg.dtype),
                                     overrides=parse_spec(spec) if spec else None)
        return {"attn_geometry": geom.spec(), "attn_geometry_source": src}
    except Exception as e:  # evidence must never kill a rung
        return {"attn_geometry": f"error: {type(e).__name__}: {str(e)[:120]}",
                "attn_geometry_source": "error"}


def moe_route_evidence(cfg):
    """Which MoE dispatch/combine route this rung's model configuration
    names — the dense-vs-sorted A/B rows regenerate PERF.md's MoE table, so
    the route must ride next to the TFLOPS it produced."""
    if not getattr(cfg, "moe_num_experts", 0):
        return {}
    route = cfg.moe_route
    return {"moe_route": route,
            "moe_kernel": cfg.moe_route_kernel if route == "sorted" else None}


def telemetry_evidence(engine):
    """graft-trace evidence for the rung: per-phase span medians (ms) and
    predicted-vs-measured drift ratios from the rung's OWN measured steps
    (runtime/telemetry drift_summary — achieved TFLOPS from flops_proxy ÷
    median step time, memory-peak ratios where the backend reports them).
    A banked TFLOPS row thereby carries its cost-model error next to the
    lint/cost evidence. Evidence must never kill a rung; LADDER_TELEMETRY=0
    opts the whole subsystem out (the engine then runs telemetry-off)."""
    if os.environ.get("LADDER_TELEMETRY", "1") != "1":
        return {}
    try:
        tel = getattr(engine, "telemetry", None)
        if tel is None or not tel.enabled:
            return {}
        return {"telemetry": tel.drift_summary()}
    except Exception as e:  # evidence must never kill a rung
        return {"telemetry_error": f"{type(e).__name__}: {str(e)[:120]}"}


def lint_evidence(engine, batch, programs=None):
    """graft-lint summary of the step program this rung actually measured
    (rule hit counts / waivers / clean flag — deepspeed_tpu/analysis): a
    banked TFLOPS row must prove the measured program passed the same
    static gates CI enforces, or a window could bank a number from a
    program the next commit is forbidden to reproduce. Trace-only, a few
    seconds against the rung's compile minutes; LADDER_LINT=0 opts out."""
    if os.environ.get("LADDER_LINT", "1") != "1":
        return {}
    try:
        from deepspeed_tpu.analysis import lint_engine_program
        return lint_engine_program(engine, batch, programs=programs)
    except Exception as e:  # evidence must never kill a rung
        return {"lint_error": f"{type(e).__name__}: {str(e)[:120]}"}


def cost_evidence(engine, batch, programs=None):
    """graft-audit static-cost summary of the measured step program
    (deepspeed_tpu/analysis/cost.py): predicted peak bytes (total +
    transient) and analytic wire bytes per inventory layer, so every
    banked TFLOPS number carries its predicted memory/comms cost next to
    the measured one — the window-to-window sanity check that a faster
    rung didn't buy its speed with a fatter schedule. Trace-only (the
    rung's own compile is never repeated for evidence); the compiled
    collective layer therefore appears only where the trace carries
    explicit collectives (shard_map programs) or reshard sites.
    LADDER_COST=0 opts out."""
    if os.environ.get("LADDER_COST", "1") != "1":
        return {}
    try:
        from deepspeed_tpu.analysis import cost_engine_program
        return cost_engine_program(engine, batch, programs=programs)
    except Exception as e:  # evidence must never kill a rung
        return {"cost_error": f"{type(e).__name__}: {str(e)[:120]}"}


def calibration_evidence(programs):
    """graft-calibrate evidence: the rung's step program priced in
    predicted wall SECONDS under the committed measured-mode calibration
    (analysis_results/cost_calibration.json), stamped next to the
    measured ms — every banked row thereby carries the calibrated
    model's claim so the drift between them is auditable per window
    (rule R016 gates the artifact itself). Silently absent when no
    calibration is banked or the entry can't price this program;
    evidence must never kill a rung."""
    if programs is None or os.environ.get("LADDER_COST", "1") != "1":
        return {}
    try:
        from deepspeed_tpu.analysis import (calibrated_seconds,
                                            calibration_entry,
                                            load_calibration,
                                            static_price_from_programs)
        entry, key = calibration_entry(load_calibration())
        if entry is None:
            return {}
        sec = calibrated_seconds(static_price_from_programs(programs),
                                 entry["coeffs"])
        if sec is None:
            return {}
        return {"predicted_step_s_calibrated": sec, "calibration_key": key}
    except Exception as e:  # evidence must never kill a rung
        return {"calibration_error": f"{type(e).__name__}: {str(e)[:120]}"}


RUNGS = {
    # harness smoke rungs (tiny model): validate the fused and offload
    # measurement paths in seconds on any backend before burning a chip
    # window on the real rungs
    "smoke": dict(model_name="test", mb=2, seq=64),
    "smoke_offload": dict(model_name="test", mb=2, seq=64, offload=True, steps=2),
    "smoke_bert": dict(model_name="bert_test", mb=2, seq=64),
    "smoke_moe": dict(model_name="test", mb=2, seq=64,
                      cfg_overrides=dict(moe_num_experts=2, moe_layer_freq=2,
                                         moe_k=1)),
    "760m_mb4": dict(model_name="760m", mb=4),
    "760m_mb8": dict(model_name="760m", mb=8),
    # plain 760m_mb8 OOMs by 2.6G; the chunked fused head removes the
    # [B,L,V] logits + cotangent buffers (~2x0.77G bf16 + f32 temps)
    "760m_mb8_fx": dict(model_name="760m", mb=8, fused_xent=True),
    "760m_mb4_fx": dict(model_name="760m", mb=4, fused_xent=True),
    # offload A/B at the bench operating point: quantifies the ZeRO-Infinity
    # streaming overhead against the dense 70-TFLOPS configuration
    "350m_offload_mb8": dict(model_name="350m", mb=8, offload=True, steps=3,
                             fused_xent=True),
    "xl_offload_mb1": dict(model_name="xl", mb=1, offload=True, steps=2),
    "xl_offload_mb4": dict(model_name="xl", mb=4, offload=True, steps=2),
    # single-chip GPT-MoE rung + its dense base A/B (measured r5 on chip:
    # 2.6x params at 1.30x step cost; larger MoE geometries OOM one chip
    # dense — EP weak-scaling evidence covers those). TFLOPS uses active
    # params (flops_per_token_from_cfg MoE accounting).
    "125m_mb8": dict(model_name="125m", mb=8, fused_xent=True),
    "125m_moe8_mb8": dict(model_name="125m", mb=8, fused_xent=True,
                          cfg_overrides=dict(moe_num_experts=8,
                                             moe_layer_freq=2, moe_k=1)),
    # dispatch-route A/B at the same operating point: 125m_moe8_mb8 runs
    # the default (sorted); this rung pins the
    # dense einsum route so the sorted-route gain is measured in one window
    # (ROADMAP 3c: >=58 active-TFLOPS target, from 48.8 dense)
    "125m_moe8_mb8_dense": dict(model_name="125m", mb=8, fused_xent=True,
                                cfg_overrides=dict(moe_num_experts=8,
                                                   moe_layer_freq=2, moe_k=1,
                                                   moe_route="dense")),
    # pipeline-schedule A/B at the 350m judged config (PR 11): same mesh,
    # same 16-microbatch global batch, only the tick schedule differs.
    # 1f1b holds the constant 2(S-1)-slot activation stash with per-tick
    # fwd/bwd interleave; chunked pays a fill/drain bubble per C=4 wave
    # and ~2x the activation bound (CPU A/B: 1f1b 1.19x faster at the
    # M=16/S=4 test shape, PERF.md §PR11 — the chip window prices the
    # same pair at real scale, where the freed HBM also buys microbatch)
    "350m_pipe4_1f1b": dict(model_name="350m", mb=16, pipe_stages=4,
                            ds={"gradient_accumulation_steps": 16,
                                "pipeline": {"schedule": "1f1b"}}),
    "350m_pipe4_chunked": dict(model_name="350m", mb=16, pipe_stages=4,
                               ds={"gradient_accumulation_steps": 16,
                                   "pipeline": {"schedule": "chunked",
                                                "chunk_microbatches": 4}}),
    "smoke_pipe": dict(model_name="test", mb=8, seq=64, pipe_stages=2,
                       ds={"gradient_accumulation_steps": 4,
                           "pipeline": {"schedule": "1f1b"}}),
    # long-context rungs: the gridded flash kernel streams K/V blocks, so
    # VMEM no longer caps sequence length; fused xent keeps the logits
    # buffers off the OOM line at long L. Rows report the chosen attention
    # block geometry + its source — run tools/attn_tune.py first to bank
    # shape-keyed winners, or pin one in the rung's attention_blocks.
    "350m_seq2k": dict(model_name="350m", mb=4, seq=2048, fused_xent=True),
    "350m_seq4k": dict(model_name="350m", mb=2, seq=4096, fused_xent=True),
    "350m_seq8k": dict(model_name="350m", mb=1, seq=8192, fused_xent=True),
    # the reference's 64-TFLOPS headline workload: BERT-large pretrain at
    # seq 128 (BASELINE.md row 1) — direct apples-to-apples rung
    "bert_large_mb64": dict(model_name="bert_large", mb=64, seq=128),
    "bert_large_mb128": dict(model_name="bert_large", mb=128, seq=128),
    "bert_large_mb256": dict(model_name="bert_large", mb=256, seq=128),
    # BERT-large ZeRO-1 + FusedAdam is the ladder's second judged config
    # ("Adam" = the optax XLA-fused Adam, this repo's FusedAdam role; on
    # one chip ZeRO-1's shards are trivially whole but the config path is
    # the judged one)
    "bert_large_seq512_mb32": dict(model_name="bert_large", mb=32, seq=512,
                                   ds={"zero_optimization": {"stage": 1},
                                       "optimizer": {"type": "Adam",
                                                     "params": {"lr": 1e-4}}}),
}


#: graft-serve latency-under-load rungs (ISSUE 14): each is a committed
#: tools/serve_bench.py configuration, so the next chip window measures
#: the serving curve for free. Rows carry the bench's own evidence
#: columns — serve_lint / serve_cost_* (graft-audit price of the decode
#: program actually served) and, via SERVE_TELEMETRY, per-tick span
#: medians + drift — next to goodput and p50/p99 TTFT / per-token
#: latency. The continuous-vs-static comparison row rides the b32 rung;
#: chunked-prefill and speculation are isolated A/Bs on one knob each.
SERVE_RUNGS = {
    # the measured decode sweet spot (PERF.md decode sweep: batch 32):
    # continuous vs static at equal offered load, the headline comparison
    "serve_qps_b32": {"SERVE_MODE": "both", "SERVE_SLOTS": "32",
                      "SERVE_QPS": "16", "SERVE_REQUESTS": "96",
                      "SERVE_PROMPT": "64", "SERVE_NEW": "32"},
    # chunked prefill A/B: every 4th prompt is 4x long; CHUNK=0 disables
    # chunking (whole-prompt prefill ticks stall in-flight decodes)
    "serve_qps_chunked_on": {"SERVE_MODE": "continuous", "SERVE_SLOTS": "8",
                             "SERVE_QPS": "8", "SERVE_REQUESTS": "48",
                             "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                             "SERVE_LONG_EVERY": "4", "SERVE_CHUNK": "16"},
    "serve_qps_chunked_off": {"SERVE_MODE": "continuous", "SERVE_SLOTS": "8",
                              "SERVE_QPS": "8", "SERVE_REQUESTS": "48",
                              "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                              "SERVE_LONG_EVERY": "4", "SERVE_CHUNK": "0"},
    # speculation A/B: KD-student drafter on/off at the same trace
    "serve_qps_spec_on": {"SERVE_MODE": "continuous", "SERVE_SLOTS": "8",
                          "SERVE_QPS": "8", "SERVE_REQUESTS": "48",
                          "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                          "SERVE_SPEC": "1", "SERVE_SPEC_K": "4"},
    "serve_qps_spec_off": {"SERVE_MODE": "continuous", "SERVE_SLOTS": "8",
                           "SERVE_QPS": "8", "SERVE_REQUESTS": "48",
                           "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                           "SERVE_SPEC": "0"},
    # graft-quant-serve A/B (ISSUE 16): fp vs int8/int4 weights + int8 KV
    # on the same trace under the SAME KV byte budget (unset POOL_BYTES =
    # half the fp full-context footprint, so fp is admission-starved at
    # saturation while quant holds every slot). Rows carry blocks-per-GB
    # and the comparison row carries goodput ratio + token-level greedy
    # match of the quantized arm vs fp (PERF.md §PR16).
    "serve_qps_wq8": {"SERVE_MODE": "quant_ab", "SERVE_SLOTS": "8",
                      "SERVE_QPS": "16", "SERVE_REQUESTS": "48",
                      "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                      "SERVE_WQ": "int8"},
    "serve_qps_wq4": {"SERVE_MODE": "quant_ab", "SERVE_SLOTS": "8",
                      "SERVE_QPS": "16", "SERVE_REQUESTS": "48",
                      "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                      "SERVE_WQ": "int4"},
    # graft-prefix-cache rungs (ISSUE 19): the seeded shared-prefix trace
    # (8 templates, each 3/4 of the prompt) served cache-on vs cache-off
    # at IDENTICAL pool bytes. The comparison row carries goodput ratio,
    # per-arm TTFT p99, hit rate / cached-blocks evidence, and the
    # token-level greedy match — which must be EXACT (a restored block is
    # the same KV bytes prefill would have written). QPS saturates the
    # 8 slots so prefill compute is the contended resource the cache
    # relieves (PERF.md §PR19). Three geometry choices are load-bearing
    # and each was MEASURED to flip the A/B when wrong:
    #  - POOL_TOKENS sizes the pool ABOVE slots x context (the
    #    default): 192 blocks = 104 in-use at saturation + 72 for the
    #    8 shared templates + headroom. At the default the spare
    #    capacity can't hold one 9-block template and the LRU thrashes
    #    (measured: hit rate 0.83 -> 0.48, 263 evictions, cache-on
    #    LOSES 0.76x). A prefix cache needs the deployment reality of
    #    spare pool; both arms price the same bytes either way.
    #  - NEW_JITTER: with every request decoding exactly NEW tokens,
    #    slots free in perfect waves of 8 and the OFF arm prefills in
    #    fully-batched cohorts — an artifact of uniform lengths that
    #    mixed hot/cold admission then breaks (measured: cache-on
    #    0.93x despite hit rate 0.75, prefill ticks UP 42 -> 48 on
    #    HALF the slot-chunks). Variable output lengths fragment both
    #    arms alike and let the 2x work cut show up as ticks.
    #  - NEW=16 << PROMPT=192 is the workload prefix caching exists
    #    for (RAG / few-shot: long shared prompt, short completion);
    #    at NEW=32 decode ticks dominate the budget and cap the best
    #    possible ratio near 1.1x.
    "serve_prefix_ab": {"SERVE_MODE": "prefix_ab", "SERVE_SLOTS": "8",
                        "SERVE_QPS": "16", "SERVE_REQUESTS": "48",
                        "SERVE_PROMPT": "192", "SERVE_NEW": "16",
                        "SERVE_NEW_JITTER": "1",
                        "SERVE_CHUNK": "32", "SERVE_SHARED_PREFIX": "8",
                        "SERVE_POOL_TOKENS": "3072"},
    # prefix-affinity fleet routing: the same shared-prefix trace through
    # 2 replicas, affinity dispatch (replicas advertise their hot root
    # prefixes in tick signals) vs pure least-loaded (FLEET_AFFINITY=0).
    # Affinity keeps same-template requests on the replica already
    # holding their prefix blocks — the control arm scatters each
    # template across both replicas, paying ~2x the fleet-wide cold
    # prefills and duplicating every template's blocks in both pools
    # (per-worker hit rate / cold counts in the replica telemetry are
    # the evidence; on a 1-core rig the goodput delta is muted because
    # the replicas' compute serializes either way).
    "serve_prefix_fleet_affinity": {
        "SERVE_MODE": "fleet", "SERVE_REPLICAS": "2", "SERVE_QPS": "16",
        "SERVE_REQUESTS": "48", "SERVE_PROMPT": "192", "SERVE_NEW": "16",
        "SERVE_NEW_JITTER": "1",
        "SERVE_SLOTS": "8", "SERVE_CHUNK": "32", "SERVE_SHARED_PREFIX": "8",
        "SERVE_POOL_TOKENS": "3072"},
    "serve_prefix_fleet_leastloaded": {
        "SERVE_MODE": "fleet", "SERVE_REPLICAS": "2", "SERVE_QPS": "16",
        "SERVE_REQUESTS": "48", "SERVE_PROMPT": "192", "SERVE_NEW": "16",
        "SERVE_NEW_JITTER": "1",
        "SERVE_SLOTS": "8", "SERVE_CHUNK": "32", "SERVE_SHARED_PREFIX": "8",
        "SERVE_POOL_TOKENS": "3072", "FLEET_AFFINITY": "0"},
    # graft-fleet scaling rungs (ISSUE 17): the SAME trace through a
    # FleetRouter over N real worker subprocesses (fleet/worker.py; each
    # builds + warms its own engine off the clock). The x1/x2/x4 trio
    # regenerates the PERF.md §PR17 goodput-scaling row at pinned TTFT
    # p99; the smoke rung proves the subprocess plumbing in seconds on
    # any backend before a window pays for the real trio.
    "serve_fleet_smoke": {"SERVE_MODE": "fleet", "SERVE_MODEL": "test",
                          "SERVE_REPLICAS": "2", "SERVE_QPS": "16",
                          "SERVE_REQUESTS": "12", "SERVE_PROMPT": "16",
                          "SERVE_NEW": "8", "SERVE_SLOTS": "4",
                          "SERVE_CHUNK": "8"},
    "serve_fleet_x1": {"SERVE_MODE": "fleet", "SERVE_REPLICAS": "1",
                       "SERVE_QPS": "16", "SERVE_REQUESTS": "64",
                       "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                       "SERVE_SLOTS": "8"},
    "serve_fleet_x2": {"SERVE_MODE": "fleet", "SERVE_REPLICAS": "2",
                       "SERVE_QPS": "16", "SERVE_REQUESTS": "64",
                       "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                       "SERVE_SLOTS": "8"},
    "serve_fleet_x4": {"SERVE_MODE": "fleet", "SERVE_REPLICAS": "4",
                       "SERVE_QPS": "16", "SERVE_REQUESTS": "64",
                       "SERVE_PROMPT": "64", "SERVE_NEW": "32",
                       "SERVE_SLOTS": "8"},
}


def run_serve_rung(tag, serve_env, retry_evidence=None):
    """One serving rung: tools/serve_bench.py in a clean subprocess (its
    own engine + scheduler state; a hung serve can't poison later
    rungs), each of its JSON rows re-emitted with the rung tag and any
    retry evidence. NB the parent has touched JAX by then and holds the
    chip: on a TPU this child cannot get it (ROADMAP launcher audit)."""
    import subprocess
    env = dict(os.environ)
    env.setdefault("SERVE_MODEL", "350m")
    env.setdefault("SERVE_TELEMETRY", "1")
    env.update(serve_env)
    p = subprocess.run([sys.executable,
                        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                     "serve_bench.py")],
                       env=env, capture_output=True, text=True)
    emitted = 0
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            row = json.loads(line)
            print(json.dumps(dict({"tag": tag}, **row,
                                  **(retry_evidence or {}))), flush=True)
            emitted += 1
        elif line.startswith("#"):
            print(line, flush=True)
    if p.returncode != 0 or not emitted:
        raise RuntimeError(f"serve rung {tag} failed rc={p.returncode}: "
                           f"{p.stderr[-400:]}")


#: graft-rlhf rungs (ISSUE 20): tools/rlhf_bench.py on the SAME indexed
#: prompt trace + per-rollout budget mix, in-flight loop vs serial
#: generate-then-train. ``rlhf_overlap_on`` emits the A/B pair + ratio
#: row in one process (both arms must bank identical experience tokens —
#: the bench asserts it); ``rlhf_overlap_off`` re-measures the serial arm
#: alone so a window can re-baseline without paying the loop. Rows carry
#: the planner-priced weight-sync evidence (gather_bytes per sync,
#: digest_verified) and the run dirs stamp rlhf_rollout / rlhf_learner
#: calibration headers (the rlhf_overlap marker collect_samples keys on).
RLHF_RUNGS = {
    "rlhf_overlap_on": {"RLHF_MODE": "ab", "RLHF_BATCH": "8",
                        "RLHF_PROMPT": "64", "RLHF_NEW": "64",
                        "RLHF_ROLLOUTS": "32", "RLHF_SLOTS": "8",
                        "RLHF_SYNC_EVERY": "1"},
    "rlhf_overlap_off": {"RLHF_MODE": "off", "RLHF_BATCH": "8",
                         "RLHF_PROMPT": "64", "RLHF_NEW": "64",
                         "RLHF_ROLLOUTS": "32", "RLHF_SLOTS": "8",
                         "RLHF_SYNC_EVERY": "1"},
}


def run_rlhf_rung(tag, rlhf_env, retry_evidence=None):
    """One graft-rlhf rung: tools/rlhf_bench.py in a clean subprocess
    (its own hybrid engine + scheduler; same isolation contract as the
    serve rungs), each JSON row re-emitted with the rung tag and retry
    evidence. Never wrapped in `timeout` (bench contract)."""
    import subprocess
    import tempfile
    env = dict(os.environ)
    env.setdefault("RLHF_MODEL", "350m")
    env.setdefault("RLHF_TELEMETRY",
                   tempfile.mkdtemp(prefix=f"rlhf_ladder_{tag}_"))
    env.update(rlhf_env)
    p = subprocess.run([sys.executable,
                        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                     "rlhf_bench.py")],
                       env=env, capture_output=True, text=True)
    emitted = 0
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            row = json.loads(line)
            print(json.dumps(dict({"tag": tag}, **row,
                                  telemetry_dir=env["RLHF_TELEMETRY"],
                                  **(retry_evidence or {}))), flush=True)
            emitted += 1
        elif line.startswith("#"):
            print(line, flush=True)
    if p.returncode != 0 or not emitted:
        raise RuntimeError(f"rlhf rung {tag} failed rc={p.returncode}: "
                           f"{p.stderr[-400:]}")


def _frontier_rungs():
    """Rungs generated FROM the committed graft-search Pareto frontier
    (analysis_results/search_pareto.json, 350m_judged space): the next
    chip window measures exactly the statically-surviving candidate set —
    never a dominated loser (ISSUE 12 / ROADMAP 3). Pareto-tied
    candidates (identical static metrics, e.g. fused-vs-split QKV, which
    the static model cannot distinguish — only the chip can) collapse to
    their first enumerated representative so the window pays one rung per
    distinct static price point; the skipped ties are listed in the
    rung's ``search_ties`` evidence. The remat/chunk/fusion knobs route
    through the engine "program" block + optimizer.legacy_fusion exactly
    as priced; attention is the ONE deliberate delta — the frontier was
    priced on the backend-reproducible XLA attention program while the
    rung measures under the bench methodology's flash kernel, so each
    rung stamps ``search_priced_backend: "xla"`` next to its candidate id
    (the priced no-remat transients are dominated by XLA's materialized
    scores; flash removes that term, which only WIDENS the frontier's
    remat/chunk wins — the window verifies, it does not assume)."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "analysis_results", "search_pareto.json")
    if not os.path.exists(path):
        return {}
    # the validated loader, not raw json: a version-bumped or corrupt
    # artifact must refuse loudly here exactly as it does in graft_lint
    from deepspeed_tpu.analysis.search import load_search_artifact
    space = load_search_artifact(path).get("spaces", {}).get("350m_judged")
    if not space:
        return {}
    # calibrated artifacts carry seconds_rank — the frontier re-ranked in
    # predicted wall seconds under the committed cost calibration — so
    # the window measures winners in the order the measured-mode model
    # expects them to finish; uncalibrated artifacts keep proxy order
    order = space.get("seconds_rank") or space["frontier"]
    rungs, seen_metrics = {}, {}
    for cid in order:
        entry = space["candidates"][cid]
        knobs, metrics = entry["knobs"], entry["metrics"]
        key = tuple(metrics.get(o) for o in space["objectives"])
        if key in seen_metrics:
            rungs[seen_metrics[key]].setdefault("retry_evidence_extra", {}) \
                .setdefault("search_ties", []).append(cid)
            continue
        from deepspeed_tpu.analysis.search import Candidate
        ds = {"program": Candidate(**knobs).program_block()}
        if knobs.get("optimizer") == "chained":
            ds["optimizer"] = {"type": "AdamW", "legacy_fusion": True,
                               "params": {"lr": 1e-4, "weight_decay": 0.01}}
        slug = (knobs["remat"].replace(":", "-").replace("_", "") +
                f"_h{knobs['lm_head_chunk']}"
                + ("" if knobs.get("fused_qkv", True) else "_qkvsplit")
                + ("" if knobs.get("fused_attn_out", True) else "_outreshape")
                + ("" if knobs.get("optimizer", "fused") == "fused" else "_optchained"))
        tag = f"350m_search_{slug}"
        seen_metrics[key] = tag
        evidence = {"search_candidate": cid,
                    "search_space": "350m_judged",
                    "search_priced_backend": "xla"}
        if space.get("seconds_rank"):
            evidence["search_predicted_seconds"] = metrics.get("predicted_seconds")
            evidence["search_seconds_rank"] = order.index(cid) + 1
            evidence["search_proxy_rank"] = space["frontier"].index(cid) + 1
        rungs[tag] = dict(
            model_name="350m", mb=space["model"]["micro_bs"],
            seq=space["model"]["seq"], ds=ds,
            retry_evidence_extra=evidence)
    return rungs


def _install_frontier_rungs():
    try:
        for tag, spec in _frontier_rungs().items():
            RUNGS.setdefault(tag, spec)
    except Exception as e:  # a corrupt artifact must not kill the ladder
        print(f"# frontier rungs unavailable: {type(e).__name__}: {e}",
              file=sys.stderr)


_install_frontier_rungs()


def _rung_retry_policy():
    from deepspeed_tpu.runtime.resilience.retry import RetryPolicy, heartbeat_sleep
    return RetryPolicy(max_attempts=int(os.environ.get("LADDER_RETRIES", "3")),
                       base_delay=float(os.environ.get("LADDER_RETRY_BASE", "15")),
                       max_delay=300.0, jitter=0.25,
                       # backoff naps keep the agent's heartbeat fresh: a rung
                       # waiting out a helper restart must not read as hung
                       sleep=heartbeat_sleep())


def main():
    enable_compile_cache()
    from deepspeed_tpu.runtime.resilience.retry import classify_failure
    deadline = time.time() + int(os.environ.get("LADDER_DEADLINE", "3600"))
    want = os.environ.get("LADDER", "760m_mb4,760m_mb8").split(",")
    print(f"# ladder seq={SEQ}: {want}", flush=True)
    for tag in want:
        if time.time() > deadline:
            print(f"# deadline reached, skipping {tag} onward", flush=True)
            break
        policy = _rung_retry_policy()
        evidence = {}  # mutated before each attempt; report() reads it live

        def attempt(i, history, _ev=evidence, _tag=tag):
            from deepspeed_tpu.elasticity import touch_heartbeat
            touch_heartbeat()  # supervised runs: fresh clock before each attempt
            _ev.clear()
            _ev.update(policy.evidence())
            if i > 1:
                print(f"# {_tag}: retry attempt {i}/{policy.max_attempts} after "
                      f"{history[-1]['error_class'] or 'transient failure'}", flush=True)

        try:
            if tag.strip() in SERVE_RUNGS:
                policy.call(run_serve_rung, tag, SERVE_RUNGS[tag.strip()],
                            retry_evidence=evidence, before_attempt=attempt)
            elif tag.strip() in RLHF_RUNGS:
                policy.call(run_rlhf_rung, tag, RLHF_RUNGS[tag.strip()],
                            retry_evidence=evidence, before_attempt=attempt)
            else:
                policy.call(run_rung, tag, retry_evidence=evidence,
                            before_attempt=attempt, **RUNGS[tag.strip()])
        except Exception as e:  # noqa: BLE001 — keep laddering past OOMs
            row = {"tag": tag, "error": f"{type(e).__name__}: {str(e)[:300]}"}
            cls = classify_failure(e)
            if cls is not None:
                # structured blocked row: the failure class + full retry
                # history, machine-readable for PERF.md's envelope table
                row["blocked"] = cls
            row.update(policy.evidence())
            cfg_ov = RUNGS.get(tag.strip(), {}).get("cfg_overrides", {})
            if cfg_ov.get("moe_num_experts"):
                # MoE error rows still carry their route evidence (a failed
                # rung must be attributable to the route that failed it)
                class _C:  # minimal cfg shim for the evidence helper
                    moe_num_experts = cfg_ov["moe_num_experts"]
                    moe_route = cfg_ov.get("moe_route", "sorted")
                    moe_route_kernel = cfg_ov.get("moe_route_kernel", "auto")
                row.update(moe_route_evidence(_C))
            print(json.dumps(row), flush=True)
            traceback.print_exc(file=sys.stderr)
    print("# DONE", flush=True)


if __name__ == "__main__":
    main()
