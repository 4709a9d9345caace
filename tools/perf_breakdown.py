"""One clean-exit TPU breakdown: times fwd, fwd+bwd, and the full engine
step as separate compiled programs, each iterated with CHAINED data
dependencies (output feeds next input). Attribution without
jax.profiler.trace.

Run: python tools/perf_breakdown.py   (one process holds the chip; on the
chip tool it is one command)

MoE mode (``BENCH_MOE=1``): instead of the engine step, attribute one MoE
layer's time into gate / dispatch / expert-matmul / combine sections by
timing nested prefix programs (gate; gate+dispatch; +experts; +combine)
per route, so the dense-vs-sorted A/B is visible per phase, not just
end-to-end. Defaults to the 125m_moe8 shape (M=768, E=8, mb=8, seq=1024 —
override via BENCH_MOE_DIM/EXPERTS/BENCH_MICRO_BS/BENCH_SEQ/BENCH_MOE_K/
BENCH_MOE_CF); routes from BENCH_MOE_ROUTES (default "dense,sorted").
Each route row also reports ``dispatch_peak_bytes`` — the routing
metadata + dispatch buffers the route materializes (the dense route's
[S,E,C] tensors vs the sorted route's [S*k] index vectors).

Pipe mode (``BENCH_PIPE=1``): the pipeline-schedule A/B — times
``train_batch`` per schedule (BENCH_PIPE_SCHEDULES, default
"1f1b,chunked,gpipe") on a pipe-only mesh (BENCH_PIPE_STAGES=4,
BENCH_PIPE_MICROS=16, BENCH_MICRO_BS=2, BENCH_SEQ=128,
BENCH_PIPE_EMBD=128, BENCH_PIPE_MODEL=test) and stamps each row with the
schedule's STATIC transient-bytes estimate (analysis.cost_engine_program,
trace-only) so the measured step time rides next to the activation bound
R010 gates — the PERF.md §PR11 table regenerates from these rows.
"""
import json
import os
import sys
import time

# BENCH_DEVICES=N forces a virtual host-device count (the pipe A/B needs
# a pipe mesh on CPU); must land in XLA_FLAGS before jax imports.
_n_dev = os.environ.get("BENCH_DEVICES")
if _n_dev and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n_dev}").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import jax
import jax.numpy as jnp

from bench_core import enable_compile_cache

enable_compile_cache()

import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

MODEL = os.environ.get("BENCH_MODEL", "350m")
MB = int(os.environ.get("BENCH_MICRO_BS", "4"))
SEQ = int(os.environ.get("BENCH_SEQ", "1024"))
N = 10


def timed(tag, fn, carry):
    """fn: carry -> carry with chained deps. Times N iterations."""
    carry = fn(carry)  # warmup (compile)
    jax.block_until_ready(carry)
    t0 = time.time()
    for _ in range(N):
        carry = fn(carry)
    jax.block_until_ready(carry)
    dt = (time.time() - t0) / N
    print(json.dumps({"tag": tag, "ms": round(dt * 1e3, 1)}), flush=True)
    return dt


def moe_sections():
    """Per-phase MoE attribution: nested prefix programs per route. Chained
    deps (loss-derived zero shift) keep the dedupe honest, same as the
    model-level sections."""
    import jax.nn
    from deepspeed_tpu.moe.sharded_moe import _capacity, top1gating, top1routing, top2gating, top2routing
    from deepspeed_tpu.ops.pallas.moe_dispatch import inverse_index, permute_rows, resolve_impl

    M = int(os.environ.get("BENCH_MOE_DIM", "768"))       # 125m n_embd
    E = int(os.environ.get("BENCH_MOE_EXPERTS", "8"))
    K = int(os.environ.get("BENCH_MOE_K", "1"))
    CF = float(os.environ.get("BENCH_MOE_CF", "1.25"))
    S = MB * SEQ                                          # tokens per group (G=1)
    F = 4 * M
    C = _capacity(S, E, (2 * CF) if K == 2 else CF, 4)
    impl = resolve_impl("auto")
    routes = os.environ.get("BENCH_MOE_ROUTES", "dense,sorted").split(",")
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    print(f"# moe breakdown M={M} E={E} k={K} cf={CF} S={S} C={C} "
          f"impl={impl} dtype={dt.__name__}", flush=True)

    rng = np.random.default_rng(0)
    wg = jnp.asarray(rng.normal(0, 0.02, (M, E)), jnp.float32)
    w1 = jnp.asarray(rng.normal(0, 0.02, (E, M, F)), dt)
    w2 = jnp.asarray(rng.normal(0, 0.02, (E, F, M)), dt)
    tokens0 = jnp.asarray(rng.normal(size=(S, M)), dt)
    itemsize = jnp.dtype(dt).itemsize

    def gate_dense(tok):
        logits = tok.astype(jnp.float32) @ wg
        if K == 2:
            return top2gating(logits, CF, 4)
        return top1gating(logits, CF, 4)

    def gate_sorted(tok):
        logits = tok.astype(jnp.float32) @ wg
        if K == 2:
            return top2routing(logits, CF, 4)
        return top1routing(logits, CF, 4)

    def dispatch_dense(tok):
        l_aux, combine, dispatch, _ = gate_dense(tok)
        return jnp.einsum("sec,sm->ecm", dispatch.astype(tok.dtype), tok), combine, l_aux

    def dispatch_sorted(tok):
        l_aux, rt, _ = gate_sorted(tok)
        flat_slot = jnp.where(rt.keep > 0, rt.expert * C + rt.slot,
                              E * C).astype(jnp.int32).reshape(1, S * K)
        src = inverse_index(flat_slot, E * C)
        rep = jnp.repeat(tok, K, axis=0) if K > 1 else tok
        buf = permute_rows(rep[None], src, flat_slot, impl=impl)
        return buf.reshape(E, C, M), (flat_slot, src, rt.weight), l_aux

    def experts(buf):  # [E,C,M] -> [E,C,M], one fused GEMM pair per projection
        h = jax.nn.gelu(jnp.einsum("ecm,emf->ecf", buf, w1))
        return jnp.einsum("ecf,efm->ecm", h, w2)

    def combine_dense(combine, eo, tok):
        return jnp.einsum("sec,ecm->sm", combine.astype(tok.dtype), eo)

    def combine_sorted(meta, eo, tok):
        flat_slot, src, weight = meta
        rows = permute_rows(eo.reshape(1, E * C, M), flat_slot, src, impl=impl)
        w = weight.astype(tok.dtype).reshape(1, S * K, 1)
        return (w * rows).reshape(S, K, M).sum(axis=1)

    for route in [r.strip() for r in routes if r.strip()]:
        disp = dispatch_dense if route == "dense" else dispatch_sorted
        comb = combine_dense if route == "dense" else combine_sorted

        def p_gate(tok):
            out = (gate_dense if route == "dense" else gate_sorted)(tok)
            return out[0]  # l_aux: scalar data dep through the whole gate

        def p_dispatch(tok):
            buf, _, l_aux = disp(tok)
            return buf.astype(jnp.float32).sum() + l_aux

        def p_expert(tok):
            buf, _, l_aux = disp(tok)
            return experts(buf).astype(jnp.float32).sum() + l_aux

        def p_full(tok):
            buf, meta, l_aux = disp(tok)
            out = comb(meta, experts(buf), tok) if route == "sorted" \
                else combine_dense(meta, experts(buf), tok)
            return out.astype(jnp.float32).sum() + l_aux

        times = {}
        for tag, fn in [("gate", p_gate), ("dispatch", p_dispatch),
                        ("expert", p_expert), ("fwd", p_full),
                        ("fwd_bwd", lambda tok: jax.grad(p_full)(tok).astype(jnp.float32).sum())]:
            @jax.jit
            def prog(carry, fn=fn):
                tok, acc = carry
                v = fn(tok)
                v = v.sum() if v.ndim else v
                shift = (v * 0).astype(tok.dtype)
                return (tok + shift, acc + v.astype(jnp.float32))

            times[tag] = timed(f"moe_{route}_{tag}", lambda c: prog(c),
                               (tokens0, jnp.float32(0)))

        # routing metadata + dispatch/combine buffers materialized per route
        if route == "dense":
            meta_bytes = S * E * C * (4 + itemsize)  # combine f32 + mask cast
        else:
            meta_bytes = S * K * (4 + 4) + E * C * 4  # slots + weights + src
        peak = meta_bytes + E * C * M * itemsize     # + the [E,C,M] buffer
        print(json.dumps({
            "tag": f"moe_{route}", "moe_route": route,
            "moe_kernel": impl if route == "sorted" else None,
            "gate_ms": round(times["gate"] * 1e3, 2),
            "dispatch_ms": round((times["dispatch"] - times["gate"]) * 1e3, 2),
            "expert_ms": round((times["expert"] - times["dispatch"]) * 1e3, 2),
            "combine_ms": round((times["fwd"] - times["expert"]) * 1e3, 2),
            "fwd_ms": round(times["fwd"] * 1e3, 2),
            "fwd_bwd_ms": round(times["fwd_bwd"] * 1e3, 2),
            "dispatch_peak_bytes": int(peak),
        }), flush=True)


def pipe_schedule_ab():
    """Per-schedule pipeline A/B: measured step time + static transient
    bytes per schedule on the same mesh/model/microbatch count."""
    from deepspeed_tpu.analysis import cost_engine_program
    from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    stages = int(os.environ.get("BENCH_PIPE_STAGES", "4"))
    micros = int(os.environ.get("BENCH_PIPE_MICROS", "16"))
    mb = int(os.environ.get("BENCH_MICRO_BS", "2"))
    seq = int(os.environ.get("BENCH_SEQ", "128"))
    embd = int(os.environ.get("BENCH_PIPE_EMBD", "128"))
    model = os.environ.get("BENCH_PIPE_MODEL", "test")
    schedules = os.environ.get("BENCH_PIPE_SCHEDULES", "1f1b,chunked,gpipe").split(",")
    steps = int(os.environ.get("BENCH_PIPE_STEPS", "5"))
    if len(jax.devices()) < stages:
        print(json.dumps({"tag": "pipe_ab", "error":
                          f"needs {stages} devices, have {len(jax.devices())}"}))
        return
    print(f"# pipe schedule A/B S={stages} M={micros} mb={mb} seq={seq} "
          f"embd={embd} model={model}", flush=True)
    rng = np.random.default_rng(0)
    for schedule in schedules:
        schedule = schedule.strip()
        set_topology(None)
        cfg = get_gpt2_config(model, n_layer=stages, n_embd=embd,
                              n_head=max(2, embd // 32), n_positions=seq)
        topo = MeshTopology(pipe=stages, data=1, devices=jax.devices()[:stages])
        pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=pipe, topology=topo,
            config={"train_batch_size": micros * mb,
                    "gradient_accumulation_steps": micros,
                    "pipeline": {"schedule": schedule},
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                    "steps_per_print": 10**9})
        batch = {"input_ids": rng.integers(0, cfg.vocab_size,
                                           (micros * mb, seq)).astype(np.int32)}
        t0 = time.time()
        engine.train_batch(batch)
        jax.block_until_ready(engine.state.params)
        compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(steps):
            engine.train_batch(batch)
        jax.block_until_ready(engine.state.params)
        dt = (time.time() - t0) / steps
        row = {"tag": f"pipe_{schedule}", "pipe_schedule": engine.pipe_schedule,
               "stages": stages, "micro_batches": micros,
               "chunk_microbatches": engine.pipe_chunk,
               "step_ms": round(dt * 1e3, 1),
               "compile_s": round(compile_s, 1),
               "loss": round(float(engine.train_batch(batch)), 4)}
        try:  # static evidence next to the measured number (trace-only)
            row.update(cost_engine_program(engine, batch))
        except Exception as e:  # evidence must never kill a row
            row["cost_error"] = f"{type(e).__name__}: {str(e)[:120]}"
        print(json.dumps(row), flush=True)
    set_topology(None)


def main():
    if os.environ.get("BENCH_MOE", "0") == "1":
        moe_sections()
        print("# DONE", flush=True)
        return
    if os.environ.get("BENCH_PIPE", "0") == "1":
        pipe_schedule_ab()
        print("# DONE", flush=True)
        return
    cfg = get_gpt2_config(MODEL, n_positions=SEQ, remat=True,
                          attention_backend="flash", dtype=jnp.bfloat16)
    model = GPT2LMHeadModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": MB,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10**9,
    })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (MB, SEQ)).astype(np.int32)
    batch = {"input_ids": ids}
    engine.initialize_state(batch)
    params = engine.state.params
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"# breakdown {MODEL} params={n_params / 1e6:.1f}M mb={MB} seq={SEQ}",
          flush=True)
    key = jax.random.PRNGKey(0)

    def loss_fn(p, ids_dev):
        logits = model.apply({"params": p}, ids_dev, deterministic=True)
        tgt = ids_dev[:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1))

    ids_dev = jnp.asarray(ids)

    # 1) forward only — chain: perturb ids by loss-derived int so each
    # dispatch differs and depends on the previous result (params passed
    # explicitly so jit doesn't bake them in as program constants)
    @jax.jit
    def fwd(p, carry):
        ids_c, acc = carry
        l = loss_fn(p, ids_c)
        shift = (l * 0).astype(jnp.int32)  # data dep, value-neutral
        return (ids_c + shift, acc + l)

    timed("fwd", lambda c: fwd(params, c), (ids_dev, jnp.float32(0)))

    # 2) fwd + bwd (grads reduced to a scalar to keep transfer off the timing)
    @jax.jit
    def fwdbwd(p, carry):
        ids_c, acc = carry
        l, g = jax.value_and_grad(loss_fn)(p, ids_c)
        gsum = sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(g))
        shift = (gsum * 0).astype(jnp.int32)
        return (ids_c + shift, acc + l)

    timed("fwd_bwd", lambda c: fwdbwd(params, c), (ids_dev, jnp.float32(0)))

    # 3) full engine step (state donation chains deps naturally)
    def full(carry):
        engine.train_batch(batch)
        return engine.state.params

    timed("engine_step", full, None)
    print("# DONE", flush=True)


if __name__ == "__main__":
    main()
