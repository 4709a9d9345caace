"""Shared measurement core for the TPU perf tools (perf_ladder, perf_breakdown):
one engine-building + fused-scan-timing + TFLOPS-reporting methodology so
the tools' numbers stay comparable. Timings run inside one scanned
program, the production loop shape."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

BASELINE_TFLOPS = 64.0  # reference headline, BASELINE.md


def model_flops_per_token(n_params, n_layers=0, hidden=0, seq=0, causal=True):
    """Training FLOPs per token: the standard ``6*N`` parameter-matmul
    estimate PLUS the attention-score term ``6N`` omits (PaLM-appendix /
    scaling-book accounting). Per layer the score matmuls (QK^T and AV)
    cost ``4*s*hidden`` FLOPs/token forward, x3 for fwd+bwd =
    ``12*s*hidden``; causal masking halves it (the gridded flash kernel
    skips dead blocks, so the compute actually executed matches the causal
    count). At seq=8k on GPT-2-350M the attention term is ~57% of 6N —
    ignoring it understated the banked long-context MFU (r4 verdict #5)."""
    attn = 12.0 * n_layers * hidden * seq
    if causal:
        attn /= 2.0
    return 6.0 * n_params + attn


def active_params_from_cfg(n_params, cfg):
    """Parameters that compute per token. MoE models route each token
    through k of E experts, so the (E - k) unused expert FFNs per MoE
    layer contribute params but no FLOPs — deriving TFLOPS from total
    params would overstate MoE rungs by the sparsity factor (2.6x at
    125m-base x 8E). Covers the GPT-2 family (``n_layer``, dense 4x FFN)
    and the llama family (``num_hidden_layers`` + ``intermediate_size``,
    SwiGLU experts: gate/up/down = 3*hidden*intermediate params each)."""
    n_experts = (getattr(cfg, "moe_num_experts", 0) or 0) if cfg is not None else 0
    if not n_experts:
        return n_params
    if hasattr(cfg, "n_layer"):  # GPT-2 family
        n_layers, ffn_p = cfg.n_layer, 8 * cfg.n_embd * cfg.n_embd + 5 * cfg.n_embd
    elif hasattr(cfg, "num_hidden_layers") and hasattr(cfg, "intermediate_size"):
        # llama family (Mixtral-style MoE): per-expert SwiGLU has no biases
        n_layers = cfg.num_hidden_layers
        ffn_p = 3 * cfg.hidden_size * cfg.intermediate_size
    else:
        return n_params
    # MoE blocks sit at i % freq == freq-1 (models/gpt2.py + llama.py block
    # placement); freq <= 0 on user cfgs must not divide-by-zero
    freq = max(getattr(cfg, "moe_layer_freq", 1) or 1, 1)
    moe_layers = sum(1 for i in range(n_layers) if i % freq == freq - 1)
    return n_params - moe_layers * (n_experts - cfg.moe_k) * ffn_p


def flops_per_token_from_cfg(n_params, cfg, seq):
    """Pull (layers, hidden, causal) out of a GPT2Config, LlamaConfig or
    BertConfig; MoE counts active params only (``active_params_from_cfg``)."""
    if hasattr(cfg, "n_layer"):  # GPT-2 family: causal
        return model_flops_per_token(active_params_from_cfg(n_params, cfg),
                                     cfg.n_layer, cfg.n_embd, seq,
                                     causal=True)
    if hasattr(cfg, "num_hidden_layers"):
        # every decoder family (llama/opt/neox/gptj/falcon/...) is causal;
        # only the BERT encoder (the config with segment embeddings) is
        # bidirectional
        causal = not hasattr(cfg, "type_vocab_size")
        return model_flops_per_token(active_params_from_cfg(n_params, cfg),
                                     cfg.num_hidden_layers, cfg.hidden_size,
                                     seq, causal=causal)
    return model_flops_per_token(n_params)


from envutil import use_compile_cache as enable_compile_cache  # noqa: E402,F401


def build_engine(model_name, mb, seq, ds_overrides=None, pipe_stages=0,
                 **cfg_overrides):
    """Engine + batch at the bench methodology's defaults (bf16, flash
    attention, remat). ``model_name`` picks the family: ``bert_<preset>``
    builds a BERT MLM engine (the reference's 64-TFLOPS headline workload,
    BERT-large pretrain); anything else is a GPT-2 causal-LM preset.
    ``pipe_stages>0`` builds the GPT-2 preset as a PipelineModule on a
    pipe-only mesh (``mb`` is then the GLOBAL batch; pass
    ``gradient_accumulation_steps`` in ``ds_overrides`` for the
    microbatch count, ``pipeline.schedule`` for the tick schedule).
    Returns (engine, batch, n_params, cfg)."""
    import deepspeed_tpu

    ds = {
        "train_batch_size": mb,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10**9,
    }
    ds.update(ds_overrides or {})
    rng = np.random.default_rng(0)
    if model_name.startswith("bert_"):
        from deepspeed_tpu.models import BertForMaskedLM, bert_mlm_loss, get_bert_config

        cfg_overrides.setdefault("max_position_embeddings", max(seq, 512))
        cfg = get_bert_config(model_name.split("_", 1)[1], remat=True,
                              attention_backend="flash", dtype=jnp.bfloat16,
                              **cfg_overrides)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=BertForMaskedLM(cfg), config=ds, loss_fn=bert_mlm_loss)
        ids = rng.integers(0, cfg.vocab_size, (mb, seq)).astype(np.int32)
        labels = np.where(rng.random((mb, seq)) < 0.15, ids, -100).astype(np.int32)
        batch = {"input_ids": ids, "labels": labels}
    else:
        from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

        cfg = get_gpt2_config(model_name, n_positions=seq, remat=True,
                              attention_backend="flash", dtype=jnp.bfloat16,
                              **cfg_overrides)
        if pipe_stages:
            from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
            from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
            from deepspeed_tpu.runtime.pipe.module import PipelineModule

            set_topology(None)
            topo = MeshTopology(pipe=pipe_stages, data=1,
                                devices=jax.devices()[:pipe_stages])
            module = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
            engine, _, _, _ = deepspeed_tpu.initialize(model=module, config=ds,
                                                       topology=topo)
        else:
            engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), config=ds)
        batch = {"input_ids": rng.integers(0, cfg.vocab_size, (mb, seq)).astype(np.int32)}
    engine.initialize_state(batch)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(engine.state.params))
    return engine, batch, n_params, cfg


def time_fused(engine, batch, fused=10, timed_dispatches=2):
    """Compile+warm one fused-scan program, then time ``timed_dispatches``
    back-to-back dispatches. Returns (n_steps, seconds, compile_seconds).
    Heartbeats (DSElasticAgent supervision) fire inside train_batches'
    _post_step after every dispatch completes."""
    from deepspeed_tpu.elasticity import touch_heartbeat
    t_start = time.time()
    touch_heartbeat()
    stack = jax.tree.map(lambda x: np.broadcast_to(x, (fused,) + np.shape(x)), batch)
    engine.train_batches(stack)
    jax.block_until_ready(engine.state.params)
    compile_s = time.time() - t_start
    t0 = time.time()
    for _ in range(timed_dispatches):
        engine.train_batches(stack)
    jax.block_until_ready(engine.state.params)
    return fused * timed_dispatches, time.time() - t0, compile_s


def time_per_dispatch(engine, batch, steps):
    """Per-dispatch loop for host-driven schedules (offload, 1-bit phases)
    where the scan path is unavailable."""
    engine.train_batch(batch)
    jax.block_until_ready(engine.state.params)
    t0 = time.time()
    for _ in range(steps):
        engine.train_batch(batch)
    jax.block_until_ready(engine.state.params)
    return steps, time.time() - t0, None


def report(tag, mb, seq, n_params, n_steps, seconds, compile_s=None, cfg=None,
           **extra):
    tok = mb * seq * n_steps / seconds
    fpt = (flops_per_token_from_cfg(n_params, cfg, seq) if cfg is not None
           else model_flops_per_token(n_params))
    n_active = active_params_from_cfg(n_params, cfg)
    tflops = fpt * tok / 1e12
    line = {"tag": tag, "params_m": round(n_params / 1e6, 1), "mb": mb,
            "step_ms": round(seconds / n_steps * 1e3, 1),
            "tokens_per_s": round(tok, 1), "tflops": round(tflops, 2),
            "vs_baseline": round(tflops / BASELINE_TFLOPS, 3),
            "attn_flops_frac": round(1.0 - 6.0 * n_active / fpt, 3)}
    if n_active != n_params:
        line["params_active_m"] = round(n_active / 1e6, 1)
    if compile_s is not None:
        line["compile_s"] = round(compile_s, 1)
    line.update(extra)
    print(json.dumps(line), flush=True)
    return tflops
