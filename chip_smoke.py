"""Quickest proof that the system starts on the chip.

    python chip_smoke.py            one chip: train, serve, kernels
    python chip_smoke.py --chips 4  four chips: the ZeRO-3 step against a
                                    one-device run of the same steps

Drives GPT-2 350M (1024 wide, 24 layers, 16 heads, 1024 positions; random
weights from a seed) through the entry points a user calls —
``deepspeed_tpu.initialize`` / ``engine.train_batch`` and
``deepspeed_tpu.init_inference`` / ``ContinuousBatchingScheduler`` — and
every Pallas kernel the package selects on a TPU against its XLA twin.

One process, which is the only one that touches JAX; it starts no child.
Any phase that raises ends the run non-zero. Without a TPU it exits
non-zero and prints no result. Per-phase lines are smoke observations,
not benchmark numbers. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import contextlib
import functools
import json
import sys
import time

import numpy as np

SEED = 0

# Teacher-forced serve check: how far below the reference forward's
# maximum an emitted token's logit may lie. The scheduler attends an int8
# KV cache (per-(position, head) absmax codes: each K/V element is off by
# up to 1/254 of its row's largest) and, on a TPU, both sides round
# matmul operands to bf16 (8 mantissa bits) in a different order — chunked
# prefill + one-token decode against one full-sequence pass. With random
# 0.02-scale weights the logits spread over about +-3 and the top two sit
# ~0.05 apart, so the arg-max may flip but stays within a few hundredths;
# a token from a wrong position or slot lands ~2-3 below the maximum.
SERVE_LOGIT_TOL = 0.25

# Per-step loss agreement of the ZeRO-3 mesh with one device: the loss is
# ~10.8 and is reduced in fp32 from bf16 activations (8 mantissa bits,
# 2^-8 = 0.4% per rounding); sharding changes the order of the gradient
# and norm reductions, so later steps see parameters that differ in their
# last bf16 bit.
ZERO3_LOSS_TOL = 0.05


def _emit(phase, **obs):
    print(json.dumps({"phase": phase, **obs}), flush=True)


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def _compiles():
    """Collects (program name, seconds) of every backend compilation in
    the block, cache hits included."""
    import jax.monitoring

    seen = []

    def listener(event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append((fun_name, duration))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def _no_recompiles(warm, steady, what):
    """After warm-up nothing compiled during it may compile again, and
    nothing new beyond one-primitive host arithmetic (milliseconds)."""
    warmed = {name for name, _ in warm}
    bad = [(name, round(secs, 3)) for name, secs in steady
           if name in warmed or secs >= 0.5]
    if bad:
        raise AssertionError(f"compilation after warm-up while {what}: {bad}")


def _kernels_compiled():
    from deepspeed_tpu.ops.pallas import backend
    return not backend.interpret_default()


def _train_setup(preset, seq, zero_stage, batch_size):
    """Model, engine config and seeded batch of the training phases."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    overrides = {"vocab_size": 50304} if preset != "test" else {}
    cfg = get_gpt2_config(preset, n_positions=seq, remat=True,
                          attention_backend="flash", dtype=jnp.bfloat16,
                          embed_onehot_grad=True,
                          fused_head_loss_chunk=min(1024, batch_size * seq),
                          **overrides)
    ds_config = {
        "train_batch_size": batch_size,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10**9,
        "seed": SEED,
    }
    rng = np.random.default_rng(SEED)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size,
                                       (batch_size, seq)).astype(np.int32)}
    return GPT2LMHeadModel(cfg), ds_config, batch


def _check_losses(losses):
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a repeated batch: {losses}")


def _topology(devices):
    """A pure data-parallel mesh over ``devices``; None = the engines'
    default, every device JAX reports (the one chip of the default run)."""
    if devices is None:
        return None
    from deepspeed_tpu.parallel.topology import MeshTopology
    return MeshTopology(devices=list(devices))


# --------------------------------------------------------------------------
# phase: train
# --------------------------------------------------------------------------
def train_phase(preset="350m", seq=1024, batch_size=8, steps=4, scan_steps=4,
                devices=None):
    import jax
    import deepspeed_tpu

    model, ds_config, batch = _train_setup(preset, seq, 0, batch_size)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config,
                                               topology=_topology(devices))

    if _kernels_compiled():
        text = engine.lower_train_step(batch).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("the lowered train step holds no tpu_custom_call: "
                                 "flash attention fell back to XLA")

    with _compiles() as warm:
        t0 = time.time()
        losses = [float(engine.train_batch(batch))]      # warm-up: compiles
        compile_s = time.time() - t0
    with _compiles() as steady:
        t0 = time.time()
        for _ in range(steps - 1):
            losses.append(float(engine.train_batch(batch)))
        step_ms = (time.time() - t0) / (steps - 1) * 1e3
    _no_recompiles(warm, steady, "training")
    _check_losses(losses)

    stack = {"input_ids": np.broadcast_to(batch["input_ids"],
                                          (scan_steps,) + batch["input_ids"].shape)}
    t0 = time.time()
    scanned = np.asarray(engine.train_batches(stack), np.float32)  # compiles the scan
    scan_compile_s = time.time() - t0
    t0 = time.time()
    scanned = np.concatenate([scanned, np.asarray(engine.train_batches(stack), np.float32)])
    scan_step_ms = (time.time() - t0) / scan_steps * 1e3
    _check_losses(np.concatenate([losses, scanned]))

    obs = dict(model=preset, tokens_per_step=batch_size * seq,
               losses=[round(l, 4) for l in losses],
               scanned_losses=[round(float(l), 4) for l in scanned],
               cold_compile_s=round(compile_s, 1), step_ms=round(step_ms, 1),
               scan_cold_compile_s=round(scan_compile_s, 1),
               scan_step_ms=round(scan_step_ms, 1),
               peak_bytes=_peak_bytes(jax.devices()[0]))
    _emit("train", **obs)
    return obs


# --------------------------------------------------------------------------
# phase: serve
# --------------------------------------------------------------------------
def _reference_rows(engine, req):
    """``[new, V]`` fp32: the plain full-sequence forward's logits at the
    positions that emitted ``req``'s output tokens, teacher-forced."""
    import jax.numpy as jnp
    ids = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])[None, :-1]
    logits = np.asarray(engine(jnp.asarray(ids)), np.float32)[0]
    if not np.all(np.isfinite(logits)):
        raise AssertionError("non-finite reference logits")
    return logits[len(req.prompt) - 1:]


def _decode_rung_check(engine, vocab, prompt, new, chunk, slots=32, n_requests=3):
    """Decode ticks on the quarter rung against the whole program, at 32
    slots (``programs.decode_rungs``: 8 and 32): the same requests through a
    scheduler that takes the rung its fed slots fit and through one held to
    the whole program emit the same tokens. Where the two part, it must be a
    near tie of the reference forward at that position (the two programs
    round a row's matmuls in tiles of their own): both tokens within
    ``SERVE_LOGIT_TOL`` of its maximum; behind it they are fed different
    tokens and compare no further."""
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 Request, ServingConfig)
    from deepspeed_tpu.utils import trace

    counters, runs = trace.recorder().counters, {}
    for ladder in ("rungs", "whole"):
        sched = ContinuousBatchingScheduler(engine, ServingConfig(
            slots=slots, page_size=16, kv_quant=True, prefill_chunk=chunk))
        if ladder == "whole":
            sched._decode_rungs = (sched.slots,)
        rng = np.random.default_rng(SEED + 1)
        reqs = [Request(prompt=rng.integers(0, vocab, (prompt,)).astype(np.int32),
                        max_new_tokens=new) for _ in range(n_requests)]
        before = counters.get(f"decode_ticks_rung_{sched._decode_rungs[0]}", 0)
        for r in reqs:
            sched.submit(r)
        sched.run_until_drained()
        ticks = counters[f"decode_ticks_rung_{sched._decode_rungs[0]}"] - before
        if ticks < new - 1 or any(len(r.output) != new for r in reqs):
            raise AssertionError(f"the {ladder} run took {ticks} decode ticks on its first rung "
                                 f"of {sched._decode_rungs} for {new} tokens a request")
        runs[ladder] = (sched._decode_rungs, reqs)
    (ladder, reqs), (_, reqs_whole) = runs["rungs"], runs["whole"]
    if len(ladder) < 2:
        raise AssertionError(f"no decode rung below the whole at {slots} slots: {ladder}")
    parted, worst = 0, 0.0
    for got, want in zip(reqs, reqs_whole):
        differ = np.flatnonzero(np.asarray(got.output) != np.asarray(want.output))
        if not differ.size:
            continue
        parted += 1
        j, at = int(differ[0]), _reference_rows(engine, got)
        gaps = [float(at[j].max() - at[j, r.output[j]]) for r in (got, want)]
        worst = max(worst, *gaps)
        if worst > SERVE_LOGIT_TOL:
            raise AssertionError(f"a decode rung's token {j} parts from the whole program's "
                                 f"with no tie behind it: {gaps} under the reference's maximum")
    return dict(ladder=list(ladder), requests=n_requests, parted_on_a_tie=parted,
                worst_gap_at_a_parting=round(worst, 4))


def serve_phase(preset="350m", prompt=128, new=64, n_requests=8, slots=8, chunk=16,
                devices=None):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import (ContinuousBatchingScheduler,
                                                 Request, ServingConfig)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    # a bf16 engine on the scheduler's own defaults
    n_positions = max(prompt + new + 1, 128)
    cfg = get_gpt2_config(preset, n_positions=n_positions, dtype=None)
    engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg),
                                          replace_with_kernel_inject=True,
                                          max_out_tokens=n_positions,
                                          topology=_topology(devices))
    sched = ContinuousBatchingScheduler(engine, ServingConfig(
        slots=slots, page_size=16, kv_quant=True,
        prefill_chunk=chunk))
    with _compiles() as warm:
        t0 = time.time()
        sched.warmup()
        compile_s = time.time() - t0

    rng = np.random.default_rng(SEED)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (prompt,)).astype(np.int32),
                    max_new_tokens=new) for _ in range(n_requests)]
    with _compiles() as steady:
        t0 = time.time()
        for i in range(0, n_requests, 2):      # arrivals spread over ticks
            for r in reqs[i:i + 2]:
                sched.submit(r)
            sched.step()
        sched.run_until_drained()
        wall = time.time() - t0
    _no_recompiles(warm, steady, "serving")
    for r in reqs:
        if len(r.output) != new:
            raise AssertionError(f"request {r.request_id} finished with "
                                 f"{len(r.output)} tokens, wanted {new} ({r.state})")

    # teacher-forced: one plain full-sequence forward of the same weights
    # over prompt + emitted tokens; each emitted token must be (nearly) the
    # arg-max at its position
    worst = 0.0
    for r in reqs[:2]:
        at = _reference_rows(engine, r)
        gap = at.max(axis=-1) - at[np.arange(new), np.asarray(r.output)]
        worst = max(worst, float(gap.max()))
    if worst > SERVE_LOGIT_TOL:
        raise AssertionError(f"an emitted token lies {worst:.3f} below the reference "
                             f"forward's maximum (tolerance {SERVE_LOGIT_TOL})")
    rung = _decode_rung_check(engine, cfg.vocab_size, prompt, new, chunk)

    ticks = dict(sched.stats()["ticks"])
    obs = dict(model=preset, requests=n_requests, prompt=prompt, new=new,
               ticks=ticks, cold_compile_s=round(compile_s, 1),
               tick_ms=round(wall / max(sum(ticks.values()), 1) * 1e3, 2),
               worst_logit_gap=round(worst, 4), decode_rung=rung,
               peak_bytes=_peak_bytes(jax.devices()[0]))
    _emit("serve", **obs)
    return obs


# --------------------------------------------------------------------------
# phase: kernels — each Pallas kernel, compiled, against its XLA twin
# --------------------------------------------------------------------------
def _run_kernel(fn, *args):
    """Jit ``fn``, require the Mosaic custom call in its lowering when the
    package compiles its kernels (a TPU), and run it."""
    import jax
    lowered = jax.jit(fn).lower(*args)
    if _kernels_compiled() and "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("kernel lowered without a tpu_custom_call")
    return lowered.compile()(*args)


BF16_EPS = 2.0 ** -8


def _close(name, got, want, ulps):
    """Every element of ``got`` within ``ulps`` bf16 roundings of ``want``,
    measured against the tensor's largest magnitude: a blockwise kernel
    and a one-pass reference cancel differently, so an element near zero
    carries the rounding of the O(max) terms that made it. A wrong mask,
    index or scale is off by O(1) of that magnitude, not by 2^-8 of it.
    ``ulps=0`` demands bit equality. Returns the worst error seen, in
    those roundings."""
    import jax
    used = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if g.shape != w.shape or not np.all(np.isfinite(g)):
            raise AssertionError(f"{name}: shape {g.shape} vs {w.shape} or non-finite output")
        worst, unit = np.abs(g - w).max(), BF16_EPS * max(np.abs(w).max(), 1.0)
        if worst > ulps * unit:
            raise AssertionError(f"{name}: off by {worst:.4g}, allowed {ulps * unit:.4g}")
        used = max(used, float(worst / unit))
    return round(used, 3)


def _reference(fn, *args):
    """The XLA twin in fp32 at highest matmul precision: at the TPU's
    default precision XLA rounds operands to bf16 inside the softmax
    backward, and is itself the less exact side of the comparison."""
    import jax
    import jax.numpy as jnp
    wide = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating) else a
            for a in args]
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*wide)


def kernels_phase(batch=4, seq=1024, heads=16, head_dim=64, width=1024):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, flash_decode
    from deepspeed_tpu.ops.pallas.moe_dispatch import inverse_index, permute_rows
    from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul
    from deepspeed_tpu.ops.quantizer.weights import quantize_leaf
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import sparse_attention
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import FixedSparsityConfig
    from deepspeed_tpu.ops.transformer.attention import xla_attention

    rng = np.random.default_rng(SEED)
    bf16 = jnp.bfloat16

    def normal(*shape, dtype=bf16):
        return jnp.asarray(rng.normal(size=shape), dtype)

    worst = {}  # kernel -> worst error, in bf16 roundings of the tensor's max

    def check(name, got, want, ulps):
        worst[name] = _close(name, got, want, ulps)

    # flash attention, forward and backward
    q, k, v = (normal(batch, seq, heads, head_dim) for _ in range(3))

    def sq_loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32) ** 2).sum()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def xla(q, k, v):
        return xla_attention(q, k, v, causal=True)

    grads = functools.partial(jax.grad, argnums=(0, 1, 2))
    check("flash_fwd", _run_kernel(flash, q, k, v), _reference(xla, q, k, v), ulps=4)
    check("flash_bwd", _run_kernel(grads(sq_loss(flash)), q, k, v),
           _reference(grads(sq_loss(xla)), q, k, v), ulps=8)

    # flash_decode: one new token per slot against a ragged cache
    slots = 8
    qd = normal(slots, 1, heads, head_dim)
    kc, vc = (normal(slots, seq, heads, head_dim) for _ in range(2))
    lengths = jnp.asarray(rng.integers(1, seq + 1, (slots,)), jnp.int32)
    check("flash_decode",
           _run_kernel(lambda q, k, v, n: flash_decode(q, k, v, n), qd, kc, vc, lengths),
           _reference(lambda q, k, v, n: xla_attention(q, k, v, causal=False, decode_lengths=n),
                      qd, kc, vc, lengths), ulps=4)

    # MoE permute, forward and backward: a pure row copy, so exact
    tokens, rows = batch * seq, batch * seq * 5 // 4
    x = normal(1, tokens, width)
    perm = rng.permutation(rows)[None, :].astype(np.int32)      # slot -> token or drop
    fwd_idx = jnp.asarray(np.where(perm < tokens, perm, tokens))
    bwd_idx = inverse_index(fwd_idx, tokens)
    cot = normal(1, rows, width)

    def permute(impl):
        return lambda x: permute_rows(x, fwd_idx, bwd_idx, impl=impl)

    def permute_loss(impl):
        return lambda x: (permute(impl)(x).astype(jnp.float32) * cot.astype(jnp.float32)).sum()

    check("moe_permute_fwd", _run_kernel(permute("pallas"), x),
           jax.jit(permute("xla"))(x), ulps=0)
    check("moe_permute_bwd", _run_kernel(jax.grad(permute_loss("pallas")), x),
           jax.jit(jax.grad(permute_loss("xla")))(x), ulps=0)

    # quant_matmul: decode-sized activations against the MLP-in projection.
    # Same codes and scales on both sides, both feed the MXU bf16 operands;
    # only the fp32 accumulation order and the output's rounding differ.
    xq = normal(slots, width)
    w = jnp.asarray(rng.normal(size=(width, 4 * width)) * 0.02, jnp.float32)
    for bits in (8, 4):
        codes, scale = quantize_leaf(w, bits, 64)

        def qmm(impl):
            return lambda x, c, s: quant_matmul(x, c, s, bits=bits, impl=impl)

        check(f"quant_matmul_int{bits}", _run_kernel(qmm("pallas"), xq, codes, scale),
               jax.jit(qmm("xla"))(xq, codes, scale), ulps=4)
    
    # the serving cache's per-slot write, int8 codes and bf16 scales, one
    # token, a chunk, and a block that straddles a window: in place (what a
    # TPU runs: ``ops/pallas/pool_write.py``'s kernel at these shapes) against
    # the scatter. A pure move of bytes into their lanes, so exact; one slot
    # is parked
    from deepspeed_tpu.models.common import _append_in_place
    pool = jnp.asarray(rng.integers(-127, 128, (slots, heads, head_dim, seq)), jnp.int8)
    scales = normal(slots, heads, seq)
    at = jnp.asarray([126, seq, 0, seq - 2] + [16 * i for i in range(4, slots)], jnp.int32)
    for length in (1, 16, 5):
        new = jnp.asarray(rng.integers(-127, 128, (slots, length, heads, head_dim)), jnp.int8)
        new_scales = normal(slots, length, heads)
        put = jnp.arange(slots)[:, None], ..., at[:, None] + jnp.arange(length)[None, :]
        check(f"kv_append_{length}",
              _run_kernel(lambda *a: _append_in_place(a[:2], a[2:4], a[4]), pool, scales, new, new_scales, at),
              [pool.at[put].set(new), scales.at[put].set(new_scales)], ulps=0)

    # block-sparse attention against dense attention under the layout's mask
    block = 64
    layout = FixedSparsityConfig(num_heads=heads, block=block, num_local_blocks=4,
                                 num_global_blocks=1,
                                 attention="unidirectional").make_layout(seq)
    mask = jnp.asarray(np.kron(layout, np.ones((block, block))).astype(bool))[None]
    qs, ks, vs = q[:2], k[:2], v[:2]

    def sparse(q, k, v):
        return sparse_attention(q, k, v, layout, block, causal=True)

    def dense(q, k, v):
        return xla_attention(q, k, v, causal=True, mask=mask)

    check("sparse_fwd", _run_kernel(sparse, qs, ks, vs), _reference(dense, qs, ks, vs), ulps=4)
    check("sparse_bwd", _run_kernel(grads(sq_loss(sparse)), qs, ks, vs),
           _reference(grads(sq_loss(dense)), qs, ks, vs), ulps=8)

    # an indexed layer's selection: the k largest of each row's scores below its
    # bound, ties to the lower position, over the blocks that hold scores: the
    # kernel's mask against the bisection's and the ranking's, entry for entry
    from deepspeed_tpu.models.deepseek_v3 import chosen_of, kth_largest
    from deepspeed_tpu.ops.pallas.sparse_select import select_top_k
    rows, extent, written, top = 32, 8 * seq, 3 * seq, seq // 4
    scores = jnp.round(normal(rows, extent, dtype=jnp.float32) * 64) / 64    # ties at the bar
    bound = jnp.asarray(rng.integers(1, extent, (rows,)), jnp.int32)
    valid = jnp.arange(extent)[None, :] < jnp.minimum(bound, written)[:, None]
    got = _run_kernel(lambda s, b: select_top_k(s, b, jnp.full((1,), 3), top, block=seq),
                      scores, bound)
    want, _ = jax.jit(lambda s, v: chosen_of(*kth_largest(s, v, top)))(scores, valid)
    check("dsa_select", jnp.where(jnp.arange(extent) < written, got, 0.0),
          want.astype(jnp.float32), ulps=0)

    # a plain latent layer's chunk: the walk that keeps a step's scores in VMEM,
    # its mask read off the positions, against XLA's loops; at the defaults the
    # long-document cell's shapes (32 heads of 128 + 64, a chunk of 512 over
    # pools of 16,384). A slot at 0 (a row of one key: values of O(1)), a chunk
    # that starts inside a block, a padded one, a slot fed nothing
    from deepspeed_tpu.models.deepseek_v3 import expanded_walk, kernel_walk
    chunk, extent, rank, dn, dr = seq // 2, 16 * seq, width // 2, 2 * head_dim, head_dim
    pool = normal(4, rank + dr, extent)
    q_nope, q_rope = normal(4, chunk, 2 * heads, dn), normal(4, chunk, 2 * heads, dr)
    w_kvb = (normal(rank, 2 * heads, 2 * dn, dtype=jnp.float32) * rank ** -0.5).astype(bf16)
    start = jnp.asarray([0, 9 * seq - chunk // 4, extent, extent - chunk], jnp.int32)
    fed = jnp.asarray([chunk, chunk, 0, chunk - 5], jnp.int32)
    got = _run_kernel(kernel_walk, q_nope, q_rope, pool, w_kvb, start, fed)
    want = _reference(lambda *a: expanded_walk(*a, seq // 2), q_nope, q_rope, pool, w_kvb, start,
                      fed)
    real = jnp.arange(chunk)[None, :, None, None] < fed[:, None, None, None]
    check("mla_prefill_walk", jnp.where(real, got, 0), jnp.where(real, want, 0), ulps=2)

    # a decode tick's read of a stored int8 pool: the kernel that reads each slot
    # as far as that slot goes against XLA's loop over every slot together; at
    # the defaults the mixed-lengths cell's full layers (32 slots of 16,384
    # positions, 48 query heads over 8 key heads of 128, blocks of 1,024). A
    # parked slot, one key, a block's edge and one past it, the whole pool
    from unittest import mock
    from deepspeed_tpu.models.common import cached_attention, decode_key_block
    from deepspeed_tpu.ops.pallas import backend
    from deepspeed_tpu.ops.pallas.pool_decode import pool_decode
    def int8_pools(slots, kv, d, extent):
        """``(keys, key scales, values, value scales)`` as a serving cache stores them."""
        return tuple(jnp.asarray(rng.integers(-127, 128, (slots, kv, d, extent)), jnp.int8)
                     if codes else jnp.asarray(rng.uniform(0.01, 0.02, (slots, kv, extent)), bf16)
                     for codes in (True, False, True, False))

    slots, kv, d, extent = 2 * heads, heads // 2, 2 * head_dim, 16 * seq
    pools = int8_pools(slots, kv, d, extent)
    held = np.minimum(np.exp(rng.normal(np.log(2 * seq), 1.0, slots)).astype(np.int64) + 1, extent)
    held[:5] = 0, 1, seq, seq + 1, extent
    fed, at = jnp.asarray(held > 0, jnp.int32), jnp.asarray(np.maximum(held - 1, 0), jnp.int32)
    qd = normal(slots, 1, 6 * kv, d)
    got, read = _run_kernel(
        lambda q, *a: pool_decode(q[:, 0], *a, window=extent, block=seq),
        qd, *pools, at, fed)
    with mock.patch.object(backend, "on_tpu", lambda: False):       # the loop, on any device
        want, _ = _reference(lambda *a: cached_attention(*a, window=extent, block=seq),
                             qd, *pools, at[:, None], fed)
    assert int(read) == int((-(-held // seq)).sum()) * seq, (int(read), held)
    check("pool_decode", got, want[:, 0], ulps=4)

    # the same read with a query head a key head, every head scored in one
    # matmul (the kernel's other body): at the defaults the chat cell's decode
    # rung, 8 of GPT-2 medium's 32 slots out of order, 16 heads of 64 over
    # 1,024 positions, one of the eight parked at the sentinel
    slots, kv, d, extent = 2 * heads, heads, head_dim, seq
    pools = int8_pools(slots, kv, d, extent)
    rung = max(slots // 4, 4)
    rows = jnp.asarray(rng.permutation(slots)[:rung], jnp.int32)
    held = rng.integers(1, extent + 1, rung)
    held[:3] = 0, 1, extent
    fed = jnp.asarray(held > 0, jnp.int32)
    at = jnp.asarray(np.where(held > 0, held - 1, extent), jnp.int32)
    qd, block = normal(rung, 1, kv, d), decode_key_block(kv, d, extent)
    got, read = _run_kernel(
        lambda q, *a: pool_decode(q[:, 0], *a[:-1], window=extent, block=block, rows=a[-1]),
        qd, *pools, at, fed, rows)
    with mock.patch.object(backend, "on_tpu", lambda: False):
        want, _ = _reference(
            lambda *a: cached_attention(*a[:-1], window=extent, block=block, rows=a[-1]),
            qd, *pools, at[:, None], fed, rows)
    assert int(read) == int((-(-held // block)).sum()) * block, (int(read), held)
    check("pool_decode_rung", got, want[:, 0], ulps=4)

    obs = dict(compiled=_kernels_compiled(), worst_bf16_roundings=worst)
    _emit("kernels", **obs)
    return obs


# --------------------------------------------------------------------------
# four chips: ZeRO-3 over fsdp=4 against one device
# --------------------------------------------------------------------------
def _state_bytes(engine):
    """Bytes of parameters + optimizer state resident on each device,
    split into leaves the ZeRO planner shards and leaves it replicates."""
    import jax
    sharded, replicated = {}, {}
    for leaf in jax.tree.leaves((engine.state.params, engine.state.opt_state)):
        into = replicated if leaf.sharding.is_fully_replicated else sharded
        for shard in leaf.addressable_shards:
            into[shard.device.id] = into.get(shard.device.id, 0) + shard.data.nbytes
    return sharded, replicated


def _three_steps(model, ds_config, batch, topology):
    import jax
    import deepspeed_tpu

    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config,
                                               topology=topology)
    if topology.mesh.size > 1:
        text = engine.lower_train_step(batch).compile().as_text()
        # XLA:CPU (the rehearsal) keeps all-reduce + slice where the TPU
        # compiler forms the reduce-scatter
        on_tpu = topology.devices[0].platform == "tpu"
        wanted = ("all-gather", "reduce-scatter" if on_tpu else "all-reduce")
        missing = [op for op in wanted if op not in text]
        if missing:
            raise AssertionError(f"the compiled ZeRO-3 step shows no {missing}")
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    jax.block_until_ready(engine.state.params)
    return losses, _state_bytes(engine)


def zero3_phase(preset="350m", seq=1024, batch_size=8, devices=None, max_share=0.30):
    import jax
    from deepspeed_tpu.parallel.topology import MeshTopology

    devices = list(devices if devices is not None else jax.devices()[:4])
    if len(devices) != 4:
        raise AssertionError(f"the four-chip phase needs 4 devices, found {len(devices)}")

    model, cfg1, batch = _train_setup(preset, seq, 0, batch_size)
    one_losses, (one_sharded, one_repl) = _three_steps(
        model, cfg1, batch, _topology(devices[:1]))
    one_total = sum(one_sharded.values()) + sum(one_repl.values())

    model, cfg4, _ = _train_setup(preset, seq, 3, batch_size)
    t0 = time.time()
    four_losses, (sharded, repl) = _three_steps(
        model, cfg4, batch, MeshTopology(fsdp=4, data=1, devices=devices))
    wall = time.time() - t0

    _check_losses(one_losses)
    _check_losses(four_losses)
    diffs = [abs(a - b) for a, b in zip(one_losses, four_losses)]
    if max(diffs) > ZERO3_LOSS_TOL:
        raise AssertionError(f"ZeRO-3 losses {four_losses} differ from one device's "
                             f"{one_losses} by more than {ZERO3_LOSS_TOL}")

    ids = [d.id for d in devices]
    per_device = [sharded.get(i, 0) for i in ids]
    if min(per_device) == 0 or len(set(per_device)) != 1:
        raise AssertionError(f"sharded state is not equal across devices: {per_device}")
    repl_per_device = [repl.get(i, 0) for i in ids]
    if len(set(repl_per_device)) != 1:
        raise AssertionError(f"replicated state differs across devices: {repl_per_device}")
    # everything the planner shards splits exactly four ways; what it
    # replicates by its own rule (small leaves) is the only excess
    if per_device[0] * 4 + repl_per_device[0] != one_total:
        raise AssertionError(f"4 x {per_device[0]} sharded + {repl_per_device[0]} "
                             f"replicated bytes != one device's {one_total}")
    share = (per_device[0] + repl_per_device[0]) / one_total
    if share > max_share:
        raise AssertionError(f"each device holds {share:.3f} of the one-device state")

    obs = dict(model=preset, one_device_losses=[round(l, 4) for l in one_losses],
               zero3_losses=[round(l, 4) for l in four_losses],
               max_loss_diff=round(max(diffs), 5), one_device_state_bytes=one_total,
               sharded_bytes_per_device=per_device,
               replicated_bytes_per_device=repl_per_device[0],
               state_share_per_device=round(share, 4), zero3_wall_s=round(wall, 1),
               peak_bytes=[_peak_bytes(d) for d in devices])
    _emit("zero3", **obs)
    return obs


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the ZeRO-3 step and its one-device comparison")
    args = parser.parse_args(argv)

    import jax
    from envutil import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports {len(devices)} device(s)",
              file=sys.stderr)
        return 1
    _emit("start", compile_cache=use_compile_cache(), chips=args.chips)

    if args.chips == 4:
        zero3_phase()
    else:
        train_phase()
        serve_phase()
        kernels_phase()
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
