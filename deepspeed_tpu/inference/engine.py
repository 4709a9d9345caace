"""InferenceEngine: jitted serving with TP sharding and a KV-cache decode
loop (reference ``deepspeed/inference/engine.py:89`` ``InferenceEngine``).

TPU-native redesign of the reference's serving path:

* MP/TP group creation (``engine.py:259``) → a ``tensor`` mesh axis; weights
  are placed by logical-axis rules or AutoTP (``module_inject`` here).
* Kernel injection (``engine.py:413`` → fused CUDA decode ops,
  ``pt_binding.cpp:1935-1975``) → the model's fused decode path (static KV
  cache + masked attention) compiled by XLA, optionally with the Pallas
  flash kernel for prefill.
* CUDA-graph capture/replay (``engine.py:532,551``) → ``jax.jit``: the
  decode step is one compiled program reused every token.
* ``generate`` runs prefill + a ``lax.while_loop`` token loop entirely on
  device, with greedy/temperature/top-k/top-p sampling and EOS early exit.
"""

from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import flax.linen as nn

from deepspeed_tpu import comm as dist
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.models.common import init_cache
from deepspeed_tpu.module_inject.replace_module import replace_transformer_layer, tp_shard_params
from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
from deepspeed_tpu.utils import trace
from deepspeed_tpu.utils.logging import log_dist


def _load_checkpoint_params(spec, base_dir: str = ""):
    """Load serving weights named by ``config.checkpoint`` (reference
    ``InferenceEngine`` checkpoint loading, ``inference/engine.py:336``).

    Accepts a consolidated ``.npz`` (``save_16bit_model`` /
    ``zero_to_fp32`` output), an engine ``save_checkpoint`` directory
    (``latest``/tag orbax checkpoint — consolidated on the fly), or a dict
    ``{"checkpoint_dir"|"path": ..., "tag": ...}``.
    """
    import os

    from deepspeed_tpu.checkpoint.zero_to_fp32 import (
        WEIGHTS_NAME, get_fp32_state_dict_from_zero_checkpoint, load_state_dict_from_npz)

    tag, original = None, spec
    if isinstance(spec, dict):
        tag = spec.get("tag")
        spec = spec.get("checkpoint_dir") or spec.get("path")
    if not isinstance(spec, str) or not spec:
        raise ValueError(f"unsupported checkpoint spec {original!r}: pass a .npz path, an "
                         f"engine checkpoint dir, or {{'checkpoint_dir': ..., 'tag': ...}}")
    path = os.path.join(base_dir, spec) if base_dir else spec
    if path.endswith(".npz"):
        if not os.path.isfile(path):
            raise ValueError(f"checkpoint npz {path!r} does not exist")
        params = load_state_dict_from_npz(path)
    elif os.path.isdir(path) and (tag is not None or os.path.exists(os.path.join(path, "latest"))):
        params = get_fp32_state_dict_from_zero_checkpoint(path, tag=tag)
    elif os.path.isdir(path) and os.path.isfile(os.path.join(path, WEIGHTS_NAME)):
        params = load_state_dict_from_npz(path)
    else:
        raise ValueError(f"checkpoint path {path!r} is neither a .npz file nor a "
                         f"checkpoint directory (no 'latest', no {WEIGHTS_NAME})")
    log_dist(f"inference weights loaded from {path}")
    return params


def _unwrap_logits(out):
    """MoE models return (logits, aux_loss); serving wants the logits."""
    if isinstance(out, (tuple, list)):
        return out[0]
    return out


def sample_logits(logits, rng, do_sample: bool, temperature: float, top_k: int, top_p: float):
    """Next-token selection on [B, V] logits (greedy or filtered sampling)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        k = min(int(top_k), logits.shape[-1])  # clamp to vocab
        kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; find threshold logit.
        # Pinned edge cases (graft-serve satellite): an EMPTY nucleus —
        # top_p <= 0, or a low temperature concentrating cum[0] ~ 1.0 above
        # top_p — keeps cutoff_idx at 0, i.e. falls back to the single
        # argmax token (never a NaN renormalization over an empty support);
        # the clip handles the opposite edge, where rounding keeps cum
        # strictly below top_p forever and the unclipped index would walk
        # off the vocab axis.
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff_idx = jnp.minimum(cutoff_idx, logits.shape[-1] - 1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1)


class InferenceEngine:
    """Serving wrapper. ``engine(input_ids)`` → logits;
    ``engine.generate(input_ids, ...)`` → generated token ids."""

    def __init__(self,
                 model: nn.Module,
                 config: DeepSpeedInferenceConfig,
                 params: Optional[Any] = None,
                 topology: Optional[MeshTopology] = None,
                 seed: int = 0):
        if not dist.is_initialized():
            dist.init_distributed(verbose=False)
        self.config = config

        # -- mesh: tensor axis from tp_size, rest data (engine.py:259)
        if topology is None:
            tp = max(1, config.tensor_parallel.tp_size)
            n = jax.device_count()
            if n % tp != 0:
                raise ValueError(f"tp_size {tp} must divide device count {n}")
            topology = MeshTopology(tensor=tp, data=n // tp, fsdp=1)
        self.topology = topology
        self.mesh = topology.mesh
        set_topology(topology)

        # -- injection policy (engine.py:413)
        span = trace.recorder().span     # children of ``init_inference``'s span
        with span("kernel_inject"):
            self.module = replace_transformer_layer(model, config)
        self.mcfg = getattr(self.module, "config", None)

        self._rng = jax.random.PRNGKey(seed)
        example = jnp.zeros((1, 8), jnp.int32)
        from deepspeed_tpu.models.common import is_seq2seq_module
        self._is_seq2seq = is_seq2seq_module(self.module)
        example_extra = {"decoder_input_ids": example} if self._is_seq2seq else {}

        with span("load_weights"):
            if params is None and config.checkpoint is not None:
                params = _load_checkpoint_params(config.checkpoint, config.base_dir)
            if params is None:
                params = self.module.init(self._rng, example, **example_extra)["params"]
        # callers may hand in boxed trees straight from model.init(); the
        # TP spec derivation below needs raw arrays (boxed leaves have no
        # .shape, so every spec would silently fall back to replicated)
        params = nn.meta.unbox(params)
        # int8 dtype means QUANTIZED weights (reference dtype=torch.int8):
        # floats are cast to the serve dtype here and quantized after TP
        # sharding below — a raw astype(int8) would destroy the weights
        quant_on = bool(config.quant.enabled) or config.dtype == jnp.int8
        cast_dtype = (jnp.bfloat16 if config.dtype == jnp.int8 else config.dtype)
        with span("place_weights"):
            if cast_dtype is not None:
                params = jax.tree.map(
                    lambda p: p.astype(cast_dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    params)
            # -- TP weight placement (ReplaceWithTensorSlicing / AutoTP)
            self.params, self.param_specs = tp_shard_params(
                params, self.module, topology, example, policy=config.injection_policy)

        # -- int8 weight quantization (reference WeightQuantization applied
        # at checkpoint load; here on the already-sharded tree, engine.py:299)
        self._wq_scales = None
        self._serve_dtype = cast_dtype or jnp.float32
        if quant_on:
            from deepspeed_tpu.runtime.weight_quantizer import WeightQuantization
            # mp_size=1: JAX sharded arrays keep their GLOBAL shape, so the
            # reference's local-shard ratio recovery must not re-multiply
            wq = WeightQuantization(mp_size=1)
            with span("quantize_weights"):
                self.params, self._wq_scales = wq.model_quantize(
                    self.params, quantize_bits=config.quant.bits,
                    group_size=max(1, config.quant.group_size))

        self._forward_fn = None
        self._prefill_fn = None
        self._decode_fn = None
        self._max_len = self._model_max_len()
        log_dist(f"InferenceEngine: tp={topology.tensor_parallel_size} "
                 f"dtype={getattr(config.dtype, '__name__', 'model-default')} max_len={self._max_len}")

    # ------------------------------------------------------------------
    def _model_max_len(self):
        for attr in ("max_position_embeddings", "n_positions"):
            v = getattr(self.mcfg, attr, None)
            if v is not None:
                return int(v)
        return self.config.max_tokens

    def _place_batch(self, ids):
        """Shard the batch over the data axes when it divides evenly —
        otherwise serve replicated (small/odd batches)."""
        dp = self.topology.data_parallel_size
        if dp > 1 and ids.shape[0] % dp == 0:
            return jax.device_put(ids, NamedSharding(self.mesh, P(("expert", "data", "fsdp"))))  # graft-lint: waive R008 inference batch, never donated
        return jax.device_put(ids, NamedSharding(self.mesh, P()))  # graft-lint: waive R008 inference batch, never donated

    def _mparams(self, params):
        """Runtime view of the weights: dequantizes int8 leaves in-graph
        (the HBM copy stays int8; XLA materializes the serve-dtype view
        per program, reference dequant-gemm kernels)."""
        if self._wq_scales is None:
            return params
        from deepspeed_tpu.runtime.weight_quantizer import dequantize_tree
        return dequantize_tree(params, self._wq_scales, self._serve_dtype)

    def _apply_decode(self, params, cache, ids):
        """One cached decode step; single source of the MoE logits unwrap."""
        logits, upd = self.module.apply({"params": self._mparams(params), "cache": cache},
                                        ids, decode=True, mutable=["cache"])
        return _unwrap_logits(logits), upd

    # ------------------------------------------------------------------
    def forward(self, input_ids, **kwargs):
        """Full-sequence logits (no cache) — reference ``engine.py:592``."""
        if self._forward_fn is None:
            def fwd(params, ids):
                return _unwrap_logits(self.module.apply({"params": self._mparams(params)}, ids))
            self._forward_fn = jax.jit(fwd)
        ids = self._place_batch(jnp.asarray(np.asarray(input_ids), jnp.int32))
        return self._forward_fn(self.params, ids)

    __call__ = forward

    # ------------------------------------------------------------------
    # serving programs — bucketed so varying requests reuse compilations
    # (the old design compiled one program per
    # (batch, prompt_len, max_new, sampling) tuple, inference/engine.py:189)
    # ------------------------------------------------------------------
    PREFILL_CHUNK = 16

    def _build_serving(self, batch: int, do_sample: bool, temperature: float,
                       top_k: int, top_p: float, eos_token_id: Optional[int], cap: int):
        """THREE programs serve every (prompt_len, max_new) combination:
        a fixed-chunk prefill, a 1-token prefill for the remainder, and one
        generation loop whose token budget is a TRACED argument. Prompts of
        any length run ceil(p/C) chunked calls + (p mod C) single calls; no
        per-shape recompiles (reference per-token kernels +
        ``inference_context.h`` workspace reuse achieve the same)."""
        eos = -1 if eos_token_id is None else int(eos_token_id)

        apply_decode = self._apply_decode

        def prefill(params, cache, ids):
            logits, upd = apply_decode(params, cache, ids)
            return upd["cache"], logits[:, -1]

        def gen_loop(params, cache, last_logits, rng, max_new):
            rng, key = jax.random.split(rng)
            tok = sample_logits(last_logits, key, do_sample, temperature, top_k, top_p).astype(jnp.int32)
            out0 = jnp.zeros((batch, cap), jnp.int32)
            done0 = (tok == eos)
            out0 = out0.at[:, 0].set(tok)

            def cond(state):
                t, done, *_ = state
                return (t < max_new) & ~jnp.all(done)

            def body(state):
                t, done, tok, cache, out, rng = state
                logits, upd = apply_decode(params, cache, tok[:, None])
                rng, key = jax.random.split(rng)
                nxt = sample_logits(logits[:, 0], key, do_sample, temperature,
                                    top_k, top_p).astype(jnp.int32)
                nxt = jnp.where(done, eos if eos >= 0 else 0, nxt)
                out = out.at[:, t].set(nxt)
                done = done | (nxt == eos)
                return t + 1, done, nxt, upd["cache"], out, rng

            t, done, tok, cache, out, rng = jax.lax.while_loop(
                cond, body, (jnp.int32(1), done0, tok, cache, out0, rng))
            # the final cache is returned (and discarded by the caller) so
            # the donated input cache has an output to alias — without it
            # donation is dead and JAX warns on every first compile
            return out, t, cache

        return {
            # one jitted prefill specializes to exactly two shapes: the
            # C-token chunk and the 1-token remainder
            "prefill": jax.jit(prefill, donate_argnums=(1,)),
            "gen_loop": jax.jit(gen_loop, donate_argnums=(1,)),
        }

    def _make_beam_fns(self, batch, beams, eos_token_id, cap, length_penalty,
                       decode_fn):
        """Generic beam-search machinery shared by decoder-only and
        encoder-decoder serving. ``decode_fn(params, cache, tok_2d, extra)
        -> (logits [batch*beams, V], new_cache)`` is the one-step decoder;
        ``extra`` is any per-call operand the step cross-references (the
        replicated encoder output for seq2seq; ``()`` for decoder-only).
        Each live hypothesis is one row of a [batch*beams] decode batch; the
        KV cache reindexes by the winning beams' source indices every step."""
        eos = -1 if eos_token_id is None else int(eos_token_id)

        def replicate(cache):
            # leaves with a leading batch dim fan out to [batch*beams, ...];
            # scalars (cache_index counters) stay shared
            def rep(x):
                if x.ndim > 0 and x.shape[0] == batch:
                    return jnp.repeat(x, beams, axis=0)
                return x
            return jax.tree.map(rep, cache)

        def reindex(cache, beam_src):
            # beam_src [batch, beams]: winning hypotheses' source beams
            def gather(x):
                if x.ndim > 0 and x.shape[0] == batch * beams:
                    xb = x.reshape((batch, beams) + x.shape[1:])
                    idx = beam_src.reshape((batch, beams) + (1,) * (x.ndim - 1))
                    return jnp.take_along_axis(xb, idx, axis=1).reshape(x.shape)
                return x
            return jax.tree.map(gather, cache)

        def beam_loop(params, cache, extra, last_logits, max_new):
            # cache arrives ALREADY replicated to [batch*beams, ...] (the
            # caller runs the jitted replicate first) so the donated input
            # aliases the loop-carried cache — inside-loop replication would
            # leave donation dead and hold 1+beams cache copies in HBM
            lp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)  # [B, V]
            scores, tok = jax.lax.top_k(lp0, beams)  # [B, beams]
            tok = tok.astype(jnp.int32)
            out0 = jnp.zeros((batch, beams, cap), jnp.int32).at[:, :, 0].set(tok)
            done0 = tok == eos
            len0 = jnp.ones((batch, beams), jnp.int32)
            vocab = lp0.shape[-1]
            # candidate set for a finished beam: only "stay finished" (eos,
            # score unchanged) — standard done-beam handling
            done_lp = jnp.full((vocab,), -jnp.inf).at[max(eos, 0)].set(0.0)

            def cond(state):
                t, done, *_ = state
                return (t < max_new) & ~jnp.all(done)

            def body(state):
                t, done, tok, scores, lens, cache, out = state
                logits, new_cache = decode_fn(params, cache,
                                              tok.reshape(batch * beams, 1), extra)
                lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                lp = lp.reshape(batch, beams, vocab)
                lp = jnp.where(done[:, :, None], done_lp[None, None, :], lp)
                total = scores[:, :, None] + lp  # [B, beams, V]
                new_scores, flat = jax.lax.top_k(total.reshape(batch, beams * vocab), beams)
                beam_src = (flat // vocab).astype(jnp.int32)
                new_tok = (flat % vocab).astype(jnp.int32)
                take = lambda a: jnp.take_along_axis(a, beam_src, axis=1)
                prev_done = take(done)
                new_done = prev_done | (new_tok == eos)
                new_lens = take(lens) + (~prev_done).astype(jnp.int32)
                out = jnp.take_along_axis(out, beam_src[:, :, None], axis=1)
                # a finished beam keeps emitting eos (or 0) — already its token
                out = out.at[:, :, t].set(jnp.where(prev_done, max(eos, 0), new_tok))
                cache = reindex(new_cache, beam_src)
                return t + 1, new_done, new_tok, new_scores, new_lens, cache, out

            t, done, tok, scores, lens, cache, out = jax.lax.while_loop(
                cond, body, (jnp.int32(1), done0, tok, scores, len0, cache, out0))
            # HF-style length normalization (length_penalty=1.0 → mean logprob)
            norm = scores / (lens.astype(jnp.float32) ** length_penalty)
            best = jnp.argmax(norm, axis=-1)
            best_out = jnp.take_along_axis(out, best[:, None, None], axis=1)[:, 0]
            return best_out, t, cache

        # replicate is NOT donated (outputs are beams× larger, nothing can
        # alias); the prefill cache dies naturally after this call
        return {"replicate": jax.jit(replicate),
                "loop": jax.jit(beam_loop, donate_argnums=(1,))}

    def _build_beam_loop(self, batch, beams, eos_token_id, cap, length_penalty):
        """Decoder-only beam search (reference relies on HF ``generate``
        over the injected kernels; here the whole search is one jitted
        while_loop over :meth:`_make_beam_fns`)."""
        apply_decode = self._apply_decode

        def decode_fn(params, cache, tok, extra):
            del extra
            logits, upd = apply_decode(params, cache, tok)
            return logits[:, 0], upd["cache"]

        return self._make_beam_fns(batch, beams, eos_token_id, cap,
                                   length_penalty, decode_fn)

    def _build_seq2seq_beam(self, batch, beams, eos_token_id, cap,
                            length_penalty):
        """Encoder-decoder beam search: encode once, replicate the decoder
        self-attention cache AND the encoder output to [batch*beams], then
        run the shared beam while_loop with a cross-attending step."""
        step = self._seq2seq_step
        encode = self._seq2seq_encode

        def first(params, cache, enc_out, start_tok):
            # the start-token step runs on the UNREPLICATED batch (every
            # beam of a row would compute the same thing); its logits seed
            # the beam fan-out exactly like decoder-only prefill logits
            logits, cache = step(params, cache, enc_out, start_tok)
            return logits[:, -1], cache

        def decode_fn(params, cache, tok, enc_rep):
            logits, cache = step(params, cache, enc_rep, tok)
            return logits[:, 0], cache

        fns = self._make_beam_fns(batch, beams, eos_token_id, cap,
                                  length_penalty, decode_fn)
        fns["first"] = jax.jit(first, donate_argnums=(1,))
        # the encoder output fans out to [batch*beams] by the SAME rule as
        # the cache (one shared jitted repeat — the row alignment between
        # the two replications is load-bearing for cross-attention)
        fns["rep_enc"] = fns["replicate"]
        fns["encode"] = jax.jit(encode)
        return fns

    def _seq2seq_step(self, params, cache, enc_out, tok):
        """One decoder step of an encoder-decoder model: self-attend the
        cache, cross-attend the encoder output (shared by the greedy and
        beam builders so the two paths cannot drift)."""
        model = self.module
        logits, upd = model.apply({"params": self._mparams(params), "cache": cache},
                                  decoder_input_ids=tok, encoder_outputs=enc_out,
                                  decode=True, mutable=["cache"])
        return _unwrap_logits(logits), upd["cache"]

    def _seq2seq_encode(self, params, enc_ids):
        model = self.module
        return model.apply({"params": self._mparams(params)}, enc_ids,
                           method=type(model).encode)

    def _build_seq2seq_serving(self, batch, do_sample, temperature, top_k, top_p,
                               eos_token_id, cap):
        """Encoder-decoder serving (T5-style): encode once, then a jitted
        decoder while_loop against the self-attention cache, cross-attending
        the encoder output every step."""
        eos = -1 if eos_token_id is None else int(eos_token_id)
        step = self._seq2seq_step
        encode = self._seq2seq_encode

        def gen_loop(params, cache, enc_out, start_tok, rng, max_new):
            logits, cache = step(params, cache, enc_out, start_tok)
            rng, key = jax.random.split(rng)
            tok = sample_logits(logits[:, -1], key, do_sample, temperature,
                                top_k, top_p).astype(jnp.int32)
            out0 = jnp.zeros((batch, cap), jnp.int32).at[:, 0].set(tok)
            done0 = tok == eos

            def cond(state):
                t, done, *_ = state
                return (t < max_new) & ~jnp.all(done)

            def body(state):
                t, done, tok, cache, out, rng = state
                logits, cache = step(params, cache, enc_out, tok[:, None])
                rng, key = jax.random.split(rng)
                nxt = sample_logits(logits[:, 0], key, do_sample, temperature,
                                    top_k, top_p).astype(jnp.int32)
                nxt = jnp.where(done, eos if eos >= 0 else 0, nxt)
                out = out.at[:, t].set(nxt)
                done = done | (nxt == eos)
                return t + 1, done, nxt, cache, out, rng

            t, done, tok, cache, out, rng = jax.lax.while_loop(
                cond, body, (jnp.int32(1), done0, tok, cache, out0, rng))
            return out, t, cache

        return {"encode": jax.jit(encode),
                "gen_loop": jax.jit(gen_loop, donate_argnums=(1,))}

    def _generate_seq2seq(self, ids_np, real_batch, batch, max_new, do_sample,
                          temperature, top_k, top_p, eos_token_id, rng,
                          decoder_start_token_id, num_beams=1,
                          length_penalty=1.0):
        mcap = getattr(self.mcfg, "max_cache_length", None) or self._max_len
        # cache slots consumed = max_new (the start token plus the max_new-1
        # fed-back tokens; the final sample is never fed back)
        if max_new > mcap:
            raise ValueError(f"max_new_tokens ({max_new}) exceeds the decoder cache "
                             f"capacity {mcap} (max_cache_length)")
        if max_new > int(self.config.max_tokens or mcap):
            raise ValueError(f"max_new_tokens ({max_new}) exceeds the configured output "
                             f"budget max_tokens={self.config.max_tokens}; raise it in "
                             f"the inference config (silently truncating would hide the miss)")
        cap = int(min(mcap, self.config.max_tokens or mcap))
        if num_beams > 1:
            key = ("seq2seq_beam", batch, num_beams, eos_token_id,
                   float(length_penalty))
        else:
            key = ("seq2seq", batch, do_sample, float(temperature), int(top_k),
                   float(top_p), eos_token_id)
        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        if key not in self._gen_cache:
            self._gen_cache[key] = (
                self._build_seq2seq_beam(batch, num_beams, eos_token_id, cap,
                                         float(length_penalty))
                if num_beams > 1 else
                self._build_seq2seq_serving(batch, do_sample, temperature,
                                            top_k, top_p, eos_token_id, cap))
        fns = self._gen_cache[key]
        start = jnp.full((batch, 1), int(decoder_start_token_id), jnp.int32)
        if max_new <= 0:  # parity with the decoder-only path's no-op return
            return np.broadcast_to(np.int32(decoder_start_token_id), (real_batch, 1))
        # NOTE: the encoder runs at the exact prompt length (no padding —
        # the encode() surface carries no padding mask, and padded tokens
        # would perturb bidirectional attention); one compile per length.
        # The encoder program is sampling-independent: cached per batch only
        if not hasattr(self, "_enc_cache"):
            self._enc_cache = {}
        if batch not in self._enc_cache:
            self._enc_cache[batch] = fns["encode"]
        enc_out = self._enc_cache[batch](self.params, self._place_batch(jnp.asarray(ids_np)))
        cache = jax.device_put(init_cache(self.module, batch),  # graft-lint: waive R008 jax-owned init_cache zeros
                               NamedSharding(self.mesh, P()))
        if num_beams > 1:
            last_logits, cache = fns["first"](self.params, cache, enc_out, start)
            cache = fns["replicate"](cache)
            enc_rep = fns["rep_enc"](enc_out)
            out, n, _ = fns["loop"](self.params, cache, enc_rep, last_logits,
                                    jnp.int32(min(max_new, cap)))
        else:
            out, n, _ = fns["gen_loop"](self.params, cache, enc_out, start, rng,
                                        jnp.int32(min(max_new, cap)))
        n = int(n)
        full = jnp.concatenate([start, out[:, :n]], axis=1)
        return full[:real_batch]

    @staticmethod
    def _pow2_bucket(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def generate(self, input_ids, max_new_tokens: Optional[int] = None, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, rng: Optional[jax.Array] = None,
                 num_beams: int = 1, length_penalty: float = 1.0, **kwargs):
        """Generate ``max_new_tokens`` continuations (reference routes
        ``generate`` through the injected model's fused decode kernels).
        ``num_beams > 1`` runs beam search (greedy expansion; HF-style
        length normalization via ``length_penalty``)."""
        if num_beams > 1 and do_sample:
            raise ValueError("num_beams > 1 requires do_sample=False (beam-sample "
                             "hybrid is not supported)")
        ids_np = np.asarray(input_ids, np.int32)
        real_batch, prompt_len = ids_np.shape
        max_new = int(max_new_tokens if max_new_tokens is not None else self.config.max_new_tokens)
        def bucket_pad_and_rng(ids_np, rng):
            # bucket/pad + rng split happen AFTER validation so a rejected
            # call never advances the engine's rng stream (seeded-run
            # reproducibility must not depend on failed requests)
            batch = self._pow2_bucket(real_batch)
            if batch != real_batch:
                ids_np = np.concatenate(
                    [ids_np, np.repeat(ids_np[:1], batch - real_batch, axis=0)], axis=0)
            if rng is None:
                # engine-stream key unless the caller supplied one (keeps
                # later rng-less calls independent of any caller key)
                self._rng, rng = jax.random.split(self._rng)
            return ids_np, batch, rng

        if self._is_seq2seq:
            start_id = kwargs.get("decoder_start_token_id",
                                  getattr(self.mcfg, "decoder_start_token_id", None))
            if start_id is None:
                raise ValueError("encoder-decoder generate needs decoder_start_token_id "
                                 "(pass it or set it on the model config) — defaulting "
                                 "silently would seed generation from the wrong token")
            ids_np, batch, rng = bucket_pad_and_rng(ids_np, rng)
            return self._generate_seq2seq(
                ids_np, real_batch, batch, max_new, do_sample, temperature, top_k,
                top_p, eos_token_id, rng, int(start_id),
                num_beams=num_beams, length_penalty=length_penalty)
        if prompt_len + max_new > self._max_len:
            raise ValueError(f"prompt ({prompt_len}) + max_new_tokens ({max_new}) exceeds the model "
                             f"context/cache length {self._max_len} "
                             f"(reference maps this to max_out_tokens)")
        if max_new > int(self.config.max_tokens or self._max_len):
            raise ValueError(f"max_new_tokens ({max_new}) exceeds the configured output budget "
                             f"max_tokens={self.config.max_tokens}; raise it in the inference "
                             f"config (silently truncating would hide the miss)")
        ids_np, batch, rng = bucket_pad_and_rng(ids_np, rng)
        cap = min(self._max_len, int(self.config.max_tokens or self._max_len))

        key = (batch, do_sample, float(temperature), int(top_k), float(top_p), eos_token_id)
        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        if key not in self._gen_cache:
            # every (bucket, sampling) combination stays warm — alternating
            # request shapes must not discard compiled programs
            self._gen_cache[key] = self._build_serving(batch, do_sample, temperature,
                                                       top_k, top_p, eos_token_id, cap)
        self._gen_key = key
        self._gen_fns = fns = self._gen_cache[key]


        ids = self._place_batch(jnp.asarray(ids_np))
        # commit the fresh cache so its placement matches the donated outputs
        # of later calls (an uncommitted first cache costs a recompile)
        cache = jax.device_put(init_cache(self.module, batch),  # graft-lint: waive R008 jax-owned init_cache zeros
                               NamedSharding(self.mesh, P()))
        C = self.PREFILL_CHUNK
        pos = 0
        last_logits = None
        while pos + C <= prompt_len:
            cache, last_logits = fns["prefill"](self.params, cache, ids[:, pos:pos + C])
            pos += C
        while pos < prompt_len:
            cache, last_logits = fns["prefill"](self.params, cache, ids[:, pos:pos + 1])
            pos += 1
        if max_new <= 0:
            return jnp.asarray(ids_np[:real_batch])
        if num_beams > 1:
            bkey = (batch, num_beams, eos_token_id, float(length_penalty))
            if not hasattr(self, "_beam_cache"):
                self._beam_cache = {}
            if bkey not in self._beam_cache:
                self._beam_cache[bkey] = self._build_beam_loop(
                    batch, num_beams, eos_token_id, cap, float(length_penalty))
            bfns = self._beam_cache[bkey]
            cache = bfns["replicate"](cache)
            out, n, _ = bfns["loop"](self.params, cache, (), last_logits,
                                     jnp.int32(min(max_new, cap)))
        else:
            out, n, _ = fns["gen_loop"](self.params, cache, last_logits, rng,
                                        jnp.int32(min(max_new, cap)))
        n = int(n)
        full = jnp.concatenate([jnp.asarray(ids_np), out[:, :n]], axis=1)
        return full[:real_batch]
