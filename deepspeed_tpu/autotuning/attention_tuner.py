"""Kernel-level autotuner for the Pallas flash-attention block geometry.

The config-level :class:`~deepspeed_tpu.autotuning.autotuner.Autotuner`
searches (ZeRO stage, micro-batch, mesh); this tuner searches one level
below it — the attention kernel's work partitioning (forward/backward
block sizes, compute tile, recompute policy) per call
shape. FlashAttention-2's result is that this partitioning, not the
algorithm, is where the last 1.5-2x of long-context throughput lives; the
best geometry depends on (seq, head_dim, heads, micro-batch, causal,
dtype), so winners are keyed by that signature and persisted through the
same artifact layout as the config tuner:

* ``exps_dir/attn_<signature>.json`` — every candidate's record (geometry,
  measured seconds, status/error), the per-experiment evidence trail;
* ``results_dir/attention_blocks.json`` — the shape-keyed winners cache
  that ``flash_attention`` resolves through at call time
  (``ops.pallas.attention_geometry``), the ``ds_config_optimal.json``
  analog.

Timing methodology matches the bench tools: one jitted program per
candidate, warmup dispatch, then the best of ``repeats`` timed bursts of
back-to-back dispatches (min — perturbations only ever add time; a burst,
because one dispatch of a millisecond kernel is mostly the host's
enqueue). The default sweep is STAGED to keep a shape at tens of compiles
instead of the hundreds of the full cross-product: the forward (q, kv)
block pair is chosen first by forward-only timing at every compute tile
(backward knobs cannot affect it), then the backward axes and the tile,
which both passes share, sweep fwd+bwd with the forward pair pinned.
:func:`kernel_seconds` splits one geometry's time into its three kernels
(forward, dq, dkv) from a device trace, for the record a change to the
kernels is judged by. On non-TPU backends the kernels run
in interpret mode; the selection machinery is identical, so CI smokes the
persist/reload path with tiny shapes while chip windows produce the real
numbers.
"""

import glob
import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.ops.pallas.attention_geometry import (CACHE_BASENAME,
                                                         AttentionGeometry,
                                                         signature,
                                                         store_winner)
from deepspeed_tpu.utils.logging import log_dist, logger

# candidate block edges, largest first pruned by divisibility/VMEM below
_BLOCK_EDGES = (2048, 1024, 512, 256, 128)
# compute tiles a block is walked in (square, clamped to the blocks)
_TILES = (256, 128, 512)
# dispatches timed as one burst
_BURST = 10
# per-grid-cell VMEM budget for candidate pruning (v5e has ~16 MiB more
# details in the Pallas guide's budget formula; leave headroom for Mosaic's
# double-buffered input windows)
_VMEM_BUDGET_BYTES = 10 * 2**20


def _vmem_bytes(blk_q: int, blk_k: int, head_dim: int, itemsize: int) -> int:
    """Working-set estimate for one grid cell of the fwd/bwd kernels: q/k/v
    input windows (x2 for double buffering), a few fp32 score tiles, and
    the fp32 accumulator scratch."""
    tiles = 2 * (blk_q + 2 * blk_k) * head_dim * itemsize  # q + k + v, dbl-buffered
    tile = max(_TILES)
    scores = 4 * min(blk_q, tile) * min(blk_k, tile) * 4
    acc = (blk_q + 2 * blk_k) * head_dim * 4
    return tiles + scores + acc


def tile_axis(lq: int, lk: int) -> List[int]:
    """Compute tiles worth sweeping for a shape: those no larger than the
    sequence, else the one the kernel would clamp to."""
    return [t for t in _TILES if t <= min(lq, lk)] or [min(lq, lk)]


def candidate_axes(lq: int, lk: int, head_dim: int, causal: bool,
                   itemsize: int = 2,
                   ) -> Tuple[List[Tuple[int, int]], List[Tuple[int, int]]]:
    """The block axes of the sweep for one shape — forward block pairs,
    backward block pairs — pruned by divisibility and the VMEM budget
    (:func:`tile_axis` is the third axis). The default tune() sweeps them
    STAGED (forward pair first, forward-only timing; then the backward
    axes on the winning pair): the full cross-product would be hundreds of
    compiles per shape, the staged sweep tens."""
    def edges(length):
        return [e for e in _BLOCK_EDGES if e <= length and length % e == 0] or [length]

    fwd_pairs = []
    for bq in edges(lq)[:2]:
        for bk in edges(lk)[:3]:
            if _vmem_bytes(bq, bk, head_dim, itemsize) <= _VMEM_BUDGET_BYTES:
                fwd_pairs.append((bq, bk))
    bwd_pairs = []
    for bq in edges(lq)[:3]:
        for bk in edges(lk)[:2]:
            if _vmem_bytes(bq, bk, head_dim, itemsize) <= _VMEM_BUDGET_BYTES:
                bwd_pairs.append((bq, bk))
    return fwd_pairs, bwd_pairs


def default_candidates(lq: int, lk: int, head_dim: int, causal: bool,
                       itemsize: int = 2) -> List[AttentionGeometry]:
    """The flat cross-product of :func:`candidate_axes` — the exhaustive
    grid for callers that want it. tune() does NOT sweep this by default
    (see the staged sweep there); pass it as ``candidates=`` to force the
    full grid."""
    fwd_pairs, bwd_pairs = candidate_axes(lq, lk, head_dim, causal, itemsize)
    cands = []
    for fq, fk in fwd_pairs:
        for bq, bk in bwd_pairs:
            for tile in tile_axis(lq, lk):
                for policy in ("lse", "recompute"):
                    cands.append(AttentionGeometry(
                        block_q=fq, block_k=fk, block_q_bwd=bq, block_k_bwd=bk,
                        tile=tile, policy=policy))
    return cands


def operands(seq: int, seq_k: int, heads: int, head_dim: int, batch: int, dtype):
    """The BLHD q, k, v a shape is timed on."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.standard_normal((batch, n, heads, head_dim)), dtype)
                 for n in (seq, seq_k, seq_k))


def _mosaic_seconds(fn, args, calls: int) -> Tuple[Optional[float], int]:
    """Device seconds one call of ``fn`` spends in Mosaic kernels, and how
    many it runs: the custom calls on the first TPU's ``XLA Ops`` line of a
    profiler trace over ``calls`` dispatches. ``(None, 0)`` where the
    backend writes no such line (the CPU's interpreter)."""
    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                              "*.xplane.pb")))
        if not found:
            return None, 0
        planes = ProfileData.from_file(found[-1]).planes
    for plane in sorted(planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:TPU:"):
            continue
        events = [ev for line in plane.lines if line.name == "XLA Ops"
                  for ev in line.events if " custom-call(" in ev.name]
        if events:
            return sum(ev.duration_ns for ev in events) * 1e-9 / calls, len(events) // calls
    return None, 0


def kernel_seconds(geom: AttentionGeometry, q, k, v, causal: bool, calls: int = 20,
                   interpret: Optional[bool] = None) -> Optional[Dict[str, float]]:
    """One geometry's device seconds a call in each of the three kernels,
    ``{"fwd", "dq", "dkv"}``, or None off the chip. A program that
    differentiates by q alone keeps the forward and dq kernels (XLA drops
    the dkv call whose results nothing reads), one by k and v keeps the
    forward and dkv: the forward's time comes off both. Under
    ``policy="recompute"`` the forward the backward runs again is in
    ``dq``'s and ``dkv``'s number."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    kwargs = dict(geom.call_kwargs(), causal=causal, interpret=interpret)

    def loss(q_, k_, v_):
        return (flash_attention(q_, k_, v_, **kwargs).astype(jnp.float32) ** 2).sum()

    programs = {"fwd": (lambda q_, k_, v_: flash_attention(q_, k_, v_, **kwargs), 1),
                "dq": (jax.grad(loss, argnums=0), 2),
                "dkv": (jax.grad(loss, argnums=(1, 2)), 2)}
    out = {}
    for name, (fn, kernels) in programs.items():
        secs, n = _mosaic_seconds(jax.jit(fn), (q, k, v), calls)
        if secs is None:
            return None
        if n != kernels + (geom.policy == "recompute" and name != "fwd"):
            logger.warning(f"attention autotune: the {name} program ran {n} kernels a call")
        out[name] = secs
    return {"fwd": out["fwd"], "dq": out["dq"] - out["fwd"], "dkv": out["dkv"] - out["fwd"]}


class AttentionBlockTuner:
    """Sweep candidate geometries for one attention call shape and persist
    the winner (see module docstring for the artifact layout)."""

    def __init__(self,
                 results_dir: str = "autotuning_results",
                 exps_dir: str = "autotuning_exps",
                 repeats: int = 3,
                 candidates: Optional[Sequence[AttentionGeometry]] = None,
                 interpret: Optional[bool] = None):
        self.results_dir = results_dir
        self.exps_dir = exps_dir
        self.repeats = max(int(repeats), 1)
        self.candidates = list(candidates) if candidates is not None else None
        self.interpret = interpret
        self.records: List[Dict[str, Any]] = []

    # ------------------------------------------------------------------
    def _time_candidate(self, geom: AttentionGeometry, q, k, v, causal: bool,
                        train: bool) -> float:
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        kwargs = dict(geom.call_kwargs(), causal=causal, interpret=self.interpret)

        if train:
            def loss(q_, k_, v_):
                return (flash_attention(q_, k_, v_, **kwargs).astype(jnp.float32) ** 2).sum()

            fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        else:
            fn = jax.jit(lambda q_, k_, v_: flash_attention(q_, k_, v_, **kwargs))

        jax.block_until_ready(fn(q, k, v))  # compile + warm
        best = float("inf")
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            for _ in range(_BURST):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / _BURST)
        return best

    # ------------------------------------------------------------------
    def _sweep(self, cands: Sequence[AttentionGeometry], q, k, v, causal: bool,
               train: bool, stage: Optional[str] = None,
               ) -> Tuple[Optional[AttentionGeometry], float]:
        best_geom, best_s = None, float("inf")
        for geom in cands:
            rec: Dict[str, Any] = {"geometry": geom.as_dict(), "status": "pending"}
            if stage is not None:
                rec["stage"] = stage
            try:
                s = self._time_candidate(geom, q, k, v, causal, train)
                rec.update(status="measured", seconds=s)
                if s < best_s:
                    best_geom, best_s = geom, s
            except Exception as e:  # unlowerable/oom candidates prune cleanly
                rec.update(status="failed", error=f"{type(e).__name__}: {str(e)[:200]}")
                logger.warning(f"attention autotune: {geom.spec()} failed: "
                               f"{rec['error'][:120]}")
            self.records.append(rec)
        return best_geom, best_s

    # ------------------------------------------------------------------
    def tune(self, *, seq: int, head_dim: int, heads: int = 1, batch: int = 1,
             seq_k: Optional[int] = None, causal: bool = True, dtype=None,
             train: bool = True) -> Tuple[Optional[AttentionGeometry], List[Dict[str, Any]]]:
        """Sweep the shape, persist and return the winner. ``train=True``
        targets the training hot path, ``train=False`` forward-only
        (prefill/serving).

        With no explicit ``candidates``, the sweep is STAGED to stay at
        tens of compiles per shape: the forward (q, kv) pair is picked
        first with forward-only timing (backward knobs can't affect it),
        then the backward axes (bwd pair x tile x policy) sweep fwd+bwd on
        the winning pair, with the compute tile both passes share.
        ``train=False`` stops after the first stage."""
        import jax
        import jax.numpy as jnp

        dtype = dtype or jnp.bfloat16
        lk = seq_k or seq
        sig = signature(seq, lk, head_dim, heads, batch, causal, jnp.dtype(dtype))
        q, k, v = operands(seq, lk, heads, head_dim, batch, dtype)

        self.records = []
        if self.candidates is not None:
            log_dist(f"attention autotune: {sig} — {len(self.candidates)} "
                     f"explicit candidates on {jax.default_backend()}")
            best_geom, best_s = self._sweep(self.candidates, q, k, v, causal, train)
        else:
            fwd_pairs, bwd_pairs = candidate_axes(
                seq, lk, head_dim, causal, itemsize=jnp.dtype(dtype).itemsize)
            tiles = tile_axis(seq, lk)
            fwd_cands = [AttentionGeometry(block_q=fq, block_k=fk, tile=tile)
                         for fq, fk in fwd_pairs for tile in tiles]
            stage2 = 0 if not train else len(bwd_pairs) * len(tiles) * 2
            log_dist(f"attention autotune: {sig} — staged sweep "
                     f"({len(fwd_cands)} fwd + {stage2} bwd candidates) "
                     f"on {jax.default_backend()}")
            best_geom, best_s = self._sweep(fwd_cands, q, k, v, causal,
                                            train=False, stage="fwd")
            if train:
                fq, fk = ((best_geom.block_q, best_geom.block_k)
                          if best_geom is not None else (None, None))
                cands = [AttentionGeometry(block_q=fq, block_k=fk,
                                           block_q_bwd=bq, block_k_bwd=bk,
                                           tile=tile, policy=policy)
                         for bq, bk in bwd_pairs
                         for tile in tiles
                         for policy in ("lse", "recompute")]
                best_geom, best_s = self._sweep(cands, q, k, v, causal,
                                                train=True, stage="train")

        self._write_exps(sig, batch=batch, heads=heads, seq=seq, seq_k=lk,
                         head_dim=head_dim, causal=causal, train=train,
                         dtype=jnp.dtype(dtype).name,
                         backend=jax.default_backend())
        if best_geom is not None:
            path = store_winner(
                sig, best_geom,
                path=os.path.join(self.results_dir, CACHE_BASENAME),
                seconds=best_s, backend=jax.default_backend(),
                candidates=len(self.records), train=bool(train),
                timestamp=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
            log_dist(f"attention autotune: {sig} -> {best_geom.spec()} "
                     f"({best_s * 1e3:.2f} ms, winners cache {path})")
        return best_geom, self.records

    # ------------------------------------------------------------------
    def _write_exps(self, sig: str, **meta: Any) -> str:
        os.makedirs(self.exps_dir, exist_ok=True)
        path = os.path.join(self.exps_dir, f"attn_{sig}.json")
        with open(path, "w") as f:
            json.dump({"signature": sig, **meta, "records": self.records},
                      f, indent=2)
        return path


def tune_attention_blocks(*, seq: int, head_dim: int, heads: int = 1,
                          batch: int = 1, causal: bool = True, dtype=None,
                          train: bool = True,
                          results_dir: str = "autotuning_results",
                          exps_dir: str = "autotuning_exps",
                          **tuner_kwargs) -> Optional[AttentionGeometry]:
    """One-call convenience wrapper: sweep, persist, return the winner."""
    tuner = AttentionBlockTuner(results_dir=results_dir, exps_dir=exps_dir,
                                **tuner_kwargs)
    best, _ = tuner.tune(seq=seq, head_dim=head_dim, heads=heads, batch=batch,
                         causal=causal, dtype=dtype, train=train)
    return best
