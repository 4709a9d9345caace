"""Blockwise flash attention as a Pallas TPU kernel (fwd + bwd).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, ``csrc/transformer/inference/csrc/
softmax.cu``): online-softmax tiling keeps the full ``L x L`` score matrix
out of HBM, accumulates in fp32 on the MXU, and exposes a custom VJP so the
backward pass is also blockwise.

Layout contract: ``[batch, length, heads, head_dim]`` (BLHD) at the public
boundary — transposed to BHLD internally for lane-friendly tiling.

On non-TPU backends the kernels run in Pallas interpret mode so CPU tests
exercise the same code path.

What a kernel does, per grid step and per score element:

* **Block against compute tile.** A grid step stages one BLOCK of each
  operand in VMEM (``block_q`` x ``block_k``; Mosaic double-buffers the next
  block's DMA behind the current step) and walks it in COMPUTE TILES of
  ``tile`` x ``tile`` with loops whose bounds are scalars. The reduction
  axis stays the innermost grid dimension, so VMEM held per step is a few
  blocks whatever the sequence length: a short sequence stages its whole
  K/V in one step, a long one streams blocks that are a multiple of the
  tile. The online-softmax state of a q tile is a loop value across its k
  tiles and touches VMEM scratch once a grid step, not once a tile.
* **Three kinds of tile**, decided from scalars before a tile's body runs
  (:func:`_k_walk` / :func:`_q_walk`, shared by the three kernels, the
  index-map clamps and the trace-time counters): DEAD tiles (above the
  causal diagonal, below the sliding window, beyond ``kv_lengths``) are
  never visited and a dead block moves no bytes; INTERIOR tiles hold only
  live scores and run a body with no positions and no select; only EDGE
  tiles (straddling the diagonal, the window's lower edge or the end of
  the valid prefix) build positions and mask.
* **Operands reach the MXU in the dtype the caller handed over**, with
  fp32 accumulation (``preferred_element_type``): ``p`` and ``ds`` are cast
  to that dtype once, right before the matmul that consumes them. Softmax
  statistics (``m``, ``l``, ``lse``, ``delta``), ``exp`` and every
  accumulator are fp32. The kernel observes the dtype; fp32 callers
  compute what they always did.
* **Statistics in the orientation of the scores, and the scores
  transposed.** All three kernels hold a tile of scores as ``[k, q]``
  (``s = k q^T``), so the per-query statistics are ``[1, q]`` rows: a few
  vector registers, reduced down the sublanes, and ``lse`` and ``delta`` —
  which live in HBM as compact ``[B, H, L]`` rows — broadcast as they
  arrive. (As ``[q, 1]`` columns they cost a register a row of 8 queries
  in every operation of every k tile: 5 ns a row a tile on a v5e, more
  than the tile's matmuls.) The forward and dq accumulate transposed too,
  ``[d, q]``, and turn the result once a q tile; dkv's two products need no
  transposed operand at all.
* **Straight-line code where the shapes fix the walk.** Where one grid
  step holds the whole sequence and no bound waits for ``kv_lengths``,
  every tile bound is a Python int and the walk is unrolled, so Mosaic
  schedules one tile's vector work under its neighbour's matmuls; the dkv
  kernel then steps in tiles of 128, which keep its scores and both
  accumulators in registers. Where a bound is a run-time scalar (a grid
  index of a streamed sequence, a length) the same walk is a loop, and an
  interior block of a streamed sequence is unrolled again.

Work partitioning is TUNABLE (``attention_geometry``): forward and backward
blocks are independent (FlashAttention-2's dq/dkv passes prefer different
tilings than the forward), the compute tile is one more field, and the
backward can either read the stashed log-sum-exp residual (``policy="lse"``) or
recompute it with an extra forward pass (``policy="recompute"`` — drops
the [B,H,L] residual per layer between fwd and bwd). Fields a call leaves
unset come from the shape-keyed defaults measured on a v5e
(``attention_geometry.default_geometry``). Longer-than-HBM contexts remain
the job of sequence parallelism (``deepspeed_tpu.parallel.ring_attention``).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.ops.pallas.attention_geometry import (AttentionGeometry,
                                                         parse_spec,
                                                         pick_block,
                                                         resolve_geometry)
from deepspeed_tpu.ops.transformer.attention import register_backend
from deepspeed_tpu.parallel.topology import BATCH_AXES, TENSOR_AXIS, get_topology
from deepspeed_tpu.utils.trace import recorder

NEG_INF = float(jnp.finfo(jnp.float32).min)

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


# ---------------------------------------------------------------------------
# which tiles hold what: one classifier for kernels, index maps and counters
# ---------------------------------------------------------------------------
def _lesser(a, b):
    """``min`` of Python ints at trace time, of traced scalars in a kernel."""
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _greater(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _ordered(lo, ilo, ihi, hi):
    """``lo <= ilo <= ihi <= hi``: an empty interior sits inside the live
    range, so the edge ranges on its two sides cover each live tile once."""
    hi = _greater(hi, lo)
    ilo = _lesser(_greater(ilo, lo), hi)
    return lo, ilo, _lesser(_greater(ihi, ilo), hi), hi


def _k_walk(q_lo, tq, tk, n, off, causal, window, kv_len):
    """The k tiles that q rows ``[q_lo, q_lo + tq)`` touch, of ``n`` tiles
    ``tk`` wide. A score is live where ``q_pos - window < k_pos <= q_pos``
    (``q_pos = row + off``, the kv-cache decode offset; each bound only if
    causal / windowed) and ``k_pos < kv_len``. Returns ``(lo, ilo, ihi,
    hi)``: tiles ``[lo, hi)`` hold a live score (the others are DEAD),
    ``[ilo, ihi)`` hold nothing else (INTERIOR), and ``[lo, ilo)`` and
    ``[ihi, hi)`` are EDGE tiles. The same call with a block's sizes says
    which blocks a grid row fetches."""
    first, last = q_lo + off, q_lo + tq - 1 + off
    lo, ilo, ihi, hi = 0, 0, n, n
    if causal:
        hi = _lesser(hi, last // tk + 1)
        ihi = _lesser(ihi, (first + 1) // tk)
    if window is not None:
        lo = _greater(lo, (first - window + 1) // tk)
        ilo = _greater(ilo, (last - window) // tk + 1)
    if kv_len is not None:
        hi = _lesser(hi, (kv_len + tk - 1) // tk)
        ihi = _lesser(ihi, kv_len // tk)
    return _ordered(lo, ilo, ihi, hi)


def _q_walk(k_lo, tk, tq, n, off, causal, window, kv_len):
    """:func:`_k_walk` seen from a k tile: the q tiles (``n`` of ``tq``
    rows) that k positions ``[k_lo, k_lo + tk)`` are touched by. A tile the
    valid prefix ends inside is an edge against every q tile, one beyond
    the prefix is dead against all of them."""
    lo, ilo, ihi, hi = 0, 0, n, n
    if causal:
        lo = _greater(lo, (k_lo - off) // tq)
        ilo = _greater(ilo, (k_lo + tk - 1 - off + tq - 1) // tq)
    if window is not None:
        hi = _lesser(hi, (k_lo + tk - 2 + window - off) // tq + 1)
        ihi = _lesser(ihi, (k_lo - tq - off + window) // tq + 1)
    if kv_len is not None:
        hi = jnp.where(k_lo < kv_len, hi, 0)
        ihi = jnp.where(k_lo + tk <= kv_len, ihi, 0)
    return _ordered(lo, ilo, ihi, hi)


def _mask_scores(s, q_first, k_first, causal, window, kv_len, transposed=False):
    """Edge tiles only: NEG_INF where a score is not live. ``s`` is
    ``[q, k]`` (``[k, q]`` if ``transposed``) and starts at position
    ``q_first`` (offset included) against ``k_first``."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    k_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    keep = None
    if causal:
        ahead = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis) - k_idx + (q_first - k_first)
        keep = ahead >= 0
        if window is not None:
            keep = keep & (ahead < window)
    if kv_len is not None:
        valid = k_idx < kv_len - k_first
        keep = valid if keep is None else keep & valid
    return s if keep is None else jnp.where(keep, s, NEG_INF)


def _apply_causal_mask(s, qi, j, blk_q, blk_k, off):
    """Mask scores [blk_q, blk_k] for q block ``qi`` vs k block ``j`` with a
    kv-cache decode offset ``off = lk - lq`` (the sparse kernels' mask)."""
    return _mask_scores(s, qi * blk_q + off, j * blk_k, True, None, None)


#: The dkv kernel holds four [k, q] fp32 tiles (scores, p, dp, ds) and two
#: [k, d] accumulators at once. At 128 x 128 all of it stays in the 64
#: vector registers, and where the walk is straight-line code that beats
#: every larger tile on a v5e (B8 H16 L1024 d64 bf16: 0.479 ms a call at
#: 128, 0.557 at 256, 0.625 at 512; PERF.md section 6, PR 29). Where the
#: walk is a loop, each tile pays the loop's fill and drain (~0.2 us) and
#: the larger tile wins (1.559 ms at 128 against 0.843 at 512), so the
#: kernel then takes the geometry's tile like the other two.
_REGISTER_TILE = 128

#: a span of tiles whose bounds are known at trace time is laid out as
#: straight-line code up to this many bodies (Mosaic then schedules one
#: tile's vector work under its neighbour's matmuls); beyond it, a loop
_UNROLL = 8


def _span(lo, hi, body, carry):
    """``body(index, carry)`` over ``[lo, hi)``: a loop where a bound is a
    run-time scalar (a grid index, ``kv_lengths``), unrolled where the
    call's shapes fix both (a block that holds the whole sequence)."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= _UNROLL:
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _walk(bounds, tile, carry, lower_edge, upper_edge):
    """Run ``tile(index, carry, edge)`` over the live tiles of ``bounds``
    (a :func:`_k_walk` result): the masking body over the edge ranges, the
    plain one over the interior. ``lower_edge`` / ``upper_edge`` say whether
    the call's masks can put an edge on that side at all."""
    lo, ilo, ihi, hi = bounds
    if lower_edge:
        carry = _span(lo, ilo, functools.partial(tile, edge=True), carry)
    carry = _span(ilo, ihi, functools.partial(tile, edge=False), carry)
    if upper_edge:
        carry = _span(ihi, hi, functools.partial(tile, edge=True), carry)
    return carry


def _within(bounds, first, count):
    """Tile bounds over the whole sequence -> over the ``count`` tiles of
    the block that starts at tile ``first``."""
    return tuple(_lesser(_greater(b - first, 0), count) for b in bounds)


def _rows(ref, t, size):
    start = t * size
    return ref.at[pl.ds(start if isinstance(start, int) else pl.multiple_of(start, size), size)]


def _when(cond):
    """``pl.when``, or nothing at all around a step the shapes decide."""
    if isinstance(cond, bool):
        return lambda body: body() if cond else None
    return pl.when(cond)


def _by_block_kind(step, blocks, body):
    """Run ``body(interior)`` for grid step ``step`` by the kind of its
    block (``blocks``: a walk over blocks): nothing for a dead block; for
    an interior block the body all of whose tiles are interior, their bounds
    known at trace time; for an edge block the body that works each tile's
    bounds out."""
    lo, ilo, ihi, hi = blocks
    inside = (step >= ilo) & (step < ihi)
    live = (step >= lo) & (step < hi)
    if isinstance(inside, bool):
        return _when(live)(lambda: body(inside))
    _when(inside)(lambda: body(True))
    _when(live & jnp.logical_not(inside))(lambda: body(False))


def _split_lengths(refs, masked):
    """With ``masked`` a kernel's first ref is the scalar-prefetched [B]
    kv-lengths: this sequence's length (else None), and the other refs."""
    if not masked:
        return None, refs
    return refs[0][pl.program_id(0)], refs[1:]


def _grid_index(axis, extent):
    """A grid axis's index: the literal 0 on an axis of one step, so the
    bounds that follow from it are known at trace time."""
    return pl.program_id(axis) if extent > 1 else 0


def _count_tiles(walk, n_outer, size_outer, size_inner, n_inner, scope, **masks):
    """Trace-time: the tiles of each kind one (batch, head) of a kernel
    runs, by the call's static masks (``kv_lengths`` are run-time values:
    a tile they would kill counts as if the sequence were full). Counted
    over every call, and apart for the calls with a window and those
    without (``attn_tiles_window_*`` / ``attn_tiles_full_*``, beside
    ``attn_walks_window`` / ``_full``, the (batch, head) walks counted:
    a window layer's live tiles a walk against a full layer's say what the
    window skipped, however often a program was traced)."""
    dead = interior = edge = 0
    for t in range(n_outer):
        lo, ilo, ihi, hi = walk(t * size_outer, size_outer, size_inner, n_inner,
                                kv_len=None, **masks)
        interior += ihi - ilo
        edge += (hi - lo) - (ihi - ilo)
        dead += n_inner - (hi - lo)
    where = "full" if masks.get("window") is None else "window"
    recorder().count(f"attn_walks_{where}", scope)
    for kind, n in (("dead", dead), ("interior", interior), ("edge", edge)):
        recorder().count(f"attn_tiles_{kind}", n * scope)
        recorder().count(f"attn_tiles_{where}_{kind}", n * scope)


_warned_fallback = set()


def _warn_fallback(reason: str):
    if reason not in _warned_fallback:
        _warned_fallback.add(reason)
        from deepspeed_tpu.utils.logging import logger
        logger.warning(f"flash attention falling back to the XLA backend: {reason}")


def per_shard(local, q, k, v, lengths):
    """Run ``local(q, k, v, lengths)`` on each device's shard of BLHD
    operands. GSPMD cannot partition a compiled Mosaic kernel ("wrap the
    call in a shard_map"), so on a multi-device mesh the call goes manual
    over every mesh axis an enclosing shard_map has not already taken:
    batch splits over the batch axes and heads over the tensor axis where
    they divide, and stay replicated where they do not (the batch-1 trace
    of parameter init). Attention is independent per (batch, head), so the
    shards need no collective."""
    topo = get_topology()
    if topo is None or topo.mesh.size == 1:
        return local(q, k, v, lengths)
    mesh = topo.mesh
    taken = set(jax.sharding.get_abstract_mesh().manual_axes)
    free = [a for a in mesh.axis_names if a not in taken]
    if all(mesh.shape[a] == 1 for a in free):
        return local(q, k, v, lengths)

    def dividing(axes, extent):
        axes = tuple(a for a in axes if a in free and mesh.shape[a] > 1)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return axes if axes and extent % n == 0 else None

    batch = dividing(BATCH_AXES, q.shape[0])
    # grouped-query heads split only where the key heads split with them
    heads = dividing((TENSOR_AXIS,), math.gcd(q.shape[2], k.shape[2]))
    blhd = P(batch, None, heads, None)
    in_specs = (blhd, blhd, blhd, None if lengths is None else P(batch))
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=blhd,
                         axis_names=set(free), check_vma=False)(q, k, v, lengths)


def _scale_split(dtype, scale):
    """``scale`` goes into q once, outside the k loop, where that rounds
    nothing the caller's dtype holds (fp32 operands, as ever; a power of
    two, which head 64's 1/8 is, in any float dtype); otherwise onto the
    fp32 scores. Returns (what multiplies q, what multiplies the scores),
    one of them None."""
    if dtype == jnp.float32 or math.frexp(scale)[0] == 0.5:
        return scale, None
    return None, scale


def _scaled(q, by):
    return q if by is None else q * jnp.asarray(by, q.dtype)


def _scores(a, b, rest):
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    return s if rest is None else s * rest


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, window, masked, tq, tk, nq, nk):
    # grid (b, h, qi, j): one K/V block per step, walked in tiles; m, l and
    # the TRANSPOSED accumulator [d, q] ride VMEM scratch from one step of a
    # q block to its next. With ``masked`` the first ref is the
    # scalar-prefetched [B] kv-lengths.
    kv_len, refs = _split_lengths(refs, masked)
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    blk_q, blk_k = q_ref.shape[0], k_ref.shape[0]
    n_qt, n_kt = blk_q // tq, blk_k // tk
    qi, j = _grid_index(2, nq), _grid_index(3, nk)
    off = nk * blk_k - nq * blk_q  # kv-cache decode offset
    masks = dict(causal=causal, window=window, kv_len=kv_len)
    into_q, rest = _scale_split(q_ref.dtype, scale)

    @_when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block(interior):
        def q_tile(t, _):
            q_lo = qi * blk_q + t * tq
            q = _scaled(_rows(q_ref, t, tq)[...], into_q)

            def tile(u, carry, edge):
                m, l, acc = carry  # [1, tq], [1, tq], [d, tq]
                k = _rows(k_ref, u, tk)[...]
                v = _rows(v_ref, u, tk)[...]
                s = _scores(k, q, rest)  # [tk, tq]
                if edge:
                    s = _mask_scores(s, q_lo + off, (j * n_kt + u) * tk, transposed=True,
                                     **masks)
                m_new = jnp.maximum(m, s.max(axis=0, keepdims=True))
                if edge:
                    # fully-masked score columns keep m = -inf; anchor the
                    # exp at 0 there so p stays finite (and exactly 0)
                    anchor = jnp.maximum(m_new, NEG_INF / 2)
                    alpha = jnp.exp(jnp.maximum(m, NEG_INF / 2) - anchor)
                else:
                    anchor = m_new  # an interior tile has a finite max in every column
                    alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - anchor)
                l_new = l * alpha + p.sum(axis=0, keepdims=True)
                acc_new = acc * alpha + jax.lax.dot_general(
                    v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new

            state = (m_ref.at[t], l_ref.at[t], acc_ref.at[t])
            bounds = ((0, 0, n_kt, n_kt) if interior else
                      _within(_k_walk(q_lo, tq, tk, nk * n_kt, off, **masks), j * n_kt, n_kt))
            carry = _walk(bounds, tile, tuple(r[...] for r in state),
                          lower_edge=window is not None, upper_edge=causal or masked)
            for r, x in zip(state, carry):
                r[...] = x
            return 0

        _span(0, n_qt, q_tile, 0)

    _by_block_kind(j, _k_walk(qi * blk_q, blk_q, blk_k, nk, off, **masks), _block)

    @_when(j == nk - 1)
    def _finalize():
        for t in range(n_qt):
            m, l = m_ref[t], l_ref[t]
            l_safe = jnp.maximum(l, 1e-37)
            o_ref[t * tq:(t + 1) * tq, :] = (acc_ref[t] / l_safe).T.astype(o_ref.dtype)
            # Rows with no live keys (query beyond every valid K) get a large
            # FINITE negative lse so the backward's exp(s - lse) is exactly 0
            # instead of exp(-inf + inf) = NaN. lse leaves as rows of a
            # [B,H,L/tq,1,tq] array, the [B,H,L] residual in the backward's
            # tiles: a trailing [..., 1] dim would tile-pad to 128 lanes —
            # 128x the HBM held as backward residuals (128 MB/layer at
            # b=16,h=16,L=1024).
            lse_ref[t] = jnp.where(l > 0, jnp.maximum(m, NEG_INF / 2) + jnp.log(l_safe),
                                   NEG_INF / 2)


def _pad_idx(fn, masked):
    """Under PrefetchScalarGridSpec, index maps receive the scalar-prefetch
    refs as extra trailing args — drop them for maps that don't care."""
    return (lambda *a: fn(*a[:-1])) if masked else fn


def _length_call(kernel, grid, in_specs, out_specs, out_shape, scratch,
                 interpret, kv_lengths, args, name):
    """One pallas_call dispatch for the optional [B]-lengths scalar-prefetch
    operand (shared by fwd and both bwd passes so the masked/unmasked
    switch cannot drift between them). ``name`` is the kernel's in the
    compiled program and in a device trace (``flash_fwd``, ``flash_bwd_dq``,
    ``flash_bwd_dkv``): unnamed, both backward kernels read as the jit that
    holds them."""
    if kv_lengths is not None:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret, name=name,
        )(kv_lengths.astype(jnp.int32), *args)
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          scratch_shapes=scratch, interpret=interpret, name=name)(*args)


def _kv_head(q_heads, kv_heads):
    """Query head -> the key/value head it reads (grouped-query attention:
    ``q_heads // kv_heads`` query heads share one). The identity where the
    counts are equal, so those kernels' index maps are what they were."""
    if q_heads == kv_heads:
        return lambda hi: hi
    if q_heads % kv_heads:
        raise ValueError(f"{q_heads} query heads do not group over {kv_heads} key heads")
    n_rep = q_heads // kv_heads
    return lambda hi: hi // n_rep


def _kv_index_map(causal, blk_q, blk_k, off, nk, masked=False, window=None,
                  kv_head=lambda hi: hi):
    """K/V block index for grid step (qi, j). Dead steps — causally dead,
    beyond the sequence's valid K prefix, or outside the sliding window —
    CLAMP to a live block: the index map re-requests the already-resident
    block, Mosaic elides the DMA, and the dead step moves no HBM bytes
    (the `pl.when` in the kernel already skips its FLOPs)."""
    if not causal and not masked and window is None:
        return lambda bi, hi, qi, j: (bi, kv_head(hi), j, 0)

    def index(bi, hi, qi, j, *lens):
        first, _, _, end = _k_walk(qi * blk_q, blk_q, blk_k, nk, off, causal, window,
                                   lens[0][bi] if masked else None)
        return (bi, kv_head(hi),
                jnp.clip(j, jnp.minimum(first, nk - 1), jnp.maximum(end - 1, first)), 0)

    return index


def _tiles(blk_q, blk_k, tile):
    """The compute tile clamped to divisors of the blocks it walks."""
    return pick_block(blk_q, tile), pick_block(blk_k, tile)


def _stat_rows(x, tq):
    """[B,H,L] statistics as [B,H,L/tq,1,tq]: one lane-dense row a q tile
    (a free reshape), addressed by a leading index inside a kernel."""
    b, h, lq = x.shape
    return x.reshape(b, h, lq // tq, 1, tq)


def _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, tile, interpret, kv_lengths=None,
               window=None):
    # q,k,v: [B,H,L,D]; kv_lengths: optional [B] valid-prefix lengths;
    # window: optional sliding-window size (causal only)
    b, h, lq, _ = q.shape
    lk = k.shape[2]
    tq, tk = _tiles(blk_q, blk_k, tile)
    _count_tiles(_k_walk, lq // tq, tq, tk, lk // tk, b * h, off=lk - lq, causal=causal,
                 window=window)
    return _fwd_program(q, k, v, kv_lengths, scale=scale, causal=causal, blk_q=blk_q,
                        blk_k=blk_k, tile=tile, interpret=interpret, window=window)


# A model calls attention once a layer with the same shapes: as an inner
# ``jit`` the kernels are traced, and lowered to Mosaic, once for all of them
# (unrolled tile walks are long programs to trace).
_PROGRAM_STATICS = ("scale", "causal", "blk_q", "blk_k", "tile", "interpret", "window")


@functools.partial(jax.jit, static_argnames=_PROGRAM_STATICS)
def _fwd_program(q, k, v, kv_lengths, *, scale, causal, blk_q, blk_k, tile, interpret, window):
    b, h, lq, d = q.shape
    lk = k.shape[2]
    nq, nk = lq // blk_q, lk // blk_k
    tq, tk = _tiles(blk_q, blk_k, tile)
    off = lk - lq
    masked = kv_lengths is not None
    kv_idx = _kv_index_map(causal, blk_q, blk_k, off, nk, masked, window,
                           _kv_head(h, k.shape[1]))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, window=window,
                               masked=masked, tq=tq, tk=tk, nq=nq, nk=nk)
    qo_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, qi, 0), masked)
    in_specs = [
        pl.BlockSpec((None, None, blk_q, d), qo_idx),
        pl.BlockSpec((None, None, blk_k, d), kv_idx),
        pl.BlockSpec((None, None, blk_k, d), kv_idx),
    ]
    out_specs = [
        pl.BlockSpec((None, None, blk_q, d), qo_idx),
        pl.BlockSpec((None, None, blk_q // tq, 1, tq),
                     _pad_idx(lambda bi, hi, qi, j: (bi, hi, qi, 0, 0), masked)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, lq // tq, 1, tq), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((blk_q // tq, 1, tq), jnp.float32),   # running max, a row a q tile
        pltpu.VMEM((blk_q // tq, 1, tq), jnp.float32),   # running denom
        pltpu.VMEM((blk_q // tq, d, tq), jnp.float32),   # output accumulator, transposed
    ]
    o, lse = _length_call(kernel, (b, h, nq, nk), in_specs, out_specs,
                          out_shape, scratch_shapes, interpret, kv_lengths,
                          (q, k, v), "flash_fwd")
    return o, lse.reshape(b, h, lq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, scale, causal, window, masked, tq, tk, nq, nk):
    # grid (b, h, qi, j) as the forward; scores transposed, [k, q], and the
    # accumulator with them, [d, q]
    kv_len, refs = _split_lengths(refs, masked)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
    blk_q, blk_k = q_ref.shape[0], k_ref.shape[0]
    n_qt, n_kt = blk_q // tq, blk_k // tk
    qi, j = _grid_index(2, nq), _grid_index(3, nk)
    off = nk * blk_k - nq * blk_q
    masks = dict(causal=causal, window=window, kv_len=kv_len)
    into_q, rest = _scale_split(q_ref.dtype, scale)

    @_when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block(interior):
        def q_tile(t, _):
            q_lo = qi * blk_q + t * tq
            q = _scaled(_rows(q_ref, t, tq)[...], into_q)
            do = _rows(do_ref, t, tq)[...]
            lse, delta = lse_ref[t], delta_ref[t]  # [1, tq]

            def tile(u, acc, edge):
                k = _rows(k_ref, u, tk)[...]
                v = _rows(v_ref, u, tk)[...]
                s = _scores(k, q, rest)  # [tk, tq]
                if edge:
                    s = _mask_scores(s, q_lo + off, (j * n_kt + u) * tk, transposed=True,
                                     **masks)
                p = jnp.exp(s - lse)
                dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
                ds = p * (dp - delta)
                return acc + jax.lax.dot_general(k, ds.astype(k.dtype), _TN,
                                                 preferred_element_type=jnp.float32)

            bounds = ((0, 0, n_kt, n_kt) if interior else
                      _within(_k_walk(q_lo, tq, tk, nk * n_kt, off, **masks), j * n_kt, n_kt))
            acc = acc_ref.at[t]
            acc[...] = _walk(bounds, tile, acc[...], lower_edge=window is not None,
                             upper_edge=causal or masked)
            return 0

        _span(0, n_qt, q_tile, 0)

    _by_block_kind(j, _k_walk(qi * blk_q, blk_q, blk_k, nk, off, **masks), _block)

    @_when(j == nk - 1)
    def _finalize():
        for t in range(n_qt):
            dq_ref[t * tq:(t + 1) * tq, :] = (acc_ref[t] * scale).T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, window, masked, tq, tk, nq, nk):
    # grid (b, h, ki, i): one Q/dO block per step against a resident K/V
    # block; scores TRANSPOSED, [k, q], so lse/delta broadcast as the rows
    # they are and dv = p @ do, dk = ds @ q take no transposed operand
    kv_len, refs = _split_lengths(refs, masked)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = refs
    blk_q, blk_k = q_ref.shape[0], k_ref.shape[0]
    n_qt, n_kt = blk_q // tq, blk_k // tk
    ki, i = _grid_index(2, nk), _grid_index(3, nq)
    off = nk * blk_k - nq * blk_q
    masks = dict(causal=causal, window=window, kv_len=kv_len)
    into_k, rest = _scale_split(k_ref.dtype, scale)  # once a k tile, not once a q tile

    @_when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _block(interior):
        def k_tile(u, _):
            k_lo = ki * blk_k + u * tk
            k = _scaled(_rows(k_ref, u, tk)[...], into_k)
            v = _rows(v_ref, u, tk)[...]

            def tile(t, carry, edge):
                dk, dv = carry
                q = _rows(q_ref, t, tq)[...]
                do = _rows(do_ref, t, tq)[...]
                s = _scores(k, q, rest)  # [tk, tq]
                if edge:
                    s = _mask_scores(s, (i * n_qt + t) * tq + off, k_lo, transposed=True,
                                     **masks)
                p = jnp.exp(s - lse_ref[t])
                dv = dv + jax.lax.dot_general(p.astype(do.dtype), do, _NN,
                                              preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
                ds = p * (dp - delta_ref[t])
                dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q, _NN,
                                              preferred_element_type=jnp.float32)
                return dk, dv

            bounds = ((0, 0, n_qt, n_qt) if interior else
                      _within(_q_walk(k_lo, tk, tq, nq * n_qt, off, **masks), i * n_qt, n_qt))
            state = (_rows(dk_acc, u, tk), _rows(dv_acc, u, tk))
            carry = _walk(bounds, tile, tuple(r[...] for r in state),
                          lower_edge=causal or masked,
                          upper_edge=window is not None or masked)
            for r, x in zip(state, carry):
                r[...] = x
            return 0

        _span(0, n_kt, k_tile, 0)

    _by_block_kind(i, _q_walk(ki * blk_k, blk_k, blk_q, nq, off, **masks), _block)

    @_when(i == nq - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_tiles(lq, lk, blk_q, blk_k, tile, masked):
    """The dq kernel's (tq, tk) and the dkv kernel's. The dkv walk is
    straight-line code where one step holds the whole sequence, no bound
    waits for ``kv_lengths`` and no span of register tiles is long enough
    to become a loop (see _REGISTER_TILE)."""
    unrolled = (lq == blk_q and lk == blk_k and not masked
                and max(blk_q, blk_k) <= _UNROLL * _REGISTER_TILE)
    return (_tiles(blk_q, blk_k, tile)
            + _tiles(blk_q, blk_k, min(tile, _REGISTER_TILE) if unrolled else tile))


def _flash_bwd(res, g, scale, causal, blk_q, blk_k, tile, interpret, window=None):
    # blk_q/blk_k here are the BACKWARD blocks (may differ from forward)
    q, k, *_, kv_lengths = res
    b, h, lq, _ = q.shape
    lk = k.shape[2]
    tq, tk, dkv_tq, dkv_tk = _bwd_tiles(lq, lk, blk_q, blk_k, tile, kv_lengths is not None)
    static = dict(off=lk - lq, causal=causal, window=window)
    _count_tiles(_k_walk, lq // tq, tq, tk, lk // tk, b * h, **static)
    _count_tiles(_q_walk, lk // dkv_tk, dkv_tk, dkv_tq, lq // dkv_tq, b * h, **static)
    return _bwd_program(res, g, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                        tile=tile, interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=_PROGRAM_STATICS)
def _bwd_program(res, g, *, scale, causal, blk_q, blk_k, tile, interpret, window):
    q, k, v, o, lse, kv_lengths = res
    b, h, lq, d = q.shape
    lk = k.shape[2]
    nq, nk = lq // blk_q, lk // blk_k
    masked = kv_lengths is not None
    tq, tk, dkv_tq, dkv_tk = _bwd_tiles(lq, lk, blk_q, blk_k, tile, masked)
    do = g
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(axis=-1)  # [B,H,Lq]

    off = lk - lq
    kv_head = _kv_head(h, k.shape[1])
    kv_idx = _kv_index_map(causal, blk_q, blk_k, off, nk, masked, window, kv_head)
    qo_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, qi, 0), masked)
    stat_q_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, qi, 0, 0), masked)
    kernel_args = dict(scale=scale, causal=causal, window=window, masked=masked,
                       nq=nq, nk=nk)

    def _call(kernel, tq, tk, grid, in_specs, out_specs, out_shape, scratch, name):
        return _length_call(functools.partial(kernel, tq=tq, tk=tk, **kernel_args), grid,
                            in_specs, out_specs, out_shape, scratch, interpret, kv_lengths,
                            (q, k, v, do, _stat_rows(lse, tq), _stat_rows(delta, tq)), name)

    n_qt = blk_q // tq
    dq = _call(
        _bwd_dq_kernel, tq, tk, (b, h, nq, nk),
        [
            pl.BlockSpec((None, None, blk_q, d), qo_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_idx),
            pl.BlockSpec((None, None, blk_q, d), qo_idx),
            pl.BlockSpec((None, None, n_qt, 1, tq), stat_q_idx),
            pl.BlockSpec((None, None, n_qt, 1, tq), stat_q_idx),
        ],
        pl.BlockSpec((None, None, blk_q, d), qo_idx),
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((n_qt, d, tq), jnp.float32)], "flash_bwd_dq")

    def _q_block(bi, ki, i, lens):
        """Q block to fetch for dkv step (ki, i): dead steps (causally,
        past the window, or of a K block beyond the valid prefix) clamp to
        a live Q block, so their fetch is elided — the kernel skips those
        steps' FLOPs too."""
        first, _, _, end = _q_walk(ki * blk_k, blk_k, blk_q, nq, off, causal, window,
                                   lens[bi] if masked else None)
        return jnp.clip(i, jnp.minimum(first, nq - 1), jnp.maximum(end - 1, first))

    def q_idx(bi, hi, ki, i, *lens):
        return (bi, hi, _q_block(bi, ki, i, lens[0] if masked else None), 0)

    def stat_idx(bi, hi, ki, i, *lens):
        return (bi, hi, _q_block(bi, ki, i, lens[0] if masked else None), 0, 0)

    def kv_in_idx(bi, hi, ki, i, *lens):
        # inputs of a length-dead K block are never read — clamp to the
        # last live block so the fetch is elided; OUTPUTS still target ki
        # (their zero-initialized accumulators must be written back)
        if masked:
            ki = jnp.minimum(ki, jnp.maximum((lens[0][bi] + blk_k - 1) // blk_k, 1) - 1)
        return (bi, kv_head(hi), ki, 0)

    kv_out_idx = _pad_idx(lambda bi, hi, ki, i: (bi, hi, ki, 0), masked)
    dk, dv = _call(
        _bwd_dkv_kernel, dkv_tq, dkv_tk, (b, h, nk, nq),
        [
            pl.BlockSpec((None, None, blk_q, d), q_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_in_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_in_idx),
            pl.BlockSpec((None, None, blk_q, d), q_idx),
            pl.BlockSpec((None, None, blk_q // dkv_tq, 1, dkv_tq), stat_idx),
            pl.BlockSpec((None, None, blk_q // dkv_tq, 1, dkv_tq), stat_idx),
        ],
        [
            pl.BlockSpec((None, None, blk_k, d), kv_out_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_out_idx),
        ],
        [
            jax.ShapeDtypeStruct((b, h) + k.shape[2:], k.dtype),
            jax.ShapeDtypeStruct((b, h) + v.shape[2:], v.dtype),
        ],
        [pltpu.VMEM((blk_k, d), jnp.float32),
         pltpu.VMEM((blk_k, d), jnp.float32)], "flash_bwd_dkv")
    if k.shape[1] != h:
        # grouped-query heads: the kernel wrote one dk, dv a QUERY head; a key
        # head's gradient is the sum over the query heads that read it (what
        # the transpose of a ``jnp.repeat`` would add up, with no repeated
        # k, v ever written)
        def grouped(t, like):
            return t.reshape(b, like.shape[1], h // like.shape[1], lk, d).astype(
                jnp.float32).sum(axis=2).astype(like.dtype)
        dk, dv = grouped(dk, k), grouped(dv, v)
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# public op (BHLD), custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash_attention_bhld(q, k, v, kv_lengths, scale, causal, blk_q, blk_k,
                          blk_q_bwd, blk_k_bwd, tile, policy, interpret,
                          window):
    o, _ = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, tile, interpret,
                      kv_lengths=kv_lengths, window=window)
    return o


def _flash_attention_bhld_fwd(q, k, v, kv_lengths, scale, causal, blk_q, blk_k,
                              blk_q_bwd, blk_k_bwd, tile, policy, interpret,
                              window):
    o, lse = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, tile, interpret,
                        kv_lengths=kv_lengths, window=window)
    # policy="recompute": don't stash the [B,H,L] log-sum-exp — the backward
    # regenerates it with one extra forward pass. Saves the residual HBM
    # held per layer between forward and backward (remat-style tradeoff).
    return o, (q, k, v, o, lse if policy != "recompute" else None, kv_lengths)


def _flash_attention_bhld_bwd(scale, causal, blk_q, blk_k, blk_q_bwd, blk_k_bwd, tile,
                              policy, interpret, window, res, g):
    q, k, v, o, lse, kv_lengths = res
    if lse is None:  # recompute policy: regenerate lse at the forward blocks
        _, lse = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, tile, interpret,
                            kv_lengths=kv_lengths, window=window)
    return _flash_bwd((q, k, v, o, lse, kv_lengths), g, scale, causal,
                      blk_q_bwd, blk_k_bwd, tile, interpret, window=window)


_flash_attention_bhld.defvjp(_flash_attention_bhld_fwd, _flash_attention_bhld_bwd)


# ---------------------------------------------------------------------------
# decode (inference): q of a few tokens vs a static KV cache with
# per-sequence valid lengths (reference fused decode softmax,
# ``csrc/transformer/inference/csrc/softmax.cu`` attn_softmax_v2 +
# ``pt_binding.cpp:1935-1975`` workspace attention). No VJP — serving only.
# ---------------------------------------------------------------------------
def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, blk_k, lq, nk):
    bi, j = pl.program_id(0), pl.program_id(2)
    length = lens_ref[bi]

    @_when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # kv blocks past the sequence's last live token move no bytes (the index
    # map clamps, Mosaic elides the DMA) and run no FLOPs
    nk_eff = (jnp.maximum(length, 1) - 1) // blk_k + 1

    @pl.when((j < nk_eff) & (length > 0))
    def _block():
        q = q_ref[...].astype(jnp.float32) * scale          # [lq, d]
        k = k_ref[...].astype(jnp.float32)                  # [blk_k, d]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [lq, blk_k]
        # q row i sits at global position length - lq + i; kv col c at
        # j*blk_k + c; causal validity: kv_pos <= q_pos
        q_pos = length - lq + jax.lax.broadcasted_iota(jnp.int32, (lq, blk_k), 0)
        k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (lq, blk_k), 1)
        valid = k_pos <= q_pos
        s = jnp.where(valid, s, NEG_INF)
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, s.max(axis=-1))
        # explicit zero for masked probs: a fully-masked row (q_pos < 0, i.e.
        # lq > length) must produce zeros, not exp(NEG_INF - NEG_INF) = 1
        p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(j == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_decode(q: jax.Array,
                 k: jax.Array,
                 v: jax.Array,
                 lengths: jax.Array,
                 *,
                 scale: Optional[float] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Length-masked attention of ``q`` [B, Lq, H, D] (the newest Lq tokens)
    against a KV cache [B, Lkv, H, D] where only ``lengths[b]`` slots are
    live. Streams one K/V block per grid step; blocks beyond a sequence's
    length are skipped (FLOPs and DMA). Rows with no live positions
    (``lq > lengths[b]``) return zeros."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = backend.interpret_default()
    blk_k = block_k or pick_block(lk)
    if lk % blk_k:
        raise ValueError(f"KV cache length {lk} not divisible by block {blk_k}")
    nk = lk // blk_k
    lengths = lengths.astype(jnp.int32)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    def kv_idx(bi, hi, j, lens):
        # index maps receive (*grid_indices, *scalar_prefetch_refs)
        last = (jnp.maximum(lens[bi], 1) - 1) // blk_k
        return (bi, hi, jnp.minimum(j, last), 0)

    kernel = functools.partial(_decode_kernel, scale=float(scale), blk_k=blk_k,
                               lq=lq, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nk),
        in_specs=[
            pl.BlockSpec((None, None, lq, d), lambda bi, hi, j, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, blk_k, d), kv_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_idx),
        ],
        out_specs=pl.BlockSpec((None, None, lq, d), lambda bi, hi, j, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((lq, 1), jnp.float32),
            pltpu.VMEM((lq, 1), jnp.float32),
            pltpu.VMEM((lq, d), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        interpret=interpret,
    )(lengths, qt, kt, vt)
    return o.transpose(0, 2, 1, 3)


@register_backend("flash")
def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    *,
                    causal: bool = True,
                    bias: Optional[jax.Array] = None,
                    mask: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    dropout_rate: float = 0.0,
                    dropout_rng: Optional[jax.Array] = None,
                    decode_lengths: Optional[jax.Array] = None,
                    kv_lengths: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    tile: Optional[int] = None,
                    policy: Optional[str] = None,
                    geometry_spec: Optional[str] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention over BLHD tensors; falls back to the XLA backend for
    features the kernel doesn't cover (bias/arbitrary mask/dropout).

    ``kv_lengths`` [B]: per-sequence valid K prefix for RIGHT-PADDED
    batches (the standard HF padding; BERT-style encoders) — handled
    natively by the kernel in forward AND backward, no XLA fallback. Only
    pass it for contiguous-prefix masks; arbitrary masks must go through
    ``mask=`` (which falls back).

    ``window``: sliding-window size (Mistral semantics, requires
    ``causal=True``) — each query attends keys in ``(pos-window, pos]``;
    out-of-window blocks skip their FLOPs and DMA in both passes, so the
    cost is O(L*window) instead of O(L^2).

    Block geometry + backward policy (``block_q``/``block_k`` forward,
    ``block_q_bwd``/``block_k_bwd`` backward, the compute ``tile`` the
    blocks are walked in, clamped to their divisors, ``policy`` in {"lse",
    "recompute"}): any knob left None takes the shape's measured default
    (``attention_geometry.resolve_geometry``).

    Direct block kwargs that don't tile the call warn and fall back to
    XLA (the historical contract). ``geometry_spec`` — a spec string, the
    vehicle for per-model ``attention_blocks`` config pins and the engine's
    ``"attention"`` block — instead joins
    the resolution as a highest-precedence layer whose blocks are CLAMPED
    to divisors like every other layer, so a pin tuned at one shape can
    never knock another shape off the kernel."""
    b, lq, h, d = q.shape
    lk = k.shape[1]

    def xla_attention(q, k, v, **kwargs):
        """The XLA backend, handed as many key heads as query heads."""
        from deepspeed_tpu.ops.transformer.attention import xla_attention as xla
        if k.shape[2] != h:
            k, v = (jnp.repeat(t, h // t.shape[2], axis=2) for t in (k, v))
        return xla(q, k, v, **kwargs)

    if decode_lengths is not None and kv_lengths is not None:
        raise ValueError("pass decode_lengths (cache decode) or kv_lengths "
                         "(padded prefill), not both")
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires causal=True")
    if window is not None and decode_lengths is not None:
        raise ValueError("window is a prefill/training feature; the decode path "
                         "attends the whole cache")
    if decode_lengths is not None:
        # KV-cache decode: per-sequence length masking in the kernel
        if bias is None and mask is None and dropout_rate == 0.0 and lk % (block_k or pick_block(lk)) == 0:
            return flash_decode(q, k, v, decode_lengths, scale=scale,
                                block_k=block_k, interpret=interpret)
        _warn_fallback("decode with bias/mask/dropout or untileable cache")
        return xla_attention(q, k, v, causal=False, bias=bias, mask=mask, scale=scale,
                             dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                             decode_lengths=decode_lengths)
    if bias is not None or mask is not None or (dropout_rate > 0.0 and dropout_rng is not None) \
            or (causal and lq > lk):
        _warn_fallback("bias/mask/dropout or lq>lk requested")
        return xla_attention(q, k, v, causal=causal, bias=bias, mask=mask, scale=scale,
                             dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                             kv_lengths=kv_lengths, window=window)
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = backend.interpret_default()
    # explicit block kwargs keep the historical contract: a size that does
    # not tile the call warns and falls back to XLA (lower-precedence
    # layers are instead clamped to divisors inside resolve_geometry)
    if (block_q and lq % block_q) or (block_k and lk % block_k) \
            or (block_q_bwd and lq % block_q_bwd) or (block_k_bwd and lk % block_k_bwd):
        _warn_fallback(f"sequence lengths ({lq}, {lk}) not tileable by "
                       f"explicit blocks")
        return xla_attention(q, k, v, causal=causal, scale=scale,
                             kv_lengths=kv_lengths, window=window)
    overrides = AttentionGeometry(block_q=block_q, block_k=block_k,
                                  block_q_bwd=block_q_bwd,
                                  block_k_bwd=block_k_bwd, tile=tile, policy=policy)
    if geometry_spec:
        overrides = overrides.merged_over(parse_spec(geometry_spec))
    geom, _ = resolve_geometry(lq, lk, d, h, b, bool(causal), q.dtype,
                               overrides=overrides)

    def local(q, k, v, kv_lengths):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        o = _flash_attention_bhld(qt, kt, vt, kv_lengths, float(scale), bool(causal),
                                  geom.block_q, geom.block_k,
                                  geom.block_q_bwd, geom.block_k_bwd, geom.tile,
                                  geom.policy, interpret,
                                  int(window) if window is not None else None)
        return o.transpose(0, 2, 1, 3)

    if interpret:
        # interpreted kernels lower to plain XLA ops, which GSPMD partitions
        return local(q, k, v, kv_lengths)
    return per_shard(local, q, k, v, kv_lengths)
