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

Scaling: K/V (fwd, bwd-dq) and Q/dO (bwd-dkv) are GRIDDED — the reduction
axis is the innermost grid dimension, one block streams into VMEM per grid
step (Mosaic double-buffers the next block's DMA behind the current
matmul), and the online-softmax state rides VMEM scratch across steps.
VMEM held per step is a few blocks, independent of sequence length, so the
single-chip ceiling is HBM, not VMEM (the previous
design staged full-length K/V per cell, capping L at ~24k). Causally dead
K blocks skip their FLOPs via ``pl.when``. Longer-than-HBM contexts remain
the job of sequence parallelism (``deepspeed_tpu.parallel.ring_attention``).

Work partitioning is TUNABLE (``attention_geometry``): forward and backward
block sizes are independent (FlashAttention-2's dq/dkv passes prefer
different tilings than the forward), the backward's causal work-skipping
is a policy (``bwd_skip``: "block" gates dead grid steps behind ``pl.when``
+ index-map clamps; "none" runs every step and masks — less scalar
overhead, sometimes faster at short L), and the backward can either read
the stashed log-sum-exp residual (``policy="lse"``) or recompute it with an
extra forward pass (``policy="recompute"`` — drops the [B,H,L] residual per
layer between fwd and bwd, which matters under remat at long L). Unset
knobs resolve through env/config/autotune-cache/shape defaults
(``attention_geometry.resolve_geometry``).
"""

import functools
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

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _apply_causal_mask(s, qi, j, blk_q, blk_k, off):
    """Mask scores [blk_q, blk_k] for q block ``qi`` vs k block ``j`` with a
    kv-cache decode offset ``off = lk - lq``."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0) + off
    k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _last_k_block(qi, blk_q, blk_k, off, nk):
    """Number of k blocks intersecting q block ``qi``'s causal window."""
    return jnp.minimum(nk, (qi * blk_q + blk_q - 1 + off) // blk_k + 1)


def _apply_kv_length_mask(s, j, blk_k, kv_len):
    """Mask score columns at-or-beyond this sequence's valid K prefix
    (right-padding contract: positions [0, kv_len) are real)."""
    k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos < kv_len, s, NEG_INF)


def _apply_window_mask(s, qi, j, blk_q, blk_k, off, window):
    """Sliding-window mask: query attends keys in (q_pos - window, q_pos]
    (Mistral semantics; combine with the causal mask for the upper edge)."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + off
    k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos > q_pos - window, s, NEG_INF)


def _first_k_block(qi, blk_q, blk_k, off, window):
    """First K block intersecting q block ``qi``'s sliding window."""
    return jnp.maximum((qi * blk_q + off - window + 1) // blk_k, 0)


def _last_q_block(ki, blk_q, blk_k, off, window):
    """Last Q block whose sliding window still reaches K block ``ki``
    (single source for the dkv kernel's skip AND its fetch clamp — the two
    must agree or skipped blocks would clamp to unfetched data)."""
    return (ki * blk_k + blk_k - 1 + window - 1 - off) // blk_q


def _n_live_blocks(kv_len, blk_k):
    """K blocks intersecting the valid prefix (>=1 so state initializes)."""
    return jnp.maximum((kv_len + blk_k - 1) // blk_k, 1)


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
    heads = dividing((TENSOR_AXIS,), q.shape[2])
    blhd = P(batch, None, heads, None)
    in_specs = (blhd, blhd, blhd, None if lengths is None else P(batch))
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=blhd,
                         axis_names=set(free), check_vma=False)(q, k, v, lengths)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, blk_q, blk_k, nq, nk, masked, window):
    # grid (b, h, qi, j): one K/V block per step; m/l/acc ride VMEM scratch.
    # With ``masked`` the first ref is the scalar-prefetched [B] kv-lengths.
    if masked:
        lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        kv_len = lens_ref[pl.program_id(0)]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        kv_len = None
    qi, j = pl.program_id(2), pl.program_id(3)
    off = nk * blk_k - nq * blk_q  # kv-cache decode offset

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nk_eff = _last_k_block(qi, blk_q, blk_k, off, nk) if causal else nk
    if masked:
        nk_eff = jnp.minimum(nk_eff, _n_live_blocks(kv_len, blk_k))
    live = j < nk_eff
    if window is not None:
        live = live & (j >= _first_k_block(qi, blk_q, blk_k, off, window))

    @pl.when(live)
    def _block():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [blk_q, blk_k]
        if causal:
            s = _apply_causal_mask(s, qi, j, blk_q, blk_k, off)
        if masked:
            s = _apply_kv_length_mask(s, j, blk_k, kv_len)
        if window is not None:
            s = _apply_window_mask(s, qi, j, blk_q, blk_k, off, window)
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, s.max(axis=-1))
        # fully-masked score rows keep m = -inf; anchor the exp at 0 there
        # so p stays finite (and exactly 0)
        anchor = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - anchor[:, None])
        alpha = jnp.exp(jnp.maximum(m, NEG_INF / 2) - anchor)
        l_new = l * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        m = m_ref[:, 0]
        l_safe = jnp.maximum(l, 1e-37)
        o_ref[...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse rides a [B,H,L] array (ref block [1, blk_q]): a trailing
        # [..., 1] dim would tile-pad to 128 lanes — 128x the HBM held as
        # backward residuals (128 MB/layer at b=16,h=16,L=1024).
        # Rows with no live keys (query beyond every valid K) get a large
        # FINITE negative lse so the backward's exp(s - lse) is exactly 0
        # instead of exp(-inf + inf) = NaN.
        lse_vec = jnp.where(l > 0, jnp.maximum(m, NEG_INF / 2) + jnp.log(l_safe),
                            NEG_INF / 2)
        lse_ref[...] = lse_vec[None, :]


def _pad_idx(fn, masked):
    """Under PrefetchScalarGridSpec, index maps receive the scalar-prefetch
    refs as extra trailing args — drop them for maps that don't care."""
    return (lambda *a: fn(*a[:-1])) if masked else fn


def _length_call(kernel, grid, in_specs, out_specs, out_shape, scratch,
                 interpret, kv_lengths, args):
    """One pallas_call dispatch for the optional [B]-lengths scalar-prefetch
    operand (shared by fwd and both bwd passes so the masked/unmasked
    switch cannot drift between them)."""
    if kv_lengths is not None:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret,
        )(kv_lengths.astype(jnp.int32), *args)
    return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          scratch_shapes=scratch, interpret=interpret)(*args)


def _kv_index_map(causal, blk_q, blk_k, off, nk, masked=False, window=None):
    """K/V block index for grid step (qi, j). Dead steps — causally dead,
    beyond the sequence's valid K prefix, or outside the sliding window —
    CLAMP to a live block: the index map re-requests the already-resident
    block, Mosaic elides the DMA, and the dead step moves no HBM bytes
    (the `pl.when` in the kernel already skips its FLOPs)."""
    if not causal and not masked and window is None:
        return lambda bi, hi, qi, j: (bi, hi, j, 0)

    def index(bi, hi, qi, j, *lens):
        last = nk - 1
        if causal:
            last = jnp.minimum(last, (qi * blk_q + blk_q - 1 + off) // blk_k)
        if masked:
            last = jnp.minimum(last, _n_live_blocks(lens[0][bi], blk_k) - 1)
        j_eff = jnp.minimum(j, last)
        if window is not None:
            j_eff = jnp.maximum(j_eff, jnp.minimum(
                _first_k_block(qi, blk_q, blk_k, off, window), last))
        return (bi, hi, j_eff, 0)

    return index


def _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret, kv_lengths=None,
               window=None):
    # q,k,v: [B,H,L,D]; kv_lengths: optional [B] valid-prefix lengths;
    # window: optional sliding-window size (causal only)
    b, h, lq, d = q.shape
    lk = k.shape[2]
    nq, nk = lq // blk_q, lk // blk_k
    off = lk - lq
    masked = kv_lengths is not None
    kv_idx = _kv_index_map(causal, blk_q, blk_k, off, nk, masked, window)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               blk_q=blk_q, blk_k=blk_k, nq=nq, nk=nk,
                               masked=masked, window=window)
    qo_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, qi, 0), masked)
    in_specs = [
        pl.BlockSpec((None, None, blk_q, d), qo_idx),
        pl.BlockSpec((None, None, blk_k, d), kv_idx),
        pl.BlockSpec((None, None, blk_k, d), kv_idx),
    ]
    out_specs = [
        pl.BlockSpec((None, None, blk_q, d), qo_idx),
        # stats ride a [B,H,1,L] array — Mosaic accepts the size-1 block
        # dim because it equals the array dim, and the caller squeezes to
        # a compact [B,H,L] residual. A trailing [..., 1] dim instead
        # would tile-pad to 128 lanes (128 MB/layer of backward
        # residuals at b=16,h=16,L=1024).
        pl.BlockSpec((None, None, 1, blk_q),
                     _pad_idx(lambda bi, hi, qi, j: (bi, hi, 0, qi), masked)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, 1, lq), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
        pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
        pltpu.VMEM((blk_q, d), jnp.float32),   # output accumulator
    ]
    o, lse = _length_call(kernel, (b, h, nq, nk), in_specs, out_specs,
                          out_shape, scratch_shapes, interpret, kv_lengths,
                          (q, k, v))
    return o, lse.reshape(b, h, lq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, scale, causal, blk_q, blk_k, nq, nk, masked, window, skip):
    if masked:
        lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
        kv_len = lens_ref[pl.program_id(0)]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
        kv_len = None
    qi, j = pl.program_id(2), pl.program_id(3)
    off = nk * blk_k - nq * blk_q

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if skip:
        nk_eff = _last_k_block(qi, blk_q, blk_k, off, nk) if causal else nk
        if masked:
            nk_eff = jnp.minimum(nk_eff, _n_live_blocks(kv_len, blk_k))
        live = j < nk_eff
        if window is not None:
            live = live & (j >= _first_k_block(qi, blk_q, blk_k, off, window))

    def _block():
        q = q_ref[...].astype(jnp.float32) * scale
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, qi, j, blk_q, blk_k, off)
        if masked:
            s = _apply_kv_length_mask(s, j, blk_k, kv_len)
        if window is not None:
            s = _apply_window_mask(s, qi, j, blk_q, blk_k, off, window)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    if skip:
        pl.when(live)(_block)
    else:
        # bwd_skip="none": every step computes unpredicated; the score masks
        # above zero dead contributions (p = exp(NEG_INF - finite lse) = 0)
        _block()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, blk_q, blk_k, nq, nk, masked, window, skip):
    if masked:
        (lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        kv_len = lens_ref[pl.program_id(0)]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        kv_len = None
    ki, i = pl.program_id(2), pl.program_id(3)
    off = nk * blk_k - nq * blk_q

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if skip:
        if causal:
            # first q block whose causal window reaches this k block
            first = jnp.maximum((ki * blk_k - off) // blk_q, 0)
        else:
            first = 0

        live = (i >= first)
        if masked:
            # K blocks entirely beyond the valid prefix contribute nothing —
            # skip all their FLOPs (their dk/dv stay at the zero-initialized acc)
            live = live & (ki * blk_k < kv_len)
        if window is not None:
            live = live & (i <= _last_q_block(ki, blk_q, blk_k, off, window))

    def _block():
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32) * scale
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, i, ki, blk_q, blk_k, off)
        if masked:
            s = _apply_kv_length_mask(s, ki, blk_k, kv_len)
        if window is not None:
            s = _apply_window_mask(s, i, ki, blk_q, blk_k, off, window)
        p = jnp.exp(s - lse[:, None])
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    if skip:
        pl.when(live)(_block)
    else:
        # bwd_skip="none": unpredicated — masking alone zeroes dead
        # contributions (fully-masked rows carry a finite large-negative
        # lse, so exp(s - lse) is exactly 0, never NaN)
        _block()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, blk_q, blk_k, interpret, window=None,
               skip=True):
    # blk_q/blk_k here are the BACKWARD blocks (may differ from forward);
    # skip=False (bwd_skip="none") drops the liveness predicates AND the
    # DMA-eliding index-map clamps — every grid step fetches and computes.
    q, k, v, o, lse, kv_lengths = res
    b, h, lq, d = q.shape
    lk = k.shape[2]
    nq, nk = lq // blk_q, lk // blk_k
    masked = kv_lengths is not None
    do = g
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(axis=-1)  # [B,H,Lq]
    # size-1 dim ahead of Lq (not after): blocks (None, None, 1, blk_q) pass
    # Mosaic's tiling rule and the buffers pad 8x (sublane) instead of 128x
    lse4 = lse.reshape(b, h, 1, lq)
    delta4 = delta.reshape(b, h, 1, lq)

    off = lk - lq
    if skip:
        kv_idx = _kv_index_map(causal, blk_q, blk_k, off, nk, masked, window)
    else:
        kv_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, j, 0), masked)
    qo_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, qi, 0), masked)
    stat_q_idx = _pad_idx(lambda bi, hi, qi, j: (bi, hi, 0, qi), masked)

    def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, args):
        return _length_call(kernel, grid, in_specs, out_specs, out_shape,
                            scratch, interpret, kv_lengths, args)

    dq = _call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, blk_q=blk_q,
                          blk_k=blk_k, nq=nq, nk=nk, masked=masked, window=window,
                          skip=skip),
        (b, h, nq, nk),
        [
            pl.BlockSpec((None, None, blk_q, d), qo_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_idx),
            pl.BlockSpec((None, None, blk_q, d), qo_idx),
            pl.BlockSpec((None, None, 1, blk_q), stat_q_idx),
            pl.BlockSpec((None, None, 1, blk_q), stat_q_idx),
        ],
        pl.BlockSpec((None, None, blk_q, d), qo_idx),
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((blk_q, d), jnp.float32)],
        (q, k, v, do, lse4, delta4))

    def _q_block(bi, ki, i, lens):
        """Q block to fetch for dkv step (ki, i): causally-dead steps clamp
        forward to the first live Q block; length-dead K blocks clamp to a
        constant so their whole i-loop re-requests one resident block (DMA
        elided — the kernel skips those steps' FLOPs too)."""
        i_eff = i
        if causal:
            i_eff = jnp.maximum(i_eff, jnp.maximum((ki * blk_k - off) // blk_q, 0))
        if window is not None:
            i_eff = jnp.minimum(i_eff, jnp.maximum(
                _last_q_block(ki, blk_q, blk_k, off, window), 0))
        if masked:
            i_eff = jnp.where(ki * blk_k < lens[bi], i_eff, 0)
        return i_eff

    def q_idx(bi, hi, ki, i, *lens):
        return (bi, hi, _q_block(bi, ki, i, lens[0] if masked else None), 0)

    def stat_idx(bi, hi, ki, i, *lens):
        return (bi, hi, 0, _q_block(bi, ki, i, lens[0] if masked else None))

    def kv_in_idx(bi, hi, ki, i, *lens):
        # inputs of a length-dead K block are never read — clamp to the
        # last live block so the fetch is elided; OUTPUTS still target ki
        # (their zero-initialized accumulators must be written back)
        ki_eff = (jnp.minimum(ki, _n_live_blocks(lens[0][bi], blk_k) - 1)
                  if masked else ki)
        return (bi, hi, ki_eff, 0)

    if not skip:
        q_idx = _pad_idx(lambda bi, hi, ki, i: (bi, hi, i, 0), masked)
        stat_idx = _pad_idx(lambda bi, hi, ki, i: (bi, hi, 0, i), masked)
        kv_in_idx = _pad_idx(lambda bi, hi, ki, i: (bi, hi, ki, 0), masked)

    kv_out_idx = _pad_idx(lambda bi, hi, ki, i: (bi, hi, ki, 0), masked)
    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, blk_q=blk_q,
                          blk_k=blk_k, nq=nq, nk=nk, masked=masked, window=window,
                          skip=skip),
        (b, h, nk, nq),
        [
            pl.BlockSpec((None, None, blk_q, d), q_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_in_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_in_idx),
            pl.BlockSpec((None, None, blk_q, d), q_idx),
            pl.BlockSpec((None, None, 1, blk_q), stat_idx),
            pl.BlockSpec((None, None, 1, blk_q), stat_idx),
        ],
        [
            pl.BlockSpec((None, None, blk_k, d), kv_out_idx),
            pl.BlockSpec((None, None, blk_k, d), kv_out_idx),
        ],
        [
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        [pltpu.VMEM((blk_k, d), jnp.float32),
         pltpu.VMEM((blk_k, d), jnp.float32)],
        (q, k, v, do, lse4, delta4))
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# public op (BHLD), custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash_attention_bhld(q, k, v, kv_lengths, scale, causal, blk_q, blk_k,
                          blk_q_bwd, blk_k_bwd, bwd_skip, policy, interpret,
                          window):
    o, _ = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret,
                      kv_lengths=kv_lengths, window=window)
    return o


def _flash_attention_bhld_fwd(q, k, v, kv_lengths, scale, causal, blk_q, blk_k,
                              blk_q_bwd, blk_k_bwd, bwd_skip, policy, interpret,
                              window):
    o, lse = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret,
                        kv_lengths=kv_lengths, window=window)
    # policy="recompute": don't stash the [B,H,L] log-sum-exp — the backward
    # regenerates it with one extra forward pass. Saves the residual HBM
    # held per layer between forward and backward (remat-style tradeoff).
    return o, (q, k, v, o, lse if policy != "recompute" else None, kv_lengths)


def _flash_attention_bhld_bwd(scale, causal, blk_q, blk_k, blk_q_bwd, blk_k_bwd,
                              bwd_skip, policy, interpret, window, res, g):
    q, k, v, o, lse, kv_lengths = res
    if lse is None:  # recompute policy: regenerate lse at the forward blocks
        _, lse = _flash_fwd(q, k, v, scale, causal, blk_q, blk_k, interpret,
                            kv_lengths=kv_lengths, window=window)
    return _flash_bwd((q, k, v, o, lse, kv_lengths), g, scale, causal,
                      blk_q_bwd, blk_k_bwd, interpret, window=window,
                      skip=(bwd_skip != "none"))


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

    @pl.when(j == 0)
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
                    bwd_skip: Optional[str] = None,
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
    ``block_q_bwd``/``block_k_bwd`` backward, ``bwd_skip`` in
    {"block", "none"}, ``policy`` in {"lse", "recompute"}): any knob left
    None resolves through the autotuner's shape-keyed winners cache, then
    v5e shape defaults (``attention_geometry.resolve_geometry``).

    Direct block kwargs that don't tile the call warn and fall back to
    XLA (the historical contract). ``geometry_spec`` — a spec string, the
    vehicle for per-model ``attention_blocks`` config pins and the engine's
    ``"attention"`` block — instead joins
    the resolution as a highest-precedence layer whose blocks are CLAMPED
    to divisors like every other layer, so a pin tuned at one shape can
    never knock another shape off the kernel."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
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
        from deepspeed_tpu.ops.transformer.attention import xla_attention
        return xla_attention(q, k, v, causal=False, bias=bias, mask=mask, scale=scale,
                             dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                             decode_lengths=decode_lengths)
    if bias is not None or mask is not None or (dropout_rate > 0.0 and dropout_rng is not None) \
            or (causal and lq > lk):
        _warn_fallback("bias/mask/dropout or lq>lk requested")
        from deepspeed_tpu.ops.transformer.attention import xla_attention
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
        from deepspeed_tpu.ops.transformer.attention import xla_attention
        return xla_attention(q, k, v, causal=causal, scale=scale,
                             kv_lengths=kv_lengths, window=window)
    overrides = AttentionGeometry(block_q=block_q, block_k=block_k,
                                  block_q_bwd=block_q_bwd,
                                  block_k_bwd=block_k_bwd,
                                  bwd_skip=bwd_skip, policy=policy)
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
                                  geom.block_q_bwd, geom.block_k_bwd,
                                  geom.bwd_skip, geom.policy, interpret,
                                  int(window) if window is not None else None)
        return o.transpose(0, 2, 1, 3)

    if interpret:
        # interpreted kernels lower to plain XLA ops, which GSPMD partitions
        return local(q, k, v, kv_lengths)
    return per_shard(local, q, k, v, kv_lengths)
