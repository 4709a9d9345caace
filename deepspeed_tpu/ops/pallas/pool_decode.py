"""A decode tick's read of a stored KV pool, as one kernel: one query a
sequence against that sequence's pool **as it is stored** (``keys`` / ``values``
[slots, kv heads, head dim, positions], int8 codes or floating, positions
minor-most; of int8 pools a scale a position, [slots, kv heads, positions]),
grouped-query: a block of positions comes into VMEM once and every key head's
codes meet the ``rep`` query heads that read them, under a running softmax in
float32. A slot's blocks past its live end move no bytes (the index map clamps,
so the DMA is elided) and run no FLOPs; a parked slot (``fed`` 0) stays on the
block the slot before it left in VMEM, reads nothing and gives zeros.

Two bodies, chosen by the static ``rep``. Several query heads a key head
(Laguna's 6 and 8, Nemotron's 16): a key head at a time, its ``rep`` queries
against its codes. ONE query head a key head (GPT-2, OLMoE): a head's products
alone are a matrix times one row and its running softmax a row of one, sixteen
times a block, which costs more than the block's bytes; there the key heads'
codes are taken side by side, [kv heads x head dim, block], and every head is
scored in one matmul against the queries laid block-diagonally, the softmax
runs over all heads at once, and the values' matmul gives every head's sum in
its own columns (``PERF.md`` section 6, PR 49: 0.168 -> 0.098 ms over GPT-2
medium's 32 full slots, 705 GB/s).

The arithmetic is ``models/common.py`` ``cached_attention``'s: the codes go into
the matmuls as they are, a position's key scale multiplies its score and its
value scale its probability, nothing is dequantised whole and no key head is
repeated; what a query may read is ``models/common.py`` ``ring_mask`` of its own
position, so a full pool (a ring of its own extent under a window of it) and a
window layer's ring are one body. XLA's form, a loop that walks every slot's
pool together as far as the longest goes, stays: it runs off the chip, and it is
what this kernel is tested against. Serving only, no VJP.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend

NEG_INF = float(jnp.finfo(jnp.float32).min)

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _kernel(steps_ref, at_ref, row_ref, last_ref, *rest,
            scale, block, n_blocks, places, window, scaled, kv, rep, d, looped=False):
    if looped:
        # the pass whose heads the index maps picked: nothing to do with it here
        rest = rest[1:]
    q_ref, k_ref, v_ref, *rest = rest
    if scaled:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_ref, l_ref, acc_ref, *scales32 = rest
    s_i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def seen_of(rows):
        # ring_mask: place r holds position t - (t - r) mod places; t mod places
        # on the scalar core, the rest one subtraction away (|t' - r| < places)
        at = at_ref[s_i]
        back = jax.lax.rem(at, places) - (
            j * block + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1))
        back = jnp.where(back < 0, back + places, back)
        return (back < window) & (at - back >= 0)

    def own_head():
        """[kv, kv * d] bool: the columns of row ``h`` that are key head
        ``h``'s, of every key head's side by side."""
        col = jax.lax.broadcasted_iota(jnp.int32, (kv, kv * d), 1)
        first = jax.lax.broadcasted_iota(jnp.int32, (kv, kv * d), 0) * d
        return (col >= first) & (col < first + d)

    def softmax_step(s, seen, key_scale, value_scale, m, l):
        """One block of the running softmax over scores ``s`` [rows, block]:
        ``(m, l, shrink, probabilities)``, the scales [rows or 1, block] float32."""
        if scaled:
            s = s * key_scale
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        shrink = jnp.exp(m - m_new)
        l = l * shrink + p.sum(axis=-1, keepdims=True)
        return m_new, l, shrink, p * value_scale if scaled else p

    @pl.when(j < steps_ref[s_i])
    def _block():
        if rep == 1:
            # a query head a key head: each head's products alone are a matrix
            # times ONE row, and sixteen running softmaxes of one row each cost
            # more than the block's bytes (PERF.md, PR 49). The key heads' codes
            # lie side by side, [kv * d, block], so every head is scored in one
            # matmul against the queries laid block-diagonally, [kv, kv * d]:
            # row h holds query h over key head h's rows and zeros elsewhere
            # (picked in float32: a mask lies as 32-bit rows do)
            q = jnp.where(own_head(), jnp.broadcast_to(
                q_ref[...].astype(jnp.float32), (kv, kv * d)), 0.0).astype(q_ref.dtype)
            s = jax.lax.dot_general(q, k_ref[...].astype(q.dtype), _NN,
                                    preferred_element_type=jnp.float32) * scale
            m, l, shrink, p = softmax_step(
                s, seen_of(kv), ks_ref[...].astype(jnp.float32) if scaled else None,
                vs_ref[...].astype(jnp.float32) if scaled else None, m_ref[...], l_ref[...])
            m_ref[...], l_ref[...] = m, l
            # [kv, kv * d]: row h's own columns are head h's sum (_finalize)
            acc_ref[...] = acc_ref[...] * shrink + jax.lax.dot_general(
                p.astype(q.dtype), v_ref[...].astype(q.dtype), _NT,
                preferred_element_type=jnp.float32)
            return
        seen = seen_of(rep)
        if scaled:
            # in float32, where a key head's row can be picked by a run-time index
            ks32_ref, vs32_ref = scales32
            ks32_ref[...] = ks_ref[...].astype(jnp.float32)          # [kv, block]
            vs32_ref[...] = vs_ref[...].astype(jnp.float32)

        # traced once and unrolled where it is lowered: eight copies of the body
        # in Python cost every program that holds the kernel a second of set-up
        # to trace, and a rolled loop cannot overlap one head's matmuls with the
        # next one's conversion (0.51 for 0.44 ms a full layer: PERF.md, PR 47)
        def head(h, _):
            q = q_ref[h]                                             # [rep, d]
            s = jax.lax.dot_general(q, k_ref[h].astype(q.dtype), _NN,
                                    preferred_element_type=jnp.float32) * scale
            m, l, shrink, p = softmax_step(
                s, seen, ks32_ref[pl.ds(h, 1), :] if scaled else None,
                vs32_ref[pl.ds(h, 1), :] if scaled else None, m_ref[h], l_ref[h])
            m_ref[h], l_ref[h] = m, l
            acc_ref[h] = acc_ref[h] * shrink + jax.lax.dot_general(
                p.astype(q.dtype), v_ref[h].astype(q.dtype), _NT,
                preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, kv, head, None, unroll=True)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
        if rep == 1:
            # each head's own columns of its row, side by side: [1, kv * d]
            out = jnp.where(own_head(), out, 0.0).sum(axis=0, keepdims=True)
        o_ref[...] = out.astype(o_ref.dtype)


def blocks_read(q_pos, fed, places: int, block: int):
    """``(blocks [b], block)``: the blocks of each sequence's pool that
    :func:`pool_decode` brings in and works on, those that start before the
    sequence's live end (none of a parked one's), and the block's positions
    (``block``, or the whole extent where that is no multiple of it)."""
    block = block if places % block == 0 else places
    ends = jnp.where(fed > 0, jnp.minimum(q_pos.astype(jnp.int32) + fed, places), 0)
    return -(-ends // block), block


# jitted, so that a model's layers of one shape share a trace
@functools.partial(jax.jit, static_argnames=("window", "block", "interpret", "parts"))
def pool_decode(q, keys, key_scale, values, value_scale, q_pos, fed, *, window: int, block: int,
                rows=None, interpret=None, part=None, parts: int = 1):
    """Grouped-query softmax attention of ONE query a sequence, ``q`` [b, H,
    d] at ``q_pos`` [b], over the stored ``keys`` / ``values`` [slots, kv heads,
    d, P] and, of int8 pools, ``key_scale`` / ``value_scale`` [slots, kv heads,
    P] (else None). Sequence ``s`` is row ``rows[s]`` of the pools (None: ``s``)
    and reads place ``r`` under ``ring_mask(q_pos, r, P, window)``, in blocks of
    ``block`` places up to the one that holds ``q_pos[s]``; ``fed`` [b] 0 is a
    parked sequence, which reads nothing and gives zeros. Queries and
    probabilities meet the pool in ``q``'s type; statistics and sums are
    float32. Returns ``(out [b, H, d] in q's type, places read)``: the sum over
    sequences of their own blocks' places.

    ``part`` of ``parts`` (``models/common.py`` ``DecodeCache`` ``parts``: a
    looped stack's cache a pass): the pools hold ``parts`` x kv heads and this
    call reads pass ``part``'s, a traced scalar: one more prefetched number,
    which the index maps take as the block along the head axis, so the pass's
    heads come into VMEM from where they lie and no others move."""
    b, heads, d = q.shape
    kv, places = keys.shape[1] // parts, keys.shape[-1]
    rep = heads // kv
    q_pos, fed = q_pos.astype(jnp.int32), fed.astype(jnp.int32)
    steps, block = blocks_read(q_pos, fed, places, block)
    n_blocks = places // block
    if interpret is None:
        interpret = backend.interpret_default()
    # a parked sequence stays where the last one that read left off: no DMA
    seq = jnp.arange(b, dtype=jnp.int32)
    reader = jnp.maximum(jax.lax.cummax(jnp.where(steps > 0, seq, -1)), 0)
    row = (seq if rows is None else jnp.asarray(rows, jnp.int32))[reader]
    last = jnp.maximum(steps[reader] - 1, 0)
    scaled = key_scale is not None

    looped = parts > 1

    # past a sequence's last live block the same block again: no new DMA
    def at_block(*leading):
        if looped:
            # the block along the head axis is the pass
            return lambda s, j, _steps, _at, row_, last_, part_: (
                row_[s], part_[0], *leading[1:], jnp.minimum(j, last_[s]))
        return lambda s, j, _steps, _at, row_, last_: (
            row_[s], *leading, jnp.minimum(j, last_[s]))

    if rep == 1:
        # the key heads' rows side by side (the leaves' own bytes), the queries
        # and what comes back one row of every head's: _kernel's one matmul
        stacked = lambda t: t.reshape(t.shape[0], parts * kv * d, places)  # noqa: E731
        operands = [q.reshape(b, 1, heads * d), stacked(keys), stacked(values)]
        ours = pl.BlockSpec((None, 1, heads * d), lambda s, j, *_: (s, 0, 0))
        pool = pl.BlockSpec((None, kv * d, block), at_block(0))
        sums = [(kv, 1), (kv, 1), (kv, kv * d)]
    else:
        operands = [q.reshape(b, kv, rep, d), keys, values]
        ours = pl.BlockSpec((None, kv, rep, d), lambda s, j, *_: (s, 0, 0, 0))
        pool = pl.BlockSpec((None, kv, d, block), at_block(0, 0))
        sums = [(kv, rep, 1), (kv, rep, 1), (kv, rep, d)] + [(kv, block)] * (2 if scaled else 0)
    in_specs = [ours, pool, pool]
    if scaled:
        operands += [key_scale, value_scale]
        in_specs += [pl.BlockSpec((None, kv, block), at_block(0))] * 2
    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(d) ** -0.5, block=block, n_blocks=n_blocks,
                          places=places, window=window, scaled=scaled, kv=kv, rep=rep, d=d,
                          **({"looped": True} if looped else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 if looped else 4, grid=(b, n_blocks),
            in_specs=in_specs, out_specs=ours,
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in sums]),
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="pool_decode",
    )(steps, q_pos, row, last,
      *([jnp.asarray(part, jnp.int32).reshape(1)] if looped else []), *operands)
    return out.reshape(b, heads, d), (steps * block).sum().astype(jnp.int32)
