"""The expanded walk of latent attention over ONE slot's pool for the queries
of a chunk (a prefill chunk of a full layer, ``models/deepseek_v3.py``), as one
kernel: a block of the slot's latent comes into VMEM and is turned positions
onto the rows once, a group of heads expands it to their keys and values there
(``c W_uk``, ``c W_uv``), scores it against the chunk's queries, masks what a
query does not attend and folds it into a running softmax. The scores,
``[heads, chunk, block]`` float32 a step, never leave VMEM: XLA's form of the
same walk (``expanded_walk``) writes them out and reads them back three times,
which is what it spends its time on (``PERF.md`` section 6, PRs 37 and 41).
Blocks past the slot's live length move no bytes and run nothing.

One body, two sources of the mask. An **indexed** layer's queries each attend
a set of their own, and hand it over as ``may [chunk, positions]``
(:func:`selected_walk`, the call ``dsa_prefill_walk``). A **plain** layer's
attend every position at or before their own: the kernel is told the chunk's
first position and reads the mask off the positions, in the blocks the chunk
itself lies in; a block wholly before the chunk's first query is folded in
with no mask at all (:func:`causal_walk`, the call ``mla_prefill_walk``).

The pool is read as it is stored (positions minor-most), once a group of
heads. Serving only, no VJP; ``expanded_walk`` runs off the chip and is what
this is tested against.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: key positions a grid step expands and attends: the chip's choice at a chunk
#: of 512 (8.29 ms a layer of the long-document cell; 256 reads 13.1, 1,024 18.1:
#: PERF.md section 6, PR 41)
BLOCK = 512
#: heads a grid step walks (the pool's block is fetched once for them all; 4
#: read 8.39 ms, 16 read 18.2)
HEADS = 8

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))      # x y^T


def _dot(x, y, dims):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _kernel(at_ref, qn_ref, qr_ref, w_ref, pool_ref, *rest, scale, rank, dn, heads, n_steps):
    # ``rest``: the selected form's mask, then the output and the scratch
    may_ref = rest[0] if len(rest) == 5 else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    j = pl.program_id(1)
    chunk, block = qn_ref.shape[1], pool_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(seen):
        """The block into the running softmax; ``seen`` [l, block] bool, or
        None where every query attends every key of the block."""
        # positions on the rows: every product below streams a whole block or
        # chunk of rows through weights of 128 columns
        latent = pool_ref[:rank, :].T                                  # [block, rank]
        rope = pool_ref[rank:, :]                                      # [dr, block]

        def score(h):
            keys = _dot(latent, w_ref[h, :, :dn], _NN).astype(latent.dtype)      # [block, dn]
            values = _dot(latent, w_ref[h, :, dn:], _NN).astype(latent.dtype)    # [block, dv]
            scores = (_dot(qn_ref[h], keys, _NT) + _dot(qr_ref[h], rope, _NN)) * scale
            if seen is not None:
                scores = jnp.where(seen, scores, NEG_INF)
            return scores, values

        def attend(h, scores, values):
            m = m_ref[h]
            m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            if may_ref is not None:
                # a query that has chosen nothing so far: its maximum is still
                # the floor, and exp(0) of a masked score would count
                p = jnp.where(seen, p, 0.0)
            shrink = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * shrink + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * shrink + _dot(p.astype(latent.dtype), values, _NN)
            m_ref[h] = m_new

        # the next head's expansion and scores are issued before this head's
        # softmax: matmuls with nothing to wait for beside the vector work
        # (with the turned latent 8.65 -> 8.29 ms a layer at the long-document
        # cell's shapes, either alone 8.42 and 9.34, the heads as a
        # ``fori_loop`` 10.0: PERF.md section 6, PR 41)
        ahead = score(0)
        for h in range(heads):
            scored, ahead = ahead, score(h + 1) if h + 1 < heads else None
            attend(h, *scored)

    live = j < at_ref[1]
    if may_ref is not None:
        pl.when(live)(lambda: fold(may_ref[...] > 0))
    else:
        # every query attends position 0, so after the first block its maximum
        # is a score and a masked one's exponent is exactly 0: no second ``where``
        first = at_ref[2]
        before = (j + 1) * block <= first + 1        # the block's last key <= the first query
        pl.when(live & before)(lambda: fold(None))

        @pl.when(live & jnp.logical_not(before))
        def _edge():
            k_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, (chunk, block), 1)
            q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (chunk, block), 0)
            fold(k_pos <= q_pos)

    @pl.when(j == n_steps - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)).astype(o_ref.dtype)


def walk_blocks(live, positions: int, block: int = BLOCK):
    """``(blocks, block)``: the key blocks a walk reads of a slot whose
    queries reach position ``live - 1``."""
    block = min(block, positions)
    return jnp.minimum(-(-live // block), positions // block).astype(jnp.int32), block


def takes(l: int, heads: int, positions: int, block: int = BLOCK, group: int = HEADS) -> bool:
    """Whether the kernel takes a chunk of ``l`` queries."""
    return l % 16 == 0 and heads % min(group, heads) == 0 and positions % min(block, positions) == 0


def _vmem_limit(group, l, dn, dr, dv, width, block, masked):
    """Bytes of VMEM the call asks for, from its shapes: the blocks of every
    operand and of the output twice (double-buffered), the scratch (a
    statistic is a column, padded out to a lane row), and the unrolled heads'
    float32 scores and exponents with their rounded copy: 27 MB for an indexed
    layer's chunk of 256 and 42 MB for a plain layer's of 512, where the chip's
    compiler allocates 16.6 and 24.6; a kernel is given 16 MB unasked. Never
    under the 48 MB the indexed layer's call has been compiled with: XLA lays
    the program's own temporaries out by it."""
    operands = 2 * (group * (l * (dn + dr + dv) + (dn + dv) * (width - dr)) + width * block) * 2
    operands += 2 * l * block * 4 if masked else 0
    scratch = group * l * (dv + 2 * 128) * 4
    steps = group * l * block * (4 + 4 + 2)
    return max(operands + scratch + steps + (4 << 20), 48 << 20)


@functools.partial(jax.jit, static_argnames=("scale", "block", "group", "interpret"))
def _walk(q_nope, q_rope, w, pool, may, at, *, scale, block, group, interpret):
    """The kernel's program: an inner ``jit``, so that the layers of a model,
    which call it with the same shapes, trace and lower it once."""
    heads, l, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], w.shape[-1] - dn
    width, positions = pool.shape[1:]
    n_steps = positions // block
    last = lambda at: jnp.maximum(at[1], 1) - 1  # noqa: E731
    by_group = lambda g, j, at: (g, 0, 0)  # noqa: E731
    dtype = pool.dtype
    operands = [q_nope.astype(dtype), q_rope.astype(dtype), w.astype(dtype), pool]
    in_specs = [pl.BlockSpec((group, l, dn), by_group),
                pl.BlockSpec((group, l, dr), by_group),
                pl.BlockSpec((group, width - dr, dn + dv), by_group),
                pl.BlockSpec((None, width, block),
                             lambda g, j, at: (at[0], 0, jnp.minimum(j, last(at))))]
    if may is not None:
        operands.append(may.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((l, block), lambda g, j, at: (0, jnp.minimum(j, last(at)))))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=width - dr, dn=dn, heads=group,
                          n_steps=n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // group, n_steps), in_specs=in_specs,
            out_specs=pl.BlockSpec((group, l, dv), by_group),
            scratch_shapes=[pltpu.VMEM((group, l, 1), jnp.float32),
                            pltpu.VMEM((group, l, 1), jnp.float32),
                            pltpu.VMEM((group, l, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, l, dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(group, l, dn, dr, dv, width, block, may is not None)),
        interpret=interpret, name="mla_prefill_walk" if may is None else "dsa_prefill_walk",
    )(at, *operands)


def _call(q_nope, q_rope, w, pool, may, at, scale, block, group, interpret):
    heads, l = q_nope.shape[:2]
    positions = pool.shape[-1]
    block, group = min(block, positions), min(group, heads)
    if not takes(l, heads, positions, block, group):
        raise ValueError(f"chunk {l}, {heads} heads, pool extent {positions}: no whole groups "
                         f"of {group} heads or blocks of {block} keys")
    if interpret is None:
        interpret = backend.interpret_default()
    at = jnp.stack([jnp.asarray(a, jnp.int32) for a in at])
    return _walk(q_nope, q_rope, w, pool, may, at, scale=float(scale), block=block, group=group,
                 interpret=bool(interpret))


def selected_walk(q_nope, q_rope, w, pool, may, slot, n_blocks, *, scale: float,
                  block: int = BLOCK, group: int = HEADS, interpret=None):
    """``q_nope`` [H, l, dn] and ``q_rope`` [H, l, dr] (rotated): the chunk's
    queries, heads first; ``w`` [H, rank, dn + dv]: ``kv_b_proj`` heads first
    too, a head's keys' half before its values'; ``pool`` [slots, rank + dr,
    positions] the whole pool; ``may`` [l, positions] float32, > 0
    where a query attends a position (causality included); ``slot`` and
    ``n_blocks`` scalars (:func:`walk_blocks`). Returns [H, l, dv] in the
    queries' type; a query with nothing to attend gives zeros."""
    return _call(q_nope, q_rope, w, pool, may, (slot, n_blocks), scale, block, group, interpret)


def causal_walk(q_nope, q_rope, w, pool, first, slot, n_blocks, *, scale: float,
                block: int = BLOCK, group: int = HEADS, interpret=None):
    """:func:`selected_walk` for queries that attend every position at or
    before their own: query ``i`` stands at position ``first + i`` (a scalar,
    as ``slot`` and ``n_blocks``), and no mask is handed over."""
    return _call(q_nope, q_rope, w, pool, None, (slot, n_blocks, first), scale, block, group,
                 interpret)
