"""The expanded walk of latent attention over ONE slot's pool, for the
queries of a chunk that each attend a set of positions of their own (an
indexed layer's prefill, ``models/deepseek_v3.py``), as one kernel: a block of
the slot's latent comes into VMEM, a group of heads expands it to their keys
and values there (``W_uk c``, ``W_uv c``), scores it against the chunk's
queries, masks what each query did not choose and folds it into a running
softmax. The scores, ``[heads, chunk, block]`` float32 a step, never leave
VMEM: XLA's form of the same walk (``expanded_walk``) writes them out and
reads them back three times, which at 128 heads is what it spends its time on
(``PERF.md`` section 6, PR 37). Blocks past the slot's live length move no
bytes and run nothing.

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

#: key positions a grid step expands and attends
BLOCK = 512
#: heads a grid step walks (the pool's block is fetched once for them all)
HEADS = 8

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(x, y, dims):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _kernel(at_ref, qn_ref, qr_ref, wk_ref, wv_ref, pool_ref, may_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, rank, heads, n_steps):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < at_ref[1])
    def _block():
        latent = pool_ref[:rank, :]                                    # [rank, block]
        rope = pool_ref[rank:, :]
        may = may_ref[...] > 0                                         # [l, block]
        for h in range(heads):
            keys = _dot(wk_ref[h], latent, _NN).astype(latent.dtype)   # [dn, block]
            scores = (_dot(qn_ref[h], keys, _NN) + _dot(qr_ref[h], rope, _NN)) * scale
            scores = jnp.where(may, scores, NEG_INF)
            m = m_ref[h]
            m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
            p = jnp.where(may, jnp.exp(scores - m_new), 0.0)
            shrink = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * shrink + p.sum(axis=-1, keepdims=True)
            values = _dot(wv_ref[h], latent, _NN).astype(latent.dtype)  # [dv, block]
            acc_ref[h] = acc_ref[h] * shrink + _dot(p.astype(latent.dtype), values, _NT)
            m_ref[h] = m_new

    @pl.when(j == n_steps - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)).astype(o_ref.dtype)


def walk_blocks(live, positions: int, block: int = BLOCK):
    """``(blocks, block)``: the key blocks :func:`selected_walk` reads of a
    slot whose queries reach position ``live - 1``."""
    block = min(block, positions)
    return jnp.minimum(-(-live // block), positions // block).astype(jnp.int32), block


def takes(l: int, heads: int, positions: int, block: int = BLOCK, group: int = HEADS) -> bool:
    """Whether :func:`selected_walk` takes a chunk of ``l`` queries."""
    return l % 16 == 0 and heads % min(group, heads) == 0 and positions % min(block, positions) == 0


def selected_walk(q_nope, q_rope, w_k, w_v, pool, may, slot, n_blocks, *, scale: float,
                  block: int = BLOCK, group: int = HEADS, interpret=None):
    """``q_nope`` [H, l, dn] and ``q_rope`` [H, l, dr] (rotated): the chunk's
    queries, heads first; ``w_k`` [H, dn, rank], ``w_v`` [H, dv, rank]: the two
    halves of ``kv_b_proj`` a head; ``pool`` [slots, rank + dr, positions] the
    whole pool; ``may`` [l, positions] float32, > 0 where a query attends a
    position (causality included); ``slot`` and ``n_blocks`` scalars
    (:func:`walk_blocks`). Returns [H, l, dv] in the queries' type; a query
    with nothing to attend gives zeros."""
    heads, l, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], w_v.shape[1]
    width, positions = pool.shape[1:]
    rank = width - dr
    block, group = min(block, positions), min(group, heads)
    if not takes(l, heads, positions, block, group):
        raise ValueError(f"chunk {l}, {heads} heads, pool extent {positions}: no whole groups "
                         f"of {group} heads or blocks of {block} keys")
    if interpret is None:
        interpret = backend.interpret_default()
    n_steps = positions // block
    at = jnp.stack([jnp.asarray(slot, jnp.int32), jnp.asarray(n_blocks, jnp.int32)])
    last = lambda at: jnp.maximum(at[1], 1) - 1  # noqa: E731
    by_group = lambda g, j, at: (g, 0, 0)  # noqa: E731
    dtype = pool.dtype
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), rank=rank, heads=group, n_steps=n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // group, n_steps),
            in_specs=[pl.BlockSpec((group, l, dn), by_group),
                      pl.BlockSpec((group, l, dr), by_group),
                      pl.BlockSpec((group, dn, rank), by_group),
                      pl.BlockSpec((group, dv, rank), by_group),
                      pl.BlockSpec((None, width, block),
                                   lambda g, j, at: (at[0], 0, jnp.minimum(j, last(at)))),
                      pl.BlockSpec((l, block), lambda g, j, at: (0, jnp.minimum(j, last(at))))],
            out_specs=pl.BlockSpec((group, l, dv), by_group),
            scratch_shapes=[pltpu.VMEM((group, l, 1), jnp.float32),
                            pltpu.VMEM((group, l, 1), jnp.float32),
                            pltpu.VMEM((group, l, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((heads, l, dv), q_nope.dtype),
        # eight heads' blocks, double-buffered, and their unrolled steps' float32
        # scores pass the 16 MB a kernel is given unasked (17.6 at these sizes)
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret, name="dsa_prefill_walk",
    )(at, q_nope.astype(dtype), q_rope.astype(dtype), w_k.astype(dtype), w_v.astype(dtype), pool,
      may.astype(jnp.float32))
