"""The absorbed step of latent attention over a serving pool, as one kernel:
one query a sequence against that sequence's latent pool ``[rank + rope,
positions]``, read as it is stored (positions minor-most) and **once**: a
block of positions comes into VMEM, gives the scores of every head
(``[q~ ; q_rope] . [c_kv ; k_r]``) and, with the same bytes, the weighted sum
of its latent rows, under a running softmax. Blocks past a sequence's live
length move no bytes (the index map clamps, so the DMA is elided) and run no
FLOPs; a sequence of length 0 (a parked slot) reads nothing and gives zeros.

XLA's form of the same step (``models/deepseek_v3.py`` ``absorbed_step``)
makes the scores and the weighted sum as two matmuls over the whole pool:
two reads of every position of every slot, live or not. That form stays: it
runs off the chip, and it is what this kernel is tested against. Serving
only, no VJP.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend

NEG_INF = float(jnp.finfo(jnp.float32).min)

#: positions a grid step reads: 1.2 MB of a bf16 pool 576 wide
BLOCK = 1024


_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(x, pool_rows, dims):
    """``x`` against rows of the pool, in the pool's own type (one MXU pass),
    accumulated in float32."""
    return jax.lax.dot_general(x.astype(pool_rows.dtype), pool_rows, dims,
                               preferred_element_type=jnp.float32)


def _selected_kernel(lens_ref, q_lat_ref, q_rope_ref, pool_ref, chosen_ref, *rest, **sizes):
    """:func:`_kernel` over the positions ``chosen_ref`` [1, block] marks."""
    _kernel(lens_ref, q_lat_ref, q_rope_ref, pool_ref, *rest, chosen_ref=chosen_ref, **sizes)


def _kernel(lens_ref, q_lat_ref, q_rope_ref, pool_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, rank, block, n_blocks, chosen_ref=None):
    s_i, j = pl.program_id(0), pl.program_id(1)
    length = lens_ref[s_i]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < length)
    def _block():
        latent = pool_ref[:rank, :]                                    # [rank, block]
        scores = (_dot(q_lat_ref[...], latent, _NN)
                  + _dot(q_rope_ref[...], pool_ref[rank:, :], _NN)) * scale
        live = j * block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) < length
        if chosen_ref is not None:
            live = live & (chosen_ref[...] > 0)
        scores = jnp.where(live, scores, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(scores - m_new), 0.0)
        shrink = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * shrink + p.sum(axis=-1, keepdims=True)
        # against the same block: probabilities [H, block] x latent^T
        acc_ref[...] = acc_ref[...] * shrink + _dot(p, latent, _NT)
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)


def blocks_read(lengths, positions: int, block: int = BLOCK):
    """``(blocks [b], block)``: the blocks of each sequence's pool that
    :func:`latent_decode` brings in and works on: those that start before its
    live length (the kernel's ``pl.when`` and its index map's clamp)."""
    block = min(block, positions)
    lengths = jnp.minimum(lengths.astype(jnp.int32), positions)
    return -(-lengths // block), block


def latent_decode(q_lat, q_rope, pool, lengths, *, scale: float, block: int = BLOCK,
                  interpret=None, chosen=None):
    """``softmax(scale x [q_lat ; q_rope] . pool[:, :length]) pool[:rank]^T``
    a sequence: ``q_lat`` [b, H, rank] (the queries already in the latent
    space) and ``q_rope`` [b, H, rope], ``pool`` [b, rank + rope, positions] as
    it is stored, ``lengths`` [b] positions that hold a token (0: read
    nothing, give zeros). Queries and probabilities meet the pool in the
    pool's type; statistics and sums are float32. Returns the heads' mixed
    latents [b, H, rank] in float32.

    ``chosen`` [b, positions] bool (an indexed layer's selection): the softmax
    runs over the live positions it marks and no other. The pool's live
    blocks are read as before, a position's column masked where it is not
    chosen (a block comes into VMEM whole, and 2,048 chosen of 16,000 live
    leave no 1,024-position block without one); the kernel is then named
    ``dsa_decode``."""
    b, heads, rank = q_lat.shape
    width, positions = pool.shape[1:]
    block = min(block, positions)
    if positions % block:
        raise ValueError(f"pool extent {positions} is no multiple of the block {block}")
    n_blocks = positions // block
    if interpret is None:
        interpret = backend.interpret_default()
    lengths = jnp.minimum(lengths.astype(jnp.int32), positions)

    def pool_block(s, j, lens):
        # past the last live block the same block again: no new DMA
        return (s, 0, jnp.minimum(j, (jnp.maximum(lens[s], 1) - 1) // block))

    query = lambda s, j, lens: (s, 0, 0)  # noqa: E731
    kernel = functools.partial(_kernel if chosen is None else _selected_kernel,
                               scale=float(scale), rank=rank, block=block, n_blocks=n_blocks)
    operands = (lengths, q_lat.astype(pool.dtype), q_rope.astype(pool.dtype), pool)
    in_specs = [pl.BlockSpec((None, heads, rank), query),
                pl.BlockSpec((None, heads, width - rank), query),
                pl.BlockSpec((None, width, block), pool_block)]
    if chosen is not None:
        operands += (chosen.astype(jnp.float32)[:, None, :],)
        in_specs.append(pl.BlockSpec((None, 1, block), pool_block))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, heads, rank), query),
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="mla_decode" if chosen is None else "dsa_decode",
    )(*operands)
