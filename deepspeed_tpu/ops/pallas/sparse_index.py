"""The index scores of a layer that picks what it attends (DeepSeek-V3.2's
"lightning indexer"): ``I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))`` over
``J`` small heads, one key ``k(s)`` a cached position. Two kernels over a
serving pool of keys ``[slots, d, positions]``, read as it is stored
(positions minor-most) and once:

* :func:`index_scores_decode`: one query a slot. A block of a slot's keys
  comes into VMEM, meets the slot's ``J`` query heads in one matmul, and
  leaves as one row of scores; blocks past the slot's live length move no
  bytes and run nothing.
* :func:`index_scores_chunk`: ``l`` queries of ONE slot (a prefill chunk)
  against that slot's keys up to a live length, a tile of queries and a block
  of keys at a time: the heads are walked inside the kernel and the ``[J, l,
  block]`` products XLA would write out never leave VMEM.

Scores of positions past the live length are not written: whoever reads them
masks by position. XLA's forms (``models/deepseek_v3.py`` ``index_scores``)
run off the chip and are what these are tested against. Serving only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend

#: key positions a grid step reads
BLOCK = 1024
#: queries a grid step of the chunk kernel scores
QUERY_TILE = 128

_NN = (((1,), (0,)), ((), ()))


def _decode_kernel(lens_ref, q_ref, w_ref, keys_ref, o_ref, *, block):
    s_i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j * block < lens_ref[s_i])
    def _block():
        keys = keys_ref[...]                                           # [d, block]
        products = jax.lax.dot_general(q_ref[...].astype(keys.dtype), keys, _NN,
                                       preferred_element_type=jnp.float32)
        o_ref[...] = (jnp.maximum(products, 0.0) * w_ref[...]).sum(axis=0, keepdims=True)


def index_scores_decode(q, w, keys, lengths, *, block: int = BLOCK, interpret=None):
    """``q`` [b, J, d], ``w`` [b, J] float32, ``keys`` [b, d, positions],
    ``lengths`` [b] -> scores [b, positions] float32, written up to the block
    that holds each sequence's last live position."""
    b, heads, d = q.shape
    positions = keys.shape[-1]
    block = min(block, positions)
    if positions % block:
        raise ValueError(f"pool extent {positions} is no multiple of the block {block}")
    if interpret is None:
        interpret = backend.interpret_default()
    lengths = jnp.minimum(lengths.astype(jnp.int32), positions)

    def key_block(s, j, lens):
        return (s, 0, jnp.minimum(j, (jnp.maximum(lens[s], 1) - 1) // block))

    query = lambda s, j, lens: (s, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_decode_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, positions // block),
            in_specs=[pl.BlockSpec((None, heads, d), query),
                      pl.BlockSpec((None, heads, 1), query),
                      pl.BlockSpec((None, d, block), key_block)],
            out_specs=pl.BlockSpec((None, 1, block), key_block)),
        out_shape=jax.ShapeDtypeStruct((b, 1, positions), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="dsa_index_decode",
    )(lengths, q.astype(keys.dtype), w.astype(jnp.float32)[..., None], keys)[:, 0]


def _chunk_kernel(at_ref, q_ref, w_ref, keys_ref, o_ref, *, heads, d, block):
    j = pl.program_id(1)

    @pl.when(j < at_ref[1])
    def _block():
        keys = keys_ref[...]                                           # [d, block]
        total = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(heads):
            products = jax.lax.dot_general(q_ref[:, h * d:(h + 1) * d], keys, _NN,
                                           preferred_element_type=jnp.float32)
            total = total + jnp.maximum(products, 0.0) * w_ref[:, h:h + 1]
        o_ref[...] = total


def chunk_tile(l: int, tile: int = QUERY_TILE) -> int:
    """Queries a grid step of :func:`index_scores_chunk` takes of ``l``, or 0
    where the kernel does not take the chunk (no whole tiles)."""
    tile = min(tile, l)
    return tile if l % tile == 0 and tile % 8 == 0 else 0


def index_scores_chunk(q, w, keys, slot, n_blocks, *, block: int = BLOCK, interpret=None):
    """``q`` [l, J x d] (head ``j``'s query in columns ``j d .. (j + 1) d``),
    ``w`` [l, J] float32, ``keys`` [slots, d, positions] the whole pool,
    ``slot`` and ``n_blocks`` scalars: the slot whose keys are read and how
    many ``block``-position blocks of them (:func:`chunk_blocks`) -> scores
    [l, positions] float32, written for those blocks only."""
    l = q.shape[0]
    heads = w.shape[1]
    d = q.shape[1] // heads
    positions = keys.shape[-1]
    block = min(block, positions)
    tile = chunk_tile(l)
    if positions % block or not tile:
        raise ValueError(f"chunk {l} / pool extent {positions}: no whole tiles of "
                         f"{QUERY_TILE} queries and {block} keys")
    if interpret is None:
        interpret = backend.interpret_default()
    at = jnp.stack([jnp.asarray(slot, jnp.int32), jnp.asarray(n_blocks, jnp.int32)])
    last = lambda at: jnp.maximum(at[1], 1) - 1  # noqa: E731
    return pl.pallas_call(
        functools.partial(_chunk_kernel, heads=heads, d=d, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(l // tile, positions // block),
            in_specs=[pl.BlockSpec((tile, heads * d), lambda i, j, at: (i, 0)),
                      pl.BlockSpec((tile, heads), lambda i, j, at: (i, 0)),
                      pl.BlockSpec((None, d, block),
                                   lambda i, j, at: (at[0], 0, jnp.minimum(j, last(at))))],
            out_specs=pl.BlockSpec((tile, block),
                                   lambda i, j, at: (i, jnp.minimum(j, last(at))))),
        out_shape=jax.ShapeDtypeStruct((l, positions), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="dsa_index_prefill",
    )(at, q.astype(keys.dtype), w.astype(jnp.float32), keys)


def chunk_blocks(live, positions: int, block: int = BLOCK):
    """``(blocks, block)``: the key blocks :func:`index_scores_chunk` reads of
    a slot whose queries reach position ``live - 1``."""
    block = min(block, positions)
    return jnp.minimum(-(-live // block), positions // block).astype(jnp.int32), block
