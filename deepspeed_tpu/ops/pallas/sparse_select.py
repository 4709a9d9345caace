"""The selection of a layer that picks what it attends (DeepSeek-V3.2's
top-k over the index scores), as one kernel: of each row of float32 scores
``[rows, positions]`` the ``k`` largest among the row's first ``bound``
positions, equal scores to the lower position, every one of them while fewer
than ``k`` exist, ``-0`` as ``+0``. No sort. A tile of rows comes into VMEM
**once**, as far as its scores were written (whole ``BLOCK``-position blocks,
as ``sparse_index.py``'s two kernels write them), and stays there as ordered
integers while the bar, the ``k``-th largest of each row, is built a bit at a
time from the top: 32 counts of the resident tile, where XLA's form
(``models/deepseek_v3.py`` ``kth_largest``) makes 32 passes over HBM and over
the pool's whole extent, live or not. More keys equal to the bar than the top
``k`` has room for are cut by position the same way (a second bisection, over
the position, run only for a tile that has such a row). The mask leaves as
float32, for the blocks that came in and no other: what
``latent_walk.selected_walk`` and ``latent_decode.latent_decode`` read.

``kth_largest`` + ``chosen_of`` run off the chip and are what this is held
to, mask for mask. Serving only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend, sparse_index

#: positions a block of scores holds: what ``sparse_index.py`` writes at a time
BLOCK = sparse_index.BLOCK
#: rows of scores resident at a time: 32 x 32,768 is 4 MB as integers
ROW_TILE = 32
#: positions a vector register holds of each of its eight rows
LANES = 128

_LOWEST = jnp.iinfo(jnp.int32).min      # a position that is not valid: below every score
_ANY = jnp.iinfo(jnp.int32).max


def _kernel(n_ref, bound_ref, scores_hbm, mask_hbm, land, keys, arrived, left, *, k, tile, block,
            position_bits):
    i = pl.program_id(0)
    n = n_ref[i]
    rows = pl.ds(i * tile, tile)
    bound = bound_ref[...]                                                   # [tile, 1]
    # a position's place in its block
    place = jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1)

    def arrive(j):
        return pltpu.make_async_copy(scores_hbm.at[rows, pl.ds(j * block, block)], land.at[j],
                                     arrived.at[j])

    def leave(j):
        return pltpu.make_async_copy(land.at[j], mask_hbm.at[rows, pl.ds(j * block, block)],
                                     left.at[0])

    def each_block(step, carry=0):
        return jax.lax.fori_loop(0, n, step, carry)

    each_block(lambda j, _: arrive(j).start() or 0)

    def order(j, _):
        # float32 as int32 in the same order (-0 as +0); what the row may not
        # choose, below them all
        arrive(j).wait()
        scores = land[j]
        bits = jax.lax.bitcast_convert_type(jnp.where(scores == 0, 0.0, scores), jnp.int32)
        bits = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        keys[j] = jnp.where(place < bound - j * block, bits, _LOWEST)
        return 0

    each_block(order)

    def count(hit):
        """[tile, 1]: a row's resident keys that ``hit(keys [tile, block],
        their first position)`` marks."""
        def block_of(j, total):
            ones = jnp.where(hit(keys[j], j * block), 1, 0)
            # folded to a register's width here, across the lanes once a count
            return sum((ones[:, c:c + LANES] for c in range(0, block, LANES)), total)
        return each_block(block_of, jnp.zeros((tile, LANES), jnp.int32)).sum(axis=-1,
                                                                              keepdims=True)

    def raise_bar(t, bar):
        # ``bar`` holds the unsigned pattern of ``kth_largest``; the keys are
        # that pattern with the top bit turned, compared as signed
        raised = bar | jax.lax.shift_left(jnp.int32(1), 31 - t)
        return jnp.where(count(lambda x, _: x >= raised ^ _LOWEST) >= k, raised, bar)

    bar = jax.lax.fori_loop(0, 32, raise_bar, jnp.zeros((tile, 1), jnp.int32)) ^ _LOWEST
    floor = jnp.maximum(bar, _LOWEST + 1)
    above = count(lambda x, _: x > bar)
    quota = k - above
    tied = count(lambda x, _: x >= floor) - above

    def raise_cut(t, cut):
        # the last position a key equal to the bar is taken at: the largest
        # with fewer than ``quota`` such keys before it
        raised = cut | jax.lax.shift_left(jnp.int32(1), position_bits - 1 - t)
        before = count(lambda x, first: (x == bar) & (place < raised - first))
        return jnp.where(before < quota, raised, cut)

    cut = jax.lax.cond(
        jnp.max(tied - quota) > 0,
        lambda: jax.lax.fori_loop(0, position_bits, raise_cut, jnp.zeros((tile, 1), jnp.int32)),
        lambda: jnp.full((tile, 1), _ANY, jnp.int32))

    def mark(j, _):
        x = keys[j]
        chosen = (x >= floor) & ((x != bar) | (place <= cut - j * block))
        land[j] = jnp.where(chosen, 1.0, 0.0)
        leave(j).start()
        return 0

    each_block(mark)
    each_block(lambda j, _: leave(j).wait() or 0)


def row_tile(rows: int, tile: int = ROW_TILE) -> int:
    """Rows of scores :func:`select_top_k` holds at a time of ``rows``, or 0
    where it does not take them (no whole tiles of eight)."""
    while tile >= 8 and rows % tile:
        tile //= 2
    return tile if tile >= 8 else 0


def tile_blocks(real, n_blocks, rows: int):
    """``n_blocks`` [...] for each tile of ``rows`` rows that holds one of the
    first ``real`` [...] rows and 0 for the others, which then choose nothing:
    [..., rows // :func:`row_tile`]."""
    first = jnp.arange(0, rows, row_tile(rows))
    return jnp.where(first < jnp.asarray(real)[..., None], jnp.asarray(n_blocks)[..., None], 0)


def takes(rows: int, positions: int, block: int = BLOCK) -> bool:
    """Whether :func:`select_top_k` takes ``rows`` rows of scores over
    ``positions``."""
    block = min(block, positions)
    return bool(row_tile(rows)) and positions % block == 0 and block % LANES == 0


def select_top_k(scores, bound, n_blocks, k: int, *, block: int = BLOCK, interpret=None):
    """``scores`` [rows, positions] float32; ``bound`` [rows]: row ``r``
    chooses among its positions below ``bound[r]``; ``n_blocks`` [rows //
    :func:`row_tile`]: the ``block``-position blocks of each tile of rows that
    hold scores (the others are not read: their positions are chosen by no
    row) -> mask [rows, positions] float32, 1 where the row chose the position
    and 0 elsewhere, written for those blocks only."""
    rows, positions = scores.shape
    block, tile = min(block, positions), row_tile(rows)
    if not takes(rows, positions, block):
        raise ValueError(f"{rows} rows of scores over {positions} positions: no whole tiles of "
                         f"8 rows and {block} positions")
    if interpret is None:
        interpret = backend.interpret_default()
    steps = positions // block
    resident = steps * tile * block * 4
    return pl.pallas_call(
        functools.partial(_kernel, k=k, tile=tile, block=block,
                          position_bits=max(positions - 1, 1).bit_length()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, 1), lambda i, n: (i, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
            scratch_shapes=[pltpu.VMEM((steps, tile, block), jnp.float32),
                            pltpu.VMEM((steps, tile, block), jnp.int32),
                            pltpu.SemaphoreType.DMA((steps,)),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((rows, positions), jnp.float32),
        # the scores as they land and as ordered integers, both resident
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=2 * resident + 8 * 1024 * 1024),
        interpret=interpret, name="dsa_select",
    )(jnp.minimum(n_blocks.astype(jnp.int32), steps), bound.astype(jnp.int32)[:, None],
      scores.astype(jnp.float32))
