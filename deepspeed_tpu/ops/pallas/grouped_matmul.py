"""Grouped matrix multiplication for the drop-free sorted MoE route.

The drop-free route (``moe/sharded_moe.py``) sorts a tick's token copies by
expert into one ``[rows, M]`` buffer with no padding between the groups,
and every expert projection becomes

    out[start_e : start_e + size_e] = lhs[start_e : start_e + size_e] @ rhs[e]

for the ``E`` groups of ``group_sizes``. Rows past ``sum(group_sizes)``
hold no defined result, and no tile that lies wholly past them is computed:
a layer that holds only some of the experts (``MOELayer.experts_held``)
hands the kernel its own groups in the smallest of a few static buffers
that holds them.

Implementations, chosen by the same kernel choice as the row permutation
(``MOELayer.route_kernel``):

* ``impl="xla"``: ``jax.lax.ragged_dot``. XLA lowers it to its own
  ragged-dot kernel on a TPU and to a masked dense form elsewhere.
* ``impl="pallas"``: the megablox grouped-matmul Pallas kernel that ships
  with JAX (``jax.experimental.pallas.ops.tpu.megablox``), tiled by
  :func:`tiling`; interpreted off the TPU.

On the chip at OLMoE's sizes (64 experts of 2048 x 1024, top-8; one
layer's three projections, bf16; PERF.md, PR 26) the Pallas kernel took
1.14 ms for a decode tick's 256 rows and 3.33 ms for a prefill tick's
16,384, ``ragged_dot`` 1.65 and 4.64 ms, and a capacity-padded ``[E, C, M]``
einsum with ``C`` = every token 1.18 and 11.9 ms.
"""

from typing import Optional, Tuple

import jax

from deepspeed_tpu.ops.pallas import backend

IMPL_CHOICES = ("xla", "pallas")

#: row tile of the Pallas kernel: small buffers (a decode tick) lose least
#: to 128, large ones (a prefill tick) to 256 (chip runs, PR 26)
ROW_TILE_SMALL, ROW_TILE_LARGE, LARGE_ROWS = 128, 256, 4096
COL_TILE = 1024


def resolve_impl(kernel: str) -> str:
    return backend.resolve_impl(kernel, IMPL_CHOICES, "moe kernel")


def tiling(rows: int, k: int, n: int) -> Tuple[int, int, int]:
    """(row, contraction, column) tiles of the Pallas kernel for a
    ``[rows, k] x [E, k, n]`` product: divisors of each extent, the whole
    extent where it has no aligned divisor."""
    cap = ROW_TILE_LARGE if rows >= LARGE_ROWS else ROW_TILE_SMALL
    return (backend.largest_block(rows, cap, 8), backend.largest_block(k, COL_TILE, 128),
            backend.largest_block(n, COL_TILE, 128))


def rows_visited(group_sizes: jax.Array, rows: int) -> jax.Array:
    """Rows of the row tiles the Pallas kernel runs over for ``group_sizes``
    packed from row 0 of a ``rows``-row buffer (an int32 scalar, traced):
    every tile a non-empty group overlaps, a tile two groups share once for
    each. Tiles past the last group are not visited, so sizes that add up
    to less than ``rows`` cost what they hold, rounded to tiles."""
    import jax.numpy as jnp
    tm = tiling(rows, 128, 128)[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
    return tiles.sum() * tm


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   impl: str = "xla", interpret: Optional[bool] = None) -> jax.Array:
    """``lhs`` [rows, k] (rows sorted by group) times ``rhs`` [E, k, n],
    group ``e`` of ``group_sizes`` [E] int32 against ``rhs[e]``; returns
    [rows, n] in ``lhs``'s dtype (fp32 accumulation)."""
    if impl == "pallas":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        if interpret is None:
            interpret = backend.interpret_default()
        return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                   tiling=tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2]),
                   interpret=interpret)
    if impl != "xla":
        raise ValueError(f"grouped matmul impl must be one of {IMPL_CHOICES}, got {impl!r}")
    return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
