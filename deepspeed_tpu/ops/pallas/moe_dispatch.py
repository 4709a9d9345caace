"""Fused row-permutation kernel for the sorted MoE dispatch/combine route.

The sorted route (``moe/sharded_moe.py``) reduces both
MoE data movements to one primitive: **permute rows of a table by a
precomputed index vector**, where an out-of-range index yields a zero row:

* dispatch: ``buf[j] = tokens[src_idx[j]]`` — each expert-capacity slot
  pulls the token routed to it (empty slots pull the zero row);
* combine-gather: ``rows[i] = buf[flat_slot[i]]`` — each token copy pulls
  its expert output back (dropped copies pull the zero row).

Because capacity assignment gives every token copy a *unique* slot (the
cumulative-sum position assignment in gating is a stable counting sort),
both directions are pure permutations-with-drop: the VJP of a gather by
``fwd_idx`` is exactly a gather by the inverse mapping ``bwd_idx`` — no
scatter-add is ever needed, which is what makes the Pallas formulation a
straight-line DMA kernel.

Implementations:

* ``impl="xla"`` (default off-TPU): ``take_along_axis`` + mask. Natively
  differentiable — XLA's gather/scatter pair, runs everywhere.
* ``impl="pallas"``: one grid step per output row; the scalar-prefetched
  index array drives the BlockSpec index map, so each step DMAs exactly
  the one source row it needs from HBM (dead slots clamp to a resident
  row and Mosaic elides the copy — same idiom as the flash kernel's
  causal skipping). Interpret mode makes it CPU-testable.

``permute_rows`` is the public entry; with ``impl="pallas"`` it carries a
custom VJP that re-enters the kernel with the inverse index map.
"""

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend

IMPL_CHOICES = ("xla", "pallas")


def resolve_impl(kernel: str) -> str:
    """Map a routing-engine kernel choice ("auto"|"xla"|"pallas") to a
    concrete impl for the current backend."""
    return backend.resolve_impl(kernel, IMPL_CHOICES, "moe kernel")


# ---------------------------------------------------------------------------
# XLA fallback: gather + mask, natively differentiable
# ---------------------------------------------------------------------------
def _xla_permute(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x: [G, N, M], idx: [G, R] int32 (entries >= N mean "zero row").
    Returns [G, R, M]."""
    n = x.shape[1]
    clipped = jnp.minimum(idx, n - 1)
    rows = jnp.take_along_axis(x, clipped[:, :, None], axis=1)
    return jnp.where((idx < n)[:, :, None], rows, jnp.zeros([], x.dtype))


# ---------------------------------------------------------------------------
# Pallas kernel: a block of output rows per grid step, one row DMA each
# ---------------------------------------------------------------------------
#: output rows gathered per grid step (one DMA in flight per row)
ROW_BLOCK = 128


def _permute_kernel(idx_ref, x_hbm, zero_hbm, out_ref, sem, *, n_rows, rb):
    g, base = pl.program_id(0), pl.program_id(1) * rb

    def row_copy(src_ref, i):
        return pltpu.make_async_copy(src_ref, out_ref.at[i], sem)

    def start(i, carry):
        src = idx_ref[g, base + i]

        @pl.when(src < n_rows)
        def _live():
            row_copy(x_hbm.at[g, src], i).start()

        @pl.when(src >= n_rows)
        def _dead():
            # dropped slots pull the zero row: same bytes, same semaphore,
            # so the waits below need not know which rows were live
            row_copy(zero_hbm, i).start()

        return carry

    def wait(i, carry):
        row_copy(zero_hbm, i).wait()
        return carry

    jax.lax.fori_loop(0, rb, start, 0)
    jax.lax.fori_loop(0, rb, wait, 0)


def _as_words(x: jax.Array) -> jax.Array:
    """View rows of a sub-32-bit table as uint32 words ``[G, N, M/pack]``.
    The TPU stores 16-bit rows two to a sublane, so a single such row is
    not a legal DMA slice; a row of 32-bit words is."""
    pack = 4 // x.dtype.itemsize
    if pack == 1:
        return x
    if x.shape[-1] % pack:
        raise ValueError(f"pallas moe permute needs a model dim divisible by "
                         f"{pack} for {x.dtype}, got {x.shape[-1]}")
    return jax.lax.bitcast_convert_type(
        x.reshape(*x.shape[:-1], x.shape[-1] // pack, pack), jnp.uint32)


def _from_words(w: jax.Array, dtype) -> jax.Array:
    if w.dtype == dtype:
        return w
    out = jax.lax.bitcast_convert_type(w, dtype)
    return out.reshape(*w.shape[:-1], -1)


def _pallas_permute(x: jax.Array, idx: jax.Array,
                    interpret: Optional[bool] = None) -> jax.Array:
    if interpret is None:
        interpret = backend.interpret_default()
    dtype = x.dtype
    x = _as_words(x)
    groups, n, m = x.shape
    r = idx.shape[1]
    # rows are an untiled major dimension of the output block: any divisor
    rb = backend.largest_block(r, ROW_BLOCK)

    out = pl.pallas_call(
        functools.partial(_permute_kernel, n_rows=n, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, r // rb),
            # the table and the zero row stay in HBM, one row per (1, m)
            # tile so a row is a whole-tile slice; each output row is one
            # row-sized DMA straight into the pipelined output block
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((None, rb, 1, m),
                                   lambda g, i, idx_ref: (g, i, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, r, 1, m), x.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), x[:, :, None, :], jnp.zeros((1, m), x.dtype))
    return _from_words(out[:, :, 0, :], dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_permute_vjp(x, fwd_idx, bwd_idx, interpret):
    return _pallas_permute(x, fwd_idx, interpret)


def _pallas_permute_fwd(x, fwd_idx, bwd_idx, interpret):
    return _pallas_permute(x, fwd_idx, interpret), (fwd_idx, bwd_idx)


def _pallas_permute_bwd(interpret, res, g):
    fwd_idx, bwd_idx = res
    # the inverse permutation: rows x[i] contributed to are exactly the
    # output rows bwd_idx[i] points at (unique-slot invariant), so the
    # cotangent is one more gather — dropped rows read the zero row
    dx = _pallas_permute(g, bwd_idx, interpret)
    f0 = lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0)
    return dx, f0(fwd_idx), f0(bwd_idx)


_pallas_permute_vjp.defvjp(_pallas_permute_fwd, _pallas_permute_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------
def permute_rows(x: jax.Array,
                 fwd_idx: jax.Array,
                 bwd_idx: jax.Array,
                 *,
                 impl: str = "xla",
                 interpret: Optional[bool] = None) -> jax.Array:
    """Permute rows of ``x`` [G, N, M] to ``[G, R, M]`` via ``fwd_idx``
    [G, R]; indices >= N produce zero rows.

    ``bwd_idx`` [G, N] must be the inverse mapping (``bwd_idx[g, i]`` = the
    output row that reads input row ``i``, or >= R when none does). It is
    only consulted by the Pallas impl's custom VJP; the XLA impl
    differentiates natively. **Both index maps must be injective on their
    live entries** — slot uniqueness is guaranteed by the capacity
    assignment in gating.
    """
    if impl == "pallas":
        return _pallas_permute_vjp(x, fwd_idx, bwd_idx, interpret)
    if impl != "xla":
        raise ValueError(f"moe dispatch impl must be one of {IMPL_CHOICES}, "
                         f"got {impl!r}")
    return _xla_permute(x, fwd_idx)


def inverse_index(fwd_idx: jax.Array, n_rows: int) -> jax.Array:
    """Inverse of an injective-with-drop index map: given ``fwd_idx`` [G, R]
    with live entries < ``n_rows`` unique per group, return ``inv`` [G,
    n_rows] where ``inv[g, j]`` is the r with ``fwd_idx[g, r] == j`` (or
    ``R`` — the drop sentinel — when no row maps there)."""
    groups, r = fwd_idx.shape
    base = jnp.full((groups, n_rows), r, jnp.int32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (groups, r), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (groups, r), 1)
    # out-of-range destinations (dropped entries) fall off the scatter
    return base.at[rows, fwd_idx].set(cols, mode="drop")
