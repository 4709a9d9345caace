"""Block-sparse flash attention (reference ``ops/sparse_attention/
sparse_self_attention.py`` + the Triton ``matmul``/``softmax`` block-sparse
kernels it drives).

The reference multiplies dense blocks selected by a layout through custom
Triton SDD/DSD kernels. Here the layout compiles into per-row *active-block
index lists*, and the Pallas kernels' inner ``fori_loop`` runs only over
those entries (a traced loop bound — masked-out K blocks are genuinely
SKIPPED, not computed-and-masked; tested by planting NaNs in dead blocks).
Forward + backward, online-softmax, fp32 accumulation on the MXU.
"""

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.ops.pallas.flash_attention import NEG_INF, _apply_causal_mask
from deepspeed_tpu.ops.sparse_attention.sparsity_config import SparsityConfig


def layout_index_lists(layout: np.ndarray):
    """[H, nQ, nK] 0/1 → (kidx [H,nQ,maxA], kcnt [H,nQ,1]) active-K lists per
    Q row, and the transposed (qidx [H,nK,maxB], qcnt [H,nK,1]) per K row
    for the backward dk/dv pass. Padded entries are 0 and never visited."""
    layout = np.asarray(layout, dtype=bool)
    h, nq, nk = layout.shape
    max_a = max(int(layout.sum(axis=2).max()), 1)
    max_b = max(int(layout.sum(axis=1).max()), 1)
    kidx = np.zeros((h, nq, max_a), np.int32)
    kcnt = np.zeros((h, nq, 1), np.int32)
    qidx = np.zeros((h, nk, max_b), np.int32)
    qcnt = np.zeros((h, nk, 1), np.int32)
    for hi in range(h):
        for r in range(nq):
            cols = np.flatnonzero(layout[hi, r])
            kidx[hi, r, :len(cols)] = cols
            kcnt[hi, r, 0] = len(cols)
        for c in range(nk):
            rows = np.flatnonzero(layout[hi, :, c])
            qidx[hi, c, :len(rows)] = rows
            qcnt[hi, c, 0] = len(rows)
    return kidx, kcnt, qidx, qcnt


# ---------------------------------------------------------------------------
# kernels (BHLD, block == layout block)
# ---------------------------------------------------------------------------
def _row(n_rows):
    """This grid step's (head, block-row) position in the flat index tables."""
    return pl.program_id(1) * n_rows + pl.program_id(2)


def _sp_fwd_kernel(kidx_ref, kcnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                   scale, causal, blk, n_rows, max_n):
    qi = pl.program_id(2)
    row = _row(n_rows)
    q = q_ref[...].astype(jnp.float32) * scale
    m0 = jnp.full((blk,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk,), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)

    def body(t, carry):
        m, l, acc = carry
        j = kidx_ref[row * max_n + t]
        k = k_ref[pl.ds(j * blk, blk), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * blk, blk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, qi, j, blk, blk, 0)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # traced upper bound: dead blocks are never visited
    m, l, acc = jax.lax.fori_loop(0, kcnt_ref[row], body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-37)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[...] = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)[:, None]


def _sp_bwd_dq_kernel(kidx_ref, kcnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, *, scale, causal, blk, n_rows, max_n):
    qi = pl.program_id(2)
    row = _row(n_rows)
    q = q_ref[...].astype(jnp.float32) * scale
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...][:, 0]
    delta = delta_ref[...][:, 0]

    def body(t, dq):
        j = kidx_ref[row * max_n + t]
        k = k_ref[pl.ds(j * blk, blk), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * blk, blk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, qi, j, blk, blk, 0)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, kcnt_ref[row], body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _sp_bwd_dkv_kernel(qidx_ref, qcnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, *, scale, causal, blk, n_rows, max_n):
    ki = pl.program_id(2)
    row = _row(n_rows)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)

    def body(t, carry):
        dk, dv = carry
        i = qidx_ref[row * max_n + t]
        q = q_ref[pl.ds(i * blk, blk), :].astype(jnp.float32) * scale
        do = do_ref[pl.ds(i * blk, blk), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * blk, blk), 0]
        delta = delta_ref[pl.ds(i * blk, blk), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, i, ki, blk, blk, 0)
        p = jnp.exp(s - lse[:, None])
        dv_new = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(0, qcnt_ref[row], body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _sp_call(kernel, idx, cnt, operands, in_specs, out_specs, out_shape,
             grid, interpret, **static):
    """One pallas_call whose per-(head, block-row) active-block table is
    scalar-prefetched into SMEM, flat: the kernels read ``idx[row * max_n
    + t]`` and ``cnt[row]`` as scalars to bound and steer their loops."""
    n_rows, max_n = idx.shape[1], idx.shape[2]
    return pl.pallas_call(
        functools.partial(kernel, n_rows=n_rows, max_n=max_n, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret,
    )(idx.reshape(-1), cnt.reshape(-1), *operands)


def _blk_spec(blk, last):
    """This grid step's own [blk, last] block of a [B, H, L, last] array."""
    return pl.BlockSpec((None, None, blk, last),
                        lambda bi, hi, i, *_: (bi, hi, i, 0))


def _full_spec(l, last):
    """The whole length of a [B, H, L, last] array for this (b, h)."""
    return pl.BlockSpec((None, None, l, last),
                        lambda bi, hi, i, *_: (bi, hi, 0, 0))


def _sp_fwd(q, k, v, kidx, kcnt, scale, causal, blk, interpret):
    b, h, l, d = q.shape
    o, lse = _sp_call(
        _sp_fwd_kernel, kidx, kcnt, (q, k, v),
        in_specs=[_blk_spec(blk, d), _full_spec(l, d), _full_spec(l, d)],
        out_specs=[_blk_spec(blk, d), _blk_spec(blk, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, l, 1), jnp.float32),
        ],
        grid=(b, h, l // blk), interpret=interpret,
        scale=scale, causal=causal, blk=blk)
    return o, lse


def _sp_bwd(res, g, scale, causal, blk, interpret):
    q, k, v, o, lse, kidx, kcnt, qidx, qcnt = res
    b, h, l, d = q.shape
    do = g
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(axis=-1, keepdims=True)
    static = dict(grid=(b, h, l // blk), interpret=interpret,
                  scale=scale, causal=causal, blk=blk)

    dq = _sp_call(
        _sp_bwd_dq_kernel, kidx, kcnt, (q, k, v, do, lse, delta),
        in_specs=[_blk_spec(blk, d), _full_spec(l, d), _full_spec(l, d),
                  _blk_spec(blk, d), _blk_spec(blk, 1), _blk_spec(blk, 1)],
        out_specs=_blk_spec(blk, d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype), **static)

    dk, dv = _sp_call(
        _sp_bwd_dkv_kernel, qidx, qcnt, (q, k, v, do, lse, delta),
        in_specs=[_full_spec(l, d), _blk_spec(blk, d), _blk_spec(blk, d),
                  _full_spec(l, d), _full_spec(l, 1), _full_spec(l, 1)],
        out_specs=[_blk_spec(blk, d), _blk_spec(blk, d)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ], **static)
    return dq, dk, dv, None, None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _sparse_attention_bhld(q, k, v, kidx, kcnt, qidx, qcnt, scale, causal, blk, interpret):
    o, _ = _sp_fwd(q, k, v, kidx, kcnt, scale, causal, blk, interpret)
    return o


def _sparse_fwd_rule(q, k, v, kidx, kcnt, qidx, qcnt, scale, causal, blk, interpret):
    o, lse = _sp_fwd(q, k, v, kidx, kcnt, scale, causal, blk, interpret)
    return o, (q, k, v, o, lse, kidx, kcnt, qidx, qcnt)


def _sparse_bwd_rule(scale, causal, blk, interpret, res, g):
    return _sp_bwd(res, g, scale, causal, blk, interpret)


_sparse_attention_bhld.defvjp(_sparse_fwd_rule, _sparse_bwd_rule)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     layout: np.ndarray, block: int, *,
                     causal: bool = False, scale: Optional[float] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Block-sparse attention over BLHD tensors with a static [H, nQ, nK]
    layout. ``block`` is the layout's block size (= kernel tile)."""
    b, l, h, d = q.shape
    layout = np.asarray(layout)
    assert layout.shape == (h, l // block, l // block), \
        f"layout {layout.shape} != (heads {h}, {l // block}, {l // block})"
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = backend.interpret_default()
    kidx, kcnt, qidx, qcnt = layout_index_lists(layout)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o = _sparse_attention_bhld(qt, kt, vt, jnp.asarray(kidx), jnp.asarray(kcnt),
                               jnp.asarray(qidx), jnp.asarray(qcnt),
                               float(scale), bool(causal), block, interpret)
    return o.transpose(0, 2, 1, 3)


class SparseSelfAttention:
    """Reference-surface wrapper (``sparse_self_attention.py``
    ``SparseSelfAttention(sparsity_config, ...)``): holds a config, caches
    the layout per sequence length, applies the kernel."""

    def __init__(self, sparsity_config: SparsityConfig, key_padding_mask_mode="add",
                 attn_mask_mode="mul"):
        self.sparsity_config = sparsity_config
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self._layouts = {}

    def get_layout(self, seq_len: int) -> np.ndarray:
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def __call__(self, query, key, value, *, causal: Optional[bool] = None,
                 scale: Optional[float] = None):
        seq_len = query.shape[1]
        if causal is None:
            causal = getattr(self.sparsity_config, "attention", "bidirectional") \
                == "unidirectional"
        return sparse_attention(query, key, value, self.get_layout(seq_len),
                                self.sparsity_config.block, causal=causal, scale=scale)
