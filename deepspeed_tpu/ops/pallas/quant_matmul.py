"""Fused per-group dequant GEMM for weight-quantized serving programs
(graft-quant-serve; reference ``csrc/transformer/inference/`` int8 path).

The served kernel arrives as int8 codes (int4: packed two-per-byte along
the contraction axis, ``ops/quantizer/weights.py`` layout) plus per-
(K-group, output-column) scales ``[G, N]``. The GEMM reads codes from HBM
— one byte (half a byte) per weight instead of two or four — and dequant
happens on the way into the MXU, never as a materialized fp copy of the
whole kernel:

* ``impl="xla"`` (default off-TPU): unpack + broadcast-scale + dot. XLA
  fuses the dequant into the matmul prologue; runs everywhere.
* ``impl="pallas"``: grid ``(M-blocks, N-blocks, K-steps)``; each step
  DMAs the code block of a few whole scale groups and their scale rows,
  dequantizes in VMEM, and accumulates the partial product into the
  output block in fp32 (``@pl.when`` k==0 init, the standard accumulation
  idiom). A step spans whole groups, as many as make its blocks tile the
  TPU's (8, 128) / int8 (32, 128) layouts. Interpret mode makes it
  CPU-testable.

Forward-only on purpose: serving programs never differentiate.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.ops.quantizer.weights import unpack_rows

IMPL_CHOICES = ("xla", "pallas")

#: output-column block cap (fp32 accumulator block stays a few hundred KB)
MAX_BN = 512
#: activation-row block cap
MAX_BM = 256
#: contraction rows dequantized per grid step (whole scale groups)
MAX_BK = 512


def resolve_impl(kernel: str) -> str:
    """Map an impl choice ("auto"|"xla"|"pallas") to a concrete impl for
    the current backend (the ``moe_dispatch.resolve_impl`` convention)."""
    return backend.resolve_impl(kernel, IMPL_CHOICES, "quant_matmul")


def _groups_per_step(g: int, rows: int) -> int:
    """Scale groups dequantized per grid step. ``rows`` is the stored code
    rows per group (halved for int4). A step's blocks must tile: the scale
    block's group count by 8 sublanes, the activation block's lanes by 128,
    the int8 code block's rows by 32 — or span the whole axis."""
    for gs in range(8, g, 8):
        if g % gs == 0 and (gs * rows) % 128 == 0 and gs * rows <= MAX_BK:
            return gs
    return g


def _dequant(q, s_ref, gs, dtype):
    """int32 codes ``[gs*rows, bn]`` times their group's scale row, as the
    activation dtype so bf16 serving feeds bf16 operands (fp32 accum)."""
    rows = q.shape[0] // gs
    parts = [q[i * rows:(i + 1) * rows].astype(jnp.float32) * s_ref[i:i + 1, :]
             for i in range(gs)]
    return jnp.concatenate(parts, axis=0).astype(dtype)


def _dot(x, w):
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _accumulate(o_ref, part):
    gi = pl.program_id(2)

    @pl.when(gi == 0)
    def _init():
        o_ref[...] = part

    @pl.when(gi != 0)
    def _accum():
        o_ref[...] += part


def _qmm8_kernel(x_ref, w_ref, s_ref, o_ref, *, gs):
    # dequant on the way into the MXU
    w = _dequant(w_ref[...].astype(jnp.int32), s_ref, gs, x_ref.dtype)
    _accumulate(o_ref, _dot(x_ref[...], w))


def _qmm4_kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, *, gs):
    # packed row i holds code rows 2i (low nibble) and 2i+1 (high nibble).
    # Interleaving them back would shuffle sublanes; instead the caller
    # splits x into its even and odd columns, so each nibble plane is a
    # plain GEMM operand. Shifts on sign-extended int32 keep the sign.
    q = w_ref[...].astype(jnp.int32)
    lo = (q << 28) >> 28
    hi = q >> 4
    part = _dot(xe_ref[...], _dequant(lo, s_ref, gs, xe_ref.dtype))
    part += _dot(xo_ref[...], _dequant(hi, s_ref, gs, xo_ref.dtype))
    _accumulate(o_ref, part)


def _pallas_quant_matmul(x: jax.Array, qw: jax.Array, scale: jax.Array,
                         bits: int, interpret: Optional[bool]) -> jax.Array:
    if interpret is None:
        interpret = backend.interpret_default()
    m, k = x.shape
    g, n = scale.shape
    rows = qw.shape[0] // g          # stored code rows per scale group
    gs = _groups_per_step(g, rows)
    bk = gs * rows
    bm = backend.largest_block(m, MAX_BM, 8 * (4 // x.dtype.itemsize))
    bn = backend.largest_block(n, MAX_BN, 128)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, gi: (i, gi))
    if bits == 4:
        kernel, xs = _qmm4_kernel, (x[:, 0::2], x[:, 1::2])
    else:
        kernel, xs = _qmm8_kernel, (x,)
    out = pl.pallas_call(
        functools.partial(kernel, gs=gs),
        grid=(m // bm, n // bn, g // gs),
        in_specs=[x_spec] * len(xs) + [
            pl.BlockSpec((bk, bn), lambda i, j, gi: (gi, j)),
            pl.BlockSpec((gs, bn), lambda i, j, gi: (gi, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, gi: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*xs, qw, scale)
    return out.astype(x.dtype)


def _xla_quant_matmul(x: jax.Array, qw: jax.Array, scale: jax.Array,
                      bits: int) -> jax.Array:
    k = x.shape[1]
    q = unpack_rows(qw) if bits == 4 else qw
    g, n = scale.shape
    w = (q.astype(jnp.float32).reshape(g, k // g, n) * scale[:, None, :])
    w = w.reshape(k, n).astype(x.dtype)
    out = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def quant_matmul(x: jax.Array, qw: jax.Array, scale: jax.Array, *,
                 bits: int = 8, impl: str = "auto",
                 interpret: Optional[bool] = None) -> jax.Array:
    """``x [M, K] @ dequant(qw, scale) [K, N]`` → ``[M, N]`` in x's dtype.

    ``qw`` is ``[K, N]`` int8 codes (bits=8) or ``[K/2, N]`` packed
    nibbles (bits=4, ``weights.pack_rows`` layout); ``scale`` is
    ``[G, N]`` fp32 with G dividing K."""
    if bits not in (8, 4):
        raise ValueError(f"quant_matmul supports bits in (8, 4), got {bits}")
    k = x.shape[1]
    g = scale.shape[0]
    if k % g != 0:
        raise ValueError(f"group count {g} must divide K={k}")
    kw = qw.shape[0] * (2 if bits == 4 else 1)
    if kw != k:
        raise ValueError(f"code rows {qw.shape[0]}"
                         f"{' (x2 packed)' if bits == 4 else ''} do not match "
                         f"x's contraction K={k}")
    resolved = resolve_impl(impl)
    if resolved == "pallas":
        return _pallas_quant_matmul(x, qw, scale, bits, interpret)
    return _xla_quant_matmul(x, qw, scale, bits)


def quant_dense_general(x: jax.Array, qkernel: jax.Array, scale: jax.Array, *,
                        bits: int = 8, n_contract: int = 1,
                        impl: str = "auto",
                        interpret: Optional[bool] = None) -> jax.Array:
    """``dot_general`` over a quantized kernel: contracts x's trailing
    ``n_contract`` dims against the kernel's leading ``n_contract`` dims
    (int4: the last contraction axis is stored halved). Output shape is
    ``x.shape[:-n_contract] + qkernel.shape[n_contract:]`` — the
    projection shapes ``models/gpt2.py`` declares."""
    bshape = x.shape[:x.ndim - n_contract]
    k = 1
    for d in x.shape[x.ndim - n_contract:]:
        k *= d
    out_dims = qkernel.shape[n_contract:]
    n = 1
    for d in out_dims:
        n *= d
    out = quant_matmul(x.reshape(-1, k), qkernel.reshape(-1, n), scale,
                       bits=bits, impl=impl, interpret=interpret)
    return out.reshape(*bshape, *out_dims)
