"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The 0.10.1 reference has *no* sequence parallelism (SURVEY §2.3: no
``deepspeed/sequence/``, no ``DistributedAttention`` — that lands in
DeepSpeed >= 0.10.2); its long-sequence story is block-sparse attention and
token dropping. Long-context scaling is a required capability here, so this
module provides the two standard schemes as first-class citizens of the
``sequence`` mesh axis:

* **Ring attention** — K/V shards rotate around the ring of sequence-axis
  neighbors via ``jax.lax.ppermute`` (ICI neighbor hops), while each device
  keeps its query shard resident and folds each incoming block into a running
  online-softmax accumulator (the same (m, l, o) streaming merge the Pallas
  flash kernel uses intra-chip). Per-chip K/V memory is L/ring_size.
* **Ulysses attention** — ``jax.lax.all_to_all`` re-shards [B, L/n, H, D]
  to [B, L, H/n, D] (head-scatter / seq-gather), runs an ordinary *local*
  attention (XLA or the Pallas flash kernel) on whole sequences with a
  slice of heads, and maps back. Exposed with the upstream API shape as
  ``DistributedAttention`` (cf. deepspeed.sequence.layer in >=0.10.2).

Both are differentiable (plain jnp + collectives, no custom VJP needed) and
compose with ZeRO/TP: the ``shard_map`` wrappers pin activations to
``P(BATCH_AXES, "sequence", "tensor", None)`` so XLA's SPMD partitioner
keeps everything else declarative.
"""

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deepspeed_tpu.ops.transformer.attention import register_backend
from deepspeed_tpu.parallel.topology import BATCH_AXES, SEQUENCE_AXIS, TENSOR_AXIS, get_topology

# clamp for "row has no visible keys yet" instead of -inf so exp(m-m) stays 1
_MASK_BASE = -1e30


def _block_summary(q, k, v, scale, q_off, k_off, causal):
    """Unnormalized attention of one (q-shard, kv-block) pair.

    Returns (o, m, l): fp32 partial output [B,Lq,H,D], row max [B,H,Lq],
    row sum-of-exp [B,H,Lq] — the online-softmax triple.
    """
    lq, lk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off + jnp.arange(lq)
        k_pos = k_off + jnp.arange(lk)
        s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None], s, _MASK_BASE)
    m = jnp.maximum(s.max(axis=-1), _MASK_BASE)
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: all s == _MASK_BASE == m → p would be 1; zero them
    p = jnp.where(s <= _MASK_BASE, 0.0, p)
    l = p.sum(axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def _merge(acc, blk):
    """Fold one block's (o, m, l) into the running accumulator."""
    o, m, l = acc
    bo, bm, bl = blk
    new_m = jnp.maximum(m, bm)
    c = jnp.exp(m - new_m)
    bc = jnp.exp(bm - new_m)
    o = o * c.transpose(0, 2, 1)[..., None] + bo * bc.transpose(0, 2, 1)[..., None]
    l = l * c + bl * bc
    return o, new_m, l


def _ring_local(q, k, v, *, axis_name, causal, scale):
    """Per-device ring attention body (runs under shard_map).

    q/k/v: [B, L_local, H_local, D]. K/V rotate ring-wise; the causal mask
    uses global positions derived from each block's source chunk index.
    """
    n = jax.lax.psum(1, axis_name)  # static axis size
    idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    q_off = idx * lq

    o = jnp.zeros((b, lq, h, d), jnp.float32)
    m = jnp.full((b, h, lq), _MASK_BASE, jnp.float32)
    l = jnp.zeros((b, h, lq), jnp.float32)
    kv = (k, v)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for s in range(n):
        src = (idx - s) % n  # which global chunk this kv block is
        blk = _block_summary(q, kv[0], kv[1], scale, q_off, src * lk, causal)
        o, m, l = _merge((o, m, l), blk)
        if s != n - 1:
            kv = jax.lax.ppermute(kv, axis_name, perm)
    l_t = l.transpose(0, 2, 1)[..., None]
    out = o / jnp.where(l_t > 0, l_t, 1.0)
    return out.astype(q.dtype)


def _ulysses_local(q, k, v, *, axis_name, inner: Callable, **kwargs):
    """Per-device Ulysses body: head-scatter/seq-gather all-to-all, local
    attention over the full sequence with H/n heads, inverse all-to-all."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    q2 = a2a(q, split_axis=2, concat_axis=1)
    k2 = a2a(k, split_axis=2, concat_axis=1)
    v2 = a2a(v, split_axis=2, concat_axis=1)
    o2 = inner(q2, k2, v2, **kwargs)
    return a2a(o2, split_axis=1, concat_axis=2)


def _resolve_mesh(mesh: Optional[Mesh]):
    if mesh is not None:
        return mesh
    topo = get_topology()
    return topo.mesh if topo is not None else None


def _activation_specs(mesh: Mesh, batch_size: int, n_heads: int):
    """(q/k/v spec) for BLHD activations, dropping axes that don't divide."""
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    prod = 1
    for a in batch_axes:
        prod *= mesh.shape[a]
    b_part = batch_axes if (prod > 1 and batch_size % prod == 0) else None
    tensor = TENSOR_AXIS if (TENSOR_AXIS in mesh.shape and mesh.shape[TENSOR_AXIS] > 1
                             and n_heads % mesh.shape[TENSOR_AXIS] == 0) else None
    return P(b_part, SEQUENCE_AXIS, tensor, None)


def _seq_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None or SEQUENCE_AXIS not in mesh.shape:
        return 1
    return mesh.shape[SEQUENCE_AXIS]


def _fallback(q, k, v, reason, **kwargs):
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    return xla_attention(q, k, v, **kwargs)


@register_backend("ring")
def ring_attention(q: jax.Array,
                   k: jax.Array,
                   v: jax.Array,
                   *,
                   causal: bool = True,
                   bias: Optional[jax.Array] = None,
                   mask: Optional[jax.Array] = None,
                   scale: Optional[float] = None,
                   dropout_rate: float = 0.0,
                   dropout_rng: Optional[jax.Array] = None,
                   mesh: Optional[Mesh] = None) -> jax.Array:
    """Ring attention over the ``sequence`` mesh axis (global-array API).

    Inputs are global [B, L, H, D]; the wrapper shard-maps them as
    ``P(batch, sequence, tensor, None)``. L must divide by the sequence
    axis. Falls back to plain XLA attention when there is no sequence axis
    (size 1) or when bias/mask/dropout are requested.
    """
    if scale is None:
        scale = q.shape[-1]**-0.5
    mesh = _resolve_mesh(mesh)
    n = _seq_axis_size(mesh)
    if (n == 1 or bias is not None or mask is not None or q.shape[1] != k.shape[1]
            or (dropout_rate > 0.0 and dropout_rng is not None)):
        # lq != lk (kv-cache decode) needs the xla path's position offset
        return _fallback(q, k, v, "no sequence axis or unsupported feature", causal=causal, bias=bias,
                         mask=mask, scale=scale, dropout_rate=dropout_rate, dropout_rng=dropout_rng)
    assert q.shape[1] % n == 0, f"sequence length {q.shape[1]} not divisible by ring size {n}"
    spec = _activation_specs(mesh, q.shape[0], q.shape[2])
    fn = shard_map(functools.partial(_ring_local, axis_name=SEQUENCE_AXIS, causal=causal, scale=float(scale)),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)


@register_backend("ulysses")
def ulysses_attention(q: jax.Array,
                      k: jax.Array,
                      v: jax.Array,
                      *,
                      causal: bool = True,
                      bias: Optional[jax.Array] = None,
                      mask: Optional[jax.Array] = None,
                      scale: Optional[float] = None,
                      dropout_rate: float = 0.0,
                      dropout_rng: Optional[jax.Array] = None,
                      local_backend: str = "xla",
                      mesh: Optional[Mesh] = None) -> jax.Array:
    """Ulysses (all-to-all) sequence parallelism (global-array API).

    Heads (after any tensor-parallel split) must divide by the sequence
    axis size. The local attention runs with the ``local_backend`` op —
    ``"flash"`` selects the Pallas kernel on TPU.
    """
    if scale is None:
        scale = q.shape[-1]**-0.5
    mesh = _resolve_mesh(mesh)
    n = _seq_axis_size(mesh)
    if (n == 1 or bias is not None or mask is not None
            or (dropout_rate > 0.0 and dropout_rng is not None)):
        # a global bias/mask spans all H heads and L keys; the shard_map body
        # only sees H/n heads, so shard-aware slicing would be needed
        return _fallback(q, k, v, "no sequence axis or unsupported feature", causal=causal, bias=bias,
                         mask=mask, scale=scale, dropout_rate=dropout_rate, dropout_rng=dropout_rng)
    spec = _activation_specs(mesh, q.shape[0], q.shape[2])
    tp = mesh.shape.get(TENSOR_AXIS, 1) if spec[2] is not None else 1
    h_local = q.shape[2] // tp
    assert h_local % n == 0, (f"{h_local} local heads not divisible by sequence axis {n} "
                              "(Ulysses needs heads % (tp*sp) == 0; use ring attention instead)")

    from deepspeed_tpu.ops.transformer.attention import _BACKENDS
    if local_backend == "flash":
        from deepspeed_tpu.ops.pallas import flash_attention as _fa  # noqa: F401
    inner = functools.partial(_BACKENDS[local_backend], causal=causal, scale=float(scale))
    fn = shard_map(functools.partial(_ulysses_local, axis_name=SEQUENCE_AXIS, inner=inner),
                   mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)


class DistributedAttention:
    """Ulysses wrapper with the upstream DeepSpeed API shape
    (``deepspeed.sequence.layer.DistributedAttention`` in >= 0.10.2):
    wraps a *local* attention callable; scatters heads / gathers sequence
    around it over the sequence process group (here: mesh axis)."""

    def __init__(self,
                 local_attention: Callable,
                 sequence_axis: str = SEQUENCE_AXIS,
                 scatter_idx: int = 2,
                 gather_idx: int = 1,
                 mesh: Optional[Mesh] = None):
        if (scatter_idx, gather_idx) != (2, 1):
            raise NotImplementedError("BLHD layout requires scatter_idx=2 (heads), gather_idx=1 (length)")
        self.local_attn = local_attention
        self.axis = sequence_axis
        self.mesh = mesh

    def __call__(self, query, key, value, *args, **kwargs):
        mesh = _resolve_mesh(self.mesh)
        n = _seq_axis_size(mesh)
        if n == 1:
            return self.local_attn(query, key, value, *args, **kwargs)
        spec = _activation_specs(mesh, query.shape[0], query.shape[2])
        local_attn = self.local_attn
        # extra args go AFTER q/k/v (upstream local_attn(q, k, v, *args) convention)
        inner = (lambda q, k, v: local_attn(q, k, v, *args, **kwargs)) if args or kwargs else local_attn
        fn = shard_map(functools.partial(_ulysses_local, axis_name=self.axis, inner=inner),
                       mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
        return fn(query, key, value)
