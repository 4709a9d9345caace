"""Sharded MoE: top-k gating + the expert-parallel MoE layer.

TPU-native redesign of reference ``deepspeed/moe/sharded_moe.py``
(``top1gating`` :179, ``top2gating`` :277, ``MOELayer`` :420).

Key departures from the reference, all forced by XLA's compilation model
(SURVEY.md §7 "static shapes vs dynamic behavior"):

* **Static capacity.** The reference computes capacity from runtime token
  counts and, with ``drop_tokens=False``, all-reduces a dynamic max
  (``sharded_moe.py:208``). Under ``jit`` every shape is static: capacity is
  computed from the *static* token count at trace time, and
  ``drop_tokens=False`` maps to the worst case ``capacity = tokens_per_group``
  (no token can ever be dropped, same semantics, no dynamic shapes).
* **Declarative all-to-all.** The reference wraps ``dist.all_to_all_single``
  in an autograd Function (``sharded_moe.py:90``). Here the dispatched tensor
  ``[groups, experts, capacity, model]`` simply carries a sharding constraint
  moving the ``experts`` dim onto the ``expert`` mesh axis; XLA's SPMD
  partitioner inserts the all-to-all (and its transpose in the backward pass)
  and overlaps it with the expert GEMMs.
* **Group-local gating.** Tokens are reshaped to ``[groups, tokens, model]``
  where each group maps to one data-parallel shard, so the cumulative-sum
  position assignment stays shard-local exactly like the reference's
  per-rank gating, with no cross-device traffic.
* **Two dispatch/combine routes.** The reference's einsum formulation
  (``sec,sm->ecm`` over a dense one-hot mask) materializes a ``[G,S,E,C]``
  combine-weights tensor and pays O(S*E*C*M) FLOPs/bytes in both passes
  for what is really a gather of <= k*S rows. The ``sorted`` route
  (default; MegaBlocks-style permutation) instead flattens each kept token
  copy to a unique slot ``expert*C + position`` — the cumulative-sum
  position assignment is a stable counting sort by expert — builds the
  ``[E*C, M]`` dispatch buffer by row permutation, and combines by gather
  + k-way weighted sum. Both routes share the gating DECISION core
  (:func:`_top1_decisions` / :func:`_top2_decisions`), so routing choices,
  RTS drops, and rng streams are identical bit-for-bit. Which route a
  layer traces is its own ``route`` field (the model configuration's
  ``moe_route``, where the engine's ``"moe"`` block lands).
"""

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

import flax.linen as nn

from deepspeed_tpu.parallel.topology import (BATCH_AXES, DATA_AXIS, EXPERT_AXIS, FSDP_AXIS,
                                             get_topology)

TOPK_GATE_TIMER = 'topk_gate'
MOE_TIMER = 'moe'
FIRST_ALLTOALL_TIMER = '1st_a2a'
SECOND_ALLTOALL_TIMER = '2nd_a2a'


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int,
              drop_tokens: bool = True) -> int:
    """Static capacity (reference ``_capacity`` ``sharded_moe.py:156`` computes
    this on-device; shapes are static under jit so we do it at trace time)."""
    if not drop_tokens:
        # worst case: one expert receives every token (reference instead
        # all-reduces a dynamic max, sharded_moe.py:208 — dynamic shapes
        # don't exist under XLA)
        return num_tokens
    capacity = math.ceil((num_tokens / num_experts) * capacity_factor)
    # a buffer larger than the token count is pure padding
    return min(max(capacity, min_capacity), num_tokens)


def _gate_capacity(num_tokens: int, num_experts: int, capacity_factor: float,
                   min_capacity: int, drop_tokens: bool, k: int) -> int:
    """THE capacity derivation — single source for the gating cores (which
    assign slots against it) and ``TopKGate.capacity`` (which the sorted
    route sizes its permutation buffers with). The two must agree or
    ``expert*C + slot`` mis-addresses the buffer; the k choices share one
    buffer, hence the factor times k (reference ``top2gating``
    ``sharded_moe.py:285`` doubles it)."""
    return _capacity(num_tokens, num_experts, k * capacity_factor, min_capacity, drop_tokens)


def sec_signature(num_tokens: int, num_experts: int, capacity_factor: float,
                  min_capacity: int, k: int = 1,
                  drop_tokens: bool = True) -> Tuple[int, int, int]:
    """The dense route's ``[S, E, C]`` trailing-shape signature for one
    group of ``num_tokens`` tokens — the tensor whose absence graft-lint
    rule R001 enforces (analysis/rules.py). Single source of truth: both
    the analyzer scenarios and the MoE parity tests derive the banned
    shape from here, so a capacity-derivation change cannot silently
    de-fang the check."""
    return (num_tokens, num_experts,
            _gate_capacity(num_tokens, num_experts, capacity_factor, min_capacity,
                           drop_tokens, k))


def multiplicative_jitter(x, rng, epsilon=1e-2):
    """Reference ``sharded_moe.py:50``: multiply by U(1-eps, 1+eps)."""
    if epsilon == 0:
        return x
    u = jax.random.uniform(rng, x.shape, x.dtype, 1.0 - epsilon, 1.0 + epsilon)
    return x * u


def gumbel_rsample(rng, shape):
    return jax.random.gumbel(rng, shape)


def _keep_top_capacity(mask: jax.Array, priority: jax.Array, capacity: int) -> jax.Array:
    """Keep at most ``capacity`` selected tokens per expert, highest
    ``priority`` first (reference ``_top_idx`` + scatter trick,
    ``sharded_moe.py:170,237``). ``mask``/[S, E] one-hot, ``priority``/[S, E]."""
    num_experts = mask.shape[1]
    # top-k over the token dim per expert; ties resolve to lowest index
    # (position priority), matching torch.topk
    top_idx = jax.lax.top_k(priority.T, capacity)[1]  # [E, C]
    sel = jnp.zeros(mask.shape, mask.dtype).at[top_idx.T, jnp.arange(num_experts)[None, :]].set(1, mode="drop")
    return mask * sel


class SortedRouting(NamedTuple):
    """Compact per-token-copy routing decisions ([S, k] arrays; the sorted
    route's whole interface — no ``[S,E,C]`` tensor exists)."""

    expert: jax.Array   # int32 — assigned expert
    slot: jax.Array     # int32 — position inside the expert's capacity buffer
    weight: jax.Array   # fp32 — combine weight (0 when dropped)
    keep: jax.Array     # int32 — 1 iff the copy survived capacity


class GroupedRouting(NamedTuple):
    """:class:`SortedRouting` of a router that limits a token's choice to some
    groups of experts (:func:`group_limited`), with which groups those were."""

    expert: jax.Array
    slot: jax.Array
    weight: jax.Array
    keep: jax.Array
    group_kept: jax.Array   # bool [S, n_group] — the groups the choice was limited to


def _top1_decisions(logits, capacity_factor, min_capacity, used_token,
                    noisy_gate_policy, drop_tokens, use_rts, rng):
    """The top-1 decision core shared by the dense and sorted routes —
    everything up to (but excluding) the ``[S,E,C]`` materialization. One
    implementation so routing choices, RTS drops, and rng-split order can
    never drift between routes."""
    logits = logits.astype(jnp.float32)
    num_tokens, num_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=1)
    capacity = _gate_capacity(num_tokens, num_experts, capacity_factor, min_capacity,
                              drop_tokens, k=1)

    if noisy_gate_policy == 'RSample' and rng is not None:
        rng, noise_rng = jax.random.split(rng)
        indices1_s = jnp.argmax(logits + gumbel_rsample(noise_rng, logits.shape), axis=1)
    else:
        indices1_s = jnp.argmax(gates, axis=1)
    mask1 = jax.nn.one_hot(indices1_s, num_experts, dtype=jnp.int32)

    if used_token is not None:
        mask1 = mask1 * used_token[:, None].astype(mask1.dtype)

    exp_counts = jnp.sum(mask1, axis=0)

    # load-balancing loss (reference sharded_moe.py:212-215)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1.astype(jnp.float32), axis=0)
    l_aux = jnp.sum(me * ce) * num_experts

    # Random Token Selection (reference sharded_moe.py:218-230): priority is
    # uniform noise so over-capacity drops are unbiased; without RTS (or in
    # deterministic eval) priority is position order.
    if use_rts and rng is not None:
        rng, rts_rng = jax.random.split(rng)
        priority = mask1 * jax.random.uniform(rts_rng, mask1.shape)
    else:
        priority = mask1.astype(jnp.float32)
    mask1 = _keep_top_capacity(mask1, priority, capacity)

    # position of each surviving token inside its expert's capacity buffer
    locations1 = jnp.cumsum(mask1, axis=0) - 1
    locations1_s = jnp.sum(locations1 * mask1, axis=1)

    gates_masked = gates * mask1.astype(gates.dtype)
    return l_aux, gates_masked, mask1, indices1_s, locations1_s, exp_counts, capacity


def top1gating(logits: jax.Array,
               capacity_factor: float,
               min_capacity: int,
               used_token: Optional[jax.Array] = None,
               noisy_gate_policy: Optional[str] = None,
               drop_tokens: bool = True,
               use_rts: bool = True,
               rng: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-1 gating (reference ``top1gating`` ``sharded_moe.py:179``).

    ``logits``: [tokens, experts] fp32. Returns
    ``(l_aux, combine_weights [S,E,C], dispatch_mask [S,E,C] bool, exp_counts [E])``.
    """
    l_aux, gates_masked, _, _, locations1_s, exp_counts, capacity = _top1_decisions(
        logits, capacity_factor, min_capacity, used_token, noisy_gate_policy,
        drop_tokens, use_rts, rng)
    locations1_sc = jax.nn.one_hot(locations1_s, capacity, dtype=gates_masked.dtype)
    combine_weights = jnp.einsum("se,sc->sec", gates_masked, locations1_sc)
    dispatch_mask = combine_weights > 0
    return l_aux, combine_weights, dispatch_mask, exp_counts


def top1routing(logits: jax.Array,
                capacity_factor: float,
                min_capacity: int,
                used_token: Optional[jax.Array] = None,
                noisy_gate_policy: Optional[str] = None,
                drop_tokens: bool = True,
                use_rts: bool = True,
                rng: Optional[jax.Array] = None) -> Tuple[jax.Array, SortedRouting, jax.Array]:
    """Top-1 gating, compact form for the sorted route: same decisions as
    :func:`top1gating` (shared core), returned as per-token (expert, slot,
    weight, keep) instead of a dense ``[S,E,C]`` tensor.
    Returns ``(l_aux, SortedRouting [S,1] fields, exp_counts [E])``."""
    l_aux, gates_masked, mask1, indices1_s, locations1_s, exp_counts, _ = _top1_decisions(
        logits, capacity_factor, min_capacity, used_token, noisy_gate_policy,
        drop_tokens, use_rts, rng)
    routing = SortedRouting(
        expert=indices1_s.astype(jnp.int32)[:, None],
        slot=locations1_s.astype(jnp.int32)[:, None],
        weight=jnp.sum(gates_masked, axis=1)[:, None],  # gate prob, 0 when dropped
        keep=jnp.sum(mask1, axis=1).astype(jnp.int32)[:, None],
    )
    return l_aux, routing, exp_counts


def _top2_decisions(logits, capacity_factor, min_capacity, drop_tokens, rng):
    """The top-2 decision core shared by the dense and sorted routes."""
    logits = logits.astype(jnp.float32)
    num_tokens, num_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=1)
    capacity = _gate_capacity(num_tokens, num_experts, capacity_factor, min_capacity,
                              drop_tokens, k=2)

    indices1_s = jnp.argmax(gates, axis=1)
    mask1 = jax.nn.one_hot(indices1_s, num_experts, dtype=jnp.int32)

    # 2nd expert via Gumbel-max on the remaining logits (sharded_moe.py:292)
    if rng is not None:
        rng, noise_rng = jax.random.split(rng)
        logits_w_noise = logits + gumbel_rsample(noise_rng, logits.shape)
    else:
        logits_w_noise = logits
    logits_except1 = jnp.where(mask1.astype(bool), -jnp.inf, logits_w_noise)
    indices2_s = jnp.argmax(logits_except1, axis=1)
    mask2 = jax.nn.one_hot(indices2_s, num_experts, dtype=jnp.int32)

    locations1 = jnp.cumsum(mask1, axis=0) - 1
    locations2 = jnp.cumsum(mask2, axis=0) - 1
    # 2nd-choice tokens queue behind all 1st-choice tokens (sharded_moe.py:303)
    locations2 = locations2 + jnp.sum(mask1, axis=0, keepdims=True)

    exp_counts = jnp.sum(mask1, axis=0)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1.astype(jnp.float32), axis=0)
    l_aux = jnp.mean(me * ce) * num_experts * num_experts

    mask1 = mask1 * (locations1 < capacity).astype(mask1.dtype)
    mask2 = mask2 * (locations2 < capacity).astype(mask2.dtype)

    locations1_s = jnp.sum(locations1 * mask1, axis=1)
    locations2_s = jnp.sum(locations2 * mask2, axis=1)

    mask1_f = mask1.astype(gates.dtype)
    mask2_f = mask2.astype(gates.dtype)
    gates1_s = jnp.einsum("se,se->s", gates, mask1_f)
    gates2_s = jnp.einsum("se,se->s", gates, mask2_f)
    denom_s = jnp.maximum(gates1_s + gates2_s, jnp.finfo(gates.dtype).eps)
    gates1_s = gates1_s / denom_s
    gates2_s = gates2_s / denom_s
    return (l_aux, (mask1, mask2), (mask1_f, mask2_f), (indices1_s, indices2_s),
            (locations1_s, locations2_s), (gates1_s, gates2_s), exp_counts, capacity)


def top2gating(logits: jax.Array,
               capacity_factor: float,
               min_capacity: int,
               drop_tokens: bool = True,
               rng: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-2 gating (reference ``top2gating`` ``sharded_moe.py:277``)."""
    (l_aux, _, (mask1_f, mask2_f), _, (locations1_s, locations2_s),
     (gates1_s, gates2_s), exp_counts, capacity) = _top2_decisions(
        logits, capacity_factor, min_capacity, drop_tokens, rng)
    gates1 = gates1_s[:, None] * mask1_f
    gates2 = gates2_s[:, None] * mask2_f
    locations1_sc = jax.nn.one_hot(locations1_s, capacity, dtype=gates1.dtype)
    locations2_sc = jax.nn.one_hot(locations2_s, capacity, dtype=gates2.dtype)
    combine1 = jnp.einsum("se,sc->sec", gates1, locations1_sc)
    combine2 = jnp.einsum("se,sc->sec", gates2, locations2_sc)
    combine_weights = combine1 + combine2
    dispatch_mask = combine_weights > 0
    return l_aux, combine_weights, dispatch_mask, exp_counts


def top2routing(logits: jax.Array,
                capacity_factor: float,
                min_capacity: int,
                drop_tokens: bool = True,
                rng: Optional[jax.Array] = None) -> Tuple[jax.Array, SortedRouting, jax.Array]:
    """Top-2 gating, compact form for the sorted route (same decisions as
    :func:`top2gating`). Returns ``(l_aux, SortedRouting [S,2] fields,
    exp_counts [E])``; copy 0 is the argmax expert, copy 1 the sampled
    second choice."""
    (l_aux, (mask1, mask2), _, (indices1_s, indices2_s),
     (locations1_s, locations2_s), (gates1_s, gates2_s), exp_counts, _) = _top2_decisions(
        logits, capacity_factor, min_capacity, drop_tokens, rng)
    keep1 = jnp.sum(mask1, axis=1)
    keep2 = jnp.sum(mask2, axis=1)
    stack = lambda a, b: jnp.stack([a, b], axis=1)
    routing = SortedRouting(
        expert=stack(indices1_s, indices2_s).astype(jnp.int32),
        slot=stack(locations1_s, locations2_s).astype(jnp.int32),
        # the normalized weights carry no mask; zero dropped copies so they
        # contribute nothing to the combine (dense route: gates*_s ride a
        # masked one-hot instead)
        weight=stack(gates1_s * keep1, gates2_s * keep2),
        keep=stack(keep1, keep2).astype(jnp.int32),
    )
    return l_aux, routing, exp_counts



def group_limited(choice, n_group: int, topk_group: int, top: int = 2):
    """Group-limited (node-limited) routing's first stage (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2): the experts in ``n_group`` groups of
    consecutive experts, a group's score the sum of its ``top`` largest
    ``choice`` scores, the ``topk_group`` best groups kept (ties: the lower
    group) and every other group's experts put out of the choice. ``choice``
    [S, E] float32 -> ``(choice with -inf outside the kept groups, kept [S,
    n_group] bool)``."""
    tokens, experts = choice.shape
    if experts % n_group or not 1 <= topk_group <= n_group:
        raise ValueError(f"group-limited routing: {experts} experts in {n_group} groups, "
                         f"{topk_group} kept")
    grouped = choice.reshape(tokens, n_group, experts // n_group)
    group_score = jax.lax.top_k(grouped, top)[0].sum(axis=-1)            # [S, n_group]
    _, best = jax.lax.top_k(group_score, topk_group)
    kept = (best[:, :, None] == jnp.arange(n_group, dtype=best.dtype)).any(axis=1)
    limited = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(tokens, experts)
    return limited, kept


def _topk_decisions(logits, k, capacity_factor, min_capacity, drop_tokens, normalize,
                    used_token=None, score="softmax", select_bias=None, scale=1.0,
                    positions=True, groups=None):
    """The decision core for any ``k`` <= experts (OLMoE: 8 of 64): softmax
    over the experts in fp32, the ``k`` largest probabilities
    (``jax.lax.top_k``; ties go to the lower index), and as combine weights
    the softmax values themselves, or, with ``normalize``, those values
    divided by their sum. Deterministic: no sampled choice, no RTS.

    ``score="sigmoid"`` scores each expert alone (DeepSeek-V3, Nemotron-H);
    ``select_bias`` [E] is added to the scores for the *choice* only, the
    weights stay the unbiased scores; ``scale`` multiplies the weights after
    the normalisation. ``positions=False`` (drop-free only) assigns no copy
    its place in its expert's buffer: the caller groups the copies itself
    (``MOELayer._held_route``), ``slot`` is zero and ``exp_counts`` counts
    all ``k`` choices. ``groups`` = ``(n_group, topk_group)`` limits the choice
    to the experts of each token's ``topk_group`` best groups
    (:func:`group_limited`, over the biased scores; a group's score its two
    largest summed, or its largest where there is no bias, as the release
    has it); the routing is then a :class:`GroupedRouting`.

    Slots are assigned choice-major, as the top-2 core does: every first
    choice queues in its expert's buffer before any second choice, so what
    a bounded capacity drops first is the lowest-ranked choices. With
    ``drop_tokens=False`` the capacity is the token count, which no expert
    can exceed (a token's ``k`` experts are distinct), and nothing drops."""
    logits = logits.astype(jnp.float32)
    num_tokens, num_experts = logits.shape
    gates = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, axis=1)
    capacity = _gate_capacity(num_tokens, num_experts, capacity_factor, min_capacity,
                              drop_tokens, k)
    compact = SortedRouting
    if select_bias is None and groups is None:
        weights, experts = jax.lax.top_k(gates, k)                  # [S, k]
    else:
        choice = gates if select_bias is None else (
            gates + select_bias.astype(jnp.float32)[None, :])
        if groups is not None:
            choice, group_kept = group_limited(choice, *groups,
                                               top=1 if select_bias is None else 2)
            compact = functools.partial(GroupedRouting, group_kept=group_kept)
        _, experts = jax.lax.top_k(choice, k)
        weights = jnp.take_along_axis(gates, experts, axis=1)
    if normalize:
        weights = weights / jnp.maximum(weights.sum(axis=1, keepdims=True),
                                        jnp.finfo(gates.dtype).eps)
    if scale != 1.0:
        weights = weights * scale
    if not positions:
        if drop_tokens:
            raise ValueError("positions=False leaves the copies' places to the caller: only "
                             "where no copy is dropped (drop_tokens=False)")
        keep = (jnp.ones(experts.shape, jnp.int32) if used_token is None
                else jnp.broadcast_to(used_token[:, None].astype(jnp.int32), experts.shape))
        # every choice's copies, not the first choice's alone: one compare and
        # add a (copy, expert), which fuses into the sum; the cumulative sum
        # below would write its [S, k, E] out (92 M elements a layer at 8,192
        # tokens x 22 of 512)
        counts = jnp.sum((experts[:, :, None] == jnp.arange(num_experts, dtype=experts.dtype))
                         & (keep[:, :, None] > 0), axis=(0, 1), dtype=jnp.int32)
        l_aux = jnp.sum(jnp.mean(gates, axis=0) * counts / num_tokens) * num_experts
        routing = compact(expert=experts.astype(jnp.int32), slot=jnp.zeros_like(keep),
                          weight=weights * keep, keep=keep)
        return l_aux, routing, counts, capacity
    masks = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)   # [S, k, E]
    if used_token is not None:
        masks = masks * used_token[:, None, None].astype(masks.dtype)
    counts = masks.sum(axis=0)                                      # [k, E]
    queued_before = jnp.cumsum(counts, axis=0) - counts             # by earlier choices
    locations = jnp.cumsum(masks, axis=0) - 1 + queued_before[None]
    slot = jnp.sum(locations * masks, axis=2)                       # [S, k]
    keep = masks.sum(axis=2) * (slot < capacity).astype(jnp.int32)
    exp_counts = counts[0]
    # load balancing as the OLMoE / Mixtral publications compute it: the
    # mean router probability of an expert times the share of tokens that
    # chose it, summed over the k choices
    l_aux = jnp.sum(jnp.mean(gates, axis=0)
                    * jnp.mean(masks.sum(axis=1).astype(jnp.float32), axis=0)) * num_experts
    routing = compact(expert=experts.astype(jnp.int32), slot=slot.astype(jnp.int32),
                      weight=weights * keep, keep=keep)
    return l_aux, routing, exp_counts, capacity


def topkrouting(logits: jax.Array, k: int, capacity_factor: float, min_capacity: int,
                drop_tokens: bool = True, normalize: bool = False,
                used_token: Optional[jax.Array] = None,
                **scoring) -> Tuple[jax.Array, SortedRouting, jax.Array]:
    """Top-``k`` gating, compact form for the sorted route. Returns
    ``(l_aux, SortedRouting [S,k] fields, exp_counts [E])``. ``scoring``:
    ``score``, ``select_bias``, ``scale``, ``groups`` of :func:`_topk_decisions`."""
    l_aux, routing, exp_counts, _ = _topk_decisions(
        logits, k, capacity_factor, min_capacity, drop_tokens, normalize, used_token, **scoring)
    return l_aux, routing, exp_counts


def topkgating(logits: jax.Array, k: int, capacity_factor: float, min_capacity: int,
               drop_tokens: bool = True, normalize: bool = False,
               used_token: Optional[jax.Array] = None, **scoring):
    """Top-``k`` gating in the dense route's form: the same decisions as
    :func:`topkrouting`, spread into ``[S,E,C]`` combine weights."""
    l_aux, routing, exp_counts, capacity = _topk_decisions(
        logits, k, capacity_factor, min_capacity, drop_tokens, normalize, used_token, **scoring)
    expert_se = jax.nn.one_hot(routing.expert, logits.shape[1], dtype=jnp.float32)  # [S,k,E]
    slot_sc = jax.nn.one_hot(routing.slot, capacity, dtype=jnp.float32)             # [S,k,C]
    combine_weights = jnp.einsum("sk,ske,skc->sec", routing.weight, expert_se, slot_sc)
    return l_aux, combine_weights, combine_weights > 0, exp_counts



def _constrain_groups(x, spec, n_groups: int):
    """Apply a sharding constraint when the group dim really maps onto the
    DP shards (one guard for the gate/dispatch/combine sites; tiny
    standalone batches fail divisibility and stay unconstrained)."""
    topo = get_topology()
    if topo is None or n_groups != topo.data_parallel_size or topo.mesh.size == 1:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, NamedSharding(topo.mesh, P(*spec)))


class TopKGate(nn.Module):
    """Gate module (reference ``TopKGate`` ``sharded_moe.py:347``): a bias-free
    fp32 linear + top-k gating. Operates on ``[groups, tokens, model]``.

    ``route="dense"`` returns the historical 4-tuple with ``[G,S,E,C]``
    combine weights; ``route="sorted"`` returns
    ``(l_aux, SortedRouting [G,S,k] fields, exp_counts)`` — same decisions
    (shared cores), compact representation."""

    model_dim: int
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 8
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    route: str = "dense"
    # k >= 2: combine weights renormalised over the k chosen experts (the
    # reference top-2, Mixtral) or the softmax values as they are (OLMoE's
    # ``norm_topk_prob`` false)
    norm_topk_prob: bool = True
    # how an expert is scored: "softmax" over the experts, or "sigmoid", each
    # alone. With ``select_bias`` the ``k`` chosen are the top of score +
    # ``e_score_correction_bias`` [E] (a parameter of this gate) and the
    # weights the unbiased scores; ``routed_scale`` multiplies the weights
    # after the normalisation (DeepSeek-V3's router, Nemotron-H's)
    score: str = "softmax"
    select_bias: bool = False
    routed_scale: float = 1.0
    # > 1: group-limited routing (:func:`group_limited`): the ``k`` are chosen
    # among the experts of a token's ``topk_group`` best groups of ``n_group``
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, tokens, used_token=None, deterministic: bool = True,
                 positions: bool = True):
        """``positions=False`` (the layer's to pass, drop-free only): no copy
        is given its place in its expert's buffer, O(S k E) by the one-hot
        cumulative sum; the layer groups the copies it holds itself."""
        if not 1 <= self.k <= self.num_experts:
            raise ValueError(f"top-k gating needs 1 <= k <= experts "
                             f"(got k={self.k}, experts={self.num_experts})")
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"gate score must be 'softmax' or 'sigmoid', got {self.score!r}")
        # the gate runs in fp32 regardless of compute dtype (reference keeps
        # wg in fp32, sharded_moe.py:373,394)
        wg = self.param("wg", nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", None)),
                        (self.model_dim, self.num_experts), jnp.float32)
        wg_value = wg.value if isinstance(wg, nn.meta.AxisMetadata) else wg

        x = tokens.astype(jnp.float32)
        rng = None
        # k==2 needs the rng too: the second expert is Gumbel-max sampled
        # during training (reference sharded_moe.py:292)
        if not deterministic and (self.use_rts or self.noisy_gate_policy is not None or self.k == 2):
            rng = self.make_rng("gating")
            if self.noisy_gate_policy == 'Jitter':
                rng, jit_rng = jax.random.split(rng)
                x = multiplicative_jitter(x, jit_rng)
        logits = jnp.einsum("gsm,me->gse", x, wg_value)
        # pin the logits group-sharded: with_sharding_constraint transposes
        # onto the COTANGENT, so the gate-weight gradient lowers as a local
        # partial + tiny [M,E] all-reduce instead of all-gathering the full
        # token array to every chip (per-chip bytes that grew with the mesh
        # — caught by the EP scaling report)
        logits = _constrain_groups(logits, (BATCH_AXES, None, None), logits.shape[0])

        cf = self._cf(deterministic)
        groups = logits.shape[0]
        rngs = jax.random.split(rng, groups) if rng is not None else None

        top1_fn = top1routing if self.route == "sorted" else top1gating
        top2_fn = top2routing if self.route == "sorted" else top2gating
        scoring = {}
        if (self.score != "softmax" or self.select_bias or self.routed_scale != 1.0
                or not positions or self.n_group > 1):
            # the top-k core alone knows these: it serves every k
            scoring = dict(score=self.score, scale=self.routed_scale, positions=positions)
            if self.n_group > 1:
                scoring["groups"] = (self.n_group, self.topk_group)
            if self.select_bias:
                bias = self.param("e_score_correction_bias",
                                  nn.with_logical_partitioning(nn.initializers.normal(0.02), (None,)),
                                  (self.num_experts,), jnp.float32)
                scoring["select_bias"] = bias.value if isinstance(bias, nn.meta.AxisMetadata) else bias
        if scoring:
            topk_fn = topkrouting if self.route == "sorted" else topkgating
            gate_fn = lambda lg, r, ut: topk_fn(lg, self.k, cf, self.min_capacity,
                                                self.drop_tokens, self.norm_topk_prob, ut,
                                                **scoring)
        elif self.k == 1:
            gate_fn = lambda lg, r, ut: top1_fn(lg, cf, self.min_capacity, ut,
                                                self.noisy_gate_policy if not deterministic else None,
                                                self.drop_tokens, self.use_rts, r)
        elif self.k == 2 and self.norm_topk_prob:
            gate_fn = lambda lg, r, ut: top2_fn(lg, cf, self.min_capacity, self.drop_tokens, r)
        else:
            topk_fn = topkrouting if self.route == "sorted" else topkgating
            gate_fn = lambda lg, r, ut: topk_fn(lg, self.k, cf, self.min_capacity,
                                                self.drop_tokens, self.norm_topk_prob, ut)

        if used_token is None:
            out = jax.vmap(lambda lg, r: gate_fn(lg, r, None))(logits, rngs) if rngs is not None \
                else jax.vmap(lambda lg: gate_fn(lg, None, None))(logits)
        else:
            ut = used_token.reshape(groups, -1)
            out = jax.vmap(lambda lg, r, u: gate_fn(lg, r, u))(logits, rngs, ut) if rngs is not None \
                else jax.vmap(lambda lg, u: gate_fn(lg, None, u))(logits, ut)
        if self.route == "sorted":
            l_aux, routing, exp_counts = out
            return l_aux.mean(), routing, exp_counts.sum(axis=0)
        l_aux, combine_weights, dispatch_mask, exp_counts = out
        return l_aux.mean(), combine_weights, dispatch_mask, exp_counts.sum(axis=0)

    def _cf(self, deterministic: bool) -> float:
        """Train-vs-eval capacity factor selection — one source for
        ``__call__`` (which hands it to the gating cores) and
        :meth:`capacity`."""
        return self.capacity_factor if not deterministic else self.eval_capacity_factor

    def capacity(self, num_tokens: int, deterministic: bool = True) -> int:
        """The static per-expert capacity this gate resolves for a group of
        ``num_tokens`` — same :func:`_gate_capacity` the gating cores assign
        slots against (the sorted route sizes its permutation buffers with
        this; any divergence would mis-address ``expert*C + slot``)."""
        return _gate_capacity(num_tokens, self.num_experts, self._cf(deterministic),
                              self.min_capacity, self.drop_tokens, self.k)


class Experts(nn.Module):
    """Parallel experts (reference ``Experts`` ``moe/experts.py:10``).

    The reference deep-copies the expert module ``num_local_experts`` times
    and loops; here one ``nn.vmap`` gives every expert its own parameters
    with a leading ``expert`` logical axis, which the sharding rules map onto
    the ``expert`` mesh axis — expert-parallel compute with zero loop
    overhead and a single fused GEMM per projection.
    """

    expert: nn.Module
    num_experts: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True, group_sizes=None, impl: str = "xla"):
        # x: [groups, experts, capacity, model] → vmap over the expert dim.
        # An unbound copy keeps params under this scope with a stable name
        # (reference state-dict path "…experts.deepspeed_experts.N").
        expert = self.expert.copy(name="deepspeed_experts")
        if getattr(expert, "num_experts", 0):
            # a bank: one module that holds every expert's weights under the
            # same paths and shapes the vmap below gives them, and also
            # takes rows sorted by expert (``group_sizes``, drop-free route)
            return expert(x, deterministic=deterministic, group_sizes=group_sizes, impl=impl)
        if group_sizes is not None:
            raise ValueError(f"{type(expert).__name__} is not an expert bank: rows grouped "
                             f"by expert need a module that takes group_sizes")
        xt = jnp.moveaxis(x, 1, 0)  # [E, G, C, M]
        vmapped = nn.vmap(
            lambda mdl, xi: mdl(xi, deterministic=deterministic),
            in_axes=0,
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            metadata_params={nn.meta.PARTITION_NAME: "expert"},
        )
        out = vmapped(expert, xt)
        return jnp.moveaxis(out, 0, 1)


ROUTE_CHOICES = ("dense", "sorted")

_warned_sorted = set()


def _warn_sorted_fallback(reason: str):
    if reason not in _warned_sorted:
        _warned_sorted.add(reason)
        from deepspeed_tpu.utils.logging import logger
        logger.warning(f"sorted MoE route falling back to the XLA permutation: {reason}")


def _num_groups(num_tokens_leading: int) -> int:
    """Pick the token-group count: one group per data-parallel shard when the
    global topology is known and divides the batch, else a single group."""
    topo = get_topology()
    if topo is None:
        return 1
    dp = topo.data_parallel_size
    if dp > 1 and num_tokens_leading % dp == 0:
        return dp
    return 1


#: the collection a held layer writes its step's counts into where the
#: caller makes it mutable (the engine's train step, ``runtime/engine.py``),
#: and the names of the counts, in the vector's order
STEP_COUNTS = "step_counts"
HELD_COUNTS = ("rows_routed", "rows_visited", "copies", "experts_touched", "rows_buffered",
               "load_max")

#: ``MOELayer.experts_held``: the row buffer is a sixteenth or a quarter of
#: the copies where the held rows fit one; a buffer under ``MIN_RUNG_ROWS``
#: is not worth a branch (a decode tick's few hundred copies take none)
RUNG_FRACTIONS, MIN_RUNG_ROWS = (16, 4), 1024


def _row_rungs(copies: int, held_share: Optional[float] = None) -> Tuple[int, ...]:
    """The static sizes a held layer's row buffer may take for ``copies``
    token copies, ascending, each but the last in whole row tiles of the
    grouped matmul; the last is every copy. A serving tick's rows are mostly
    padding or another device's and vary tick by tick: ``RUNG_FRACTIONS`` of
    the copies. A training step (``held_share`` given: the held experts'
    part of all) has a real token in every row and gets the share of an even
    router, which its own gradient then pulls toward the held experts (the
    only ones whose output it sees): one rung, at twice that share."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import ROW_TILE_LARGE as tile
    if held_share is None:
        rungs = [-(-copies // (part * tile)) * tile for part in RUNG_FRACTIONS]
    else:
        rungs = [-(-int(2 * held_share * copies) // tile) * tile]
    return tuple(r for r in rungs if MIN_RUNG_ROWS <= r < copies) + (copies,)


class MOELayer(nn.Module):
    """The MoE layer (reference ``MOELayer`` ``sharded_moe.py:420``):
    gate → dispatch → all-to-all → experts → all-to-all → combine.

    On TPU the two all-to-alls are not explicit ops: the dispatched tensor's
    sharding constraint moves the ``experts`` dim onto the ``expert`` mesh
    axis (and the group dim off it), and XLA emits the all-to-all pair in
    forward and backward. Both routes produce the same ``[G,E,C,M]``
    dispatched tensor with the same constraint pair, so the transfer stays
    capacity-bounded either way; what differs is how it is BUILT —
    ``dense``: the reference einsum over a ``[G,S,E,C]`` one-hot
    (O(S*E*C*M) FLOPs/bytes fwd+bwd); ``sorted``: row permutation of the
    <= k*S dispatched tokens (O(k*S*M) moved, zero mask FLOPs).

    ``route_kernel`` is the sorted route's permutation: ``"xla"`` (gather,
    runs everywhere), ``"pallas"`` (``ops/pallas/moe_dispatch.py``) or
    ``"auto"`` (pallas on a TPU, xla elsewhere).
    """

    expert: nn.Module
    model_dim: int
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 8
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    route: str = "sorted"
    route_kernel: str = "auto"
    norm_topk_prob: bool = True
    # the gate's scoring (``TopKGate``): "softmax" | "sigmoid", a selection
    # bias, a scale on the normalised weights
    score: str = "softmax"
    select_bias: bool = False
    routed_scale: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # ``(first, count)``: the experts this device holds of ``num_experts``
    # (its share of an expert-parallel layer). The gate routes over all of
    # them; only copies routed to a held expert are grouped, computed and
    # combined, so the result is this device's part of the layer's sum and
    # ``expert`` is a bank of ``count``. None: all of them, today's layer
    experts_held: Optional[Tuple[int, int]] = None
    # > 0: the experts live in a latent space of this size, between two
    # bias-free projections ``latent_down`` [model, latent] and ``latent_up``
    # that every device holds whole; the gate still reads the model's state
    latent_dim: int = 0
    # a module [..., model] -> [..., model] every token passes through, added
    # to the routed result (held whole by every device, like the projections)
    shared_expert: Optional[nn.Module] = None
    # of the latent projections (the gate's weight is float32, the bank's
    # are its own)
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, hidden_states, used_token=None, deterministic: bool = True,
                 router_input=None):
        """``router_input``: what the gate reads, shaped like ``hidden_states``,
        where the router does not read what the experts read (a router placed
        before attention); None, today's layer: the gate reads the tokens."""
        orig_shape = hidden_states.shape
        orig_dtype = hidden_states.dtype
        d_model = orig_shape[-1]
        batch = orig_shape[0]
        route, kernel = self.route, self.route_kernel
        if route not in ROUTE_CHOICES:
            raise ValueError(f"moe route must be one of {ROUTE_CHOICES}, got {route!r}")

        groups = _num_groups(batch)
        tokens = hidden_states.reshape(groups, -1, d_model)  # [G, S, M]

        def constrain(x, spec):
            return _constrain_groups(x, spec, groups)

        tokens = constrain(tokens, (BATCH_AXES, None, None))
        # what the gate reads; the same array where no router input is given,
        # so that program is the one it was
        gate_tokens = tokens if router_input is None else constrain(
            router_input.reshape(tokens.shape), (BATCH_AXES, None, None))

        gate = TopKGate(self.model_dim, self.num_experts, self.k, self.capacity_factor,
                        self.eval_capacity_factor, self.min_capacity, self.noisy_gate_policy,
                        self.drop_tokens, self.use_rts, route=route,
                        norm_topk_prob=self.norm_topk_prob, score=self.score,
                        select_bias=self.select_bias, routed_scale=self.routed_scale,
                        n_group=self.n_group, topk_group=self.topk_group, name="gate")
        if (self.experts_held is not None or self.latent_dim) and route != "sorted":
            raise ValueError("experts_held and latent_dim are options of the sorted route")

        if route == "sorted":
            out, l_aux, exp_counts, kept_counts, routed_counts, capacity = self._sorted_route(
                gate, gate_tokens, tokens, used_token, deterministic, kernel, constrain,
                orig_dtype, groups)
        else:
            out, l_aux, exp_counts, kept_counts, routed_counts, capacity = self._dense_route(
                gate, gate_tokens, tokens, used_token, deterministic, constrain, orig_dtype)

        out = out.reshape(orig_shape)
        if self.shared_expert is not None:
            with jax.named_scope("moe_shared"):
                out = out + self.shared_expert.copy(name="shared_expert")(hidden_states)
        # expert-load observability (threaded to monitor/ by the engine):
        # exp_counts = first-choice routing decisions pre-drop (the reference
        # contract, and the signal the aux loss balances), kept_counts =
        # surviving token COPIES post-capacity (all k choices),
        # routed_counts = all k copies pre-capacity (kept's denominator —
        # sown only where the route exposes it: the dense top-2 gate's
        # public 4-tuple hides the second-choice decisions),
        # capacity_slots = buffer slots per expert
        self.sow("intermediates", "exp_counts", exp_counts)
        self.sow("intermediates", "kept_counts", kept_counts)
        if routed_counts is not None:
            self.sow("intermediates", "routed_counts", routed_counts)
        self.sow("intermediates", "capacity_slots",
                 jnp.asarray(groups * capacity, jnp.int32))
        return out, l_aux.astype(jnp.float32), exp_counts

    def _dense_route(self, gate, gate_tokens, tokens, used_token, deterministic, constrain,
                     orig_dtype):
        l_aux, combine_weights, dispatch_mask, exp_counts = gate(gate_tokens, used_token,
                                                                 deterministic)

        # dispatch: [G,S,E,C] × [G,S,M] → [G,E,C,M] (reference 'sec,sm->ecm').
        # Pin the einsum output G-sharded FIRST: both operands are G-sharded,
        # so the einsum is comm-free, and the NEXT constraint reshards
        # G-sharded→E-sharded as a capacity-bounded all-to-all (payload
        # tokens×M per chip, flat in the mesh). Without this pin GSPMD may
        # instead ALL-GATHER the full token array to every chip — per-chip
        # bytes that grow with the mesh (caught by the EP scaling report).
        dispatched = jnp.einsum("gsec,gsm->gecm", dispatch_mask.astype(orig_dtype), tokens)
        dispatched = constrain(dispatched, (BATCH_AXES, None, None, None))
        # "first all-to-all": group dim leaves the expert mesh axis, expert dim
        # takes it (reference _AllToAll forward, sharded_moe.py:475)
        dispatched = constrain(dispatched, ((DATA_AXIS, FSDP_AXIS), EXPERT_AXIS, None, None))

        expert_out = Experts(self.expert, self.num_experts, name="experts")(dispatched, deterministic)
        expert_out = constrain(expert_out, ((DATA_AXIS, FSDP_AXIS), EXPERT_AXIS, None, None))

        # "second all-to-all" made EXPLICIT on the input side: reshard the
        # expert outputs E-sharded -> G-sharded (capacity-bounded payload,
        # flat per chip) so the combine einsum and its whole backward stay
        # local. Leaving the reshard to the OUTPUT constraint let GSPMD
        # all-gather the [G,S,M] cotangent in the backward instead —
        # per-chip bytes growing with the mesh (EP scaling report).
        expert_out = constrain(expert_out, (BATCH_AXES, None, None, None))

        # combine: [G,S,E,C] × [G,E,C,M] → [G,S,M]
        combined = jnp.einsum("gsec,gecm->gsm", combine_weights.astype(orig_dtype), expert_out)
        combined = constrain(combined, (BATCH_AXES, None, None))
        kept_counts = dispatch_mask.sum(axis=(0, 1, 3)).astype(jnp.int32)
        # k=1: every routed copy is a first choice, so exp_counts IS the
        # kept denominator; k=2: the dense gate's public return hides the
        # second-choice routing — no exact denominator to report
        routed_counts = exp_counts if self.k == 1 else None
        return combined, l_aux, exp_counts, kept_counts, routed_counts, combine_weights.shape[-1]

    def _sorted_route(self, gate, gate_tokens, tokens, used_token, deterministic, kernel,
                      constrain, orig_dtype, groups):
        from deepspeed_tpu.ops.pallas.moe_dispatch import (inverse_index, permute_rows,
                                                           resolve_impl)
        num_tokens = tokens.shape[1]
        d_model = tokens.shape[2]
        E = self.num_experts

        impl = resolve_impl(kernel)
        topo = get_topology()
        on_mesh = topo is not None and topo.mesh.size > 1
        if impl == "pallas" and on_mesh:
            # pallas_call has no SPMD partitioning rule on a live mesh; the
            # XLA permutation lowers to the same per-shard gathers
            _warn_sorted_fallback("pallas MoE dispatch on a multi-device mesh")
            impl = "xla"
        # drop-free routing over an expert bank on one device: the copies
        # are grouped by expert with no padding between the groups (below);
        # everywhere else each expert owns ``capacity`` rows of the buffer
        ragged = (not self.drop_tokens and not on_mesh
                  and getattr(self.expert, "num_experts", 0) > 0)
        # there the kernel choice is the grouped matmul's; the rows move by
        # XLA's gather. The row-DMA kernel views 16-bit rows as 32-bit words
        # through a [.., M/2, 2] reshape, which the TPU tiles 64 times over:
        # at a prefill tick's 16,384 rows of 2,048 it made the tick 208 ms
        # (PERF.md, PR 26)
        permute_impl = "xla" if ragged else impl
        if self.experts_held is not None:
            if not ragged:
                raise NotImplementedError(
                    "experts_held needs the drop-free grouped layout of one device "
                    "(drop_tokens=False over an expert bank); on a mesh the layer's "
                    "exchange is not built")
            return self._held_route(gate, gate_tokens, tokens, used_token, deterministic, impl,
                                    orig_dtype)

        with jax.named_scope("moe_route"):
            l_aux, routing, exp_counts = gate(gate_tokens, used_token, deterministic)
            capacity = gate.capacity(num_tokens, deterministic)
            k = routing.expert.shape[-1]
            # which experts each token took, [G, S, k], best first (read
            # with mutable=["intermediates"]; costs nothing otherwise)
            self.sow("intermediates", "expert_choice", routing.expert)
            kept_counts = jnp.zeros((E,), jnp.int32).at[routing.expert.reshape(-1)].add(
                routing.keep.reshape(-1).astype(jnp.int32))
            if ragged:
                # one device, so one group (``_num_groups``): ``kept_counts``
                # are its group sizes, and group e starts where the groups
                # before it end. The buffer holds S*k rows, every one a copy
                sizes = kept_counts
                starts = jnp.cumsum(kept_counts) - kept_counts
                base = starts[routing.expert].reshape(groups, -1)
                rows = num_tokens * k
                capacity = -(-rows // E)   # evidence only: mean rows an expert
            else:
                base = (routing.expert * capacity).reshape(groups, -1)
                rows = E * capacity
            # each kept copy owns a unique row base + position (the cumsum
            # position assignment is a stable counting sort by expert);
            # dropped copies park on the sentinel → zero rows / no reads
            flat_slot = jnp.where(routing.keep.reshape(groups, -1) > 0,
                                  base + routing.slot.reshape(groups, -1),
                                  rows).astype(jnp.int32)
            flat_slot = constrain(flat_slot, (BATCH_AXES, None))
            src = inverse_index(flat_slot, rows)  # [G, rows] — row -> token copy
            src = constrain(src, (BATCH_AXES, None))

            tokens = self._latent("latent_down", tokens, orig_dtype)
            d_model = tokens.shape[2]
            # [G, S, M] -> [G, S*k, M], copy j of token s at row s*k + j (the
            # reshape order of the [S, k] routing fields)
            tok_rep = jnp.repeat(tokens, k, axis=1) if k > 1 else tokens
            # dispatch = pure row permutation
            dispatched = permute_rows(tok_rep, src, flat_slot, impl=permute_impl)

        with jax.named_scope("moe_experts"):
            experts = Experts(self.expert, self.num_experts, name="experts")
            if ragged:
                expert_out = experts(dispatched[0], deterministic,
                                     group_sizes=sizes, impl=impl)[None]
            else:
                # same constraint pair as the dense route so the expert
                # all-to-all still moves only the capacity-bounded
                # [G,E,C,M] buffer
                dispatched = dispatched.reshape(groups, E, capacity, d_model)
                dispatched = constrain(dispatched, (BATCH_AXES, None, None, None))
                dispatched = constrain(dispatched,
                                       ((DATA_AXIS, FSDP_AXIS), EXPERT_AXIS, None, None))
                expert_out = experts(dispatched, deterministic)
                expert_out = constrain(expert_out,
                                       ((DATA_AXIS, FSDP_AXIS), EXPERT_AXIS, None, None))
                expert_out = constrain(expert_out, (BATCH_AXES, None, None, None))
                expert_out = expert_out.reshape(groups, rows, d_model)

        with jax.named_scope("moe_combine"):
            # combine: gather each copy's expert output back and weight it —
            # k fused multiply-adds per token instead of the [G,S,E,C] einsum
            gathered = permute_rows(expert_out, flat_slot, src, impl=permute_impl)
            weights = routing.weight.astype(orig_dtype).reshape(groups, num_tokens * k, 1)
            combined = (weights * gathered).reshape(groups, num_tokens, k, d_model).sum(axis=2)
            combined = constrain(combined, (BATCH_AXES, None, None))
        combined = self._latent("latent_up", combined, orig_dtype)

        # all k copies pre-capacity: the compact routing names every copy's
        # expert, so the kept denominator is exact for every k (k=1: equals
        # exp_counts; k>=2: adds the later choices the dense return hides)
        routed_counts = exp_counts if k == 1 else (
            exp_counts
            + jnp.zeros((E,), jnp.int32).at[routing.expert[..., 1:].reshape(-1)].add(1))
        return combined, l_aux, exp_counts, kept_counts, routed_counts, capacity

    def _held_route(self, gate, gate_tokens, tokens, used_token, deterministic, impl,
                    orig_dtype):
        """The sorted route where this device holds ``experts_held`` of the
        experts (drop-free, one device, so one group): a copy routed to an
        expert another device holds is that device's to compute, and a copy
        of a padding position is nobody's. What moves is the rows that are
        real and held here. Their number is known from the routing alone,
        before any row moves, and the buffer that the gather, the expert
        matmuls, the activation and the combine run over is sized by it, on
        the device: the smallest of :func:`_row_rungs`' static sizes that
        holds them, the largest every copy, so no routing can lose a row."""
        from deepspeed_tpu.ops.pallas.grouped_matmul import rows_visited
        from deepspeed_tpu.ops.pallas.moe_dispatch import permute_rows
        num_tokens, E = tokens.shape[1], self.num_experts
        first, count = self.experts_held

        with jax.named_scope("moe_route"):
            l_aux, routing, exp_counts = gate(gate_tokens, used_token, deterministic,
                                              positions=False)
            k = routing.expert.shape[-1]
            copies = num_tokens * k
            # which experts each token took, [G, S, k], best first (read
            # with mutable=["intermediates"]; costs nothing otherwise)
            self.sow("intermediates", "expert_choice", routing.expert)
            # the gate counted every choice's copies: the held experts' are
            # this device's group sizes, packed from row 0
            sizes = exp_counts[first:first + count]
            held_rows = sizes.sum()
            kept_counts = jnp.zeros((E,), jnp.int32).at[first:first + count].set(sizes)
            # row -> copy: one stable sort puts the real copies of held
            # experts first, by expert and inside one by token; copy j of
            # token s is s*k + j (the reshape order of the [S, k] fields)
            expert = routing.expert.reshape(-1) - first
            mine = (routing.keep.reshape(-1) > 0) & (expert >= 0) & (expert < count)
            _, copy_of = jax.lax.sort(
                (jnp.where(mine, expert, count), jnp.arange(copies, dtype=jnp.int32)),
                num_keys=1, is_stable=True)
            rungs = _row_rungs(copies, None if deterministic else count / self.num_experts)
            rung = sum((held_rows > rows).astype(jnp.int32) for rows in rungs[:-1])

        for collection, with_max in (("cache", False), (STEP_COUNTS, True)):
            if not self.is_mutable_collection(collection):
                continue
            extra = (sizes.max(),) if with_max else ()
            # for the host, beside a serving tick's tokens or a training
            # step's loss: rows routed to experts held here, rows of the
            # tiles the expert matmuls run over, copies routed to any expert,
            # held experts that got a row (whose weights the tick streams),
            # rows of the buffer chosen; a step adds the fullest held
            # expert's rows (``HELD_COUNTS`` names them in this order)
            visited = jnp.stack([rows_visited(sizes, rows) for rows in rungs])
            self.variable(collection, "moe_rows", jnp.zeros, (5 + len(extra),),
                          jnp.int32).value = jnp.stack(
                [held_rows, visited[rung], exp_counts.sum(), (sizes > 0).sum(),
                 jnp.asarray(rungs, jnp.int32)[rung], *extra]).astype(jnp.int32)

        if isinstance(routing, GroupedRouting) and self.is_mutable_collection("cache"):
            # what group-limited routing exists to bound: the real rows whose
            # kept groups include one with an expert held here (the rows that
            # visit this device at all), beside the real rows routed
            per = E // self.n_group
            reaches = routing.group_kept[..., first // per:(first + count - 1) // per + 1].any(-1)
            real = routing.keep[..., 0] > 0
            self.variable("cache", "moe_group_rows", jnp.zeros, (2,), jnp.int32).value = (
                jnp.stack([(reaches & real).sum(), real.sum()]).astype(jnp.int32))

        tokens = self._latent("latent_down", tokens, orig_dtype)[0]
        weights = routing.weight.reshape(-1)

        def through(rows, experts, tokens, weights, copy_of, held_rows, sizes):
            """The held rows through a buffer of ``rows`` rows: [S, M]."""
            copy_of = copy_of[:rows]
            real = jnp.arange(rows, dtype=jnp.int32) < held_rows
            with jax.named_scope("moe_route"):
                token_of = jnp.where(real, copy_of // k, num_tokens)
                dispatched = permute_rows(tokens[None], token_of[None], token_of[None],
                                          impl="xla")[0]
            with jax.named_scope("moe_experts"):
                out = experts(dispatched, deterministic, group_sizes=sizes, impl=impl)
            with jax.named_scope("moe_combine"):
                # each row back to its token, weighted: a token's up to k rows
                # add up in float32. Rows past the held ones hold no defined
                # result (``grouped_matmul``) and add nothing: zeroed BEFORE
                # the product, so that the weights' gradient multiplies no
                # undefined row either
                weighted = (weights[copy_of].astype(orig_dtype)[:, None]
                            * jnp.where(real[:, None], out, 0))
                return jnp.zeros(tokens.shape, jnp.float32).at[token_of].add(
                    weighted.astype(jnp.float32), mode="drop").astype(orig_dtype)

        experts = Experts(self.expert, self.num_experts, name="experts")
        buffers = [functools.partial(through, rows) for rows in rungs]
        operands = (tokens, weights, copy_of, held_rows, sizes)
        if len(buffers) == 1:
            combined = buffers[0](experts, *operands)
        else:
            combined = nn.switch(rung, buffers, experts, *operands)
        combined = self._latent("latent_up", combined[None], orig_dtype)
        # the gate counts every choice: ``exp_counts`` are the routed counts
        return combined, l_aux, exp_counts, kept_counts, exp_counts, -(-copies // E)

    def _latent(self, name, x, dtype):
        """One of the two bias-free projections around the experts' latent
        space (``latent_dim``), or ``x`` as it is where there is none."""
        if not self.latent_dim:
            return x
        down = name == "latent_down"
        with jax.named_scope("moe_" + name):
            return nn.Dense(self.latent_dim if down else self.model_dim, use_bias=False,
                            dtype=dtype, param_dtype=self.param_dtype,
                            kernel_init=nn.with_logical_partitioning(
                                nn.initializers.normal(0.02),
                                ("embed", None) if down else (None, "embed")),
                            name=name)(x)
