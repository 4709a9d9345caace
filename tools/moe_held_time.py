"""Device time of one held expert layer's routed part alone at the
reasoning cell's prefill shape (8,192 positions x 22 of 512 experts, 128
held, latent 1,024, width 2,688, bf16), stage by stage: what chose how
``MOELayer._held_route`` groups, moves and combines its rows (PERF.md,
PR 31). ``before`` is the path that sized its buffer for every copy (two
sorts for the positions, a third for the rows' sources, gathers and the
``k``-way sum over all ``S k``); the stages are the new path's, the
alternatives it did not take beside them.

    python3 tools/moe_held_time.py [--fed 1606] [--steps 20] [--only stage:rows]
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--fed", type=int, default=1606)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--only", default="", help="comma-separated prefixes of the names to run")
    parser.add_argument("--latent", type=int, default=1024)
    parser.add_argument("--width", type=int, default=2688)
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.moe.sharded_moe import _row_rungs
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul
    from deepspeed_tpu.ops.pallas.moe_dispatch import permute_rows

    S, k, E, first, count, latent, width = args.tokens, 22, 512, 0, 128, args.latent, args.width
    copies = S * k
    impl = "pallas" if jax.devices()[0].platform == "tpu" else "xla"
    rng = np.random.default_rng(0)
    used_np = np.zeros(S, bool)
    left = args.fed                      # fed positions as a prefill tick has them: slots' prefixes
    for s in rng.permutation(S // 128):
        take = min(128, left)
        used_np[s * 128:s * 128 + take] = True
        left -= take
    experts_np = np.stack([rng.permutation(E)[:k] for _ in range(S)]).astype(np.int32)
    experts, used = jnp.asarray(experts_np), jnp.asarray(used_np)
    weights = jnp.asarray(rng.random((S, k)).astype(np.float32))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    tokens = jax.random.normal(keys[0], (S, latent), jnp.bfloat16)
    # operands of every program that uses them: a closed-over array is a
    # constant of the program, 1.4 GB to compile in
    bank_w = (jax.random.normal(keys[1], (count, latent, width), jnp.bfloat16) * 0.02,
              jax.random.normal(keys[2], (count, width, latent), jnp.bfloat16) * 0.02)
    held_rows = int((used_np[:, None] & (experts_np >= first) & (experts_np < first + count)).sum())

    def bank(x, sizes, w):
        h = jnp.square(jax.nn.relu(grouped_matmul(x, w[0], sizes, impl=impl)))
        return grouped_matmul(h, w[1], sizes, impl=impl)

    def mine_of(experts, used):
        e = experts.reshape(-1)
        return jnp.repeat(used, k) & (e >= first) & (e < first + count)

    def before(tokens, experts, weights, used, w):
        flat = jnp.where(jnp.repeat(used, k), experts.reshape(-1), E)
        at = jnp.arange(copies, dtype=jnp.int32)
        by_expert, order = jax.lax.sort((flat, at), num_keys=1, is_stable=True)
        starts = jnp.searchsorted(by_expert, jnp.arange(E + 1, dtype=flat.dtype))
        _, slot = jax.lax.sort((order, at - starts[by_expert].astype(jnp.int32)), num_keys=1)
        sizes = jnp.diff(starts).astype(jnp.int32)[first:first + count]
        mine = mine_of(experts, used)
        base = (jnp.cumsum(sizes) - sizes)[jnp.clip(experts.reshape(-1) - first, 0, count - 1)]
        flat_slot = jnp.where(mine, base + slot, copies).astype(jnp.int32)[None]
        by_row, src = jax.lax.sort((flat_slot, at[None]), dimension=1, num_keys=1)
        src = jnp.where(by_row < copies, src, copies)
        token_of = jnp.where(src < copies, src // k, S)
        out = bank(permute_rows(tokens[None], token_of, token_of, impl="xla")[0], sizes, w)[None]
        gathered = permute_rows(out, flat_slot, src, impl="xla")
        weight = (weights * mine.reshape(S, k)).astype(jnp.bfloat16).reshape(1, copies, 1)
        return (weight * gathered).reshape(S, k, latent).sum(axis=1)

    def counts_by_compare(experts, used):
        hot = (experts[:, :, None] == jnp.arange(E, dtype=jnp.int32)) & used[:, None, None]
        return hot.sum(axis=(0, 1), dtype=jnp.int32)

    def order_by_sort(experts, used):
        key = jnp.where(mine_of(experts, used), experts.reshape(-1) - first, count)
        return jax.lax.sort((key, jnp.arange(copies, dtype=jnp.int32)), num_keys=1, is_stable=True)[1]

    def order_by_compaction(rows, experts, used):
        """not taken: mask, cumulative sum and scatter into ``rows``, then a sort of ``rows``"""
        mine = mine_of(experts, used)
        at = jnp.arange(copies, dtype=jnp.int32)
        idx = jnp.full((rows,), copies, jnp.int32).at[
            jnp.where(mine, jnp.cumsum(mine.astype(jnp.int32)) - 1, rows)].set(at, mode="drop")
        key = jnp.where(idx < copies, experts.reshape(-1)[jnp.minimum(idx, copies - 1)] - first, count)
        return jax.lax.sort((key, idx), num_keys=1, is_stable=True)[1]

    def rows_through(rows, combine, tokens, weights, copy_of, sizes, w):
        copy_of = copy_of[:rows]
        real = jnp.arange(rows, dtype=jnp.int32) < held_rows
        token_of = jnp.where(real, copy_of // k, S)
        out = bank(permute_rows(tokens[None], token_of[None], token_of[None], impl="xla")[0], sizes, w)
        weight = jnp.where(real, weights.reshape(-1)[copy_of], 0.0)
        if combine == "scatter":
            weighted = jnp.where(real[:, None], weight.astype(out.dtype)[:, None] * out, 0)
            return jnp.zeros((S, latent), jnp.float32).at[token_of].add(
                weighted.astype(jnp.float32), mode="drop")
        # not taken: the combine as one matmul with a [S, rows] matrix of weights
        place = jnp.where(token_of[None, :] == jnp.arange(S, dtype=jnp.int32)[:, None],
                          weight[None, :], 0.0).astype(jnp.bfloat16)
        return jnp.dot(place, jnp.where(real[:, None], out, 0), preferred_element_type=jnp.float32)

    def timed(name, fn, *xs):
        if args.only and not any(name.startswith(o) for o in args.only.split(",")):
            return
        f = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*xs))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = f(*xs)
        jax.block_until_ready(out)
        print(json.dumps({"what": name, "ms": round((time.perf_counter() - t0) / args.steps * 1e3, 3),
                          "compile_s": round(compile_s, 1)}), flush=True)

    rungs = _row_rungs(copies)
    print(json.dumps({"device": jax.devices()[0].device_kind, "copies": copies, "fed": int(used_np.sum()),
                      "held_rows": held_rows, "rungs": rungs}), flush=True)
    timed("before", before, tokens, experts, weights, used, bank_w)
    timed("stage:counts_by_compare", counts_by_compare, experts, used)
    timed("stage:order_by_sort", order_by_sort, experts, used)
    timed("stage:order_by_compaction", functools.partial(order_by_compaction, rungs[0]), experts, used)
    sizes = counts_by_compare(experts, used)[first:first + count]
    copy_of = order_by_sort(experts, used)
    for rows in rungs:
        if rows < held_rows:
            continue
        for combine in ("scatter", "matmul")[:2 if rows == rungs[0] else 1]:
            timed(f"stage:rows[{rows}]:{combine}", functools.partial(rows_through, rows, combine),
                  tokens, weights, copy_of, sizes, bank_w)
        timed(f"stage:bank[{rows}]", bank, jnp.zeros((rows, latent), jnp.bfloat16), sizes, bank_w)


if __name__ == "__main__":
    main(sys.argv[1:])
