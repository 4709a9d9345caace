"""Device time of dots3-note-prev's attention alone at the long-context cell's
shapes (``models/deepseek_v3.py``), a piece at a time and a layer at a time,
each kernel held against XLA's form of the same numbers first:

* decode, 32 slots live at ``--live`` positions: the index scores (the kernel
  ``ops/pallas/sparse_index.py`` against XLA's two einsums), the selection
  (``kth_largest`` + ``chosen_of`` against ``lax.top_k``), the selected
  absorbed step as it runs (the kernel reads every live block and masks)
  against a GATHER of the 2,048 chosen rows and XLA's absorbed step over
  them, and the window step over the ring;
* prefill, ``--fed`` slots each ending a chunk at ``--live`` positions: one
  slot's index scores (kernel against XLA), the selection over them (keys,
  bar, quota, ties and the mask: XLA's passes against the kernel
  ``ops/pallas/sparse_select.py``, at ``--live`` and at half of it), and a
  whole attention layer of each kind (projections, write, scores, selection,
  walk) by key block, the full layer's walk as the chip's kernel
  (``ops/pallas/latent_walk.py``) or, with ``--walk xla``, as XLA's loops;
  with ``--scopes`` the full layer's device time by named scope
  (``dsa_index``, ``dsa_select``, the walk, the rest) from a profiler trace.

    python3 tools/dsa_attention_time.py [--slots 32] [--fed 8] [--live 16000]

Prints one JSON line a variant. The numbers that count are the chip's.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _time(fn, *args, steps=20):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3


def _by_scope(call, text, scopes, steps=3):
    """Device ms a call of ``call()`` by the innermost of ``scopes`` an
    operation's ``op_name`` holds (``other``: none), and of the dozen largest
    operation families, from a profiler trace of ``steps`` calls; ``text`` is
    the compiled program, which names each instruction's scope where the
    trace's events do not."""
    import re
    import shutil
    import tempfile
    import jax
    from benchmarks.lib import trace
    where = dict(re.findall(r"^\s*(?:ROOT )?(%[^\s=]+) = .*?op_name=\"([^\"]*)\"", text, re.M))
    out_dir = tempfile.mkdtemp(prefix="dsa_scopes_")
    try:
        jax.profiler.start_trace(out_dir)
        for _ in range(steps):
            out = call()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        loaded = trace.load(trace.find_xplane(out_dir))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    if not loaded["devices"]:
        return None                    # off the chip the trace has no device plane
    ms, largest = dict.fromkeys((*scopes, "other"), 0.0), {}
    for event, seconds in trace.self_times(next(iter(loaded["devices"].values()))).items():
        named = re.split(r"[/\"\s]", event + " " + where.get(
            "%" + trace.op_name(event).lstrip("%"), ""))
        scope = next((s for s in scopes if s in named), "other")
        ms[scope] += seconds * 1e3 / steps
        family = f"{scope}:{trace.op_family(event)}"
        largest[family] = largest.get(family, 0.0) + seconds * 1e3 / steps
    # and the dozen largest operation families, each under its scope
    ms["largest"] = {k: round(v, 3) for k, v in sorted(largest.items(), key=lambda kv: -kv[1])[:12]}
    return ms


def _layer(cfg, kind):
    """One attention layer of ``kind`` under the top level's two cache leaves."""
    import flax.linen as nn
    import jax.numpy as jnp
    from deepspeed_tpu.models.deepseek_v3 import LatentAttention

    class OneLayer(nn.Module):
        @nn.compact
        def __call__(self, ids, decode=True):
            table = self.param("table", nn.initializers.normal(1.0), (64, cfg.hidden_size),
                               cfg.param_dtype)
            index = self.variable("cache", "position_index", lambda: jnp.zeros([], jnp.int32))
            length = self.variable("cache", "chunk_length", lambda: jnp.zeros([], jnp.int32))
            # a pool leaf of the cache's full extent, which a window layer alone
            # has none of: it is what gives the slots their capacity
            self.variable("cache", "cached_latent", jnp.zeros,
                          (ids.shape[0], cfg.decode_cache_len, 1, 8), cfg.dtype)
            fed = length.value if index.value.ndim else None
            return LatentAttention(cfg, kind, name="self_attn")(table[ids % 64], decode, fed)


    return OneLayer()


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--slots", type=int, default=32)
    parser.add_argument("--fed", type=int, default=8, help="slots a prefill tick feeds")
    parser.add_argument("--live", type=int, default=16000, help="live positions a busy slot")
    parser.add_argument("--chunk", type=int, nargs="+", default=[256, 512])
    parser.add_argument("--blocks", type=int, nargs="+", default=[256, 512])
    parser.add_argument("--walk", choices=["kernel", "xla"], default="kernel",
                        help="a full layer's prefill walk: the chip's kernel, or XLA's loops")
    parser.add_argument("--scopes", action="store_true",
                        help="a full layer's device time by named scope, from a trace")
    parser.add_argument("--config", default="dots3-note-prev",
                        help="benchmarks/configs/<name>.json; dots3-note-test rehearses on a CPU")
    args = parser.parse_args(argv)
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import opcounts_dots3_note as ops
    from benchmarks.lib.harness import load_json
    from benchmarks.lib.peaks import PEAKS
    from deepspeed_tpu.inference.serving import programs
    from deepspeed_tpu.models import deepseek_v3 as model
    from deepspeed_tpu.ops.pallas import latent_decode, sparse_index, sparse_select

    if args.walk == "xla":
        model.LatentAttention._walks_in_kernel = lambda self, l, pool, start: False
    config = load_json(ROOT, "benchmarks", "configs", args.config + ".json")
    preset = "dots3-note-test" if args.config.endswith("-test") else "dots3-note-prev"
    peaks = PEAKS["TPU v5 lite"]
    h, dn, dr, dv, rank, _ = ops.heads(config, "F")
    j, d, top_k = config["index_n_heads"], config["index_head_dim"], config["index_topk"]
    b, positions, live = args.slots, config["serve"]["max_out_tokens"], args.live
    bf16 = jnp.bfloat16 if preset == "dots3-note-prev" else jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    device = jax.devices()[0].device_kind

    def say(**fields):
        print(json.dumps({"device": device, "slots": b, "live": live, **fields}), flush=True)

    def least_ms(flops, nbytes):
        return ops.roofline_ms(flops, nbytes, peaks)[0]

    # ---- decode, the pieces ------------------------------------------------
    index_keys = jax.random.normal(keys[0], (b, d, positions), bf16)
    q_i = jax.random.normal(keys[1], (b, j, d), bf16)
    w_i = jax.random.normal(keys[2], (b, j), jnp.float32) * (j * d) ** -0.5
    lengths = jnp.full((b,), live, jnp.int32)
    alive = jnp.arange(positions)[None, :] < lengths[:, None]
    kernel = jax.jit(lambda q, w, k, n: sparse_index.index_scores_decode(q, w, k, n))
    plain = jax.jit(lambda q, w, k: model.index_scores(q[:, None], w[:, None], k)[:, 0])
    scores = kernel(q_i, w_i, index_keys, lengths)
    gap = float(jnp.abs(jnp.where(alive, scores - plain(q_i, w_i, index_keys), 0)).max())
    least = least_ms(*ops.index_kernel(config, b, b * live, b * live))
    for name, ms in (("kernel", _time(kernel, q_i, w_i, index_keys, lengths, steps=50)),
                     ("xla_whole_pool", _time(plain, q_i, w_i, index_keys, steps=50))):
        say(what="decode_index", form=name, ms=ms, least_ms=least,
            roofline_pct=100 * least / ms, max_abs_gap_to_xla=gap)

    choose = jax.jit(lambda s, n: model.chosen_of(*model.kth_largest(
        s, jnp.arange(positions)[None, :] < n[:, None], top_k))[0])
    by_sort = jax.jit(lambda s, n: jax.lax.top_k(
        jnp.where(jnp.arange(positions)[None, :] < n[:, None], s, -jnp.inf), top_k)[1])
    chosen, rows = choose(scores, lengths), by_sort(scores, lengths)
    same = bool((jnp.take_along_axis(chosen, rows, axis=1)).all()) and \
        int(chosen.sum()) == b * min(top_k, live)
    say(what="decode_select", form="bisection_and_mask", ms=_time(choose, scores, lengths, steps=50),
        same_set_as_top_k=same)
    say(what="decode_select", form="lax_top_k", ms=_time(by_sort, scores, lengths, steps=20))
    tile = sparse_select.row_tile(b)
    in_kernel = jax.jit(lambda s, n: sparse_select.select_top_k(
        s, n, sparse_index.chunk_blocks(n, positions)[0].reshape(-1, tile).max(-1), top_k))
    differing = int(((in_kernel(scores, lengths) > 0) & alive != chosen).sum())
    say(what="decode_select", form="kernel", ms=_time(in_kernel, scores, lengths, steps=50),
        differing_mask_entries=differing)

    pool = jax.random.normal(keys[3], (b, rank + dr, positions), bf16)
    q_lat = jax.random.normal(keys[4], (b, h, rank), bf16)
    q_rope = jax.random.normal(keys[5], (b, h, dr), bf16)
    scale = (dn + dr) ** -0.5
    masked = jax.jit(lambda q, r, p, n, c: latent_decode.latent_decode(q, r, p, n, scale=scale,
                                                                         chosen=c))

    def gathered(q, r, p, at):
        picked = jnp.take_along_axis(p, at[:, None, :], axis=2)             # [b, width, top_k]
        q_all = jnp.concatenate([q, r], axis=-1)
        s = jnp.einsum("bhw,bwk->bhk", q_all, picked, preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(s, axis=-1).astype(p.dtype)
        return jnp.einsum("bhk,bck->bhc", probs, picked[:, :rank],
                          preferred_element_type=jnp.float32)

    gathered = jax.jit(gathered)
    gap = float(jnp.abs(masked(q_lat, q_rope, pool, lengths, chosen)
                        - gathered(q_lat, q_rope, pool, rows)).max())
    least = least_ms(*ops.selected_decode_kernel(config, b, b * min(top_k, live)))
    for name, ms in (("kernel_reads_live_and_masks",
                      _time(masked, q_lat, q_rope, pool, lengths, chosen, steps=50)),
                     ("xla_gather_of_chosen_rows", _time(gathered, q_lat, q_rope, pool, rows,
                                                         steps=10))):
        say(what="decode_selected_attention", form=name, ms=ms, least_ms=least,
            roofline_pct=100 * least / ms, max_abs_gap_between_forms=gap)

    hs, dns, drs, dvs, ranks, _ = ops.heads(config, "S")
    window = config["sliding_window_size"]
    for chunk in args.chunk:
        ring = model.window_ring_positions(window, chunk)
        held = jax.random.normal(keys[3], (b, ranks + drs, ring), bf16)
        w_kvb = (jax.random.normal(keys[6], (ranks, hs, dns + dvs)) * ranks ** -0.5).astype(bf16)
        step = jax.jit(lambda qn, qr, p, w, at: model.window_step(
            qn, qr, p, w, at, at >= 0, window))
        ms = _time(step, jax.random.normal(keys[4], (b, hs, dns), bf16),
                   jax.random.normal(keys[5], (b, hs, drs), bf16), held, w_kvb, lengths - 1,
                   steps=50)
        least = least_ms(ops.attention_flops(config, "S", b, b * window),
                         b * window * (ranks + drs) * 2)
        say(what="decode_window_step", form="xla_whole_ring", ring=ring, ms=ms, least_ms=least,
            roofline_pct=100 * least / ms)

    # ---- prefill: one slot's index scores, then whole layers ---------------
    for chunk in args.chunk:
        q_c = jax.random.normal(keys[1], (chunk, j * d), bf16)
        w_c = jax.random.normal(keys[2], (chunk, j), jnp.float32) * (j * d) ** -0.5
        blocks, _ = sparse_index.chunk_blocks(jnp.int32(live), positions)
        kernel = jax.jit(lambda q, w, k, n: sparse_index.index_scores_chunk(q, w, k, 3, n))
        plain = jax.jit(lambda q, w, k: model.index_scores(q.reshape(chunk, j, d), w, k[3]))
        got = kernel(q_c, w_c, index_keys, blocks)
        gap = float(jnp.abs((got - plain(q_c, w_c, index_keys))[:, :live]).max())
        pairs = chunk * (live - (chunk - 1) / 2)
        least = least_ms(*ops.index_kernel(config, chunk, pairs, live))
        for name, ms in (("kernel", _time(kernel, q_c, w_c, index_keys, blocks, steps=20)),
                         ("xla_whole_pool", _time(plain, q_c, w_c, index_keys, steps=10))):
            say(what="prefill_index_one_slot", chunk=chunk, form=name, ms=ms, least_ms=least,
                roofline_pct=100 * least / ms, max_abs_gap_to_xla=gap)

        @jax.jit
        def by_passes(s, at):
            # keys, bar, quota, ties and the mask the walk reads: XLA's passes
            # over the whole extent, as a chunk ran them before the kernel
            ordered, bar, quota = model.kth_largest(
                s, jnp.arange(positions)[None, :] <= at[:, None], top_k)
            tied = ((ordered == bar[:, None]) & (ordered > 0)).sum(axis=-1)
            return jax.lax.cond(
                (tied > quota).any(), lambda: model.chosen_of(ordered, bar, quota)[0],
                lambda: ordered >= jnp.maximum(bar, jnp.uint32(1))[:, None]).astype(jnp.float32)

        @jax.jit
        def in_kernel(s, at, n):
            return sparse_select.select_top_k(
                s, at + 1, jnp.full((chunk // sparse_select.row_tile(chunk),), n), top_k)

        for ends in (live, live // 2):
            at = ends - chunk + jnp.arange(chunk)
            n, size = sparse_index.chunk_blocks(jnp.int32(ends), positions)
            held = jnp.where(jnp.arange(positions) < n * size, got, 0.0)
            differing = (in_kernel(got, at, n) != by_passes(held, at))[:, :int(n) * size]
            say(what="prefill_select_one_slot", chunk=chunk, live=ends, form="bisection",
                ms=_time(by_passes, held, at, steps=10))
            say(what="prefill_select_one_slot", chunk=chunk, live=ends, form="kernel",
                ms=_time(in_kernel, got, at, n, steps=20),
                differing_mask_entries=int(differing.sum()))

    for chunk in args.chunk:
        for layer, kind in (("full", "F"), ("sliding", "S")):
            for block in args.blocks:
                if kind == "S" and block != args.blocks[0]:
                    continue
                cfg = model.get_deepseek_v3_config(
                    preset, decode_cache_len=positions, attention_key_block=block,
                    window_ring=model.window_ring_positions(window, chunk), dtype=bf16,
                    param_dtype=bf16)
                module = _layer(cfg, cfg.kind_of(0 if kind == "F" else 2))
                params = jax.jit(lambda key: nn.meta.unbox(module.init(
                    key, jnp.zeros((1, 8), jnp.int32), decode=False)["params"]))(keys[7])
                cache = programs.make_slot_cache(module, b)
                cache, _ = programs.without_next_tokens(cache)
                fed = jnp.where(jnp.arange(b) < args.fed, chunk, 0).astype(jnp.int32)
                start = jnp.where(fed > 0, live - chunk, positions).astype(jnp.int32)

                def tick(params, cache, start, fed, ids):
                    held = programs.with_write_positions(cache, start, fed)
                    out, upd = module.apply({"params": params, "cache": held}, ids,
                                            mutable=["cache"])
                    return upd["cache"], out

                run = jax.jit(tick, donate_argnums=(1,))
                ids = jnp.zeros((b, chunk), jnp.int32)
                text = (run.lower(params, cache, start, fed, ids).compile().as_text()
                        if args.scopes and kind == "F" else None)
                cache, out = run(params, cache, start, fed, ids)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(5):
                    cache, out = run(params, cache, start, fed, ids)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / 5 * 1e3
                tokens = args.fed * chunk
                pairs = ops.tick_pairs(config, tokens, args.fed, args.fed * live)
                if kind == "F":
                    flops = (2 * b * chunk * (ops.attention_matrices(config, "F")
                                              + ops.indexer_matrices(config))
                             + ops.index_flops(config, pairs["live"])
                             + ops.cheaper_attention_flops(
                                 config, "F", tokens, pairs["selected"],
                                 ops.selected_positions(config, tokens, args.fed,
                                                        args.fed * live)))
                else:
                    flops = (2 * b * chunk * ops.attention_matrices(config, "S")
                             + ops.cheaper_attention_flops(
                                 config, "S", tokens, pairs["window"],
                                 ops.window_positions(config, tokens, args.fed, args.fed * live)))
                least = flops / peaks["bf16_flops"] * 1e3
                say(what="prefill_layer", layer=layer, chunk=chunk, key_block=block,
                    walk=args.walk if kind == "F" else "xla",
                    fed_slots=args.fed, ms=ms, least_ms=least, roofline_pct=100 * least / ms,
                    finite=bool(jnp.isfinite(out.astype(jnp.float32)).all()))
                if text is not None:
                    held = [cache]

                    def again():
                        held[0], out = run(params, held[0], start, fed, ids)
                        return out

                    say(what="prefill_layer_by_scope", layer=layer, chunk=chunk, key_block=block,
                        fed_slots=args.fed, ms=_by_scope(again, text, (
                            "dsa_index", "dsa_select", "dsa_attend_prefill")))
                    cache = held[0]
                del cache, params


if __name__ == "__main__":
    main(sys.argv[1:])
