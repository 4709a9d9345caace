"""Device time of one layer's latent attention alone at the long-document
cell's shapes (``models/deepseek_v3.py``): the absorbed decode step over 32
slots' pools (the kernel, ``ops/pallas/latent_decode.py``, against XLA's two
matmuls over the whole pool) and the expanded prefill walk over the fed slots'
live key blocks (XLA's loops by key block; the kernel that keeps a step's
scores in VMEM, ``ops/pallas/latent_walk.py``, with the mask read off the
positions as a plain layer runs it, and with the same mask handed over as an
indexed layer hands its selection), each against the least time the chip could
take for the mathematics it was fed (``benchmarks/lib/opcounts_joyai_llm_flash``).

    python3 tools/mla_attention_time.py [--slots 32] [--fed 6 16] [--live 9000]

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


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--slots", type=int, default=32)
    parser.add_argument("--fed", type=int, nargs="+", default=[6, 16],
                        help="slots a prefill tick feeds")
    parser.add_argument("--live", type=int, default=9000, help="live positions a busy slot")
    parser.add_argument("--chunk", type=int, nargs="+", default=[512])
    parser.add_argument("--blocks", type=int, nargs="+", default=[512, 1024, 2048])
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import opcounts_joyai_llm_flash as ops
    from benchmarks.lib.harness import load_json
    from benchmarks.lib.peaks import PEAKS
    from deepspeed_tpu.models import deepseek_v3 as model
    from deepspeed_tpu.ops.pallas.latent_decode import latent_decode

    config = load_json(ROOT, "benchmarks", "configs", "joyai-llm-flash.json")
    peaks = PEAKS["TPU v5 lite"]
    heads, dn, dr, dv, rank = ops._heads(config)
    b, positions = args.slots, config["serve"]["max_out_tokens"]
    bf16 = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(keys[0], (b, rank + dr, positions), bf16)
    w_kvb = (jax.random.normal(keys[1], (rank, heads, dn + dv)) * rank ** -0.5).astype(bf16)
    device = jax.devices()[0].device_kind
    one = dict(config, num_hidden_layers=1, first_k_dense_replace=0)

    # decode: every slot live at ``live`` positions
    q_lat = jax.random.normal(keys[2], (b, heads, rank), bf16)
    q_rope = jax.random.normal(keys[3], (b, heads, dr), bf16)
    lengths = jnp.full((b,), args.live, jnp.int32)
    scale = (dn + dr) ** -0.5
    least = max(ops.attention_flops(one, b, b * args.live) / peaks["bf16_flops"],
                b * args.live * (rank + dr) * 2 / peaks["hbm_bytes_s"]) * 1e3
    for name, fn in (("kernel", jax.jit(lambda q, r, p, n: latent_decode(q, r, p, n, scale=scale))),
                     ("xla_whole_pool_float32", jax.jit(lambda q, r, p, n: model._mix_whole_pool(
                         q, r, p, n, scale)))):
        ms = _time(fn, q_lat, q_rope, pool, lengths, steps=50)
        print(json.dumps({"device": device, "what": "decode", "form": name, "slots": b,
                          "live": args.live, "ms": ms, "least_ms": least,
                          "roofline_pct": 100 * least / ms}), flush=True)

    # prefill: ``fed`` slots each end their chunk at ``live`` positions, the rest parked
    def causal_may(start, chunk):
        return lambda s: (jnp.arange(positions)[None, :]
                          <= start[s] + jnp.arange(chunk)[:, None]).astype(jnp.float32)

    forms = [(f"xla_key_block_{block}",
              lambda *operands, block=block: model.expanded_walk(*operands, block))
             for block in args.blocks]
    forms += [("kernel_mask_from_positions", model.kernel_walk),
              ("kernel_mask_handed", lambda qn, qr, p, w, s, f: model.kernel_walk(
                  qn, qr, p, w, s, f, causal_may(s, qn.shape[1])))]
    for chunk in args.chunk:
        q_nope = jax.random.normal(keys[2], (b, chunk, heads, dn), bf16)
        q_rope_c = jax.random.normal(keys[3], (b, chunk, heads, dr), bf16)
        for n_fed in args.fed:
            fed = jnp.where(jnp.arange(b) < n_fed, chunk, 0).astype(jnp.int32)
            start = jnp.where(fed > 0, args.live - chunk, positions).astype(jnp.int32)
            pairs = n_fed * chunk * (args.live - (chunk - 1) / 2)
            least = ops.attention_flops(one, n_fed * chunk, pairs,
                                        expanded_positions=n_fed * args.live) \
                / peaks["bf16_flops"] * 1e3
            for form, fn in forms:
                ms = _time(jax.jit(fn), q_nope, q_rope_c, pool, w_kvb, start, fed, steps=10)
                print(json.dumps({"device": device, "what": "prefill_walk", "form": form,
                                  "chunk": chunk, "fed_slots": n_fed, "live": args.live, "ms": ms,
                                  "least_ms": least, "roofline_pct": 100 * least / ms}),
                      flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
