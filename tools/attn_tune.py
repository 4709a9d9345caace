"""Sweep flash-attention block geometries on the live backend and bank the
shape-keyed winners. One process, which holds the chip until it exits.

Each shape's sweep writes its candidate records to ATTN_EXPS_DIR and merges
the winner into ATTN_RESULTS_DIR/attention_blocks.json — the cache
``flash_attention`` resolves through at call time, so a later run from
the same working directory picks the tuned geometry up.

Run: python tools/attn_tune.py           (background; poll stdout)
Env: ATTN_SHAPES=1024:64:16:8,4096:64:16:2,8192:64:16:1
         (colon-separated seq:head_dim:heads:micro_batch, comma list)
     ATTN_CAUSAL=1          ATTN_TRAIN=1  (fwd+bwd vs fwd-only)
     ATTN_REPEATS=3         ATTN_DTYPE=bfloat16
     ATTN_CANDIDATES="block_q=512,tile=256;default"
         (semicolon list of geometry specs to time instead of the staged
         sweep; "default" is the kernel's own shape defaults)
     ATTN_KERNELS=1  (also print the three kernels' device milliseconds
         apart — forward, dq, dkv — for the winner and the defaults, or
         for every explicit candidate; chip only)
     ATTN_RESULTS_DIR=autotuning_results  ATTN_EXPS_DIR=autotuning_exps
     (the CI smoke redirects both to a tmp dir)
"""
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    from envutil import use_compile_cache

    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning.attention_tuner import (AttentionBlockTuner,
                                                          kernel_seconds, operands)
    from deepspeed_tpu.ops.pallas.attention_geometry import AttentionGeometry, parse_spec

    shapes = os.environ.get("ATTN_SHAPES", "2048:64:16:4,4096:64:16:2,8192:64:16:1")
    causal = os.environ.get("ATTN_CAUSAL", "1") not in ("0", "false")
    train = os.environ.get("ATTN_TRAIN", "1") not in ("0", "false")
    dtype = jnp.dtype(os.environ.get("ATTN_DTYPE", "bfloat16"))
    explicit = [AttentionGeometry() if spec.strip() == "default" else parse_spec(spec)
                for spec in os.environ.get("ATTN_CANDIDATES", "").split(";") if spec.strip()]
    apart = os.environ.get("ATTN_KERNELS", "0") not in ("0", "false")
    tuner = AttentionBlockTuner(
        results_dir=os.environ.get("ATTN_RESULTS_DIR", "autotuning_results"),
        exps_dir=os.environ.get("ATTN_EXPS_DIR", "autotuning_exps"),
        repeats=int(os.environ.get("ATTN_REPEATS", "3")),
        candidates=explicit or None)

    for spec in shapes.split(","):
        try:
            seq, head_dim, heads, mb = (int(x) for x in spec.strip().split(":"))
            from deepspeed_tpu.elasticity import touch_heartbeat
            touch_heartbeat()  # supervised runs: fresh clock before each sweep
            t0 = time.time()
            best, records = tuner.tune(seq=seq, head_dim=head_dim, heads=heads,
                                       batch=mb, causal=causal, dtype=dtype,
                                       train=train)
            measured = [r for r in records if r["status"] == "measured"]
            # the winner's own timing — staged sweeps mix fwd-only and
            # fwd+bwd records, so a min over all of them would report a
            # stage-1 number for a stage-2 winner
            win_ms = None
            if best is not None:
                win_ms = round(min(r["seconds"] for r in measured
                                   if r["geometry"] == best.as_dict()) * 1e3, 2)
            kernels = None
            if apart:
                qkv = operands(seq, seq, heads, head_dim, mb, dtype)
                kernels = {}
                for geom in explicit or [g for g in (best, AttentionGeometry()) if g is not None]:
                    secs = kernel_seconds(geom, *qkv, causal)
                    kernels[geom.spec() or "default"] = secs and {
                        name: round(s * 1e3, 4) for name, s in secs.items()}
            print(json.dumps({
                "shape": spec.strip(), "backend": jax.default_backend(),
                "causal": causal, "train": train,
                "candidates": len(records), "measured": len(measured),
                "winner": best.as_dict() if best else None,
                "winner_ms": win_ms,
                "all_ms": {AttentionGeometry(**r["geometry"]).spec() or "default":
                           round(r["seconds"] * 1e3, 4) for r in measured},
                "kernels_ms": kernels,
                "elapsed_s": round(time.time() - t0, 1),
            }), flush=True)
        except Exception as e:  # keep sweeping past per-shape failures
            print(json.dumps({"shape": spec.strip(),
                              "error": f"{type(e).__name__}: {str(e)[:300]}"}),
                  flush=True)
            traceback.print_exc(file=sys.stderr)
    print("# DONE", flush=True)


if __name__ == "__main__":
    main()
