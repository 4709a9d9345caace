"""Cross-backend loss-curve parity harness (BASELINE.md north star:
"bit-identical loss curves vs CPU reference").

``curve()`` trains a small GPT-2 for N steps under conditions chosen to be
backend-reproducible — fp32 params AND fp32 compute, ``highest`` matmul
precision (on TPU this forces the 6-pass fp32 matmul instead of bf16
passes), deterministic seeded data, no dropout — and returns the per-step
losses as exact bit patterns (fp32 hex), so comparison is free of
print-precision noise.

``compare()`` reports bit-identity, max |Δ|, and max ULP distance between
two curves: an accelerator's against the CPU reference curve computed in
a scrubbed subprocess. ``PARITY_MAX_ULP`` is the enforcement envelope — 0 (default) demands
bit-identity, a positive value pins the measured-and-documented envelope.

Reference-pinning caveat (measured): XLA:CPU splits its compute threads
per virtual device, and thread partitioning changes matmul reduction
order — an 8-virtual-device process drifts ~1 ULP/step from a 1-device
process on the SAME machine. The CPU reference is therefore always run at
exactly ONE pinned CPU device
(``envutil.cpu_subprocess_env(n_virtual_devices=1)``); with that pinned, curves
are bit-reproducible across processes (test_loss_parity).

Run directly: ``python tools/parity_check.py`` → one JSON line
{"backend", "curve_hex"}.
"""

import json
import os
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = int(os.environ.get("PARITY_STEPS", "8"))
SEED = int(os.environ.get("PARITY_SEED", "0"))


def curve(steps: int = STEPS, seed: int = SEED):
    """Per-step fp32 losses for the reproducible config, as float values."""
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    from envutil import use_compile_cache
    use_compile_cache()

    import numpy as np
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    cfg = get_gpt2_config("test", n_layer=2, n_embd=64, n_head=4, n_positions=64,
                          dropout=0.0, dtype=jnp.float32)
    model = GPT2LMHeadModel(cfg)
    # the WORKLOAD must not depend on jax.device_count(): a 4-chip slice and
    # the 1-CPU reference must train the same batches through the same
    # program, so the curve is pinned to ONE device regardless of backend
    topo = MeshTopology(data=1, devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, topology=topo,
        config={"train_batch_size": 4,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10**9})
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        batch = {"input_ids": rng.integers(0, cfg.vocab_size,
                                           (4, 64)).astype(np.int32)}
        loss = engine.train_batch(batch)
        losses.append(np.float32(np.asarray(jax.device_get(loss))))
    return [float(l) for l in losses]


def to_hex(values):
    return [format(struct.unpack(">I", struct.pack(">f", float(v)))[0], "08x")
            for v in values]


def from_hex(hexes):
    return [struct.unpack(">f", struct.pack(">I", int(h, 16)))[0] for h in hexes]


def _ulp_distance(a: float, b: float) -> int:
    """ULP distance between two fp32 values (monotone integer mapping)."""
    def key(x):
        (i,) = struct.unpack(">i", struct.pack(">f", float(x)))
        return i if i >= 0 else -(i & 0x7FFFFFFF)
    return abs(key(a) - key(b))


def compare(curve_a, curve_b):
    """Parity report between two same-length fp32 loss curves."""
    assert len(curve_a) == len(curve_b), (len(curve_a), len(curve_b))
    diffs = [abs(a - b) for a, b in zip(curve_a, curve_b)]
    ulps = [_ulp_distance(a, b) for a, b in zip(curve_a, curve_b)]
    return {
        "steps": len(curve_a),
        "bit_identical": all(u == 0 for u in ulps),
        "max_abs_diff": max(diffs) if diffs else 0.0,
        "max_ulp": max(ulps) if ulps else 0,
    }


def precision_attribution():
    """R002's per-(src->dst, scope) upcast tally for the parity program —
    the graft-lint metric that tells the ROADMAP-4 ULP hunt *where* the
    numerics widen. Surfacing it here means the hunt reads ONE report:
    the curve and its attribution come from the same tool invocation
    instead of cross-referencing a separate lint run. Trace-only (a
    couple of seconds next to the training steps); any failure degrades
    to an error string rather than killing the curve.
    ``PARITY_ATTRIBUTION=0`` opts out."""
    if os.environ.get("PARITY_ATTRIBUTION", "1") != "1":
        return None
    try:
        from deepspeed_tpu.analysis import run_program_rules
        from deepspeed_tpu.analysis import scenarios as scen

        info = scen.SCENARIOS["train_batch_parity"]()
        _, metrics = run_program_rules(info, rules=["R002"])
        return metrics.get("precision_attribution", {})
    except Exception as e:  # noqa: BLE001 — evidence must never kill the curve
        return {"error": f"{type(e).__name__}: {str(e)[:160]}"}


def main():
    import jax
    vals = curve()
    out = {"backend": jax.default_backend(),
           "curve_hex": to_hex(vals),
           "curve": [round(v, 6) for v in vals]}
    attribution = precision_attribution()
    if attribution is not None:
        out["precision_attribution"] = attribution
    print(json.dumps(out))


if __name__ == "__main__":
    main()
