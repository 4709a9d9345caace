"""Device time of one Mamba-2 decode step alone at a cell's shape: the
recurrence ``models/nemotron_h.py`` ``ssm_step`` over ``[slots, groups,
heads a group, head dim, state]`` float32 state, donated, as XLA fuses it,
against the bytes it has to move (the state read and written once).

    python3 tools/ssm_step_time.py [--slots 64]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--slots", type=int, default=64)
    parser.add_argument("--steps", type=int, default=200)
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import get_nemotron_h_config, ssm_step

    cfg = get_nemotron_h_config("nemotron-3-super-120b-a12b")
    b, g, n, p = args.slots, cfg.n_groups, cfg.ssm_state_size, cfg.mamba_head_dim
    r = cfg.mamba_num_heads // g
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (b, g, r, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, g, r), jnp.float32) - 4.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (g, r), jnp.float32, 0.0, 2.7))
    bm = jax.random.normal(keys[3], (b, g, n), jnp.bfloat16)
    cm = jax.random.normal(keys[4], (b, g, n), jnp.bfloat16)
    state = jax.random.normal(keys[5], (b, g, r, p, n), jnp.float32)

    step = jax.jit(lambda s, x, dt, bm, cm: ssm_step(x, dt, a, bm, cm, s)[::-1],
                   donate_argnums=(0,))
    state, y = step(state, x, dt, bm, cm)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, y = step(state, x, dt, bm, cm)
    jax.block_until_ready((state, y))
    ms = (time.perf_counter() - t0) / args.steps * 1e3
    nbytes = 2 * state.size * 4
    print(json.dumps({"device": jax.devices()[0].device_kind, "slots": b,
                      "ms_a_step": ms, "state_bytes_read_and_written": nbytes,
                      "GB_s": nbytes / ms / 1e6, "share_of_819_GB_s_pct": nbytes / ms / 1e6 / 8.19}))


if __name__ == "__main__":
    main(sys.argv[1:])
