"""Round-4 features end to end: ZeRO-Infinity parameter offload + staged
knowledge distillation under an elastic restart supervisor.

What it shows, reference-call-for-call:
  1. Train a teacher briefly (GPT-2, any preset).
  2. Distill onto a half-depth student via ``init_compression(engine, cfg,
     teacher_model=(module, params))`` — layer_reduction seeds the student
     from teacher layers; logit-KL + layerwise-MSE mix in-graph from
     ``schedule_offset``.
  3. The student trains with ``offload_param`` (params rest in pinned host
     memory / NVMe and stream through the chip). NB: ``offload_optimizer``
     does not combine with KD (its host-driven step never reaches the
     in-graph KD gate — init_compression rejects it); pair the two offloads
     in non-distillation configs.
  4. The loop calls ``touch_heartbeat()``, so the whole script runs under
     the elastic restart supervisor unchanged:
         bin/ds_elastic -c examples/ds_config_zero3.json \
             --world-sizes 8,4 --supervise python examples/distill_and_offload.py

Quick CPU smoke:  python examples/distill_and_offload.py --cpu --steps 6
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--teacher-layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--offload", default="cpu", choices=["cpu", "nvme"])
    ap.add_argument("--nvme-path", default="/tmp/ds_tpu_example_nvme")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU with 8 virtual devices (CI/smoke)")
    args = ap.parse_args()

    if args.cpu:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.compression.compress import init_compression
    from deepspeed_tpu.elasticity import touch_heartbeat
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    n_dev = jax.device_count()
    rng = np.random.default_rng(0)

    def batch(vocab):
        return {"input_ids": rng.integers(0, vocab, (2 * n_dev, args.seq)).astype(np.int32)}

    # -- 1. teacher -------------------------------------------------------
    tcfg = get_gpt2_config("test", n_layer=args.teacher_layers, n_positions=args.seq)
    teacher, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(tcfg),
        config={"train_batch_size": 2 * n_dev,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    for _ in range(max(args.steps // 4, 2)):
        teacher.train_batch(batch(tcfg.vocab_size))
        touch_heartbeat()
    t_params = jax.device_get(teacher.state.params)
    print(f"teacher trained ({args.teacher_layers} layers)")

    # -- 2+3. half-depth student: distillation + ZeRO-Infinity ------------
    scfg = get_gpt2_config("test", n_layer=args.teacher_layers // 2,
                           n_positions=args.seq, remat=True)
    ds_config = {
        "train_batch_size": 2 * n_dev,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 3,
            "offload_param": ({"device": "cpu"} if args.offload == "cpu" else
                              {"device": "nvme", "nvme_path": args.nvme_path,
                               "max_in_cpu": int(5e7)}),
        },
        "compression_training": {
            "layer_reduction": {"enabled": True,
                                "keep_number_layer": args.teacher_layers // 2,
                                "module_name_prefix": "transformer.h",
                                "teacher_layer": list(range(1, args.teacher_layers, 2)),
                                "other_module_name": ["transformer.wte", "transformer.ln_f"]},
            "knowledge_distillation": {"enabled": True, "kd_coef": 0.5,
                                       "temperature": 2.0, "layerwise_coef": 0.1,
                                       "schedule_offset": 0},
        },
    }
    student, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(scfg),
                                                config=ds_config)
    init_compression(student, ds_config, teacher_model=(GPT2LMHeadModel(tcfg), t_params))
    for i in range(args.steps):
        loss = student.train_batch(batch(scfg.vocab_size))
        touch_heartbeat()
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  distill loss {float(jnp.asarray(loss)):.4f}")
    memkind = jax.tree.leaves(student.state.params)
    memkind = memkind[0].sharding.memory_kind if memkind else "released-to-nvme"
    print(f"student params rest in: {memkind}")


if __name__ == "__main__":
    main()
