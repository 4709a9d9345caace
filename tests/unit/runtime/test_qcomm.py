"""ZeRO++ quantized-communication tests: the collectives must move fewer
bytes on the wire (reference qgZ ``runtime/comm/coalesced_collectives.py:31``,
quantized weight gather ``partition_parameters.py:628``), not merely apply
QDQ numerics."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
from deepspeed_tpu.parallel.topology import MeshTopology

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}
_OP_RE = re.compile(r"=\s+(.*?)\s+(?:all-to-all|all-gather|all-reduce|reduce-scatter"
                    r"|collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_payload_bytes(hlo_text: str) -> int:
    """Sum result-payload bytes of every collective op in optimized HLO.
    Handles both array-typed and tuple-typed (coalesced) collectives."""
    total = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        for dtype, dims in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def _build_engine(quantized: bool, gas: int = 1):
    topo = MeshTopology(fsdp=4, data=2)
    cfg = get_gpt2_config("test", n_embd=64, n_head=4, n_positions=32)
    zero = {"stage": 3, "stage3_param_persistence_threshold": 0}
    if quantized:
        zero.update(zero_quantized_weights=True, zero_quantized_gradients=True)
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), topology=topo, config={
        "train_batch_size": 8 * gas, "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": zero})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8 * gas, 32)).astype(np.int32)}
    engine.initialize_state(batch)
    return engine, batch


class TestQuantizedCollectives:

    def test_wire_bytes_drop(self):
        """The quantized engine's compiled step must move far fewer collective
        bytes than the fp32/bf16 baseline — this is the whole point of ZeRO++."""
        base, batch = _build_engine(quantized=False)
        quant, _ = _build_engine(quantized=True)
        key = jax.random.PRNGKey(0)
        base_hlo = base._train_step_fn.lower(
            base.state, base._shard_batch(batch, True), key).compile().as_text()
        quant_hlo = quant._train_step_fn.lower(
            quant.state, quant._shard_batch(batch, True), key).compile().as_text()
        base_bytes = collective_payload_bytes(base_hlo)
        quant_bytes = collective_payload_bytes(quant_hlo)
        assert quant._use_qcomm
        assert base_bytes > 0 and quant_bytes > 0
        # int8 gather (~2x vs bf16) + int8/int4 grad hops (~4x vs f32):
        # demand a clear >40% aggregate reduction
        assert quant_bytes < 0.6 * base_bytes, (
            f"quantized step moves {quant_bytes}B vs baseline {base_bytes}B")
        # and the payload-bearing ops must actually be int8
        assert re.search(r"s8\[[\d,]*\]\S*\s+all-gather\(", quant_hlo), "no int8 all-gather"
        assert re.search(r"s8\[[\d,]*\]\S*\s+all-to-all\(", quant_hlo), "no int8 all-to-all"

    def test_training_converges_close_to_baseline(self):
        base, batch = _build_engine(quantized=False)
        quant, _ = _build_engine(quantized=True)
        base_losses, quant_losses = [], []
        for _ in range(8):
            base_losses.append(float(base.train_batch(batch)))
            quant_losses.append(float(quant.train_batch(batch)))
        assert quant_losses[-1] < quant_losses[0], f"not learning: {quant_losses}"
        # quantization noise must not derail convergence
        assert abs(quant_losses[-1] - base_losses[-1]) < 0.15 * base_losses[-1], (
            f"base {base_losses[-1]} vs quant {quant_losses[-1]}")

    def test_gas_scan_composes(self):
        quant, batch = _build_engine(quantized=True, gas=2)
        l0 = float(quant.train_batch(batch))
        l1 = float(quant.train_batch(batch))
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0

    def test_fallback_on_sequence_mesh(self):
        """sequence axis >1 → shard_map qcomm unsupported (ring attention
        owns that axis manually) → QDQ fallback trains."""
        topo = MeshTopology(sequence=2, fsdp=4, data=1)
        cfg = get_gpt2_config("test", n_embd=64, n_head=4, n_positions=32,
                              attention_backend="ring")
        engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), topology=topo, config={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3, "zero_quantized_gradients": True}})
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
        engine.initialize_state(batch)
        assert not engine._use_qcomm
        assert np.isfinite(float(engine.train_batch(batch)))

    def test_tensor_axis_composes_with_int8_wire(self):
        """A TP=2 × fsdp×data mesh must still get real
        int8 payloads on the ZeRO collectives — manual over (data, fsdp),
        GSPMD keeps the TP psums in full precision."""
        topo = MeshTopology(tensor=2, fsdp=2, data=2)
        cfg = get_gpt2_config("test", n_embd=64, n_head=4, n_positions=32)
        zero = {"stage": 3, "stage3_param_persistence_threshold": 0,
                "zero_quantized_weights": True, "zero_quantized_gradients": True}
        engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2LMHeadModel(cfg), topology=topo, config={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": zero})
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
        engine.initialize_state(batch)
        assert engine._use_qcomm, "TP mesh must not fall back to QDQ"
        # params carry the tensor axis AND the compiled step has int8 wire
        attn_kernel = engine.state.params["h_0"]["attn"]["c_attn"]["kernel"]
        assert "tensor" in jax.tree.leaves(tuple(attn_kernel.sharding.spec))
        key = jax.random.PRNGKey(0)
        hlo = engine._train_step_fn.lower(
            engine.state, engine._shard_batch(batch, True), key).compile().as_text()
        assert "s8[" in hlo, "no int8 payload on the wire under TP"
        losses = [float(engine.train_batch(batch)) for _ in range(4)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


class TestQcommPrimitives:
    """Direct numerics of the inside-shard_map building blocks."""

    def test_quantized_allgather_roundtrip(self):
        from deepspeed_tpu.runtime.zero.qcomm import quantized_allgather
        topo = MeshTopology(fsdp=4, data=2)
        x = np.random.default_rng(1).normal(size=(16, 8)).astype(np.float32)
        xs = jax.device_put(x, jax.NamedSharding(topo.mesh, P("fsdp", None)))

        fn = jax.shard_map(lambda s: quantized_allgather(s, 0, "fsdp", 4),
                           mesh=topo.mesh, in_specs=P("fsdp", None), out_specs=P(),
                           check_vma=False)
        out = np.asarray(fn(xs))
        err = np.abs(out - x).max() / (np.abs(x).max() + 1e-9)
        assert err < 1 / 100, f"int8 gather error {err}"  # int8 ⇒ ~1/254 relative

    def test_quantized_grad_reduce_matches_mean(self):
        from deepspeed_tpu.runtime.zero.qcomm import quantized_grad_reduce
        topo = MeshTopology(fsdp=4, data=2)
        rng = np.random.default_rng(2)
        # 8 per-device partials of a [32, 16] grad leaf sharded over fsdp dim 0
        partials = rng.normal(size=(8, 32, 16)).astype(np.float32)
        true_mean = partials.mean(axis=0)
        spec = P("fsdp", None)

        def body(p):
            g = p.reshape(32, 16)  # this device's full-size partial
            return quantized_grad_reduce(g, spec, fsdp_axis="fsdp", fsdp_size=4,
                                         data_axis="data", data_size=2, group_size=64)

        fn = jax.shard_map(body, mesh=topo.mesh,
                           in_specs=P(("data", "fsdp"), None, None), out_specs=spec,
                           check_vma=False)
        out = np.asarray(fn(jax.device_put(
            partials, jax.NamedSharding(topo.mesh, P(("data", "fsdp"), None, None)))))
        rel = np.abs(out - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
        # int8 hop + two int4 hops: grouped-absmax error stays in the few-% range
        assert rel < 0.12, f"quantized reduce error {rel}"


def test_communication_data_type_halves_dense_wire_bytes():
    """communication_data_type must put 16-bit (not f32) gradient payloads
    on the dense-path reduction wire — reference reduces in the configured
    comm dtype (engine communication_data_type property). fp16 is the
    pinned dtype here: current XLA CPU check-fails compiling bf16
    reduce-scatters inside large programs ("Invalid binary instruction
    opcode copy"); the lowering is dtype-generic, so fp16 coverage pins
    the mechanism."""
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    def payload(comm_dtype):
        set_topology(None)
        cfg = get_gpt2_config("test", n_embd=64, n_head=4, n_positions=32)
        ds = {"train_batch_size": 8,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 2}}
        if comm_dtype:
            ds["communication_data_type"] = comm_dtype
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(cfg), topology=MeshTopology(fsdp=8), config=ds)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
        engine.initialize_state(batch)
        hlo = engine.lower_train_step(batch).compile().as_text()
        set_topology(None)
        return collective_payload_bytes(hlo)

    full = payload(None)
    half = payload("fp16")
    assert half < 0.7 * full, (full, half)
    # training still converges with 16-bit reductions
    cfg = get_gpt2_config("test", n_embd=64, n_head=4, n_positions=32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), topology=MeshTopology(fsdp=8),
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "communication_data_type": "fp16",
                "zero_optimization": {"stage": 2}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
