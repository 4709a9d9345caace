"""AOT compiles for a described TPU v5e — the only file that does this.

The TPU's compiler is installed next to the CPU backend and compiles for
a chip that is described, not attached. Interpret mode cannot see what
Mosaic refuses (block shapes against the (8, 128) tiling, single-row
slices, scoped VMEM) or what GSPMD refuses (an unpartitionable kernel on
a mesh), so the kernels of the main paths compile here at GPT-2 350M
widths, plus one serving tick and one four-device ZeRO-3 step at reduced
depth. Nothing runs: a compile that passes is not a chip run.

Only one process may load the TPU's library, and it keeps it until it
exits: the topology is described inside a module-scoped fixture (never at
import or collection), every compile happens in the test's own process,
and no other test file may do the same.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

B, L, H, D, E = 8, 1024, 16, 64, 1024  # micro-batch, positions, heads, head dim, width
SLOTS, CHUNK = 8, 16
bf16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the package steered to what it does on a
    TPU (compiled kernels, "auto" = pallas) and the persistent compile
    cache off: an executable for a described device is written to the
    cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.ops.pallas import backend
    from deepspeed_tpu.parallel.topology import get_topology, set_topology

    on_tpu, topology = backend.on_tpu, get_topology()
    backend.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    set_topology(None)
    yield SingleDeviceSharding(topo.devices[0])
    backend.on_tpu = on_tpu
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    set_topology(topology)


def _compile(fn, sharding, *shapes, **jit_kwargs):
    """Compile ``fn`` for the described chip; returns the compiled HLO."""
    args = [jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), a)
            for a in shapes]
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def _shape(*dims, dtype=bf16):
    return jax.ShapeDtypeStruct(dims, dtype)


def _kernel_text(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


def _kernel_calls(text, name):
    """The compiled ``text``'s custom calls of the Pallas kernel ``name``
    (XLA names the instruction after the call)."""
    return [line for line in text.splitlines()
            if "custom-call(" in line and line.split("ROOT ")[-1].lstrip().startswith("%" + name)]


def _relayouts(compiled, elements):
    """Instructions that exist only to move or retype an array of at least
    ``elements`` elements: ``copy`` / ``convert`` / ``transpose`` (and a
    fusion XLA named after a copy) outside fusion bodies, where each is a
    pass over HBM of its own."""
    import re
    found, fused = [], False
    for line in compiled.as_text().splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        if fused or not m:
            continue
        name, dims, op = m.groups()
        if (op in ("copy", "convert", "transpose") or (op == "fusion" and name.startswith("copy"))) \
                and np.prod([int(d) for d in dims.split(",") if d] or [1]) >= elements:
            found.append(line.strip()[:160])
    return found


def _assert_pool_written_in_place(compiled, leaf_bytes, layers, logits_bytes):
    """No pass over a whole pool leaf beside the aliased write and the read
    attention makes, and temporaries under one pool leaf a layer (beside the
    head's logits, which no layer owns)."""
    assert not _relayouts(compiled, leaf_bytes)      # int8: a leaf's bytes are its elements
    assert compiled.memory_analysis().temp_size_in_bytes < layers * leaf_bytes + logits_bytes


def _assert_no_rows_of_a_pool_are_made(text, sequences, row):
    """No instruction of the compiled ``text`` makes ``[sequences, *row]`` (a
    pool's rows [kv heads, head dim, positions] of the sequences a decode
    program runs) by a concatenation, copy, gather or transpose (the rung's
    rows picked out) or in any type but int8 (the codes converted): the pool
    itself, written in place, is all that has that shape."""
    import re
    shape = ",".join(str(d) for d in (sequences,) + tuple(row))
    made = []
    for line in text.splitlines():
        m = re.search(r"= (\w+)\[" + shape + r"\]\S* ([\w\-]+)\(", line)
        if m and (m.group(1) != "s8" or m.group(2) in ("concatenate", "copy", "gather",
                                                       "transpose", "convert")):
            made.append(line.strip()[:160])
    assert not made, made


def _sq_grads(fn):
    return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum(), argnums=(0, 1, 2))


# ---------------------------------------------------------------------------
# kernels at 350M widths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("batch,heads", [(B, H), (4, 25)], ids=["gpt2_medium", "gpt2_xl"])
def test_flash_attention_compiles(one_chip, batch, heads, backward):
    """Both training cells' call shapes (XL: the 4 sequences a micro-batch
    that ``per_shard`` hands a chip, 25 heads) at the default geometry:
    whole-sequence blocks walked in compute tiles, against Mosaic's tiling
    rules and scoped VMEM."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True)

    qkv = _shape(batch, L, heads, D)
    _kernel_text(_compile(_sq_grads(fn) if backward else fn, one_chip, qkv, qkv, qkv))


@pytest.mark.parametrize("lq", [1, CHUNK], ids=["decode_tick", "prefill_chunk"])
def test_flash_decode_compiles(one_chip, lq):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_decode
    cache = _shape(SLOTS, L, H, D)
    _kernel_text(_compile(flash_decode, one_chip, _shape(SLOTS, lq, H, D), cache, cache,
                          _shape(SLOTS, dtype=jnp.int32)))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("dtype", [bf16, jnp.float32], ids=["bf16", "fp32"])
def test_moe_permute_compiles(one_chip, backward, dtype):
    from deepspeed_tpu.ops.pallas.moe_dispatch import permute_rows
    tokens, slots = B * L, B * L * 5 // 4

    def fn(x, fwd_idx, bwd_idx):
        return permute_rows(x, fwd_idx, bwd_idx, impl="pallas")

    if backward:
        fn = jax.grad(lambda x, f, b, fn=fn: fn(x, f, b).astype(jnp.float32).sum())
    _kernel_text(_compile(fn, one_chip, _shape(1, tokens, E, dtype=dtype),
                          _shape(1, slots, dtype=jnp.int32),
                          _shape(1, tokens, dtype=jnp.int32)))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("m,k,n", [(SLOTS, E, 3 * E), (SLOTS * CHUNK, E, 4 * E), (SLOTS, 4 * E, E)],
                         ids=["qkv_decode", "mlp_in_prefill", "mlp_out_decode"])
def test_quant_matmul_compiles(one_chip, bits, m, k, n):
    from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul, resolve_impl
    assert resolve_impl("auto") == "pallas"

    def fn(x, codes, scale):
        return quant_matmul(x, codes, scale, bits=bits)

    _kernel_text(_compile(fn, one_chip, _shape(m, k),
                          _shape(k * bits // 8, n, dtype=jnp.int8),
                          _shape(k // 64, n, dtype=jnp.float32)))


@pytest.mark.parametrize("length", [1, CHUNK, 5], ids=["decode_tick", "prefill_chunk",
                                                       "verify_block"])
@pytest.mark.parametrize("heads,head_dim,positions", [(H, D, L), (16, 128, 2048), (25, D, L)],
                         ids=["gpt2_medium", "olmoe", "gpt2_xl"])
def test_kv_append_compiles(one_chip, heads, head_dim, positions, length):
    """The serving cache's write alone: 32 slots of int8 codes and bf16
    scales, K and V in one call, every pool donated: no pass over a pool
    leaf and next to no temporary. Which code writes is the shapes' to say:
    ``ops/pallas/pool_write.py``'s kernel, one call a piece, where the rows
    pack into 32-bit words; the slots' loop for GPT-2 XL's 25 heads, whose
    bfloat16 scales do not."""
    from deepspeed_tpu.models.common import _append_in_place
    slots = 32
    pool, scale = _shape(slots, heads, head_dim, positions, dtype=jnp.int8), _shape(slots, heads, positions)
    new, new_scale = _shape(slots, length, heads, head_dim, dtype=jnp.int8), _shape(slots, length, heads)

    def fn(k, v, ks, vs, nk, nv, nks, nvs, pos):
        return _append_in_place([k, v, ks, vs], [nk, nv, nks, nvs], pos)

    compiled = _compile(fn, one_chip, pool, pool, scale, scale, new, new, new_scale, new_scale,
                        _shape(slots, dtype=jnp.int32), donate_argnums=(0, 1, 2, 3))
    text = compiled.as_text()
    if heads % 2:
        assert "tpu_custom_call" not in text and "jit(_append_piece)/while" in text
        assert set(_write_loop_trip_counts(compiled)) == {slots}
    else:
        assert len(_kernel_calls(text, "pool_write")) == text.count("tpu_custom_call") == 1
        assert "jit(_append_piece)/while" not in text
    assert not _relayouts(compiled, slots * heads * head_dim * positions)
    # under one pool's 128-position windows (a leaf is `positions / 128` of them)
    assert compiled.memory_analysis().temp_size_in_bytes < slots * heads * head_dim * 128


@pytest.mark.parametrize("sequences", [8, 32], ids=["quarter_rung", "every_slot"])
@pytest.mark.parametrize("heads,places,window,pool", [(48, 16384, 16384, jnp.int8),
                                                      (64, 768, 512, jnp.int8),
                                                      (48, 16384, 16384, bf16)],
                         ids=["full_int8", "ring_int8", "full_bf16"])
def test_pool_decode_compiles(one_chip, sequences, heads, places, window, pool):
    """A decode tick's read of a stored pool at the mixed-lengths cell's shapes
    (32 slots, 8 key heads of 128, blocks of 1,024; a full layer's 48 query
    heads over 16,384 positions, a sliding layer's 64 over a ring of 768),
    the rung's rows handed in: the kernel alone, no copy of a pool and no
    temporary beside the few scalars it is steered by."""
    from deepspeed_tpu.ops.pallas.pool_decode import pool_decode
    slots, kv, d = 32, 8, 128
    leaf = _shape(slots, kv, d, places, dtype=pool)
    scale = _shape(slots, kv, places) if pool == jnp.int8 else None
    ints = _shape(sequences, dtype=jnp.int32)

    def fn(q, keys, key_scale, values, value_scale, q_pos, fed, rows):
        return pool_decode(q, keys, key_scale, values, value_scale, q_pos, fed, window=window,
                           block=1024, rows=rows)

    compiled = _compile(fn, one_chip, _shape(sequences, heads, d), leaf, scale, leaf, scale,
                        ints, ints, ints)
    assert _kernel_text(compiled).count("tpu_custom_call") == 1
    assert not _relayouts(compiled, slots * kv * d * places // 4)
    assert compiled.memory_analysis().temp_size_in_bytes < 4096


@pytest.mark.parametrize("rows", [256, 16384], ids=["decode_tick", "prefill_tick"])
def test_grouped_matmul_compiles(one_chip, rows):
    """The drop-free route's expert projection at OLMoE's published widths
    (64 experts of 2048 x 1024) and its two tick shapes (32 x 1 and 32 x 64
    positions, 8 experts each), tiled as ``grouped_matmul.tiling`` says."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul, resolve_impl
    assert resolve_impl("auto") == "pallas"

    def fn(x, w, sizes):
        return grouped_matmul(x, w, sizes, impl="pallas")

    _kernel_text(_compile(fn, one_chip, _shape(rows, 2048), _shape(64, 2048, 1024),
                          _shape(64, dtype=jnp.int32)))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_block_sparse_attention_compiles(one_chip, backward):
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import sparse_attention
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import FixedSparsityConfig
    block = 64
    layout = FixedSparsityConfig(num_heads=H, block=block, num_local_blocks=4,
                                 num_global_blocks=1,
                                 attention="unidirectional").make_layout(L)

    def fn(q, k, v):
        return sparse_attention(q, k, v, layout, block, causal=True)

    qkv = _shape(2, L, H, D)
    _kernel_text(_compile(_sq_grads(fn) if backward else fn, one_chip, qkv, qkv, qkv))


# ---------------------------------------------------------------------------
# whole programs at 350M width, reduced depth
# ---------------------------------------------------------------------------
def _cell_family(family):
    """``(module, slots, chunk, kv_quant)`` of a family's serving cell, at the
    depth and widths the whole-program tests below compile it at."""
    if family == "gpt2":
        from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
        return GPT2LMHeadModel(get_gpt2_config("350m", n_layer=2, dtype=bf16)), 32, CHUNK, True
    if family == "olmoe":
        from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config
        return LlamaForCausalLM(get_llama_config("olmoe-1b-7b", num_hidden_layers=1, dtype=bf16,
                                                 decode_cache_len=2048)), 32, 64, True
    if family == "nemotron_h":
        from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM, get_nemotron_h_config
        return NemotronHForCausalLM(get_nemotron_h_config(
            "nemotron-h-test", hidden_size=256, head_dim=64, mamba_num_heads=16, mamba_head_dim=64,
            ssm_state_size=128, chunk_size=128, moe_latent_size=128, moe_intermediate_size=256,
            moe_shared_expert_intermediate_size=512, experts_held=(4, 4), decode_cache_len=256,
            max_position_embeddings=256, vocab_size=1024, dtype=bf16)), 16, 128, True
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM, get_deepseek_v3_config
    return DeepseekV3ForCausalLM(get_deepseek_v3_config(
        "joyai-llm-flash", num_hidden_layers=2, vocab_size=32320, experts_held=(0, 64),
        decode_cache_len=16384, dtype=bf16, param_dtype=bf16)), 32, 512, False


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_serving_program_compiles(one_chip, program, attention):
    """The scheduler's chunked prefill and decode tick at 8 slots over an
    int8 KV cache, as ``serve_programs`` jits them."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    module = GPT2LMHeadModel(get_gpt2_config("350m", n_layer=2, dtype=None,
                                             attention_backend=attention))
    params = jax.eval_shape(
        lambda key: nn.meta.unbox(module.init(key, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, SLOTS, kv_quant=True))
    apply_fn = make_apply_fn(module)
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(SLOTS, dtype=jnp.int32), _shape(SLOTS, CHUNK, dtype=jnp.int32),
                    _shape(SLOTS, dtype=jnp.int32))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(SLOTS, dtype=jnp.int32),)
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    # either tick writes its tokens through ``pool_write``, one kernel a layer,
    # and a decode tick reads its stored pool through ``pool_decode`` whatever
    # the backend a chunk's attention takes: one more a layer
    text = compiled.as_text()
    kernels, writes = text.count("tpu_custom_call"), len(_kernel_calls(text, "pool_write"))
    assert writes == 2 and "jit(_append_piece)/while" not in text
    assert kernels == 4 if program == "decode" else (kernels > writes) == (attention == "flash")


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_gpt2_serving_program_writes_the_pool_in_place(one_chip, program):
    """The chat cell's programs (32 slots x 1,024 positions, 16 heads of
    64, int8 KV, bf16 weights, 16-token chunks) at two layers: a pool whose
    head size is half a lane row is held positions-minor by the chip, and
    a write that does not follow it costs four relayout copies of the
    pool a layer (ISSUE 27)."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    slots, layers = 32, 2
    module = GPT2LMHeadModel(get_gpt2_config("350m", n_layer=layers, dtype=bf16))
    params = jax.eval_shape(
        lambda key: jax.tree.map(lambda p: p.astype(bf16), nn.meta.unbox(
            module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots, kv_quant=True))
    apply_fn = make_apply_fn(module)
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32), _shape(slots, CHUNK, dtype=jnp.int32),
                    _shape(slots, dtype=jnp.int32))
        logits = slots * CHUNK * module.config.vocab_size * 2
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32),)
        logits = slots * module.config.vocab_size * 2
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    _assert_pool_written_in_place(compiled, slots * L * H * D, layers, logits)
    # the write is one Mosaic kernel a layer (``ops/pallas/pool_write.py``: the
    # slots' loop is in neither program), and a decode tick reads the pool where
    # it lies: one more a layer (``ops/pallas/pool_decode.py``), no pool leaf
    # converted outside it
    text = compiled.as_text()
    assert len(_kernel_calls(text, "pool_write")) == layers
    assert "jit(_append_piece)/while" not in text
    assert text.count("tpu_custom_call") == (2 * layers if program == "decode" else layers)
    if program == "decode":
        _assert_no_rows_of_a_pool_are_made(text, slots, (H, D, L))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_olmoe_serving_program_compiles(one_chip, program):
    """One OLMoE layer at its published widths through the serving programs
    of the benchmark's cell: 32 slots of 2,048 positions over the int8
    cache, 64-token chunks, the grouped expert matmuls as Mosaic kernels
    and the row permutations as XLA gathers."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)
    from deepspeed_tpu.models.llama import LlamaForCausalLM, get_llama_config

    slots, chunk = 32, 64
    module = LlamaForCausalLM(get_llama_config("olmoe-1b-7b", num_hidden_layers=1, dtype=bf16,
                                               decode_cache_len=2048))
    params = jax.eval_shape(
        lambda key: jax.tree.map(lambda p: p.astype(bf16), nn.meta.unbox(
            module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots, kv_quant=True))
    apply_fn = make_apply_fn(module)
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32), _shape(slots, chunk, dtype=jnp.int32),
                    _shape(slots, dtype=jnp.int32))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32),)
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    # gate, up, down and the write (a 64-token chunk is one piece); and a decode
    # tick's read of the stored pool
    text = compiled.as_text()
    assert len(_kernel_calls(text, "pool_write")) == 1
    assert "jit(_append_piece)/while" not in text
    assert text.count("tpu_custom_call") == (4 if program == "prefill" else 5)
    if program == "decode":
        assert text.count("%pool_decode") >= 1
        _assert_no_rows_of_a_pool_are_made(text, slots, (16, 128, 2048))
    # a prefill tick's temporaries stay under a gigabyte: no [E, C, M] buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    logits = slots * (chunk if program == "prefill" else 1) * module.config.vocab_size * 2
    _assert_pool_written_in_place(compiled, slots * 2048 * 16 * 128, 1, logits)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_nemotron_h_serving_program_updates_the_state_pool_in_place(one_chip, program):
    """The hybrid model's serving programs at the tiny preset's layer
    pattern (Mamba, experts a quarter held, attention), widths the chip's
    tiling takes: 16 slots of recurrent state donated with the cache and
    written back where they lie: no pass over a whole state leaf beside the
    update itself, and the whole cache aliased."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)
    from deepspeed_tpu.moe.sharded_moe import _row_rungs

    module, slots, chunk, _ = _cell_family("nemotron_h")
    params = jax.eval_shape(
        lambda key: jax.tree.map(lambda p: p.astype(bf16), nn.meta.unbox(
            module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots, kv_quant=True))
    apply_fn = make_apply_fn(module)
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32), _shape(slots, chunk, dtype=jnp.int32),
                    _shape(slots, dtype=jnp.int32))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32),)
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    # two expert layers x (up, down) x the row buffers the held layer may
    # take (``sharded_moe._row_rungs``): a prefill tick's 16 x 128 x top-4 =
    # 8,192 copies take 2,048 rows or all (a sixteenth, 512, is under
    # ``MIN_RUNG_ROWS``), a decode tick's 64 copies one buffer and no branch
    assert _row_rungs(slots * chunk * 4) == (2048, 8192) and _row_rungs(slots * 4) == (64,)
    # (and the attention layer's write, two key heads whose bfloat16 scales
    # pack into one row of words, and its read of its stored pool in a decode tick)
    text = compiled.as_text()
    assert len(_kernel_calls(text, "pool_write")) == 1
    assert "jit(_append_piece)/while" not in text
    assert text.count("tpu_custom_call") == (9 if program == "prefill" else 6)
    state = cache["layers_0"]["mixer"]["ssm_state"]
    assert state.shape == (slots, 2, 8, 64, 128) and state.dtype == jnp.float32
    # (a prefill tick relays its chunk's activations, as large at these widths)
    assert not [line for line in _relayouts(compiled, state.size) if "f32[16,2,8,64,128]" in line]
    memory = compiled.memory_analysis()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    # every pool and state leaf comes back in place (the index vectors and
    # the layers' counters, a few hundred bytes, are fresh outputs)
    assert memory.alias_size_in_bytes >= cache_bytes - 1024
    if program == "decode":
        # one step: nothing the size of a state leaf is held beside the cache
        assert memory.temp_size_in_bytes < state.size * 4
    else:
        # the branches share their temporaries, and the largest buffer's are
        # those of one buffer for every copy, which compiles to 31,870,976 bytes
        assert memory.temp_size_in_bytes <= 31_870_976


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_joyai_llm_flash_serving_program_reads_the_latent_pool_as_it_lies(one_chip, program):
    """The long-document cell's programs at the published widths and two
    layers (the dense one and an expert layer, 64 of 256 held): the cell's 32 slots x
    16,384 positions of ONE bfloat16 latent pool a layer, 576 values a
    position, donated and written in place. The decode tick is absorbed: no
    copy, convert or transpose of anything the size of a pool, and its
    temporaries are a fortieth of one. The prefill tick walks the keys in
    blocks, a fed slot at a time in the kernel that keeps a step's scores in
    VMEM: nothing near the [slots, heads, chunk, positions] scores (25.8 GB)
    or an expanded pool (6.4 GB) is ever held."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)

    positions, layers = 16384, 2
    module, slots, chunk, _ = _cell_family("joyai_llm_flash")
    params = jax.eval_shape(
        lambda key: nn.meta.unbox(module.init(key, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots))
    pools = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
             if path[-1].key == "cached_latent"]
    assert [(p.shape, p.dtype) for p in pools] == [((slots, 1, 576, positions), bf16)] * layers
    assert not [path for path, _ in jax.tree_util.tree_flatten_with_path(cache)[0]
                if path[-1].key in ("cached_key", "cached_value")]
    apply_fn = make_apply_fn(module)
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32), _shape(slots, chunk, dtype=jnp.int32),
                    _shape(slots, dtype=jnp.int32))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32),)
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    pool_bytes = pools[0].size * 2
    assert not _relayouts(compiled, pools[0].size)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= layers * pool_bytes
    # the latent's write is ``pool_write``'s too: a call a layer a piece of at
    # most a window's tokens (a chunk's four), and no loop over the slots
    text = compiled.as_text()
    assert len(_kernel_calls(text, "pool_write")) == layers * (1 if program == "decode"
                                                               else -(-chunk // 128))
    assert "jit(_append_piece)/while" not in text
    if program == "decode":
        # one kernel a layer reads the pool, as it lies (``latent_decode``)
        assert compiled.as_text().count("%mla_decode") >= layers
        assert memory.temp_size_in_bytes < pool_bytes // 40        # against 604 MB
    else:
        # a fed slot's walk is one kernel a layer, its scores in VMEM
        # (``latent_walk.causal_walk``), and XLA's loops of the walk are gone
        assert compiled.as_text().count("%mla_prefill_walk") >= layers
        # the largest row buffer of the held route (131,072 copies of 2,048) and
        # the dense layer's 7,168-wide activations; compiles to 1,821,700,608
        # bytes (1,821,313,536 with the walk as XLA's loops)
        assert memory.temp_size_in_bytes < 1.823e9


# ---------------------------------------------------------------------------
# indexed and window layers of latent attention (ISSUE 37)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel", ["index_decode", "index_chunk", "selected_decode",
                                    "selected_walk", "causal_walk", "select_chunk",
                                    "select_decode"])
def test_sparse_attention_kernels_compile(one_chip, kernel):
    """The five kernels of an indexed layer at the long-context cell's
    shapes: 32 slots x 32,768 positions, 64 index heads of 128, 128 heads over
    a 576-wide latent, a chunk of 256 queries of one slot; the selection over
    a chunk's rows and over a row a slot. The walk's causal form at the
    long-document cell's: 32 heads, a chunk of 512, pools of 16,384."""
    from deepspeed_tpu.ops.pallas import latent_decode, latent_walk, sparse_index, sparse_select
    slots, positions, heads, d = 32, 32768, 64, 128
    lengths = _shape(slots, dtype=jnp.int32)
    if kernel == "index_decode":
        compiled = _compile(sparse_index.index_scores_decode, one_chip, _shape(slots, heads, d),
                            _shape(slots, heads, dtype=jnp.float32), _shape(slots, d, positions),
                            lengths)
    elif kernel == "index_chunk":
        compiled = _compile(
            lambda q, w, keys, slot, n: sparse_index.index_scores_chunk(q, w, keys, slot, n),
            one_chip, _shape(256, heads * d), _shape(256, heads, dtype=jnp.float32),
            _shape(slots, d, positions), _shape(dtype=jnp.int32), _shape(dtype=jnp.int32))
    elif kernel.startswith("select_"):
        rows = 256 if kernel == "select_chunk" else slots
        compiled = _compile(
            lambda scores, bound, n: sparse_select.select_top_k(scores, bound, n, 2048),
            one_chip, _shape(rows, positions, dtype=jnp.float32), _shape(rows, dtype=jnp.int32),
            _shape(rows // sparse_select.row_tile(rows), dtype=jnp.int32))
    elif kernel == "causal_walk":
        compiled = _compile(
            lambda qn, qr, w, pool, first, slot, n: latent_walk.causal_walk(
                qn, qr, w, pool, first, slot, n, scale=0.07),
            one_chip, _shape(32, 512, 128), _shape(32, 512, 64), _shape(32, 512, 256),
            _shape(slots, 576, 16384), *[_shape(dtype=jnp.int32)] * 3)
        assert "%mla_prefill_walk" in compiled.as_text()
    elif kernel == "selected_walk":
        compiled = _compile(
            lambda qn, qr, w, pool, may, slot, n: latent_walk.selected_walk(
                qn, qr, w, pool, may, slot, n, scale=0.07),
            one_chip, _shape(128, 256, 128), _shape(128, 256, 64), _shape(128, 512, 256),
            _shape(slots, 576, positions),
            _shape(256, positions, dtype=jnp.float32), _shape(dtype=jnp.int32),
            _shape(dtype=jnp.int32))
    else:
        compiled = _compile(
            lambda q, r, pool, n, chosen: latent_decode.latent_decode(q, r, pool, n, scale=0.07,
                                                                      chosen=chosen),
            one_chip, _shape(slots, 128, 512), _shape(slots, 128, 64),
            _shape(slots, 576, positions), lengths, _shape(slots, positions, dtype=jnp.bool_))
    _kernel_text(compiled)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_dots3_note_serving_program_keeps_a_ring_and_scores_a_slot_at_a_time(one_chip, program):
    """The long-context cell's programs at the published widths, its 32 slots
    and two layers, one of each kind (the dense indexed one; a window layer
    with 32 of 256 experts held): the full layer's latent and index keys over
    32,768 positions, the window layer's latent over a ring of 768 (513 - 1 +
    a chunk of 256), all donated and written in place. The decode tick scores,
    selects and attends in kernels and holds a hundredth of a pool in temporaries.
    The prefill tick scores one slot's keys at a time: nothing near the
    [slots, chunk, positions] index scores (1.07 GB a layer) is held, and
    its temporaries are those of the held route's largest row buffer at
    hidden 5,120."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3ForCausalLM, get_deepseek_v3_config,
                                                  window_ring_positions)

    slots, chunk, positions = 32, 256, 32768
    ring = window_ring_positions(513, chunk)
    assert ring == 768
    module = DeepseekV3ForCausalLM(get_deepseek_v3_config(
        "dots3-note-prev", num_hidden_layers=2,
        layer_types=("full_attention", "sliding_attention"), vocab_size=19008,
        experts_held=(0, 32), decode_cache_len=positions, window_ring=ring, dtype=bf16,
        param_dtype=bf16))
    params = jax.eval_shape(
        lambda key: nn.meta.unbox(module.init(key, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots))
    shapes = {path[-1].key: (leaf.shape, leaf.dtype)
              for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0] if leaf.ndim == 4}
    assert shapes == {"cached_latent": ((slots, 1, 576, positions), bf16),
                      "cached_index_key": ((slots, 1, 128, positions), bf16),
                      "cached_window_latent": ((slots, 1, 1088, ring), bf16)}
    apply_fn = make_apply_fn(module)
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32), _shape(slots, chunk, dtype=jnp.int32),
                    _shape(slots, dtype=jnp.int32))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32),)
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    pool_bytes = slots * 576 * positions * 2
    assert not _relayouts(compiled, slots * 576 * positions)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes + slots * (128 * positions + 1088 * ring) * 2
    text = compiled.as_text()
    assert text.count("%dsa_select") >= 1
    # the writes are ``pool_write``'s, a call a leaf's cache a piece: the full
    # layer's latent and its index keys, and the window layer's ring twice for
    # a chunk (the second a ring earlier), once for a token; no loop over the slots
    pieces = 1 if program == "decode" else -(-chunk // 128)
    assert len(_kernel_calls(text, "pool_write")) == (2 + (1 if program == "decode" else 2)) * pieces
    assert "jit(_append_piece)/while" not in text
    if program == "decode":
        assert text.count("%dsa_index_decode") >= 1 and text.count("%dsa_decode") >= 1
        assert memory.temp_size_in_bytes < pool_bytes // 30          # compiles to 37 MB
    else:
        assert text.count("%dsa_index_prefill") >= 1 and text.count("%dsa_prefill_walk") >= 1
        # a slot's scores and its mask pass from kernel to kernel: XLA makes no
        # pass of its own over them (the selection's 32 counts were such passes)
        assert not [line for line in text.splitlines()
                    if f"[{chunk},{positions}]" in line and " custom-call(" not in line]
        # 65,536 copies of 5,120 in float32 twice (the held route's last rung) and
        # the dense layer's 13,824-wide activations; compiles to 2,945,114,624
        # bytes, and to 2,945,695,232 with the selection as XLA's passes
        assert memory.temp_size_in_bytes <= 2_945_695_232


@pytest.mark.parametrize("program", ["prefill", "decode_rung"])
def test_laguna_serving_program_keeps_int8_rings_beside_int8_pools(one_chip, program):
    """The mixed-lengths cell's programs at the published widths, its 32 slots
    and two layers, one of each kind (the dense full layer of 48 query heads;
    a sliding layer of 64 with all 256 experts and the shared one): the full
    layer's int8 keys and values over 16,384 positions, the sliding layer's
    over a ring of the window and a chunk, all donated and written in place;
    the walk reads the stored codes a block at a time (no pass over a whole
    pool, nothing dequantised whole, no key head repeated), a decode tick's as
    ONE kernel a walking layer (ISSUE 47), and the head is made for the one
    position a slot a prefill tick keeps."""
    import json
    import os
    import flax.linen as nn
    from benchmarks.families import laguna as family
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(__file__))))
    with open(os.path.join(root, "benchmarks", "configs", "laguna-xs2.json")) as f:
        config = json.load(f)
    config.update(num_hidden_layers=2, layer_types=config["layer_types"][:2],
                  mlp_layer_types=config["mlp_layer_types"][:2],
                  num_attention_heads_per_layer=config["num_attention_heads_per_layer"][:2])
    dep = config["serve"]
    slots, chunk, positions = dep["slots"], dep["prefill_chunk"], dep["max_out_tokens"]
    ring = family.window_ring(config, dep)
    module = family.model(config, dep)
    params = jax.eval_shape(
        lambda key: jax.tree.map(lambda p: p.astype(bf16), nn.meta.unbox(
            module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots, kv_quant=True))
    shapes = {path[-1].key: (leaf.shape, leaf.dtype)
              for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0] if leaf.ndim == 4}
    assert shapes == {"cached_key": ((slots, 8, 128, positions), jnp.int8),
                      "cached_value": ((slots, 8, 128, positions), jnp.int8),
                      "cached_window_key": ((slots, 8, 128, ring), jnp.int8),
                      "cached_window_value": ((slots, 8, 128, ring), jnp.int8)}
    apply_fn = make_apply_fn(module)
    n = slots // 4
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (_shape(slots, dtype=jnp.int32), _shape(slots, chunk, dtype=jnp.int32),
                    _shape(slots, dtype=jnp.int32))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0, rung=True)
        operands = (_shape(n, dtype=jnp.int32), _shape(n, dtype=jnp.int32))
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    pool_bytes = slots * 8 * 128 * positions
    assert not _relayouts(compiled, pool_bytes)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * pool_bytes + 2 * slots * 8 * 128 * ring
    text = compiled.as_text()
    assert "jit(_append_piece)/while" not in text
    if program == "prefill":
        # gate, up, down: once a size of the held route's row buffer (a prefill
        # tick's three); a chunk's walk stays XLA's loop, a sequence at a time;
        # the writes: a piece a window's tokens, once into the full layer's pool
        # and twice into the sliding layer's ring (the second a ring earlier)
        pieces = -(-chunk // 128)
        assert len(_kernel_calls(text, "pool_write")) == 3 * pieces
        assert text.count("tpu_custom_call") == 9 + 3 * pieces and "%pool_decode" not in text
        # no [slots, chunk, 100,352] logits (1.6 GB at 256): the held route's
        # last rung at hidden 2,048 and the dense layer's 8,192-wide activations
        assert memory.temp_size_in_bytes < slots * chunk * config["vocab_size"] * 2
    else:
        # gate, up, down, and ONE kernel a walking layer (the full layer's pool,
        # the sliding layer's ring: ``ops/pallas/pool_decode.py``); outside it no
        # pass over a quarter of a pool leaf, and temporaries under one block set
        # (a block of keys, of values and of their scales: what the kernel holds
        # of a pool at a time) beside the head's float32 logits, which no layer
        # owns; compiles to 5,106,176 bytes
        # and a token's write a layer (a ring takes one token in one write)
        walks = [line for line in text.splitlines() if line.lstrip().startswith("%pool_decode")]
        assert len(_kernel_calls(text, "pool_write")) == 2
        assert text.count("tpu_custom_call") == 3 + 2 + 2 and len(walks) == 2
        pool = next(leaf for leaf in jax.tree.leaves(cache) if leaf.shape[-1] == positions
                    and leaf.ndim == 4)
        assert not _whole_leaf_passes(compiled, pool)
        block_set = 2 * 8 * dep["decode_key_block"] * (128 + 2)
        assert memory.temp_size_in_bytes < block_set + n * config["vocab_size"] * 4


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_ouro_serving_program_loops_its_passes_over_pools_written_in_place(one_chip, program):
    """The looped-stack cell's programs at the published widths, its 16 slots
    and 512 positions, two of its 48 layers and all four passes: one set of
    leaves a layer whose int8 pools hold the four passes' heads side by side,
    donated and written in place (no pass over a whole pool leaf beside the
    write and the walk, nothing pool-sized made: a slice of a pass's pool in
    front of the kernel would copy it), the passes ONE loop on the device (a
    layer's body is in the program once, not once a pass), a decode tick's walk
    one kernel a layer whose index map picks the pass's heads, and the head
    made for the one position a slot a prefill tick keeps."""
    import json
    import os
    import re
    import flax.linen as nn
    from benchmarks.families import ouro as family
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step,
                                                          make_apply_fn, make_slot_cache)

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(__file__))))
    with open(os.path.join(root, "benchmarks", "configs", "ouro-2.6b.json")) as f:
        config = json.load(f)
    layers, passes = 2, config["total_ut_steps"]
    config.update(num_hidden_layers=layers, layer_types=config["layer_types"][:layers])
    dep = config["serve"]
    slots, chunk, positions = dep["slots"], dep["prefill_chunk"], dep["max_out_tokens"]
    module = family.model(config, dep)
    assert module.config.loop_passes == passes == 4
    params = jax.eval_shape(
        lambda key: jax.tree.map(lambda p: p.astype(bf16), nn.meta.unbox(
            module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots, kv_quant=True))
    pools = [leaf for leaf in jax.tree.leaves(cache) if leaf.ndim == 4]
    assert len(pools) == 2 * layers
    assert {(leaf.shape, leaf.dtype) for leaf in pools} == {
        ((slots, passes * 16, 128, positions), jnp.dtype(jnp.int8))}
    apply_fn = make_apply_fn(module)
    ints = lambda *dims: _shape(*dims, dtype=jnp.int32)  # noqa: E731
    if program == "prefill":
        step = build_prefill_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (ints(slots), ints(slots, chunk), ints(slots))
    else:
        step = build_decode_step(apply_fn, False, 1.0, 0, 1.0)
        operands = (ints(slots),)
    compiled = _compile(step, one_chip, params, cache, *operands, donate_argnums=(1,))
    memory, text = compiled.memory_analysis(), compiled.as_text()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    assert memory.alias_size_in_bytes >= cache_bytes - 1024
    assert not _relayouts(compiled, pools[0].size // passes)     # nor of one pass's share
    # (at two layers the compiler stages a pool through its fast memory on the way to a
    # kernel, four slots at a time: ``slice-start`` / ``copy-start``, not the program's
    # doing and 4 of 96 leaves at the cell's depth: PERF.md section 6, PR 50)
    # (and a chunk's write lays its 128 tokens out as the leaf lies and rolls them
    # into place, [slots, 16 heads, 128, 2 x 256 positions]: at 512 positions the size
    # of ONE pass's share of a pool, and no read of it)
    assert not [line for line in _whole_leaf_passes(compiled, pools[0])
                if not re.match(r"%(slice|copy)-(start|done)|%pad", line)]
    # temporaries: the projections' kernels relaid once for the loop (three a layer,
    # 8.4 MB each) and a tick's activations; under one pool leaf, whatever the passes
    assert memory.temp_size_in_bytes < pools[0].size + 2 * (slots * chunk if program == "prefill"
                                                            else slots) * config["vocab_size"]
    # the layer's body once in the program: its MLP's last matmul is one instruction
    outside, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
        elif not fused and "layers_0/mlp/down_proj/dot_general" in line:
            outside.append(line)
    assert len(outside) == 1 and "_looped/while/body" in outside[0]
    # the write is a kernel a layer inside the pass loop, handed the pass as one more
    # prefetched scalar (a token's 128-position window, or a chunk's two, of the pass's
    # heads; every leaf of the layer in one call, the pools aliased through it); a decode
    # tick's walk is another; the slots' write loop is in neither program
    calls = {name: _kernel_calls(text, name) for name in ("pool_write", "pool_decode")}
    assert len(calls["pool_write"]) == layers
    assert len(calls["pool_decode"]) == (layers if program == "decode" else 0)
    assert text.count("tpu_custom_call") == (2 * layers if program == "decode" else layers)
    assert all("_looped/while/body" in line for found in calls.values() for line in found)
    assert "jit(_append_piece)/while" not in text and not _write_loop_trip_counts(compiled)
    if program == "decode":
        _assert_no_rows_of_a_pool_are_made(text, slots, (16, 128, positions))
    else:
        # no [slots, chunk, 49,152] logits: the head for the last fed position a slot
        assert not re.search(rf"\\[{slots},{chunk},{config['vocab_size']}\\]", text)


# ---------------------------------------------------------------------------
# a rung: the prefill (ISSUE 33) and decode (ISSUE 42) programs over a quarter of the slots
# ---------------------------------------------------------------------------
def _write_loop_trip_counts(compiled):
    """The bound each ``_append_piece`` loop's condition compares its
    counter with (the compiled loop keeps it as a constant)."""
    import re
    counts, bound = [], None
    for line in compiled.as_text().splitlines():
        if line.endswith("{") and not line.startswith(" "):
            bound = None
        m = re.search(r"= s32\[\]\S* constant\((\d+)\)", line)
        if m:
            bound = int(m.group(1))
        if "ROOT" in line and "_append_piece)/while/cond/lt" in line and "direction=LT" in line:
            counts.append(bound)
    return counts


def _write_grids(compiled):
    """``(sequences, windows)`` of each ``pool_write`` call of a compiled
    program, its grid: read off the operands the call is handed (the
    prefetched rows, one a sequence, then each leaf's tokens laid out over the
    windows a sequence may touch)."""
    import re
    grids = []
    for line in _kernel_calls(compiled.as_text(), "pool_write"):
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, output_to_operand", line).group(1)
        shapes = [[int(d) for d in dims.split(",")] for dims in re.findall(r"\w+\[([\d,]+)\]", operands)]
        rows, laid_out = shapes[0], next(shape for shape in shapes if len(shape) > 1)
        assert len(rows) == 1 and laid_out[0] == rows[0] and laid_out[-1] % 128 == 0
        grids.append((rows[0], laid_out[-1] // 128))
    return grids


def _whole_leaf_passes(compiled, leaf):
    """Instructions outside fusion bodies that MAKE an array over every slot
    of a quarter or more of ``leaf`` [slots, ...]: what a program over a few
    slots' rows has no business making (the TPU's compiler opens a gather on
    the slots' axis with slices of the whole pool: ``common.slot_rows``)."""
    import re
    dtype = {"int8": "s8", "bfloat16": "bf16", "float32": "f32"}[str(leaf.dtype)]
    found, fused = [], False
    for line in compiled.as_text().splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if fused or not m or m.group(2) not in ("fusion", "copy", "gather", "slice", "convert",
                                                "transpose"):
            continue
        for dims in re.findall(dtype + r"\[([\d,]+)\]", m.group(1)):
            dims = [int(d) for d in dims.split(",")]
            if dims[0] == leaf.shape[0] and np.prod(dims) >= leaf.size // 4:
                found.append(line.strip()[:160])
                break
    return found


@pytest.mark.parametrize("family, program", [("gpt2", "prefill"), ("olmoe", "prefill"),
                                             ("nemotron_h", "prefill"), ("gpt2", "decode")])
def test_a_quarter_rung_program_runs_a_quarter(one_chip, family, program):
    """The prefill program over a quarter of each cell's slots, and the chat
    cell's decode program over 8 of its 32 (ISSUE 42), handed the slots they
    run: no copy, convert or transpose the size of a pool and no pass over
    one (the rows are picked where they are written and read), the write's
    kernel runs a grid step a sequence of the rung, the cache comes back in place and
    the temporaries are no more than the whole program's. (A latent pool
    has no such program: ``prefill_rungs``.)"""
    import re
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (POOL_LEAVES, build_decode_step,
                                                          build_prefill_step, decode_rungs,
                                                          make_apply_fn, make_slot_cache,
                                                          prefill_rungs)

    module, slots, chunk, kv_quant = _cell_family(family)
    n = (prefill_rungs if program == "prefill" else decode_rungs)(slots)[0]
    assert n == slots // 4
    params = jax.eval_shape(
        lambda key: jax.tree.map(lambda p: p.astype(bf16), nn.meta.unbox(
            module.init(key, jnp.zeros((1, 8), jnp.int32))["params"])), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots, kv_quant=kv_quant))
    apply_fn = make_apply_fn(module)
    ints = lambda *dims: _shape(*dims, dtype=jnp.int32)
    if program == "prefill":
        build = build_prefill_step
        operands = lambda rows: (ints(rows), ints(rows, chunk), ints(rows))
    else:
        build, operands = build_decode_step, lambda rows: (ints(rows),)
    whole = _compile(build(apply_fn, False, 1.0, 0, 1.0), one_chip, params, cache,
                     *operands(slots), donate_argnums=(1,))
    rung = _compile(build(apply_fn, False, 1.0, 0, 1.0, rung=True), one_chip, params, cache,
                    ints(n), *operands(n), donate_argnums=(1,))
    pool = next(leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
                if path[-1].key in POOL_LEAVES)
    # (at the hybrid's reduced widths a chunk's activations, [n, ...], are as
    # large as its pool, 512 KB, which the compiler also moves whole: there
    # only what holds a row for every slot counts)
    assert not [line for line in _relayouts(rung, pool.size)
                if family != "nemotron_h" or f"[{slots}," in line]
    assert family == "nemotron_h" or not _whole_leaf_passes(rung, pool)
    # the write: a call a layer in either program, over the sequences it runs
    # (the rung's rows are the kernel's index map's), and the slots' loop in neither
    grids, grids_whole = _write_grids(rung), _write_grids(whole)
    assert len(grids) == len(grids_whole) > 0
    assert {rows for rows, _ in grids_whole} == {slots} and {rows for rows, _ in grids} == {n}
    assert not _write_loop_trip_counts(whole) and not _write_loop_trip_counts(rung)
    if program == "decode":
        # the rung's rows are read where they lie, by the kernel's index map:
        # one kernel a layer in either program beside the write's and no [n,
        # heads, head dim, positions] made of a pool, gathered, copied or converted
        for text, rows in ((rung.as_text(), n), (whole.as_text(), slots)):
            assert text.count("tpu_custom_call") == 2 + len(grids)
            _assert_no_rows_of_a_pool_are_made(text, rows, pool.shape[1:])
    memory, memory_whole = rung.memory_analysis(), whole.memory_analysis()
    assert memory.temp_size_in_bytes <= memory_whole.temp_size_in_bytes
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    assert memory.alias_size_in_bytes >= cache_bytes - 1024
    # what is gathered of a leaf that holds a row a slot (a pool, the
    # recurrent state) is the rung's rows, a quarter of it, never more
    gathered = [int(np.prod([int(d) for d in dims.split(",")])) for dims in
                re.findall(r"= \w+\[([\d,]+)\]\S* gather\(", rung.as_text())]
    by_row = max(leaf.size for leaf in jax.tree.leaves(cache) if leaf.ndim > 1)
    assert max(gathered, default=0) <= by_row // 4


def _train_engine(devices, zero_stage, fsdp):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology

    cfg = get_gpt2_config("350m", n_layer=2, n_positions=L, remat=True,
                          attention_backend="flash", dtype=bf16, vocab_size=50304,
                          embed_onehot_grad=True, fused_head_loss_chunk=1024)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        topology=MeshTopology(fsdp=fsdp, data=1, devices=devices),
        config={"train_batch_size": B,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "zero_optimization": {"stage": zero_stage}, "steps_per_print": 10**9})
    return engine, {"input_ids": np.zeros((B, L), np.int32)}


def test_train_step_compiles_on_one_chip(one_chip, topo):
    from deepspeed_tpu.parallel.topology import set_topology
    try:
        engine, batch = _train_engine(topo.devices[:1], zero_stage=0, fsdp=1)
        lowered = engine.lower_train_step(batch)
        assert "tpu_custom_call" in lowered.as_text()  # flash, not its XLA fallback
        compiled = lowered.compile()
    finally:
        set_topology(None)
    _kernel_text(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_zero3_step_compiles_over_four_chips(one_chip, topo):
    """The flash kernel inside a GSPMD-partitioned step: Mosaic kernels
    cannot be partitioned automatically, so this compiles only because
    ``flash_attention`` goes manual per shard on a mesh."""
    from deepspeed_tpu.parallel.topology import set_topology
    try:
        engine, batch = _train_engine(topo.devices, zero_stage=3, fsdp=4)
        compiled = engine.lower_train_step(batch).compile()
    finally:
        set_topology(None)
    text = _kernel_text(compiled)
    assert "all-gather" in text and "reduce-scatter" in text


def test_smallthinker_train_step_compiles_and_fits_one_chip(one_chip, topo):
    """The SmallThinker cell's step as the benchmark builds it, at the cell's
    sizes (four layers of the published widths, 16 of 64 experts held, the
    one sequence of 16,384 a step that ``pretrain-seq16k`` gives): the held route's backward (``gmm`` / ``tgmm`` over
    group sizes short of the rows, inside ``nn.switch`` under remat), the
    flash kernels with a window of 4,096 and 28 query heads on 4 key heads,
    forward, dq and dkv, in one program that fits the chip beside its fp32
    masters and Adam moments."""
    import json

    import deepspeed_tpu
    from benchmarks.lib import harness
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    root = harness.REPO_ROOT
    config = harness.load_json(root, "benchmarks", "configs", "smallthinker-21b-a3b.json")
    traffic = harness.load_json(root, "benchmarks", "traffic", "pretrain-seq16k.json")
    family = harness.load_module(root, "benchmarks", "families", "smallthinker.py")
    dep, seqs, seq = config["train"], traffic["seqs_per_chip"], traffic["seq_len"]
    model = family.model(config, dep, n_positions=seq, remat=dep["remat"],
                         attention_backend=dep["attention_backend"], dtype=bf16,
                         fused_head_loss_chunk=dep["fused_head_loss_chunk"])
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, topology=MeshTopology(fsdp=1, data=1, devices=topo.devices[:1]),
            config={"train_batch_size": seqs, "optimizer": dep["optimizer"],
                    "bf16": {"enabled": True}, "gradient_clipping": dep["gradient_clipping"],
                    "zero_optimization": {"stage": 0}, "steps_per_print": 10**9})
        lowered = engine.lower_train_step({"input_ids": np.zeros((seqs, seq), np.int32)})
        compiled = lowered.compile()
    finally:
        set_topology(None)
    text = _kernel_text(compiled)
    # megablox's kernels are named after the jit that holds them, with or
    # without what differentiated them around the name
    import re
    for kernel in (r"%flash_fwd", r"%flash_bwd_dq", r"%flash_bwd_dkv", r"(?<!t)gmm", r"tgmm"):
        assert re.search(kernel, text), kernel
    mem = compiled.memory_analysis()
    print(json.dumps({"argument_gib": mem.argument_size_in_bytes / 2**30,
                      "temp_gib": mem.temp_size_in_bytes / 2**30,
                      "output_gib": mem.output_size_in_bytes / 2**30,
                      "alias_gib": mem.alias_size_in_bytes / 2**30}))
    # that it compiled says it fits the chip's 15.75 GiB (the compiler refuses
    # a program that does not); the masters and moments are most of it
    assert mem.argument_size_in_bytes > 7.8e9


def _deepseek_v32_programs(one_chip, layers, slots=16, chunk=256, positions=32768):
    """``{program: compiled}`` of the 32k-context cell's three programs at the
    published widths over ``layers`` layers (the first dense), with the cache's
    4-axis leaves' shapes."""
    import flax.linen as nn
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          build_prefill_step, build_verify_step,
                                                          make_apply_fn, make_slot_cache)
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM, get_deepseek_v3_config

    module = DeepseekV3ForCausalLM(get_deepseek_v3_config(
        "deepseek-v3.2", num_hidden_layers=layers, first_k_dense_replace=1, vocab_size=16160,
        experts_held=(0, 8), decode_cache_len=positions, attention_key_block=256, dtype=bf16,
        param_dtype=bf16))
    params = jax.eval_shape(
        lambda key: nn.meta.unbox(module.init(key, jnp.zeros((1, 8), jnp.int32))["params"]),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: make_slot_cache(module, slots))
    shapes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if leaf.ndim == 4:
            shapes.setdefault(path[-1].key, []).append((leaf.shape, leaf.dtype))
    apply_fn = make_apply_fn(module)
    ints = lambda *dims: _shape(*dims, dtype=jnp.int32)  # noqa: E731
    steps = {"prefill": (build_prefill_step(apply_fn, False, 1.0, 0, 1.0),
                         (ints(slots), ints(slots, chunk), ints(slots))),
             "decode": (build_decode_step(apply_fn, False, 1.0, 0, 1.0), (ints(slots),)),
             "verify": (build_verify_step(apply_fn), (ints(slots), ints(slots, 2)))}
    return shapes, lambda program: _compile(steps[program][0], one_chip, params, cache,
                                            *steps[program][1], donate_argnums=(1,))


@pytest.mark.parametrize("program", ["prefill", "decode", "verify"])
def test_deepseek_v32_serving_program_indexes_every_layer_and_fits_beside_its_pools(one_chip,
                                                                                     program):
    """The 32k-context cell's three programs at the published widths (hidden
    7,168, 128 heads, ``q_lora_rank`` 1,536, a 16,384 x 7,168 ``o_proj``, a
    dense layer 18,432 wide, 8 of 256 experts held under group-limited routing,
    YaRN), its 16 slots of 32,768 positions and two layers, the dense one and an
    expert layer, BOTH indexed: every layer has an index-key pool beside its
    latent pool, all donated and written in place; each tick scores, selects and
    attends in the kernels dots3-note's full layers run (the shapes are theirs),
    and a prefill tick's temporaries are those ``serve.memory`` quotes."""
    slots, chunk, positions = 16, 256, 32768
    shapes, compile_program = _deepseek_v32_programs(one_chip, 2)
    assert shapes == {"cached_latent": [((slots, 1, 576, positions), bf16)] * 2,
                      "cached_index_key": [((slots, 1, 128, positions), bf16)] * 2}
    compiled = compile_program(program)
    pool_bytes = slots * 576 * positions * 2
    assert not _relayouts(compiled, slots * 576 * positions)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * (pool_bytes + slots * 128 * positions * 2)
    text = compiled.as_text()
    assert "jit(_append_piece)/while" not in text
    if program == "verify":
        # two tokens a slot are no shape the walk's kernel takes: XLA's walk under
        # the selection, which no cell runs (the runner cannot turn speculation
        # on); held here to compiling and to fitting beside the pools
        assert memory.temp_size_in_bytes < pool_bytes // 10
        return
    assert text.count("%dsa_select") >= 2
    if program == "decode":
        assert text.count("%dsa_index_decode") >= 2 and text.count("%dsa_decode") >= 2
        assert len(_kernel_calls(text, "pool_write")) == 4
        assert memory.temp_size_in_bytes < pool_bytes // 10
    elif program == "prefill":
        assert text.count("%dsa_index_prefill") >= 2 and text.count("%dsa_prefill_walk") >= 2
        assert len(_kernel_calls(text, "pool_write")) == 4 * -(-chunk // 128)
        # a slot's scores and its mask pass from kernel to kernel
        assert not [line for line in text.splitlines()
                    if f"[{chunk},{positions}]" in line and " custom-call(" not in line]
        # 4,096 positions x 18,432 of the dense layer's two activations and the
        # held route's last rung of 32,768 copies of 7,168 in float32
        assert memory.temp_size_in_bytes <= DEEPSEEK_V32_PREFILL_TEMP_BYTES


#: what ``benchmarks/configs/deepseek-v3.2.json`` ``serve.memory`` quotes for a
#: prefill tick (the sandbox compile for the chip: 2,064,272,384 bytes at these
#: two layers, 2,092,239,872 at the cell's six, whose layers share them)
DEEPSEEK_V32_PREFILL_TEMP_BYTES = 2_100_000_000
