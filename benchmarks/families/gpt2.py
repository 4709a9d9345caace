"""The GPT-2 family: builds the package's model from a configuration file
whose ``family`` is ``gpt2``, maps the package's parameter tree onto the
reference's flat names, and holds the two sides against each other.

The reference (``benchmarks/reference/gpt2.py``) is run a block at a time
through one jitted program per piece, a few sequences to a call, so that
it fits beside the engine's state and, on a mesh, gathers one block's
weights at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import trace
from benchmarks.reference import gpt2 as ref


def model(config, deployment, **overrides):
    """The package's GPT-2 at the sizes of ``config`` (the parsed
    configuration file). ``deployment`` is its ``train`` or ``serve`` block."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    sizes = dict(vocab_size=deployment.get("vocab_rows", config["vocab_size"]),
                 n_positions=config["n_positions"], n_embd=config["n_embd"],
                 n_layer=config["n_layer"], n_head=config["n_head"],
                 layer_norm_epsilon=config["layer_norm_epsilon"])
    return GPT2LMHeadModel(GPT2Config(**{**sizes, **overrides}))


def op_label(text, stats=None):
    """Names this family's kernels in a device trace: the only Mosaic
    custom calls in GPT-2's programs are the flash-attention kernels
    (forward, and the two of the backward), whatever XLA numbered them."""
    return "pallas:attn" if trace.is_custom_call(text) else trace.op_family(text)


def to_reference(params):
    """The package's parameter tree -> the reference's flat dict. Only
    views and reshapes: the leaves stay where and how they are sharded."""
    flat = {"wte": params["wte"], "wpe": params["wpe"],
            "ln_f.g": params["ln_f"]["LayerNorm_0"]["scale"],
            "ln_f.b": params["ln_f"]["LayerNorm_0"]["bias"]}
    n_layer = sum(1 for k in params if k.startswith("h_"))
    for i in range(n_layer):
        blk, pre = params[f"h_{i}"], f"h.{i}."
        e = blk["attn"]["c_attn"]["kernel"].shape[0]
        flat.update({
            pre + "ln_1.g": blk["ln_1"]["LayerNorm_0"]["scale"],
            pre + "ln_1.b": blk["ln_1"]["LayerNorm_0"]["bias"],
            pre + "ln_2.g": blk["ln_2"]["LayerNorm_0"]["scale"],
            pre + "ln_2.b": blk["ln_2"]["LayerNorm_0"]["bias"],
            # [E, 3, H, D] -> [E, 3E]: q | k | v, heads contiguous in each
            pre + "attn.c_attn.w": blk["attn"]["c_attn"]["kernel"].reshape(e, 3 * e),
            pre + "attn.c_attn.b": blk["attn"]["c_attn"]["bias"].reshape(3 * e),
            # [H, D, E] -> [E, E]
            pre + "attn.c_proj.w": blk["attn"]["c_proj"]["kernel"].reshape(e, e),
            pre + "attn.c_proj.b": blk["attn"]["c_proj"]["bias"],
            pre + "mlp.c_fc.w": blk["mlp"]["c_fc"]["kernel"],
            pre + "mlp.c_fc.b": blk["mlp"]["c_fc"]["bias"],
            pre + "mlp.c_proj.w": blk["mlp"]["c_proj"]["kernel"],
            pre + "mlp.c_proj.b": blk["mlp"]["c_proj"]["bias"],
        })
    return flat


_embed = jax.jit(ref.embed)
_block = jax.jit(ref.block, static_argnums=2)
_head = jax.jit(ref.head)


@jax.jit
def _head_nll_sum(params, x, ids):
    """Sum (not mean) of next-token NLL, so calls over parts of a batch add."""
    return ref.nll(ref.head(params, x), ids) * (ids.shape[0] * (ids.shape[1] - 1))


def _top(flat):
    return {k: flat[k] for k in ("wte", "wpe", "ln_f.g", "ln_f.b")}


def reference_logits(flat, ids, n_head):
    """Reference logits [B, L, V], a block to a program."""
    x = _embed(_top(flat), ids)
    for i in range(ref.n_layers(flat)):
        x = _block(ref.block_params(flat, i), x, n_head)
    return _head(_top(flat), x)


def reference_loss(flat, ids, n_head, seqs_per_call, place=None):
    """Mean next-token loss of the reference over ``ids`` [B, L],
    ``seqs_per_call`` sequences at a time. ``place`` puts each part on the
    devices (a mesh's batch sharding); None leaves it to JAX."""
    total = 0.0
    for at in range(0, ids.shape[0], seqs_per_call):
        part = jnp.asarray(ids[at:at + seqs_per_call])
        if place is not None:
            part = jax.device_put(part, place)
        x = _embed(_top(flat), part)
        for i in range(ref.n_layers(flat)):
            x = _block(ref.block_params(flat, i), x, n_head)
        total += float(_head_nll_sum(_top(flat), x, part))
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def reference_grad_norm(flat, ids, n_head, seqs_per_call):
    """Global L2 norm of the gradient of the reference's mean loss over
    ``ids``, accumulated ``seqs_per_call`` sequences at a time. Blocks are
    wrapped in ``jax.checkpoint`` to bound memory; that changes no value."""
    def part_loss(p, part):
        x = ref.embed(p, part)
        for i in range(ref.n_layers(p)):
            x = jax.checkpoint(ref.block, static_argnums=2)(ref.block_params(p, i), x, n_head)
        return ref.nll(ref.head(p, x), part) * part.shape[0]

    grad = jax.jit(jax.grad(part_loss))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    total = None
    for at in range(0, ids.shape[0], seqs_per_call):
        g = grad(flat, jnp.asarray(ids[at:at + seqs_per_call]))
        total = g if total is None else add(total, g)
    sq = jax.jit(lambda t: sum(jnp.sum(jnp.square(v)) for v in jax.tree.leaves(t)))(total)
    return float(np.sqrt(float(sq))) / ids.shape[0]
