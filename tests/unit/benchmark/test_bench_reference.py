"""The plain reference against the package's GPT-2 at the ``test`` preset
on the CPU, in float32: the two are independent writings of one model, so
logits and loss agree to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import gpt2 as family
from benchmarks.reference import gpt2 as ref

CONFIG = {"vocab_size": 256, "n_positions": 128, "n_embd": 64, "n_layer": 2, "n_head": 4,
          "layer_norm_epsilon": 1e-5}


@pytest.fixture(scope="module")
def model_and_params():
    import flax.linen as nn
    model = family.model(CONFIG, {})
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"])
    # biases and LayerNorm offsets start at zero: move them, or a swapped
    # or dropped one would go unseen
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree.map(lambda p: p + 0.05 * jax.random.normal(next(keys), p.shape), params)
    return model, params


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (3, 40)), jnp.int32)


def test_reference_imports_nothing_from_the_package():
    import inspect
    assert "deepspeed_tpu" not in inspect.getsource(ref)


def test_logits_agree(model_and_params, ids):
    model, params = model_and_params
    want = model.apply({"params": params}, ids)
    got = ref.forward(family.to_reference(params), ids, CONFIG["n_head"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


def test_blockwise_logits_are_the_forward_pass(model_and_params, ids):
    _, params = model_and_params
    flat = family.to_reference(params)
    np.testing.assert_allclose(np.asarray(family.reference_logits(flat, ids, 4)),
                               np.asarray(ref.forward(flat, ids, 4)), atol=1e-5)


@pytest.mark.parametrize("seqs_per_call", [1, 3])
def test_loss_agrees(model_and_params, ids, seqs_per_call):
    from deepspeed_tpu.runtime.engine import default_causal_lm_loss
    model, params = model_and_params
    want = float(default_causal_lm_loss(model.apply({"params": params}, ids), {"input_ids": ids}))
    flat = family.to_reference(params)
    assert float(ref.loss(flat, ids, 4)) == pytest.approx(want, rel=1e-5)
    assert family.reference_loss(flat, np.asarray(ids), 4, seqs_per_call) == pytest.approx(want, rel=1e-5)


def test_gradient_norm_agrees(model_and_params, ids):
    from deepspeed_tpu.runtime.engine import default_causal_lm_loss
    model, params = model_and_params
    grads = jax.grad(lambda p: default_causal_lm_loss(model.apply({"params": p}, ids),
                                                      {"input_ids": ids}))(params)
    want = float(jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads))))
    got = family.reference_grad_norm(family.to_reference(params), np.asarray(ids), 4, 1)
    assert got == pytest.approx(want, rel=1e-4)


def test_a_wrong_model_is_told_apart(model_and_params, ids):
    """The comparison has teeth: one fewer head, or no causal mask, moves
    the logits far outside the tolerance above."""
    _, params = model_and_params
    flat = family.to_reference(params)
    right = np.asarray(ref.forward(flat, ids, 4))
    assert np.abs(np.asarray(ref.forward(flat, ids, 2)) - right).max() > 1e-3
    flipped = np.asarray(ref.forward(flat, ids[:, ::-1], 4))[:, ::-1]
    assert np.abs(flipped - right).max() > 1e-3
