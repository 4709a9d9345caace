"""Eigenvalue (curvature) estimation — reference ``runtime/eigenvalue.py``
analog used by MoQ scheduling."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.eigenvalue import Eigenvalue, hessian_top_eigenvalue


def test_quadratic_top_eigenvalue_exact():
    """loss = 0.5 x^T diag(d) x has Hessian diag(d): top eig = max(d)."""
    d = jnp.asarray([1.0, 7.5, 3.0, 0.25])

    def loss(x):
        return 0.5 * jnp.sum(d * x * x)

    eig = hessian_top_eigenvalue(loss, jnp.ones((4,)), max_iter=200, tol=1e-6)
    assert eig == pytest.approx(7.5, rel=1e-3)


def test_per_layer_eigenvalues_on_pytree():
    """Two 'layers' with known diagonal curvature: per-layer power
    iteration isolates each block's top eigenvalue."""
    curv = {"h_0": 2.0, "h_1": 9.0}

    def loss(params):
        return sum(0.5 * c * jnp.sum(jnp.square(params[k]["w"]))
                   for k, c in curv.items())

    ev = Eigenvalue(max_iter=200, tol=1e-6, layer_name="h", layer_num=2)
    params = {"h_0": {"w": jnp.ones((3,))}, "h_1": {"w": jnp.ones((2,))}}
    eigs = ev.compute_eigenvalue(loss, params)
    assert eigs[0] == pytest.approx(2.0, rel=1e-3)
    assert eigs[1] == pytest.approx(9.0, rel=1e-3)


def test_zero_curvature_layer_replaced_by_max():
    """Reference post-processing: layers with no curvature signal get the
    max eigenvalue so MoQ ratios stay finite."""
    def loss(params):
        return 0.5 * 4.0 * jnp.sum(jnp.square(params["h_0"]["w"]))  # h_1 unused

    ev = Eigenvalue(max_iter=100, tol=1e-6, layer_name="h", layer_num=2)
    params = {"h_0": {"w": jnp.ones((3,))}, "h_1": {"w": jnp.ones((2,))}}
    eigs = ev.compute_eigenvalue(loss, params)
    assert eigs[0] == pytest.approx(4.0, rel=1e-3)
    assert eigs[1] == pytest.approx(eigs[0])


def test_missing_layer_subtree_raises():
    ev = Eigenvalue(layer_name="h", layer_num=3)
    with pytest.raises(KeyError, match="h_2"):
        ev.compute_eigenvalue(lambda p: 0.0, {"h_0": jnp.ones(2), "h_1": jnp.ones(2)})


def test_gpt2_layer_curvature_runs():
    """End-to-end on a real model: per-block curvature of the LM loss."""
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.models.gpt2 import cross_entropy_loss

    cfg = get_gpt2_config("test")
    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 250, (2, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    @jax.jit  # else every product of the iteration traces the model anew
    def loss(p):
        logits = model.apply({"params": p}, ids)
        return cross_entropy_loss(logits[:, :-1], ids[:, 1:])

    ev = Eigenvalue(max_iter=8, tol=1e-2, layer_name="h", layer_num=cfg.n_layer)
    eigs = ev.compute_eigenvalue(loss, params)
    assert len(eigs) == cfg.n_layer
    assert all(np.isfinite(e) and e >= 0 for e in eigs)
