"""Static memory estimator (analysis/memory.py): liveness semantics on
hand-built programs with known buffer lifetimes, scaling behavior on
scan residuals, and the tolerance-banded agreement cross-check against
XLA's own ``memory_analysis()`` on small compiled programs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis import estimate_memory
from deepspeed_tpu.analysis.program import ProgramInfo

KB = 1024


def _est(fn, *args):
    return estimate_memory(jax.make_jaxpr(fn)(*args))


class TestLiveness:
    def test_chain_holds_two_buffers(self):
        """x -> y -> z: at any slot at most two of the three same-size
        buffers are live (x dies when y's consumer runs)."""
        x = jnp.ones(1024, jnp.float32)  # 4 KiB

        def chain(x):
            y = x * 2.0
            return y + 1.0

        est = _est(chain, x)
        assert est.input_bytes == 4 * KB
        assert est.output_bytes == 4 * KB
        assert 8 * KB <= est.peak_bytes <= 13 * KB  # 2 live + slack for consts
        # the transient peak (inputs excluded) can never exceed the total
        assert 4 * KB <= est.peak_transient_bytes <= est.peak_bytes

    def test_input_held_to_the_end_separates_the_timelines(self):
        """When the input stays live at the peak (used by the LAST eqn),
        the transient timeline — which R010 budgets — excludes it."""
        x = jnp.ones(1024, jnp.float32)

        def f(x):
            y = jnp.tanh(x)
            return y + x  # x live across the whole program

        est = _est(f, x)
        assert est.peak_transient_bytes <= est.peak_bytes - 4 * KB

    @pytest.mark.parametrize("consumer,is_view", [("dot", True), ("add", False),
                                                  ("dot_and_add", False)])
    def test_a_transpose_that_only_dots_read_is_a_view(self, consumer, is_view):
        """A dot names its operand's axes in any order, so a transpose it
        alone reads is no buffer (and keeps what it views alive); read by
        anything else, or returned, it is a copy."""
        x = jnp.ones((64, 16), jnp.float32)  # 4 KiB
        w = jnp.ones((64, 4), jnp.float32)   # 1 KiB

        def f(x, w):
            y = jnp.tanh(x)                  # 4 KiB, a transient
            t = y.T                          # 4 KiB, unless a view
            out = t @ w                      # [16, 4]: 256 B
            if consumer == "add":
                out = (t + 1.0)[:, :4]
            elif consumer == "dot_and_add":
                out = out + (t + 1.0)[:, :4]
            return out

        est = _est(f, x, w)
        if is_view:
            # y lives through the dot that reads its view, and nothing
            # else of its size does
            assert 4 * KB <= est.peak_transient_bytes < 5 * KB
        else:
            assert est.peak_transient_bytes >= 8 * KB
        returned = _est(lambda x, w: (jnp.tanh(x).T, jnp.tanh(x).T @ w), x, w)
        assert returned.output_bytes >= 4 * KB

    def test_fanout_holds_all_branches(self):
        """Three branches off one input, combined at the end: all three
        branch buffers + the input are live at the join."""
        x = jnp.ones(1024, jnp.float32)

        def fanout(x):
            a, b, c = x * 2, x * 3, x * 4
            return a + b + c

        est = _est(fanout, x)
        assert est.peak_bytes >= 4 * 4 * KB  # x + a + b + c

    def test_dead_branch_cheaper_than_live_branch(self):
        """A big buffer consumed immediately costs less *transient* peak
        than one held across the program (held: the [N] buffer AND its
        same-size successor coexist; freed: only the [N] buffer exists
        before its reduction) — the ordering property R010's activation
        bound rides on."""
        x = jnp.ones(8 * 1024, jnp.float32)  # 32 KiB

        def held(x):
            big = x * 2          # held across the small chain below
            s = jnp.sum(x)
            s = s * 3 + 1
            return big + s       # second [N]-sized buffer while big lives

        def freed(x):
            big = (x * 2).sum()  # reduced immediately
            s = jnp.sum(x) * 3 + 1
            return big + s

        assert (_est(held, x).peak_transient_bytes
                > _est(freed, x).peak_transient_bytes)

    def test_scan_residuals_scale_with_length(self):
        """Under grad, scan's per-tick residuals stack into [K, ...]
        outputs of the forward scan — the estimator must see the linear
        growth (this is exactly the chunked-pipe liveness the 1F1B
        refactor attacks)."""
        w = jnp.ones((64, 64), jnp.float32)

        def loss(w, length):
            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, jnp.ones((8, 64)), None, length=length)
            return out.sum()

        small = estimate_memory(jax.make_jaxpr(lambda w: jax.grad(loss)(w, 2))(w))
        big = estimate_memory(jax.make_jaxpr(lambda w: jax.grad(loss)(w, 16))(w))
        assert big.peak_bytes > 2 * small.peak_bytes

    def test_attribution_names_scopes_and_buffers(self):
        @jax.jit
        def inner(x):
            return x @ x

        est = _est(lambda x: inner(x).sum(), jnp.ones((64, 64)))
        assert "<inputs>" in est.by_scope
        assert est.top_live and all(t["bytes"] > 0 for t in est.top_live)
        assert est.eqns > 0

    def test_works_on_program_info(self):
        x = jnp.ones(256)
        info = ProgramInfo(name="t", jaxpr=jax.make_jaxpr(lambda x: x * 2)(x))
        est = estimate_memory(info)
        assert est.peak_bytes >= 2 * KB


class TestBackendAgreement:
    """Estimator vs XLA's compiled memory stats: tolerance-banded, CPU.
    The static estimate is a logical upper-ish bound (no fusion, no
    buffer sharing below jaxpr level); agreement within a small constant
    factor on simple programs is the contract."""

    BAND = (0.25, 4.0)

    @pytest.mark.parametrize("name,fn,args", [
        ("matmul_chain",
         lambda a, b: jnp.tanh(a @ b) @ b,
         (np.ones((128, 128), np.float32), np.ones((128, 128), np.float32))),
        ("elementwise",
         lambda a, b: (a * 2 + b).sum(),
         (np.ones((64, 1024), np.float32), np.ones((64, 1024), np.float32))),
    ])
    def test_single_device_band(self, name, fn, args):
        args = [jnp.asarray(a) for a in args]
        est = estimate_memory(jax.make_jaxpr(fn)(*args))
        compiled = jax.jit(fn).lower(*args).compile()
        ma = compiled.memory_analysis()
        if ma is None:  # backend without memory stats: nothing to check
            pytest.skip("backend provides no memory_analysis()")
        xla_total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes)
        ratio = est.peak_bytes / max(xla_total, 1)
        assert self.BAND[0] <= ratio <= self.BAND[1], (
            f"{name}: static {est.peak_bytes} vs XLA {xla_total} (ratio {ratio:.2f})")

    def test_grad_program_band(self):
        """The shape the scenario matrix actually judges: fwd+bwd with
        residuals held across the backward."""
        w = jnp.ones((128, 128), jnp.float32)

        def loss(w):
            h = jnp.tanh(w @ w)
            return (jnp.tanh(h @ w) ** 2).sum()

        grad = jax.grad(loss)
        est = estimate_memory(jax.make_jaxpr(grad)(w))
        ma = jax.jit(grad).lower(w).compile().memory_analysis()
        if ma is None:
            pytest.skip("backend provides no memory_analysis()")
        xla_total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes)
        ratio = est.peak_bytes / max(xla_total, 1)
        assert self.BAND[0] <= ratio <= self.BAND[1], ratio

    @pytest.mark.parametrize("remat", ["none", "every_1",
                                       "every_1:dots_saveable", "every_2"])
    def test_searched_candidate_band_per_remat_family(self, remat):
        """One SEARCHED candidate per remat-policy family, priced through
        the real engine path graft-search uses, cross-checked against
        XLA's own ``memory_analysis()`` of the same step — the search's
        objective function stays pinned to the backend's numbers across
        its most program-reshaping axis (ISSUE 12 satellite)."""
        from deepspeed_tpu.analysis.search import SPACES, Candidate, build_candidate_engine
        from deepspeed_tpu.parallel.topology import set_topology

        cand = Candidate(remat=remat, lm_head_chunk=32)
        engine, batch, _ = build_candidate_engine(SPACES["gpt2_test_gate"], cand)
        try:
            step = engine.traced_programs(batch, lower=False)["train_step"]
            est = estimate_memory(step["jaxpr"])
            ma = engine.lower_train_step(batch).compile().memory_analysis()
        finally:
            set_topology(None)
        if ma is None:
            pytest.skip("backend provides no memory_analysis()")
        xla_total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes)
        ratio = est.peak_bytes / max(xla_total, 1)
        assert self.BAND[0] <= ratio <= self.BAND[1], (
            f"{cand.cid}: static {est.peak_bytes} vs XLA {xla_total} "
            f"(ratio {ratio:.2f})")
