"""Golden-fixture coverage for every graft-lint rule: one deliberately-bad
program per rule asserting it FIRES, and a minimally-different clean
program asserting it does NOT (the false-positive guard). The clean
tier-1 model matrix is covered separately in test_scenarios.py."""

import ast
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis import ERROR, WARN, check_program
from deepspeed_tpu.analysis.core import RULES
from deepspeed_tpu.analysis.source_rules import r008_source


def _jaxpr(fn, *args):
    return jax.make_jaxpr(fn)(*args)


def _rules_hit(findings):
    return {f.rule for f in findings}


def test_registry_has_all_rules():
    assert {f"R00{i}" for i in range(1, 9)} <= set(RULES)
    for r in RULES.values():
        assert r.doc, f"{r.id} has no doc"


# ---------------------------------------------------------------------------
# R001 dense [S,E,C]
# ---------------------------------------------------------------------------
class TestR001:
    S, E, C = 16, 4, 4

    def test_fires_on_dense_dispatch(self):
        def dense(x, w):  # the GShard einsum shape: one-hot [S,E,C] mask
            mask = jnp.zeros((self.S, self.E, self.C), x.dtype) + w
            return jnp.einsum("sec,sm->ecm", mask, x).sum()

        jx = _jaxpr(jax.grad(dense), jnp.ones((self.S, 8)), jnp.ones(()))
        fs = check_program(jx, rules=["R001"], metadata={"moe_sec": [(self.S, self.E, self.C)]})
        assert fs and all(f.severity == ERROR for f in fs)

    def test_silent_without_signature_metadata(self):
        def dense(x, w):
            mask = jnp.zeros((self.S, self.E, self.C), x.dtype) + w
            return jnp.einsum("sec,sm->ecm", mask, x).sum()

        jx = _jaxpr(jax.grad(dense), jnp.ones((self.S, 8)), jnp.ones(()))
        assert not check_program(jx, rules=["R001"])

    def test_clean_on_sorted_style_program(self):
        def sorted_route(x, w):  # permutation route: [E*C, M] only
            idx = jnp.arange(self.S) % (self.E * self.C)
            buf = jnp.zeros((self.E * self.C, 8), x.dtype).at[idx].add(x * w)
            return buf.sum()

        jx = _jaxpr(jax.grad(sorted_route), jnp.ones((self.S, 8)), jnp.ones(()))
        assert not check_program(jx, rules=["R001"],
                                 metadata={"moe_sec": [(self.S, self.E, self.C)]})


# ---------------------------------------------------------------------------
# R002 precision
# ---------------------------------------------------------------------------
class TestR002:
    def test_fires_on_float64(self):
        with jax.enable_x64():
            jx = _jaxpr(lambda x: x.astype(jnp.float64).sum(), jnp.ones(4, jnp.float32))
        fs = check_program(jx, rules=["R002"])
        assert any(f.severity == ERROR and "float64" in f.message for f in fs)

    def test_warns_on_unallowlisted_upcast_on_parity_path(self):
        jx = _jaxpr(lambda x: (x.astype(jnp.float32) ** 2).sum(), jnp.ones(4, jnp.bfloat16))
        fs = check_program(jx, rules=["R002"], metadata={"parity": True})
        assert any(f.severity == WARN and "upcast" in f.message for f in fs)

    def test_allowlisted_scope_is_clean_and_attributed(self):
        @jax.jit
        def softmax_stats(x):  # scope name lands in the allowlist
            return jax.nn.softmax(x.astype(jnp.float32)).sum()

        jx = _jaxpr(lambda x: softmax_stats(x), jnp.ones(4, jnp.bfloat16))
        from deepspeed_tpu.analysis import ProgramInfo, run_program_rules
        info = ProgramInfo(name="t", jaxpr=jx, metadata={"parity": True})
        fs, metrics = run_program_rules(info, rules=["R002"])
        assert not fs
        # the upcast is still attributed for the ULP hunt
        assert any("bfloat16->float32" in k for k in metrics["precision_attribution"])

    def test_upcasts_ignored_off_parity_path(self):
        jx = _jaxpr(lambda x: (x.astype(jnp.float32) ** 2).sum(), jnp.ones(4, jnp.bfloat16))
        assert not check_program(jx, rules=["R002"])


# ---------------------------------------------------------------------------
# R003 host transfers
# ---------------------------------------------------------------------------
class TestR003:
    def test_fires_on_device_put_inside_step(self):
        jx = _jaxpr(lambda x: jax.device_put(x) * 2, jnp.ones(4))
        fs = check_program(jx, rules=["R003"])
        assert any(f.severity == ERROR and "device_put" in f.message for f in fs)

    def test_fires_on_pure_callback(self):
        def f(x):
            return jax.pure_callback(lambda v: np.asarray(v),
                                     jax.ShapeDtypeStruct((4,), jnp.float32), x)

        fs = check_program(_jaxpr(f, jnp.ones(4)), rules=["R003"])
        assert any("pure_callback" in f.message for f in fs)

    def test_debug_callback_is_warn_and_waivable_via_allowlist(self):
        def f(x):
            jax.debug.callback(lambda v: None, x)
            return x * 2

        fs = check_program(_jaxpr(f, jnp.ones(4)), rules=["R003"])
        assert fs and all(f.severity == WARN for f in fs)
        assert not check_program(_jaxpr(f, jnp.ones(4)), rules=["R003"],
                                 metadata={"allow_callbacks": ["debug_callback"]})

    def test_clean_program(self):
        assert not check_program(_jaxpr(lambda x: (x * 2).sum(), jnp.ones(4)),
                                 rules=["R003"])


# ---------------------------------------------------------------------------
# R004 remat coverage
# ---------------------------------------------------------------------------
class TestR004:
    def _loss(self, inside_remat: bool):
        def big_block(x):
            return jnp.tanh(x @ x.T)  # [256, 256] f32 = 256 KiB intermediate

        def loss(x):
            blk = jax.checkpoint(big_block) if inside_remat else big_block
            y = blk(x)
            z = jax.checkpoint(lambda a: jnp.sin(a).sum())(y)  # ensure remat present
            return z

        return loss

    def test_fires_on_uncovered_large_activation(self):
        # coverage is judged on the FORWARD program (rule doc): grad's
        # partial-eval inlines covered primals to the top level
        jx = _jaxpr(self._loss(inside_remat=False), jnp.ones((256, 64)))
        fs = check_program(jx, rules=["R004"],
                           metadata={"remat_threshold_bytes": 64 << 10})
        assert any(f.severity == WARN and "outside remat" in f.message for f in fs)

    def test_clean_when_covered_by_remat(self):
        jx = _jaxpr(self._loss(inside_remat=True), jnp.ones((256, 64)))
        fs = check_program(jx, rules=["R004"],
                           metadata={"remat_threshold_bytes": 64 << 10})
        # the [256,256] block output is produced inside the remat region
        assert not [f for f in fs if "(256, 256)" in f.message]

    def test_inert_without_remat_or_expectation(self):
        jx = _jaxpr(jax.grad(lambda x: jnp.tanh(x @ x.T).sum()), jnp.ones((256, 64)))
        assert not check_program(jx, rules=["R004"],
                                 metadata={"remat_threshold_bytes": 1 << 10})


# ---------------------------------------------------------------------------
# R005 donation
# ---------------------------------------------------------------------------
class TestR005:
    def test_fires_when_step_does_not_donate(self):
        hlo = jax.jit(lambda s, b: (s + b, b.sum())).lower(
            jnp.ones(8), jnp.ones(8)).as_text()
        fs = check_program(hlo_text=hlo, metadata={"expect_donation": True},
                           rules=["R005"], kind="train_step")
        assert any(f.severity == ERROR and "donate" in f.message for f in fs)

    def test_clean_when_donating(self):
        hlo = jax.jit(lambda s, b: (s + b, b.sum()), donate_argnums=(0,)).lower(
            jnp.ones(8), jnp.ones(8)).as_text()
        assert not check_program(hlo_text=hlo, metadata={"expect_donation": True},
                                 rules=["R005"], kind="train_step")

    def test_inert_without_expectation(self):
        hlo = jax.jit(lambda s, b: (s + b, b.sum())).lower(
            jnp.ones(8), jnp.ones(8)).as_text()
        assert not check_program(hlo_text=hlo, rules=["R005"])


# ---------------------------------------------------------------------------
# R006 weak types
# ---------------------------------------------------------------------------
class TestR006:
    def test_fires_on_python_scalar_input(self):
        fs = check_program(_jaxpr(lambda x: x + 1.0, 3.0), rules=["R006"])
        assert any("weak-typed" in f.message for f in fs)

    def test_clean_on_committed_array_input(self):
        # an explicit dtype commits the type (jnp.asarray(3.0) alone stays
        # weak — that's precisely the hazard R006 reports)
        assert not check_program(_jaxpr(lambda x: x + 1.0, jnp.asarray(3.0, jnp.float32)),
                                 rules=["R006"])


# ---------------------------------------------------------------------------
# R007 sharding coverage
# ---------------------------------------------------------------------------
class TestR007:
    def test_fires_on_unsharded_large_intermediate(self):
        jx = _jaxpr(lambda x: jnp.tanh(x @ x.T).sum(), jnp.ones((128, 16)))
        fs = check_program(jx, rules=["R007"],
                           metadata={"multi_device": True,
                                     "shard_threshold_bytes": 16 << 10})
        assert any("unsharded intermediate" in f.message for f in fs)

    def test_clean_with_sharding_constraint(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

        def f(x):
            y = jnp.tanh(x @ x.T)
            y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P("d")))
            return y.sum()

        fs = check_program(_jaxpr(f, jnp.ones((128, 16))), rules=["R007"],
                           metadata={"multi_device": True,
                                     "shard_threshold_bytes": 16 << 10})
        assert not fs

    def test_inert_on_single_device(self):
        jx = _jaxpr(lambda x: jnp.tanh(x @ x.T).sum(), jnp.ones((128, 16)))
        assert not check_program(jx, rules=["R007"],
                                 metadata={"shard_threshold_bytes": 1 << 10})


# ---------------------------------------------------------------------------
# R008 AST
# ---------------------------------------------------------------------------
def _ast_findings(src, relpath="pkg/mod.py"):
    src = textwrap.dedent(src)
    return r008_source([(relpath, src, ast.parse(src))])


class TestR008:
    def test_fires_on_raw_device_put(self):
        fs = _ast_findings("""
            import jax
            def restore(tree, sh):
                return jax.device_put(tree, sh)
        """)
        assert len(fs) == 1 and not fs[0].waived and fs[0].location.endswith(":4")

    def test_fires_on_from_import_alias(self):
        fs = _ast_findings("""
            from jax import device_put as dput
            def restore(tree):
                return dput(tree)
        """)
        assert len(fs) == 1

    def test_inline_waiver_marks_but_does_not_gate(self):
        fs = _ast_findings("""
            import jax
            def barrier():
                (jax.device_put(0.0) + 0).block_until_ready()  # graft-lint: waive R008 fresh scalar
        """)
        assert len(fs) == 1 and fs[0].waived and "fresh scalar" in fs[0].waiver_reason

    def test_device_py_itself_is_exempt(self):
        fs = _ast_findings("""
            import jax
            def owned_device_put(tree):
                return jax.device_put(tree)
        """, relpath="deepspeed_tpu/utils/device.py")
        assert not fs

    def test_fires_on_frozen_host_state_in_jit(self):
        fs = _ast_findings("""
            import time, jax
            import numpy as np
            @jax.jit
            def step(x):
                t = time.time()
                noise = np.random.default_rng(0).normal()
                return x * t + noise
        """)
        msgs = " ".join(f.message for f in fs)
        assert "time.time" in msgs and "np.random.default_rng" in msgs

    def test_jit_detection_covers_partial_and_nested(self):
        fs = _ast_findings("""
            import time, jax
            from functools import partial
            @partial(jax.jit, static_argnums=0)
            def outer(n, x):
                def inner(y):
                    return y * time.time()
                return inner(x)
        """)
        assert len(fs) == 1

    def test_clean_outside_jit(self):
        fs = _ast_findings("""
            import time
            def main():
                t0 = time.time()
                return t0
        """)
        assert not fs
