"""Golden fixtures for the graft-audit cost rules R009-R013: one
deliberately-bad program per rule asserting it FIRES and a minimally
different clean program asserting it does NOT (same contract as
test_rules.py for R001-R008), plus the collective inventory and the
cost-baseline ratchet semantics."""

import json

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.analysis import (ERROR, INFO, WARN, RULES, build_cost,
                                    load_cost_baseline, r013_cost_ratchet,
                                    run_cost_rules)
from deepspeed_tpu.analysis.hlo_cost import (CollectiveOp, compiled_collectives,
                                             infer_axes, inventory,
                                             parse_replica_groups,
                                             stablehlo_collectives)
from deepspeed_tpu.analysis.program import ProgramAnalyzer, ProgramInfo

MESH_AXES = {"x": 2, "y": 4}


def _shard_map(fn, in_specs, out_specs):
    import numpy as np
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device host mesh")
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("x", "y"))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def _cost(fn, *args, metadata=None):
    info = ProgramInfo(name="fixture", jaxpr=jax.make_jaxpr(fn)(*args),
                       metadata=dict(metadata or {}, mesh_axes=MESH_AXES))
    analyzer = ProgramAnalyzer(info)
    cost = build_cost(info, analyzer=analyzer, compile=False)
    return info, cost, analyzer


def test_registry_has_cost_rules():
    assert {"R009", "R010", "R011", "R012", "R013"} <= set(RULES)
    for rid in ("R009", "R010", "R011", "R012", "R013"):
        assert RULES[rid].layer == "cost"
        assert RULES[rid].doc


# ---------------------------------------------------------------------------
# R009 collective-signature drift
# ---------------------------------------------------------------------------
class TestR009:
    def _psum_program(self):
        def f(x):
            return jax.lax.psum(x, "x")
        return _shard_map(f, P("x"), P())

    def test_exact_count_clean_then_drifts(self):
        f = self._psum_program()
        x = jnp.ones(8, jnp.float32)
        sig_ok = [{"layer": "jaxpr", "kind": "all_reduce", "count": 1}]
        info, cost, an = _cost(f, x, metadata={"collective_signature": sig_ok})
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R009"]

        sig_drift = [{"layer": "jaxpr", "kind": "all_reduce", "count": 2}]
        info, cost, an = _cost(f, x, metadata={"collective_signature": sig_drift})
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R009"]
        assert fs and fs[0].severity == ERROR and "drift" in fs[0].message

    def test_max_bytes_fires_on_fat_collective(self):
        f = self._psum_program()
        x = jnp.ones(64 * 1024, jnp.float32)  # 256 KiB through the psum
        sig = [{"layer": "jaxpr", "kind": "all_reduce", "max_bytes": 1024}]
        info, cost, an = _cost(f, x, metadata={"collective_signature": sig})
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R009"]
        assert fs and "bytes" in fs[0].message

    def test_backend_excluded_entry_is_unchecked_not_passed(self):
        f = self._psum_program()
        x = jnp.ones(8, jnp.float32)
        sig = [{"layer": "compiled", "kind": "reduce_scatter", "min_count": 1,
                "backends": ["tpu"]}]
        info, cost, an = _cost(f, x, metadata={"collective_signature": sig})
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R009"]
        assert cost.unchecked_signature and \
            cost.unchecked_signature[0]["kind"] == "reduce_scatter"

    def test_unknown_signature_key_rejected_loudly(self):
        f = self._psum_program()
        x = jnp.ones(8, jnp.float32)
        sig = [{"layer": "jaxpr", "kind": "all_reduce", "cout": 1}]  # typo
        info, cost, an = _cost(f, x, metadata={"collective_signature": sig})
        with pytest.raises(ValueError, match="unknown keys"):
            run_cost_rules(info, cost, an)

    def test_dense_dispatch_component_fires_on_sec_einsum(self):
        S, E, C = 16, 4, 4

        def dense(x, w):
            mask = jnp.zeros((S, E, C), x.dtype) + w
            return jnp.einsum("sec,sm->ecm", mask, x).sum()

        meta = {"moe_sec": [(S, E, C)],
                "collective_signature": [
                    {"layer": "jaxpr", "kind": "dense_dispatch", "count": 0}]}
        info, cost, an = _cost(jax.grad(dense), jnp.ones((S, 8)), jnp.ones(()),
                               metadata=meta)
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R009"]
        assert fs and "dense_dispatch" in fs[0].message

        def sorted_route(x, w):
            idx = jnp.arange(S) % (E * C)
            return jnp.zeros((E * C, 8), x.dtype).at[idx].add(x * w).sum()

        info, cost, an = _cost(jax.grad(sorted_route), jnp.ones((S, 8)),
                               jnp.ones(()), metadata=meta)
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R009"]


# ---------------------------------------------------------------------------
# R010 activation budget
# ---------------------------------------------------------------------------
class TestR010:
    def _fat(self):
        def f(x):
            a = x * 2  # 1 MiB intermediates
            b = jnp.tanh(a)
            return (a + b).sum()
        return f, jnp.ones(256 * 1024, jnp.float32)

    def test_fires_below_budget_silent_above_and_without(self):
        f, x = self._fat()
        info, cost, an = _cost(f, x, metadata={"activation_budget_bytes": 64 * 1024})
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R010"]
        assert fs and fs[0].severity == ERROR and "budget" in fs[0].message

        info, cost, an = _cost(f, x, metadata={"activation_budget_bytes": 64 << 20})
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R010"]

        info, cost, an = _cost(f, x)  # no budget declared: inventoried, not gated
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R010"]


# ---------------------------------------------------------------------------
# R011 redundant collectives
# ---------------------------------------------------------------------------
class TestR011:
    def test_fires_on_duplicate_identical_psum(self):
        def f(x):
            return jax.lax.psum(x, "x") + jax.lax.psum(x, "x")

        info, cost, an = _cost(_shard_map(f, P("x"), P()), jnp.ones(8))
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R011"]
        assert fs and fs[0].severity == WARN and "duplicate" in fs[0].message

    def test_clean_on_distinct_operands(self):
        def f(x):
            return jax.lax.psum(x, "x") + jax.lax.psum(x * 2, "x")

        info, cost, an = _cost(_shard_map(f, P("x"), P()), jnp.ones(8))
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R011"]

    def test_fires_on_loop_invariant_collective_in_scan(self):
        def f(w, x):
            def body(c, _):
                return c + jax.lax.psum(w, "x"), None  # w: scan const
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out

        info, cost, an = _cost(_shard_map(f, (P("x"), P("x")), P("x")),
                               jnp.ones(8), jnp.ones(8))
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R011"]
        assert fs and "loop-invariant" in fs[0].message

    def test_clean_on_carry_dependent_collective_in_scan(self):
        def f(x):
            def body(c, _):
                # carry-derived; the reduced value re-enters the carry as a
                # varying one (jax 0.9 types carries by their manual axes)
                return jax.lax.pcast(jax.lax.psum(c, "x"), "x", to="varying") * 0.5, None
            out, _ = jax.lax.scan(body, x, None, length=4)
            return out

        info, cost, an = _cost(_shard_map(f, P("x"), P("x")), jnp.ones(8))
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R011"]


# ---------------------------------------------------------------------------
# R012 host-transfer bytes
# ---------------------------------------------------------------------------
class TestR012:
    def _cb(self, n):
        import numpy as np

        def f(x):
            y = jax.pure_callback(lambda v: np.asarray(v),
                                  jax.ShapeDtypeStruct((n,), jnp.float32), x)
            return y.sum()
        return f, jnp.ones(n, jnp.float32)

    def test_fires_over_budget(self):
        f, x = self._cb(512 * 1024)  # 2 MiB crossing the host boundary
        info, cost, an = _cost(f, x)
        fs = [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R012"]
        assert fs and fs[0].severity == WARN and "host boundary" in fs[0].message

    def test_clean_under_budget(self):
        f, x = self._cb(64)
        info, cost, an = _cost(f, x)
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R012"]

    def test_metadata_budget_raises_the_bar(self):
        f, x = self._cb(512 * 1024)
        info, cost, an = _cost(f, x, metadata={"host_transfer_budget_bytes": 8 << 20})
        assert not [fi for fi in run_cost_rules(info, cost, an) if fi.rule == "R012"]


# ---------------------------------------------------------------------------
# R013 cost ratchet
# ---------------------------------------------------------------------------
class TestR013:
    def _cost_for(self, scale):
        def f(x):
            return (jnp.tanh(x * 2) + x).sum()
        info, cost, _ = _cost(f, jnp.ones(scale * 1024, jnp.float32))
        return cost

    def _baseline_for(self, cost, **overrides):
        entry = {"peak_bytes": cost.memory.peak_bytes,
                 "peak_transient_bytes": cost.memory.peak_transient_bytes,
                 "bytes_moved": cost.bytes_moved(),
                 "collective_counts": {l: cost.counts(l) for l in cost.inventory}}
        entry.update(overrides)
        return {"version": 1, "tolerance": 0.05, "programs": {"fixture": entry}}

    def test_within_tolerance_clean(self):
        cost = self._cost_for(256)
        fs = r013_cost_ratchet({"fixture": cost}, self._baseline_for(cost))
        assert not [f for f in fs if f.severity == ERROR]

    def test_growth_fires(self):
        cost = self._cost_for(256)
        shrunk = self._baseline_for(cost,
                                    peak_bytes=cost.memory.peak_bytes // 2)
        fs = r013_cost_ratchet({"fixture": cost}, shrunk)
        errs = [f for f in fs if f.severity == ERROR]
        assert errs and "regression" in errs[0].message

    def test_improvement_reports_info_not_error(self):
        cost = self._cost_for(256)
        fat = self._baseline_for(cost, peak_bytes=cost.memory.peak_bytes * 4)
        fs = r013_cost_ratchet({"fixture": cost}, fat)
        assert not [f for f in fs if f.severity == ERROR]
        assert any(f.severity == INFO and "improvement" in f.message for f in fs)

    def test_new_collective_count_fires(self):
        cost = self._cost_for(256)
        base = self._baseline_for(cost)
        # pretend the baseline had zero reshards on a layer we now have...
        cost.inventory.setdefault("jaxpr", {"counts": {}, "bytes_moved": 0,
                                            "bytes_by_axis": {}})
        cost.inventory["jaxpr"]["counts"]["all_to_all"] = 2
        base["programs"]["fixture"]["collective_counts"]["jaxpr"] = {"all_to_all": 0}
        fs = r013_cost_ratchet({"fixture": cost}, base)
        assert any(f.severity == ERROR and "new collectives" in f.message for f in fs)

    def test_unknown_scenario_is_info(self):
        cost = self._cost_for(256)
        fs = r013_cost_ratchet({"fixture": cost},
                               {"version": 1, "tolerance": 0.05, "programs": {}})
        assert fs and fs[0].severity == INFO and "no cost baseline" in fs[0].message

    def test_load_rejects_unknown_keys(self, tmp_path):
        bad = tmp_path / "cost_baseline.json"
        bad.write_text(json.dumps({"version": 1, "programs": {
            "x": {"peak_bytes": 1, "peek_bytes": 2}}}))
        with pytest.raises(ValueError, match="unknown keys"):
            load_cost_baseline(str(bad))
        bad.write_text(json.dumps({"version": 99, "programs": {}}))
        with pytest.raises(ValueError, match="version"):
            load_cost_baseline(str(bad))


# ---------------------------------------------------------------------------
# inventory parsing units (no tracing)
# ---------------------------------------------------------------------------
class TestInventoryParsing:
    def test_compiled_hlo_parse(self):
        txt = ("  %all-reduce.1 = f32[256]{0} all-reduce(f32[256]{0} %p0), "
               "channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add\n"
               "  %ag = f32[64,32]{1,0} all-gather(f32[8,32]{1,0} %p1), "
               "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n"
               "  %cp = f32[8]{0} collective-permute(f32[8]{0} %p2), "
               "source_target_pairs={{0,1},{1,0}}\n")
        ops = compiled_collectives(txt, {"x": 2, "y": 4})
        kinds = {op.kind: op for op in ops}
        assert kinds["all_reduce"].bytes_in == 1024
        assert kinds["all_reduce"].group_size == 4
        assert kinds["all_reduce"].axes == "y"  # contiguous stride-1 groups
        assert kinds["all_gather"].bytes_out == 64 * 32 * 4
        assert kinds["all_gather"].axes == "full"
        assert kinds["collective_permute"].n_groups == 2
        inv = inventory(ops)
        assert inv["compiled"]["counts"] == {"all_gather": 1, "all_reduce": 1,
                                             "collective_permute": 1}
        assert inv["compiled"]["bytes_moved"] > 0

    def test_replica_group_iota_transpose(self):
        groups, n, g = parse_replica_groups(
            "replica_groups=[4,2]<=[2,2,2]T(1,0,2)")
        assert (n, g) == (4, 2)
        assert sorted(sum((list(grp) for grp in groups), [])) == list(range(8))

    def test_infer_axes_names_the_strided_axis(self):
        # x-axis groups over a {x:2, y:4} row-major mesh: stride 4
        assert infer_axes([(0, 4), (1, 5), (2, 6), (3, 7)], {"x": 2, "y": 4}) == "x"
        assert infer_axes([(0, 1, 2, 3), (4, 5, 6, 7)], {"x": 2, "y": 4}) == "y"
        assert infer_axes([(0, 1, 2, 3, 4, 5, 6, 7)], {"x": 2, "y": 4}) == "full"

    def test_stablehlo_parse(self):
        txt = ('    %2 = "stablehlo.all_reduce"(%1) ({\n'
               "    ^bb0(%a: tensor<f32>, %b: tensor<f32>):\n"
               '      "stablehlo.return"(%a) : (tensor<f32>) -> ()\n'
               "    }) {replica_groups = dense<[[0, 1]]> : tensor<1x2xi64>} : "
               "(tensor<4x8xf32>) -> tensor<4x8xf32>\n")
        ops = stablehlo_collectives(txt)
        assert len(ops) == 1
        assert ops[0].kind == "all_reduce"
        assert ops[0].bytes_in == 4 * 8 * 4
        assert ops[0].group_size == 2

    def test_bytes_moved_model(self):
        ar = CollectiveOp("all_reduce", "compiled", 1000, 1000, 4, 2, "x")
        assert ar.bytes_moved() == int(2 * 1000 * 3 / 4)
        ag = CollectiveOp("all_gather", "compiled", 250, 1000, 4, 2, "x")
        assert ag.bytes_moved() == int(1000 * 3 / 4)
        cp = CollectiveOp("collective_permute", "compiled", 1000, 1000, 2, 8, "x")
        assert cp.bytes_moved() == 1000
