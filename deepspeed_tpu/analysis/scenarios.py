"""The graft-lint scenario matrix: representative traced programs.

Each scenario builder traces one real program shape the repo ships —
model fwd+bwd (gpt2/llama/bert), the MoE sorted route (top1/top2, where
R001's ``[S,E,C]`` ban has teeth), the pipeline scan step, and the
engine's full ``train_batch`` step (the parity path, where donation and
precision are judged). Builders TRACE only — ``jax.make_jaxpr`` /
``.lower()`` — no compilation, no device buffers beyond tiny init
params, so the whole matrix runs on CPU in seconds and can gate CI
between chip windows.

Scenario metadata is where repo knowledge enters the rules: the MoE
scenarios declare their banned ``(S, E, C)`` signature via
``sharded_moe.sec_signature`` (single source with the gating cores);
``train_batch`` declares ``parity``/``expect_donation``; multi-device
scenarios declare ``multi_device``.

What a scenario traces is decided by the model and serving configuration
it builds, and by nothing else. :data:`SCENARIO_CONFIG` holds the values
the declared signatures, budgets and committed baselines are written for; a
test seeds a regression (the dense MoE route, an fp tick where int8 is
banked) by patching an entry, and the declared side stays as committed.
"""

from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.program import ProgramInfo

SCENARIOS: Dict[str, Callable[[], ProgramInfo]] = {}

#: the route the MoE scenarios build their layers and models with, and the
#: weight dtype ``serve_quant_decode_step`` serves
SCENARIO_CONFIG = {"moe_route": "sorted", "serve_weight_dtype": "int8"}


class ScenarioSkipped(Exception):
    """Raised by a builder when its program cannot trace on this runtime
    (e.g. too few devices) — reported, not fatal.
    ``kind`` is a stable machine-readable gap class so reports carry a
    structured ``blocking_gap: {kind, detail}`` instead of a prose string
    (the ROADMAP-5 burn-down reads the kind, not the wording). ``probe``
    carries the 16-device subprocess probe's structured outcome
    (``"ok"``/``"failed"``/``"version"``) when one ran — consumers gate on
    it, never on the detail wording."""

    def __init__(self, detail: str, kind: str = "other", probe: Optional[str] = None):
        super().__init__(detail)
        self.kind = kind
        self.probe = probe


#: the composition scenario's gap burn-down order (ROADMAP item 5): each
#: entry blocks the ones after it, so progress is strictly monotone in
#: this list and the ratchet test (tests/unit/analysis/test_scenarios.py)
#: asserts the current gap's rank never moves backward. ``device_count``
#: is burned down: a <16-device run probes the 16-virtual-device build in
#: a subprocess (:func:`_probe_composition_16dev`) and reports the REAL
#: next gap, so the ambient device count no longer masks it.
COMPOSITION_GAP_ORDER = ("device_count", "partial_manual", "moe_in_pipe", "none")


_COMPOSITION_PROBE_CACHE = None


def _probe_composition_16dev() -> Dict[str, str]:
    """Build the composition scenario in a fresh subprocess with 16 forced
    virtual devices and report its blocking gap. The XLA host-device count
    is fixed at backend init, so an 8-device tier-1 run cannot raise it
    in-process — but the *gap inventory* must not stop at "device_count"
    when the real blocker is one notch further (the ROADMAP-5 burn-down
    metric). Cached per process; any probe failure degrades to the old
    device_count skip, never to a crash."""
    global _COMPOSITION_PROBE_CACHE
    if _COMPOSITION_PROBE_CACHE is not None:
        return _COMPOSITION_PROBE_CACHE
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, repo) if repo not in sys.path else None
    from envutil import cpu_subprocess_env
    child = (
        "import json, sys\n"
        f"sys.path.insert(0, {repo!r})\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from deepspeed_tpu.analysis.scenarios import SCENARIOS, ScenarioSkipped\n"
        "try:\n"
        "    SCENARIOS['composition_3d_ep_zeropp']()\n"
        "    print('GAP ' + json.dumps({'kind': 'none',\n"
        "                               'detail': 'traces clean on 16 devices'}))\n"
        "except ScenarioSkipped as e:\n"
        "    print('GAP ' + json.dumps({'kind': e.kind, 'detail': str(e)}))\n")
    env = cpu_subprocess_env(n_virtual_devices=16)
    # recursion guard: if the forced device count does not take effect in
    # the child (flag ignored, env re-pinned), the child's own builder must
    # fall back to the plain device_count skip instead of forking a
    # grandchild probe
    env["DS_COMPOSITION_PROBE"] = "1"
    gap = None
    try:
        p = subprocess.run([sys.executable, "-c", child], env=env,
                           capture_output=True, text=True, timeout=120, cwd=repo)
        for line in p.stdout.splitlines():
            if line.startswith("GAP "):
                gap = json.loads(line[len("GAP "):])
    except Exception:  # noqa: BLE001 — probe is best-effort
        gap = None
    if gap is None or gap["kind"] == "device_count":
        gap = {"kind": "device_count", "probe": "failed",
               "detail": "needs 16 virtual devices and the 16-device probe "
                         "subprocess failed; run GRAFT_LINT_DEVICES=16"}
    else:
        gap = {"kind": gap["kind"], "probe": "ok",
               "detail": f"[probed on 16 subprocess devices] {gap['detail']}"}
    _COMPOSITION_PROBE_CACHE = gap
    return gap


def composition_gap_rank(kind: str) -> int:
    """Rank of a gap kind in the burn-down order; unknown kinds rank -1
    (strictly behind every known gap — a regression by definition)."""
    try:
        return COMPOSITION_GAP_ORDER.index(kind)
    except ValueError:
        return -1


def composition_blocking_gap() -> Dict[str, str]:
    """Build the ROADMAP-5 composition scenario and report its FIRST
    blocking gap as structured data: ``{"kind", "detail"}`` (plus
    ``"probe"`` when the 16-device subprocess probe produced the answer),
    with kind ``"none"`` once the full pipe x expert x tensor x fsdp +
    qgZ program traces clean."""
    try:
        SCENARIOS["composition_3d_ep_zeropp"]()
    except ScenarioSkipped as e:
        gap = {"kind": e.kind, "detail": str(e)}
        if e.probe is not None:
            gap["probe"] = e.probe
        return gap
    return {"kind": "none", "detail": "composition traces clean"}


def scenario(name: str):
    def wrap(fn):
        SCENARIOS[name] = fn
        return fn

    return wrap


def _model_fwd_bwd(name, model, variables, loss):
    grad = jax.grad(loss)
    return ProgramInfo(name=name, jaxpr=jax.make_jaxpr(grad)(variables),
                       kind="fwd_bwd",
                       # the --cost pass compiles on demand for the
                       # post-SPMD collective inventory + backend
                       # memory/flops cross-check; plain runs never call it
                       lower=lambda: jax.jit(grad).lower(variables))


# ---------------------------------------------------------------------------
@scenario("gpt2_fwd_bwd")
def gpt2_fwd_bwd() -> ProgramInfo:
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config

    cfg = get_gpt2_config("test")
    model = GPT2LMHeadModel(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)

    def loss(v):
        out = model.apply(v, ids)
        logits = out[0] if isinstance(out, tuple) else out
        return logits.astype(jnp.float32).sum()

    return _model_fwd_bwd("gpt2_fwd_bwd", model, variables, loss)


@scenario("llama_fwd_bwd")
def llama_fwd_bwd() -> ProgramInfo:
    from deepspeed_tpu.models import LlamaForCausalLM, get_llama_config

    cfg = get_llama_config("test")
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)

    def loss(v):
        out = model.apply(v, ids)
        logits = out[0] if isinstance(out, tuple) else out
        return logits.astype(jnp.float32).sum()

    return _model_fwd_bwd("llama_fwd_bwd", model, variables, loss)


@scenario("bert_fwd_bwd")
def bert_fwd_bwd() -> ProgramInfo:
    from deepspeed_tpu.models import BertForMaskedLM, get_bert_config

    cfg = get_bert_config("test")
    model = BertForMaskedLM(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)

    def loss(v):
        out = model.apply(v, ids)
        logits = out[0] if isinstance(out, tuple) else out
        return logits.astype(jnp.float32).sum()

    return _model_fwd_bwd("bert_fwd_bwd", model, variables, loss)


# ---------------------------------------------------------------------------
def _moe_program(name: str, k: int) -> ProgramInfo:
    import flax.linen as nn

    from deepspeed_tpu.moe.sharded_moe import MOELayer, sec_signature

    class _Expert(nn.Module):
        @nn.compact
        def __call__(self, x, deterministic=True):
            return nn.Dense(x.shape[-1], use_bias=False)(x)

    B, L, M, E, cf, min_cap = 2, 16, 8, 4, 1.0, 1
    S = B * L  # one group without a topology
    layer = MOELayer(expert=_Expert(), model_dim=M, num_experts=E, k=k,
                     capacity_factor=cf, eval_capacity_factor=cf,
                     min_capacity=min_cap, route=SCENARIO_CONFIG["moe_route"])
    x = jnp.zeros((B, L, M), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)

    def loss(v, xx):
        (out, l_aux, _), _ = layer.apply(v, xx, mutable=["intermediates"])
        return (out ** 2).sum() + l_aux

    grad = jax.grad(loss, argnums=(0, 1))
    jaxpr = jax.make_jaxpr(grad)(variables, x)
    return ProgramInfo(
        name=name, jaxpr=jaxpr, kind="fwd_bwd",
        lower=lambda: jax.jit(grad).lower(variables, x),
        metadata={"moe_sec": [sec_signature(S, E, cf, min_cap, k=k)],
                  # committed for the sorted route: zero dense [S,E,C]
                  # einsums feeding the dispatch/combine endpoints
                  "collective_signature": [
                      {"layer": "jaxpr", "kind": "dense_dispatch", "count": 0,
                       "note": "sorted MoE dispatch is a permutation, "
                               "never an [S,E,C] einsum"}]})


@scenario("moe_top1_route")
def moe_top1_route() -> ProgramInfo:
    return _moe_program("moe_top1_route", k=1)


@scenario("moe_top2_route")
def moe_top2_route() -> ProgramInfo:
    return _moe_program("moe_top2_route", k=2)


# ---------------------------------------------------------------------------
def _engine_program(name: str, engine, example_batch, extra_metadata=None) -> ProgramInfo:
    programs = engine.traced_programs(example_batch)
    step = programs["train_step"]
    metadata = dict(step["metadata"])
    for key, value in (extra_metadata or {}).items():
        if key == "collective_signature":  # extend, don't clobber, the
            metadata.setdefault(key, [])   # engine-declared entries
            metadata[key] = list(metadata[key]) + list(value)
        else:
            metadata[key] = value
    return ProgramInfo(name=name, jaxpr=step["jaxpr"], hlo_text=step["hlo_text"],
                       kind="train_step", metadata=metadata,
                       lower=step.get("lower"))


@scenario("train_batch_parity")
def train_batch_parity() -> ProgramInfo:
    """The engine's fused train step for a tiny GPT-2 — the program the
    CPU parity envelope (ROADMAP item 4) judges. ``parity: True`` arms
    R002's upcast attribution; ``expect_donation`` arms R005."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    set_topology(None)
    try:
        # pinned to the first 8 devices: a GRAFT_LINT_DEVICES=16 run must
        # not shift this program (and its cost baseline entry) onto a
        # different mesh
        topo = (MeshTopology(data=8, devices=jax.devices()[:8])
                if len(jax.devices()) >= 8 else None)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(get_gpt2_config("test")), topology=topo,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 0}})
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        return _engine_program("train_batch_parity", engine, batch,
                               {"parity": True})
    finally:
        set_topology(None)


@scenario("train_batch_telemetry")
def train_batch_telemetry() -> ProgramInfo:
    """The ``train_batch_parity`` engine config with the telemetry block
    ON — the gate that graft-trace instrumentation can never silently
    enter the compiled program. The builder traces the SAME engine twice
    (telemetry-off first, jaxpr-only) and stamps the off-trace's
    recursive eqn count as ``expect_eqn_count``; rule R015 fails on any
    divergence, and R003 must stay clean on the telemetry-on program
    (spans are host-side, so no callback can appear in the jaxpr)."""
    import deepspeed_tpu
    from deepspeed_tpu.analysis.program import ProgramAnalyzer
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    base = {"train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}}
    batch = {"input_ids": np.zeros((8, 32), np.int32)}

    def build(extra):
        topo = (MeshTopology(data=8, devices=jax.devices()[:8])
                if len(jax.devices()) >= 8 else None)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(get_gpt2_config("test")), topology=topo,
            config={**base, **extra})
        return engine

    set_topology(None)
    try:
        off = build({}).traced_programs(batch, lower=False)["train_step"]
        off_count = len(ProgramAnalyzer(ProgramInfo(
            name="telemetry_off", jaxpr=off["jaxpr"], kind="train_step")).records())
        # enabled telemetry, default output_path: tracing never writes, so
        # no run dir is created (the sink is lazy; the header only lands on
        # a real train_batch)
        engine = build({"telemetry": {"enabled": True}})
        return _engine_program("train_batch_telemetry", engine, batch,
                               {"expect_eqn_count": off_count})
    finally:
        set_topology(None)


@scenario("pipe_scan_step")
def pipe_scan_step() -> ProgramInfo:
    """The pipeline engine's scan step on a pipe=2 mesh."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
    from deepspeed_tpu.models import get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    if len(jax.devices()) < 8:
        raise ScenarioSkipped("pipe_scan_step expects >=8 host devices")
    set_topology(None)
    try:
        cfg = get_gpt2_config("test", n_layer=2)
        topo = MeshTopology(pipe=2, data=2, fsdp=2, devices=jax.devices()[:8])
        pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=pipe, topology=topo,
            config={"train_batch_size": 16, "gradient_accumulation_steps": 4,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
        batch = {"input_ids": np.zeros((16, 32), np.int32)}
        return _engine_program("pipe_scan_step", engine, batch)
    except NotImplementedError as e:  # partial-manual shard_map gap
        raise ScenarioSkipped(f"shard_map unsupported here: {e}") from e
    finally:
        set_topology(None)


# ---------------------------------------------------------------------------
def _zero_step(name: str, stage: int) -> ProgramInfo:
    """A ZeRO-``stage`` step on a data=2 x fsdp=4 mesh: the program whose
    comms schedule the blueprint quantifies (state sharded over fsdp,
    grads averaged over data). The engine stamps the stage's collective
    signature from ``DeepSpeedZeroConfig.cost_metadata`` — all-gathers
    must exist (sharding is real), the reduce-scatter entry is TPU-judged
    (XLA:CPU decomposes RS into AR+slice; inventoried as unchecked)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    if len(jax.devices()) < 8:
        raise ScenarioSkipped(f"{name} expects >=8 host devices")
    set_topology(None)
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(get_gpt2_config("test")),
            topology=MeshTopology(data=2, fsdp=4, devices=jax.devices()[:8]),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": stage}})
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        return _engine_program(name, engine, batch)
    finally:
        set_topology(None)


@scenario("zero2_train_step")
def zero2_train_step() -> ProgramInfo:
    return _zero_step("zero2_train_step", stage=2)


@scenario("zero3_train_step")
def zero3_train_step() -> ProgramInfo:
    return _zero_step("zero3_train_step", stage=3)


@scenario("moe_ep_step")
def moe_ep_step() -> ProgramInfo:
    """The engine's MoE step on an expert=4 x data=2 mesh — where the
    sorted route's "exactly two capacity-bounded all-to-alls per layer"
    claim has wire bytes behind it. Each MoE layer applies the
    G-sharded->E-sharded constraint *pair* on the dispatch buffer and its
    mirror on the combine side (2 logical a2a per direction); the cost
    pass counts those chained-constraint reshards at the jaxpr layer,
    backend-independently."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    if len(jax.devices()) < 8:
        raise ScenarioSkipped("moe_ep_step expects >=8 host devices")
    set_topology(None)
    try:
        cfg = get_gpt2_config("test", moe_num_experts=4, moe_layer_freq=2, moe_k=1,
                              moe_route=SCENARIO_CONFIG["moe_route"])
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(cfg),
            topology=MeshTopology(expert=4, data=2, devices=jax.devices()[:8]),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 0}})
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        return _engine_program("moe_ep_step", engine, batch, {
            "collective_signature": [
                # committed for the sorted route, whatever route the engine
                # was handed (the engine declares the same of a sorted model)
                {"layer": "jaxpr", "kind": "dense_dispatch", "count": 0,
                 "note": "sorted MoE dispatch is a permutation, never an "
                         "[S,E,C] einsum"},
                {"layer": "jaxpr", "kind": "resharding", "min_count": 4,
                 "note": "2 capacity-bounded a2a reshards per MoE layer "
                         "per direction (dispatch + combine, fwd + bwd)"},
                # ...and the partitioner honors them: exactly 2 a2a per
                # layer per direction in the compiled program (1 MoE
                # layer here -> 4 total). More would mean GSPMD chose a
                # gather-everywhere strategy; fewer, a silently-local
                # (replicated) expert layout.
                {"layer": "compiled", "kind": "all_to_all", "count": 4,
                 "note": "exactly 2 all-to-alls per MoE layer per direction"}]})
    finally:
        set_topology(None)


#: the committed 1F1B activation budget (MiB) for the pipe=2 scenario
#: mesh below. Formula (README "Pipeline parallelism"): stash ring
#: ``2(S-1)`` boundary slots + 2 in transit (S=2: 4 x 16 KiB) + the fp32
#: grad accumulators (~0.6 MiB params) + one tick's recompute transient
#: (block internals + [mb, seq, vocab] epilogue logits) + the optimizer
#: update's own temporaries — measured 1.90 MiB static transient on the
#: pinned container, committed at 2.0 MiB (~5% headroom). Strictly below
#: the chunked schedule's 2.25 MiB measured transient (and its prior
#: 4 MiB commit), so the SAME budget fails the chunked schedule — the
#: ratchet with teeth (test_cost_gate).
PIPE_1F1B_BUDGET_MB = 2.0


def _pipe_engine_program(name: str, pipeline_cfg: dict) -> ProgramInfo:
    """Shared pipe=2-only builder."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_gpt2_config
    from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    if len(jax.devices()) < 2:
        raise ScenarioSkipped(f"{name} needs >=2 devices")
    set_topology(None)
    try:
        cfg = get_gpt2_config("test", n_layer=2)
        topo = MeshTopology(pipe=2, data=1, devices=jax.devices()[:2])
        pipe = PipelineModule(layers=gpt2_pipe_layers(cfg), topology=topo)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=pipe, topology=topo,
            config={"train_batch_size": 8, "gradient_accumulation_steps": 4,
                    "pipeline": pipeline_cfg,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        return _engine_program(name, engine, batch)
    except NotImplementedError as e:  # partial-manual shard_map gap
        raise ScenarioSkipped(f"shard_map unsupported here: {e}") from e
    finally:
        set_topology(None)


@scenario("pipe_chunked_step")
def pipe_chunked_step() -> ProgramInfo:
    """The chunked-wave pipeline schedule — kept as the A/B reference
    against ``pipe_1f1b_step`` (same mesh, same model, same microbatch
    count). Its committed budget is its own measured static transient
    (2.25 MiB) + headroom — tightened from the pre-1F1B 4 MiB commit;
    ``DS_PIPE_ACT_BUDGET_MB`` below the estimate (e.g. the 1F1B bound)
    is the seeded R010 regression proving the chunked schedule cannot
    pass the 1F1B budget."""
    return _pipe_engine_program(
        "pipe_chunked_step",
        # measured 2.25 MiB static transient on the pinned container
        {"chunk_microbatches": 2, "activation_budget_mb": 2.5})


@scenario("pipe_1f1b_step")
def pipe_1f1b_step() -> ProgramInfo:
    """The 1F1B schedule (the default) under its committed activation
    bound (:data:`PIPE_1F1B_BUDGET_MB` — formula in the constant's
    docstring). R010 gates the manual-vjp program's static transient
    against it; R009 pins the 4-``collective_permute`` signature (2 per
    tick boundary across the 3 phase bodies). Any schedule regression —
    an extra stash slot, autodiff residuals sneaking back in, a third
    boundary buffer — fails lint on CPU before a chip window pays for
    it."""
    return _pipe_engine_program(
        "pipe_1f1b_step",
        {"schedule": "1f1b", "activation_budget_mb": PIPE_1F1B_BUDGET_MB})


#: the committed activation budget (MiB) for the graft-serve decode tick
#: below (16 slots x 512 positions, tp=2, tiny GPT-2). Measured static
#: transient on the pinned container: 10.02 MiB (the per-slot KV write is
#: O(slots) bytes; what is live is the tick's new pools and, since a decode
#: tick reads its pool where it lies (ISSUE 49), one step of the walk: a
#: block of the keys and of the values, here the whole 512 positions, which
#: took it from 8.41); committed at 10.8 MiB (~8% headroom). A write that
#: rebuilds a pool fails R010 under it: the ``dense`` masked write that
#: ISSUE 27 removed measured 2.1 MiB over the tick of its day. Off the TPU
#: this program traces the scatter and XLA's loop, not the two kernels the
#: chip runs (the write's, ``ops/pallas/pool_write.py`` under ``models/
#: common.py`` ``slot_pool_append``, and the read's under
#: ``cached_attention``): those ones' temporaries are held by the compile
#: tests alone (``tests/unit/ops/test_tpu_compile.py``).
SERVE_DECODE_BUDGET_MB = 10.8


@scenario("serve_decode_step")
def serve_decode_step() -> ProgramInfo:
    """The graft-serve fixed-shape decode tick (inference/serving): one
    token per slot against the per-slot ragged cache, on a tensor=2
    serving mesh so the program carries real post-SPMD collectives. The
    traced program IS the served one — same ``make_apply_fn`` +
    ``build_decode_step`` the scheduler jits — so R009 pins the tp
    collective signature, R010 gates the per-tick transient against
    :data:`SERVE_DECODE_BUDGET_MB`, and R013 ratchets both against the
    committed cost baseline."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving import make_slot_cache
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          make_apply_fn)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    if len(jax.devices()) < 2:
        raise ScenarioSkipped("serve_decode_step needs >=2 devices for the "
                              "tensor=2 serving mesh")
    set_topology(None)
    try:
        slots = 16
        cfg = get_gpt2_config("test", n_layer=2, n_positions=512)
        topo = MeshTopology(tensor=2, data=1, fsdp=1, devices=jax.devices()[:2])
        engine = InferenceEngine(GPT2LMHeadModel(cfg),
                                 DeepSpeedInferenceConfig(), topology=topo)
        cache = make_slot_cache(engine.module, slots)
        decode = build_decode_step(make_apply_fn(engine.module, engine._mparams),
                                   do_sample=False, temperature=1.0, top_k=0,
                                   top_p=1.0)
        write_pos = jnp.zeros((slots,), jnp.int32)
        jaxpr = jax.make_jaxpr(decode)(engine.params, cache, write_pos)
        return ProgramInfo(
            name="serve_decode_step", jaxpr=jaxpr, kind="serve_decode",
            lower=lambda: jax.jit(decode).lower(engine.params, cache, write_pos),
            metadata={
                "serve_slots": slots,
                "activation_budget_bytes": int(SERVE_DECODE_BUDGET_MB * 2**20),
                "collective_signature": [
                    # tp=2 row-parallel projections: attention out-proj +
                    # MLP out-proj per block, plus the tied LM head —
                    # 2*n_layer + 1 all-reduces per decode tick
                    {"layer": "compiled", "kind": "all_reduce", "count": 5,
                     "note": "2 all-reduces per block + 1 for the tied "
                             "LM head on the tp=2 serving mesh"},
                    {"layer": "compiled", "kind": "all_gather", "max_count": 2,
                     "note": "at most the two embedding-table gathers — "
                             "more would mean GSPMD re-gathers the KV pool "
                             "per tick"}]})
    finally:
        set_topology(None)


#: committed activation budget (MiB) for the QUANTIZED graft-serve decode
#: tick (8 slots x 256 positions, n_embd=128 bf16 compute, tp=2). The
#: int8-weight program's transient is dominated by the int8 KV pools and
#: one step of the walk over them (no pool is dequantised whole since ISSUE
#: 49, which took it from 2.63); measured static transient on the pinned
#: container: 1.64 MiB, committed at 1.8 MiB (~10% headroom).
#: Served fp, the program is back to full-width kernels: peak bytes jump
#: past the R013 tolerance, the seeded regression.
SERVE_QUANT_DECODE_BUDGET_MB = 1.8


@scenario("serve_quant_decode_step")
def serve_quant_decode_step() -> ProgramInfo:
    """graft-quant-serve's decode tick: the SAME ``make_apply_fn`` +
    ``build_decode_step`` program as :func:`serve_decode_step`, but served
    the way the quantized scheduler builds it — int8 per-group weight
    codes with dequant fused into the GEMM (``_quant_view``), int8 KV
    pools (``make_slot_cache(kv_quant=True)``), bf16 compute. A weight-
    heavier config (n_embd=128) than the fp reference makes the weight
    path the dominant term, so the A/B against ``serve_decode_step``
    prices exactly what quantization buys per tick.

    The served dtype is :data:`SCENARIO_CONFIG`'s, handed to the builder
    as the scheduler hands its ``ServingConfig.weight_dtype``; the banked
    cost is int8's, so anything else fails R013."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving import make_slot_cache
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          make_apply_fn)
    from deepspeed_tpu.inference.serving.scheduler import _quant_view
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    if len(jax.devices()) < 2:
        raise ScenarioSkipped("serve_quant_decode_step needs >=2 devices for "
                              "the tensor=2 serving mesh")
    set_topology(None)
    try:
        slots = 8
        cfg = get_gpt2_config("test", n_layer=2, n_embd=128, n_head=8,
                              n_positions=256, dtype=jnp.bfloat16)
        topo = MeshTopology(tensor=2, data=1, fsdp=1, devices=jax.devices()[:2])
        engine = InferenceEngine(GPT2LMHeadModel(cfg),
                                 DeepSpeedInferenceConfig(), topology=topo)
        wd = SCENARIO_CONFIG["serve_weight_dtype"]
        module, params = engine.module, engine.params
        if wd != "fp":
            module, params = _quant_view(module, params, wd, 64)
        cache = make_slot_cache(module, slots, kv_quant=True)
        decode = build_decode_step(make_apply_fn(module, engine._mparams),
                                   do_sample=False, temperature=1.0, top_k=0,
                                   top_p=1.0)
        write_pos = jnp.zeros((slots,), jnp.int32)
        jaxpr = jax.make_jaxpr(decode)(params, cache, write_pos)
        return ProgramInfo(
            name="serve_quant_decode_step", jaxpr=jaxpr, kind="serve_decode",
            lower=lambda: jax.jit(decode).lower(params, cache, write_pos),
            metadata={
                "serve_slots": slots,
                "serve_weight_dtype": "int8",  # what the baseline banks
                "serve_kv_quant": True,
                "activation_budget_bytes": int(SERVE_QUANT_DECODE_BUDGET_MB * 2**20),
                "collective_signature": [
                    # same tp=2 skeleton as serve_decode_step: 2 row-parallel
                    # all-reduces per block + 1 for the tied LM head — but in
                    # bf16, so the compiled wire bytes land strictly below
                    # the fp tick's (the headline A/B the baseline pins)
                    {"layer": "compiled", "kind": "all_reduce", "count": 5,
                     "note": "2 all-reduces per block + 1 for the tied "
                             "LM head, bf16 activations on the tp=2 mesh"},
                    {"layer": "compiled", "kind": "all_gather", "max_count": 2,
                     "note": "at most the two embedding-table gathers — "
                             "more would mean GSPMD re-gathers the int8 "
                             "codes or the KV pool per tick"}]})
    finally:
        set_topology(None)


@scenario("serve_prefix_decode_step")
def serve_prefix_decode_step() -> ProgramInfo:
    """graft-prefix-cache's decode tick: the SAME program as
    :func:`serve_decode_step`, as a scheduler with ``prefix_cache="on"``
    builds it. The cache is a HOST-SIDE allocator
    change — ref-counted content-addressed blocks, restore/publish
    through host row copies — so the compiled decode program must be
    BYTE-IDENTICAL to the uncached one: same budget, same tp=2
    collective signature (R009), same banked cost (R013). Any delta here
    means prefix caching leaked into the traced program, which would put
    the cache on the latency path it exists to shorten."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving import make_slot_cache
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          make_apply_fn)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology

    if len(jax.devices()) < 2:
        raise ScenarioSkipped("serve_prefix_decode_step needs >=2 devices "
                              "for the tensor=2 serving mesh")
    set_topology(None)
    try:
        slots = 16
        cfg = get_gpt2_config("test", n_layer=2, n_positions=512)
        topo = MeshTopology(tensor=2, data=1, fsdp=1, devices=jax.devices()[:2])
        engine = InferenceEngine(GPT2LMHeadModel(cfg),
                                 DeepSpeedInferenceConfig(), topology=topo)
        cache = make_slot_cache(engine.module, slots)
        decode = build_decode_step(make_apply_fn(engine.module, engine._mparams),
                                   do_sample=False, temperature=1.0, top_k=0,
                                   top_p=1.0)
        write_pos = jnp.zeros((slots,), jnp.int32)
        jaxpr = jax.make_jaxpr(decode)(engine.params, cache, write_pos)
        return ProgramInfo(
            name="serve_prefix_decode_step", jaxpr=jaxpr, kind="serve_decode",
            lower=lambda: jax.jit(decode).lower(engine.params, cache, write_pos),
            metadata={
                "serve_slots": slots,
                "serve_prefix_cache": "on",
                # same budget as serve_decode_step ON PURPOSE: prefix
                # caching must not move the decode tick's transient a byte
                "activation_budget_bytes": int(SERVE_DECODE_BUDGET_MB * 2**20),
                "collective_signature": [
                    {"layer": "compiled", "kind": "all_reduce", "count": 5,
                     "note": "2 all-reduces per block + 1 for the tied "
                             "LM head on the tp=2 serving mesh — identical "
                             "to serve_decode_step (host-side cache only)"},
                    {"layer": "compiled", "kind": "all_gather", "max_count": 2,
                     "note": "at most the two embedding-table gathers — "
                             "more would mean prefix caching leaked into "
                             "the compiled program"}]})
    finally:
        set_topology(None)


#: committed activation budget (MiB) for the graft-rlhf rollout decode
#: tick below (8 slots x 128 positions, tiny GPT-2 served at
#: tensor=2/data=4 from a ZeRO-3 hybrid engine's inference view).
#: Measured static transient on the pinned container: 1.05 MiB;
#: committed at 1.25 MiB (~19% headroom).
RLHF_ROLLOUT_BUDGET_MB = 1.25


@scenario("rlhf_rollout_step")
def rlhf_rollout_step() -> ProgramInfo:
    """graft-rlhf's rollout decode tick: the continuous-scheduler decode
    program exactly as the RLHF loop serves it — built over a
    ``DeepSpeedHybridEngine``'s inference view (ZeRO-3 training params on
    a data=2/fsdp=4 mesh, relayouted into the tp=2 serving placement
    through the PR-15 planner), one token per slot against the per-slot
    ragged cache. R009 pins the tp collective signature of the tick the
    learner overlaps with, R010 gates its per-tick transient against
    :data:`RLHF_ROLLOUT_BUDGET_MB`, and R013 ratchets both against the
    committed baseline. The planner's priced summary of the
    train-mesh→serve-mesh weight sync (the per-``sync_every`` cost the
    rollout loop stamps as evidence) rides the metadata next to the
    compiled inventory — the reshard_resume pattern."""
    import deepspeed_tpu
    import numpy as np
    from deepspeed_tpu.inference.serving import make_slot_cache
    from deepspeed_tpu.inference.serving.programs import (build_decode_step,
                                                          make_apply_fn)
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.rlhf.sync import plan_params_sync

    if len(jax.devices()) < 8:
        raise ScenarioSkipped("rlhf_rollout_step expects >=8 host devices "
                              "(data=2/fsdp=4 train mesh, tp=2 serve mesh)")
    set_topology(None)
    try:
        slots = 8
        seq = 32
        cfg = get_gpt2_config("test", n_layer=2, n_positions=128)
        ds = {"train_batch_size": 8,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 3,
                                    "stage3_param_persistence_threshold": 0},
              "hybrid_engine": {"enabled": True, "max_out_tokens": 128,
                                "inference_tp_size": 2},
              "steps_per_print": 10**9}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(cfg), config=ds,
            loss_fn=lambda logits, batch: logits.mean(),
            topology=MeshTopology(data=2, fsdp=4))
        engine.initialize_state({"input_ids": np.zeros((8, seq), np.int32)})
        engine._infer_engine = engine._build_inference_engine()
        infer = engine._infer_engine
        sync_plan = plan_params_sync(engine._inference_params_value(),
                                     engine.mesh, infer.params, infer.mesh)
        sync_plan.pop("plan_s", None)  # static evidence only, no wall time
        set_topology(infer.topology)
        cache = make_slot_cache(infer.module, slots)
        decode = build_decode_step(make_apply_fn(infer.module, infer._mparams),
                                   do_sample=False, temperature=1.0, top_k=0,
                                   top_p=1.0)
        write_pos = jnp.zeros((slots,), jnp.int32)
        jaxpr = jax.make_jaxpr(decode)(infer.params, cache, write_pos)
        return ProgramInfo(
            name="rlhf_rollout_step", jaxpr=jaxpr, kind="serve_decode",
            lower=lambda: jax.jit(decode).lower(infer.params, cache, write_pos),
            metadata={
                "serve_slots": slots,
                "rlhf_weight_sync_plan": sync_plan,
                "activation_budget_bytes": int(RLHF_ROLLOUT_BUDGET_MB * 2**20),
                "collective_signature": [
                    # the hybrid engine builds its serve mesh over ALL
                    # devices (tensor=2, data=4 on the 8-device rig), so
                    # the compiled tick carries the serve_decode_step tp
                    # skeleton PLUS small data-axis redistributions of
                    # the 8-slot batch (measured: 3072 bytes/tick on the
                    # g4 axis — the slot ids land data-sharded, GSPMD
                    # regathers them for the replicated cache update)
                    {"layer": "compiled", "kind": "all_reduce", "count": 5,
                     "note": "2 all-reduces per block + 1 for the tied "
                             "LM head on the hybrid engine's tp=2 serve "
                             "mesh"},
                    {"layer": "compiled", "kind": "all_gather",
                     "max_count": 14,
                     "note": "2 embedding-table gathers + the data-axis "
                             "slot-batch regathers of the tensor=2/data=4 "
                             "hybrid serve mesh; more would mean the "
                             "learner's ZeRO layout leaked through the "
                             "weight sync into the compiled rollout tick"},
                    {"layer": "compiled", "kind": "collective_permute",
                     "max_count": 4,
                     "note": "slot-batch redistribution between the "
                             "data-sharded token ids and the replicated "
                             "KV cache — O(slots) bytes, not O(params)"}]})
    finally:
        set_topology(None)


@scenario("reshard_resume")
def reshard_resume() -> ProgramInfo:
    """graft-elastic's restore-path data movement, as a static program the
    cost rules can gate. A world-size change reshards every leaf: the
    traced program maps the gpt2 ``test`` ZeRO param tree from its saved
    4-way ``fsdp`` chunking to (a) the scale-up 8-way layout and (b) the
    scale-down 2-way layout on the same 8-device fleet — the two
    directions ``resume_elastic`` executes (scale-up = slice+permute,
    scale-down = gather). R009 pins the compiled collective signature;
    R013 ratchets the restore path's gather bytes (``bytes_moved``)
    against the committed baseline. The host-side planner prices the same
    transition (``runtime/elastic/planner.py``) and its summary rides the
    metadata as evidence next to the compiled inventory."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel, get_gpt2_config
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.elastic.layout import spec_entries
    from deepspeed_tpu.runtime.elastic.planner import plan_reshard
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 8:
        raise ScenarioSkipped("reshard_resume expects >=8 host devices")
    set_topology(None)
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2LMHeadModel(get_gpt2_config("test")),
            topology=MeshTopology(data=2, fsdp=4, devices=jax.devices()[:8]),
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    # stage 3 with persistence threshold 0: every param is
                    # fsdp-sharded — the layout a world-size change actually
                    # has to re-chunk (the test model's params are all tiny)
                    "zero_optimization": {"stage": 3,
                                          "stage3_param_persistence_threshold": 0}})
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        abstract = engine.abstract_state(batch)
        mesh = engine.mesh
        src_shardings = engine.state_shardings.params
        aparams = abstract.params

        def remap(spec, shape, repl):
            """The target layout's spec: every ``fsdp``-chunked dim re-chunks
            over ``repl`` ("data","fsdp") = 8-way scale-up or ("data",) =
            2-way scale-down, where divisibility allows."""
            width = 1
            for a in repl:
                width *= mesh.shape[a]
            entries = []
            for entry, n in zip(spec_entries(spec, len(shape)), shape):
                if entry == ["fsdp"] and n % width == 0:
                    entry = list(repl)
                if entry is None:
                    entries.append(None)
                else:
                    entries.append(tuple(entry) if len(entry) > 1 else entry[0])
            return P(*entries)

        def retarget(repl):
            return jax.tree.map(
                lambda s, a: NamedSharding(mesh, remap(s.spec, a.shape, repl)),
                src_shardings, aparams)

        up, down = retarget(("data", "fsdp")), retarget(("data",))

        def reshard(params):
            return params, params  # two restore directions, one program

        jaxpr = jax.make_jaxpr(reshard)(aparams)
        # host-planner evidence: the same transition priced without devices
        # (world 4 -> 8 and 4 -> 2 over a pure fsdp axis)
        def layout_for(axes):
            return {"version": 1, "world_size": axes["fsdp"], "mesh_axes": axes,
                    "leaves": {str(i): {"shape": list(a.shape), "dtype": str(a.dtype),
                                        "spec": [["fsdp"] if a.shape and a.shape[0] % 8 == 0
                                                 else None] + [None] * (len(a.shape) - 1)}
                               for i, a in enumerate(jax.tree.leaves(aparams))}}
        plan_up = plan_reshard(layout_for({"fsdp": 4}), layout_for({"fsdp": 8}))
        plan_down = plan_reshard(layout_for({"fsdp": 4}), layout_for({"fsdp": 2}))
        return ProgramInfo(
            name="reshard_resume", jaxpr=jaxpr, kind="reshard",
            lower=lambda: jax.jit(reshard, in_shardings=(src_shardings,),
                                  out_shardings=(up, down)).lower(aparams),
            metadata={
                "multi_device": True,
                "mesh_axes": {str(a): int(s) for a, s in mesh.shape.items()},
                "reshard_plan": {"scale_up": plan_up.summary(),
                                 "scale_down": plan_down.summary()},
                "collective_signature": [
                    # scale-down re-chunks 4-way -> 2-way: each wider target
                    # shard gathers its halves — the restore path's gather leg
                    {"layer": "compiled", "kind": "all_gather", "min_count": 1,
                     "note": "scale-down leg gathers saved shards into the "
                             "wider target chunks"},
                    # a reshard never REDUCES: any all-reduce would mean the
                    # identity program is summing state
                    {"layer": "compiled", "kind": "all_reduce", "count": 0,
                     "note": "resharding moves bytes, never sums them"}]})
    finally:
        set_topology(None)


@scenario("composition_3d_ep_zeropp")
def composition_3d_ep_zeropp() -> ProgramInfo:
    """ROADMAP item 5's never-executed full composition: pipe x expert x
    tensor x fsdp (all >=2, 16 virtual devices) with qgZ quantized
    gradients. This builder ATTEMPTS the real construction so the first
    blocking gap on any runtime is *inventoried* in the report's
    skipped-scenarios section instead of staying folklore. The old first
    link — 8 forced host devices — is burned down: a <16-device run
    probes the 16-device build in a subprocess and reports the gap
    *behind* it: MoE blocks unsupported inside the pipelined GPT-2."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_gpt2_config
    from deepspeed_tpu.models.gpt2 import gpt2_pipe_layers
    from deepspeed_tpu.parallel.topology import MeshTopology, set_topology
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    if len(jax.devices()) < 16:
        import os
        if os.environ.get("DS_COMPOSITION_PROBE"):
            # already inside a probe child whose forced device count did
            # not take effect: report plainly, never fork a grandchild
            raise ScenarioSkipped(
                f"needs 16 virtual devices (probe child has "
                f"{len(jax.devices())})", kind="device_count")
        # device_count burn-down: the host-device count cannot change after
        # backend init, but the blocking-gap INVENTORY must not stop here —
        # probe the 16-device build out of process and report the real gap.
        # In-process tracing still needs GRAFT_LINT_DEVICES=16.
        gap = _probe_composition_16dev()
        raise ScenarioSkipped(gap["detail"], kind=gap["kind"], probe=gap.get("probe"))
    set_topology(None)
    try:
        cfg = get_gpt2_config("test", n_layer=4, moe_num_experts=2,
                              moe_layer_freq=2, moe_k=1)
        topo = MeshTopology(pipe=2, expert=2, tensor=2, fsdp=2, data=1,
                            devices=jax.devices()[:16])
        try:
            layers = gpt2_pipe_layers(cfg)
        except ValueError as e:  # MoE-in-pipe unsupported (aux-loss drop)
            raise ScenarioSkipped(f"MoE blocks in the pipelined GPT-2: {e}",
                                  kind="moe_in_pipe") from e
        pipe = PipelineModule(layers=layers, topology=topo)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=pipe, topology=topo,
            config={"train_batch_size": 8, "gradient_accumulation_steps": 4,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 3,
                                          "zero_quantized_gradients": True}})
        batch = {"input_ids": np.zeros((8, 32), np.int32)}
        return _engine_program("composition_3d_ep_zeropp", engine, batch)
    except NotImplementedError as e:
        raise ScenarioSkipped(f"composition untraceable here: {e}",
                              kind="partial_manual") from e
    finally:
        set_topology(None)


# ---------------------------------------------------------------------------
def build(names: Optional[List[str]] = None):
    """Build the matrix. Returns ``(programs, skipped)`` where ``skipped``
    maps each scenario this runtime cannot trace to its structured
    blocking gap ``{"kind", "detail"}`` (``ScenarioSkipped.kind``) — the
    shape the report commits as ``skipped_scenarios`` so gap burn-down is
    a metric, not a prose diff."""
    unknown = [n for n in names or [] if n not in SCENARIOS]
    if unknown:
        raise ValueError(f"unknown scenario(s) {unknown}; valid: {sorted(SCENARIOS)}")
    programs, skipped = [], {}
    for name in names or list(SCENARIOS):
        try:
            info = SCENARIOS[name]()
            if len(jax.devices()) > 1 and "multi_device" not in info.metadata:
                info.metadata["multi_device"] = info.kind == "train_step"
            programs.append(info)
        except ScenarioSkipped as e:
            skipped[name] = {"kind": e.kind, "detail": str(e)}
    return programs, skipped
