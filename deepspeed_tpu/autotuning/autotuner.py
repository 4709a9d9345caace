"""TPU-native autotuner (reference ``autotuning/autotuner.py``).

The reference tunes by launching one training *process* per candidate config
and scraping metrics from logs (``Autotuner.tune`` autotuner.py:404,
``run_ds_config`` :1052, resource manager ``scheduler.py``). On TPU the
compiler is the experiment harness: every candidate is AOT-compiled in
process (``DeepSpeedEngine.lower_train_step``) and XLA reports exactly how
much HBM the step needs (``memory_analysis()``) and how many flops/bytes it
moves (``cost_analysis()``). OOM candidates are pruned without ever
allocating a buffer; only the top-k survivors get real timed steps.

Search space (reference ``DEFAULT_TUNING_SPACE_ZERO_*`` constants.py:150):
ZeRO stage x micro-batch-size ladder. The micro-batch ladder per stage
doubles until compilation reports the step no longer fits
(reference ``get_min_max_micro_batch_size`` autotuner.py:849).
"""

import json
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from deepspeed_tpu.autotuning.config import (AUTOTUNING, AUTOTUNING_METRIC_FLOPS,
                                             AUTOTUNING_METRIC_LATENCY,
                                             DeepSpeedAutotuningConfig,
                                             get_autotuning_config)
from deepspeed_tpu.utils.logging import log_dist, logger

# per-chip peaks for the roofline cost model, bf16 matmul FLOP/s and HBM B/s
# (published per-chip figures), keyed by device_kind prefix
_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5": (459e12, 1228e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v3": (123e12, 900e9),
}
# the test backend: only the relative ranking of candidates matters there
_CPU_PEAKS = (1e12, 100e9)


def _device_peaks():
    """(FLOP/s, B/s) of the first device. A device that is not in the
    table is an error, not a default: a made-up peak would rank
    candidates by a roofline the chip does not have."""
    import jax
    device = jax.devices()[0]
    if device.platform == "cpu":
        return _CPU_PEAKS
    for prefix, peaks in _PEAKS.items():
        if device.device_kind.startswith(prefix):
            return peaks
    raise ValueError(f"autotuner has no peak FLOP/s and bandwidth for device_kind "
                     f"{device.device_kind!r}; add its published figures to _PEAKS")


def _device_mem_budget() -> int:
    """Bytes one device may hold: what the backend reports, or on the CPU
    backend (which reports nothing) the host's physical memory."""
    import jax
    device = jax.devices()[0]
    stats = device.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if device.platform == "cpu":
        return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    raise ValueError(f"{device.device_kind!r} reports no memory limit; set "
                     f"autotuning.mem_budget_bytes")


@dataclass
class Experiment:
    """One tuning candidate (reference exp dicts, ``autotuner.py:304``)."""
    name: str
    zero_stage: int
    micro_batch_size: int
    config: Dict[str, Any]
    tensor: int = 1
    sequence: int = 1
    offload: str = "none"          # none | optimizer | infinity
    status: str = "pending"        # pruned | compiled | measured | failed
    mem_bytes: Optional[int] = None
    arg_bytes: Optional[int] = None  # device-resident inputs (state) alone
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    est_step_s: Optional[float] = None
    measured_step_s: Optional[float] = None
    metric_val: Optional[float] = None
    error: str = ""

    def record(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in
                ("name", "zero_stage", "micro_batch_size", "tensor", "sequence", "offload",
                 "status", "mem_bytes", "arg_bytes", "flops",
                 "bytes_accessed", "est_step_s", "measured_step_s", "metric_val", "error")} | {
                    "ds_config": self.config}


class Autotuner:
    """Discover the fastest runnable (ZeRO stage, micro batch size) for a
    model on the current mesh (reference ``Autotuner`` autotuner.py:42).

    ``model_factory(overrides: dict) -> module`` lets candidates rebuild the
    model (e.g. to flip ``remat``); plain ``model=`` tunes engine knobs only.
    """

    def __init__(self, model=None, config: Optional[Dict[str, Any]] = None,
                 example_batch=None, topology=None,
                 model_factory: Optional[Callable[[Dict[str, Any]], Any]] = None):
        assert (model is None) != (model_factory is None), \
            "pass exactly one of model= or model_factory="
        assert config is not None and example_batch is not None
        self.user_config = dict(config)
        self.autotuning_config: DeepSpeedAutotuningConfig = get_autotuning_config(self.user_config)
        self.model_factory = model_factory or (lambda overrides: model)
        self.example_batch = example_batch
        self.topology = topology
        self.records: List[Experiment] = []
        self.best: Optional[Experiment] = None
        self.model_info: Dict[str, Any] = {}
        self.start_time: Optional[float] = None

    # ------------------------------------------------------------------
    def metric(self) -> str:
        return self.autotuning_config.metric

    def fast_enabled(self) -> bool:
        return self.autotuning_config.fast

    def mp_size(self) -> int:
        return self.autotuning_config.mp_size

    def max_train_micro_batch_size_per_gpu(self) -> int:
        return self.autotuning_config.max_train_micro_batch_size_per_gpu

    def min_train_micro_batch_size_per_gpu(self) -> int:
        return self.autotuning_config.min_train_micro_batch_size_per_gpu

    def get_model_num_params(self):
        return self.model_info.get("num_params")

    # ------------------------------------------------------------------
    def model_info_profile_run(self) -> Dict[str, Any]:
        """Parameter count/bytes via ``jax.eval_shape`` — no process launch,
        no allocation (reference launches a whole profile experiment,
        ``model_info_profile_run`` autotuner.py:663)."""
        import jax

        engine = self._build_engine({})
        abstract = engine.abstract_state(self.example_batch)
        leaves = jax.tree.leaves(abstract.params)
        num_params = sum(int(np.prod(l.shape)) for l in leaves)
        param_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
        self.model_info = {"num_params": num_params, "param_bytes": param_bytes}
        log_dist(f"autotuning: model has {num_params / 1e6:.1f}M parameters")
        return self.model_info

    # ------------------------------------------------------------------
    def _dp_world(self, tensor: int = 1, sequence: int = 1) -> int:
        if (tensor, sequence) == (1, 1):
            if self.topology is not None:
                return (self.topology.mesh.shape["data"] * self.topology.mesh.shape["fsdp"]
                        * self.topology.mesh.shape["expert"])
            import jax
            return max(len(jax.devices()) // self.mp_size(), 1)
        # tuned mesh: candidates resolve their topology from the config's
        # mesh block (see _candidate_topology) — dp is what that resolution
        # yields: everything not on the tensor/sequence axes, minus a
        # user-pinned pipe axis (preserved by the _candidate_config merge).
        # expert stays OUT of the divisor: the expert axis carries batch
        # (dp_world includes it everywhere else — topology.data_parallel_size)
        import jax
        um = self.user_config.get("mesh") or {}
        fixed = tensor * sequence * int(um.get("pipe", 1))
        return max(len(jax.devices()) // max(fixed, 1), 1)

    def _candidate_topology(self, tensor: int, sequence: int):
        """Mesh for a candidate. When the axes are NOT being tuned, the
        user's topology passes through. When they are, return None so the
        ENGINE resolves the mesh from the candidate config's mesh block —
        the same resolve_topology_axes path production takes with the
        emitted ds_config_optimal.json, including the stage-aware fsdp
        carve (a hand-built MeshTopology(tensor=t) would leave fsdp=1 and
        benchmark a mesh the shipped config never produces)."""
        if (tensor, sequence) == (1, 1):
            return self.topology
        return None

    def _build_engine(self, overrides: Dict[str, Any], micro_batch_size: int = 1,
                      tensor: int = 1, sequence: int = 1, offload: str = "none"):
        """Build the engine for a candidate from the SAME config dict that
        gets recorded/emitted (``_candidate_config``) — one construction
        path, so the benchmarked engine and the optimal-config artifact can
        never drift."""
        import deepspeed_tpu

        stage = overrides.get("zero_stage",
                              (self.user_config.get("zero_optimization") or {}).get("stage", 0))
        cfg = self._candidate_config(stage, micro_batch_size, tensor, sequence, offload)
        model = self.model_factory(overrides)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=cfg, topology=self._candidate_topology(tensor, sequence))
        # candidate engines must never re-enter autotuning themselves
        # (DS_AUTOTUNING is still set in the environment)
        engine._autotune = None
        return engine

    @staticmethod
    def _apply_offload(zero: Dict[str, Any], offload: str) -> None:
        if offload == "optimizer":
            zero["offload_optimizer"] = {"device": "cpu"}
        elif offload == "infinity":
            # the full ZeRO-Infinity recipe (stage 3 enforced by candidate
            # generation): params rest pinned-host + host C++ Adam
            zero["offload_param"] = {"device": "cpu"}
            zero["offload_optimizer"] = {"device": "cpu"}

    def _scaled_batch(self, global_batch: int):
        """Tile the user's example batch out to ``global_batch`` samples."""
        def tile(x):
            x = np.asarray(x)
            reps = (global_batch + x.shape[0] - 1) // x.shape[0]
            return np.concatenate([x] * reps, axis=0)[:global_batch]
        import jax
        return jax.tree.map(tile, self.example_batch)

    # ------------------------------------------------------------------
    def _compile_candidate(self, exp: Experiment, mem_budget: int) -> bool:
        """AOT-compile one candidate; fill mem/cost stats; prune on OOM.
        Returns True if the candidate fits."""
        peak_flops, peak_bw = _device_peaks()
        try:
            engine = self._build_engine({"zero_stage": exp.zero_stage}, exp.micro_batch_size,
                                        exp.tensor, exp.sequence, exp.offload)
            batch = self._scaled_batch(engine.config.train_batch_size)
            compiled = engine.lower_train_step(batch).compile()
        except Exception as e:  # shape/mesh/unsupported combos prune cleanly
            exp.status, exp.error = "failed", f"{type(e).__name__}: {e}"
            logger.warning(f"autotuning: {exp.name} failed to compile: {exp.error[:200]}")
            return False
        ma = compiled.memory_analysis()
        if ma is not None:
            exp.mem_bytes = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            exp.arg_bytes = int(ma.argument_size_in_bytes)
        ca = compiled.cost_analysis()
        if isinstance(ca, list):  # jax 0.4.x: one dict per device program
            ca = ca[0] if ca else None
        if ca:
            exp.flops = float(ca.get("flops", 0.0))
            exp.bytes_accessed = float(ca.get("bytes accessed", 0.0))
            exp.est_step_s = max(exp.flops / peak_flops, exp.bytes_accessed / peak_bw)
        if exp.mem_bytes is not None and exp.mem_bytes > mem_budget:
            exp.status = "pruned"
            log_dist(f"autotuning: {exp.name} pruned "
                     f"({exp.mem_bytes / 2**30:.2f} GiB > {mem_budget / 2**30:.2f} GiB budget)")
            return False
        exp.status = "compiled"
        return True

    def _measure_candidate(self, exp: Experiment) -> None:
        """Run real timed steps for a compile-survivor (reference
        ``run_tuning_micro_batch_sizes`` autotuner.py:740).

        Timing goes through ``engine.train_batches`` (one ``lax.scan`` of
        ``steps`` optimizer steps per dispatch): the fused dispatch is the
        production loop shape. Host-driven schedules (offload,
        1-bit) fall back to per-step inside train_batches itself."""
        import jax
        at = self.autotuning_config
        steps = max(at.end_profile_step - at.start_profile_step, 1)
        try:
            engine = self._build_engine({"zero_stage": exp.zero_stage}, exp.micro_batch_size,
                                        exp.tensor, exp.sequence, exp.offload)
            batch = self._scaled_batch(engine.config.train_batch_size)
            engine.initialize_state(batch)
            stack = jax.tree.map(
                lambda x: np.broadcast_to(np.asarray(x), (steps,) + np.shape(x)), batch)
            engine.train_batches(stack)  # warmup + compile
            jax.block_until_ready(engine.state.params)
            t0 = time.perf_counter()
            engine.train_batches(stack)
            jax.block_until_ready(engine.state.params)
            exp.measured_step_s = (time.perf_counter() - t0) / steps
            exp.status = "measured"
        except Exception as e:
            exp.status, exp.error = "failed", f"{type(e).__name__}: {e}"
            # a config that crashed at runtime must never be selected on the
            # strength of its compile-time estimate
            exp.metric_val = None
            logger.warning(f"autotuning: {exp.name} failed to run: {exp.error[:200]}")

    def _metric_val(self, exp: Experiment) -> Optional[float]:
        """Higher is better for every metric (latency is negated)."""
        step_s = exp.measured_step_s if exp.measured_step_s is not None else exp.est_step_s
        if step_s is None or step_s <= 0:
            return None
        if self.metric() == AUTOTUNING_METRIC_LATENCY:
            return -step_s
        if self.metric() == AUTOTUNING_METRIC_FLOPS:
            return (exp.flops or 0.0) / step_s
        # throughput: samples/sec across the job
        return exp.config.get("train_batch_size", exp.micro_batch_size) / step_s

    # ------------------------------------------------------------------
    def _stages_to_tune(self) -> List[int]:
        zs = self.autotuning_config.zero_stages
        user_stage = (self.user_config.get("zero_optimization") or {}).get("stage", None)
        if isinstance(zs, list):
            return sorted(set(int(s) for s in zs))
        if zs == "all":
            if isinstance(user_stage, int):
                return [user_stage]  # reference honors an explicit user stage
            return [0, 1, 2, 3]
        return [int(zs)]

    def _mbs_ladder(self, tensor: int = 1, sequence: int = 1) -> List[int]:
        lo = max(self.min_train_micro_batch_size_per_gpu(), 1)
        hi = self.max_train_micro_batch_size_per_gpu()
        if self.autotuning_config.max_train_batch_size:
            gas = int(self.user_config.get("gradient_accumulation_steps", 1))
            # cap against the CANDIDATE's dp world: a tp=4 mesh has fewer dp
            # replicas, so its per-replica micro-batch may legally be larger
            hi = min(hi, self.autotuning_config.max_train_batch_size
                     // (gas * self._dp_world(tensor, sequence)))
        ladder, v = [], lo
        while v <= hi:
            ladder.append(v)
            v *= 2
        return ladder

    def tune(self) -> Optional[Experiment]:
        """Main loop (reference ``Autotuner.tune`` autotuner.py:404): per
        ZeRO stage, walk the micro-batch ladder; compile-prune; rank by the
        roofline estimate; measure the global top-k; pick the best."""
        self.start_time = time.time()
        self.model_info_profile_run()
        at = self.autotuning_config
        mem_budget = at.mem_budget_bytes or _device_mem_budget()
        log_dist(f"autotuning: memory budget {mem_budget / 2**30:.2f} GiB, "
                 f"metric={self.metric()}, stages={self._stages_to_tune()}")

        import jax
        n_dev = len(jax.devices())
        meshes = []
        for t in sorted(set(int(x) for x in at.tp_sizes)):
            for sq in sorted(set(int(x) for x in at.sp_sizes)):
                if t * sq <= n_dev and n_dev % (t * sq) == 0:
                    meshes.append((t, sq))
                else:
                    logger.warning(f"autotuning: mesh tensor={t} x sequence={sq} does not "
                                   f"divide {n_dev} devices; skipped")
        if not meshes:
            raise ValueError(f"autotuning: no (tp, sp) pair from tp_sizes="
                             f"{at.tp_sizes} x sp_sizes={at.sp_sizes} divides "
                             f"{n_dev} devices — include 1 in the lists for a baseline")
        for stage in self._stages_to_tune():
            offloads = ["none"]
            if at.tune_offload:
                offloads.append("optimizer")
                if stage == 3:
                    offloads.append("infinity")
            for t, sq in meshes:
                for off in offloads:
                    suffix = (f"_tp{t}" if t > 1 else "") + (f"_sp{sq}" if sq > 1 else "") \
                        + (f"_{off}" if off != "none" else "")
                    for mbs in self._mbs_ladder(t, sq):
                        exp = Experiment(name=f"z{stage}_mbs{mbs}{suffix}",
                                         zero_stage=stage, micro_batch_size=mbs,
                                         tensor=t, sequence=sq, offload=off,
                                         config=self._candidate_config(stage, mbs, t, sq, off))
                        self.records.append(exp)
                        if not self._compile_candidate(exp, mem_budget):
                            # doubling mbs only grows memory: end this ladder
                            # on the first pruned (or failed) candidate —
                            # reference get_min_max_micro_batch_size stops
                            # the same way
                            break

        survivors = [e for e in self.records if e.status == "compiled"]
        for exp in survivors:
            exp.metric_val = self._metric_val(exp)

        if at.measure and survivors:
            top = sorted(survivors, key=lambda e: e.metric_val or 0.0, reverse=True)[:at.top_k]
            # offload estimates come from the grads-only device program and
            # omit host-update time — optimistic. Guarantee the best DENSE
            # survivor is also measured so offload crowding the top_k can
            # never shadow a faster dense config.
            if any(e.offload != "none" for e in top):
                dense = [e for e in survivors if e.offload == "none" and e not in top
                         and e.metric_val is not None]
                if dense:
                    top.append(max(dense, key=lambda e: e.metric_val))
            for exp in top:
                self._measure_candidate(exp)
                if exp.status == "measured":
                    exp.metric_val = self._metric_val(exp)

        # measured times beat roofline estimates — never compare across the
        # two (the estimate is an optimistic lower bound on step time)
        ranked = [e for e in self.records if e.metric_val is not None]
        measured = [e for e in ranked if e.status == "measured"]
        self.best = max(measured or ranked, key=lambda e: e.metric_val, default=None)
        self.write_tuning_results()
        if self.best is not None:
            log_dist(f"autotuning: best = {self.best.name} "
                     f"({self.metric()}={self.best.metric_val:.2f}, "
                     f"{len(self.records)} experiments, {time.time() - self.start_time:.0f}s)")
        return self.best

    def _candidate_config(self, stage: int, mbs: int, tensor: int = 1,
                          sequence: int = 1, offload: str = "none") -> Dict[str, Any]:
        cfg = json.loads(json.dumps({k: v for k, v in self.user_config.items() if k != AUTOTUNING}))
        zero = cfg.setdefault("zero_optimization", {})
        zero["stage"] = stage
        self._apply_offload(zero, offload)
        gas = int(cfg.get("gradient_accumulation_steps", 1))
        cfg["train_batch_size"] = mbs * gas * self._dp_world(tensor, sequence)
        cfg["train_micro_batch_size_per_gpu"] = mbs
        if tensor > 1 or sequence > 1:
            # merge over any user mesh block: tuned axes override, the rest
            # (pipe/expert/data) keep the user's intent
            mesh = dict(cfg.get("mesh") or {})
            mesh.update(tensor=tensor, sequence=sequence)
            cfg["mesh"] = mesh
        return cfg

    # ------------------------------------------------------------------
    def write_tuning_results(self) -> None:
        """Persist per-experiment records + the winning config (reference
        ``write_optimal_config`` autotuner.py:1072)."""
        at = self.autotuning_config
        os.makedirs(at.exps_dir, exist_ok=True)
        os.makedirs(at.results_dir, exist_ok=True)
        for exp in self.records:
            with open(os.path.join(at.exps_dir, f"{exp.name}.json"), "w") as f:
                json.dump(exp.record(), f, indent=2)
        if self.best is not None:
            with open(os.path.join(at.results_dir, "ds_config_optimal.json"), "w") as f:
                json.dump(self.best.config, f, indent=2)
            with open(os.path.join(at.results_dir, "summary.json"), "w") as f:
                json.dump({"best": self.best.name, "metric": self.metric(),
                           "metric_val": self.best.metric_val,
                           "num_experiments": len(self.records),
                           "model_info": self.model_info}, f, indent=2)

    def print_tuning_results(self) -> None:
        """Tabulated result dump (reference ``print_tuning_results``
        autotuner.py:108)."""
        cols = ("name", "status", "mem_bytes", "est_step_s", "measured_step_s", "metric_val")
        rows = [[str(getattr(e, c)) for c in cols] for e in self.records]
        widths = [max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
                  for i, c in enumerate(cols)]
        line = "  ".join(c.ljust(w) for c, w in zip(cols, widths))
        print(line)
        print("-" * len(line))
        for r in rows:
            print("  ".join(v.ljust(w) for v, w in zip(r, widths)))
        if self.best is not None:
            print(f"optimal: {self.best.name} -> {os.path.join(self.autotuning_config.results_dir, 'ds_config_optimal.json')}")
