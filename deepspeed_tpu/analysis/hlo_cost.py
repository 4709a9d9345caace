"""Collective inventory + analytic bytes-moved model over traced programs.

The DeepSpeed blueprint's comms claims are countable: ZeRO-3 partitions
gradients with reduce-scatter not all-reduce, the sorted MoE route pays
exactly two capacity-bounded all-to-alls per layer, the pipe scan moves
one boundary activation per tick over ``collective_permute``. This module
turns a traced program into a list of :class:`CollectiveOp`s so R009 can
gate those signatures and R013 can ratchet total wire bytes.

Three inventory layers, honest about what each can see:

* ``jaxpr`` — explicit collective primitives (``psum``/``ppermute``/
  ``all_gather``/``psum_scatter``/``all_to_all``; only ``shard_map``
  regions have them, e.g. the pipe engine) **plus** *logical* collectives:
  chained ``sharding_constraint`` pairs (the MoE dispatch/combine
  G-sharded→E-sharded reshard idiom — a capacity-bounded all-to-all in
  intent, whatever GSPMD lowers it to). Backend-independent.
* ``stablehlo`` — ``stablehlo.all_reduce`` etc. in the lowered module
  (again only manual regions; GSPMD programs carry ``Sharding`` custom
  calls, not collectives, before partitioning).
* ``compiled`` — the post-SPMD, post-optimization HLO of
  ``lowered.compile().as_text()``: the collectives that actually run.
  **Backend caveat (XLA:CPU):**
  XLA:CPU decomposes reduce-scatter into all-reduce + dynamic-slice, so
  kind-exact reduce-scatter expectations must be declared per-backend
  (R009 ``backends`` field) and are *inventoried as unchecked* elsewhere
  rather than silently passed. (All-to-all survives on CPU — as a
  tuple-typed variadic op, which the parser handles.)

The per-op analytic model (``CollectiveOp.bytes_moved``) is the standard
ring/bidirectional-exchange cost **per participant** — the number that
must stay flat as the mesh grows:

=================  =================================
all_reduce          ``2 * bytes_in * (g-1)/g``
all_gather          ``bytes_out * (g-1)/g``
reduce_scatter      ``bytes_in * (g-1)/g``
all_to_all          ``bytes_in * (g-1)/g``
collective_permute  ``bytes_in``
resharding          ``bytes_in`` (whole-buffer upper bound)
=================  =================================
"""

import dataclasses
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from deepspeed_tpu.analysis.program import ProgramAnalyzer, aval_bytes

#: canonical collective kinds (plus the jaxpr-only logical kinds
#: ``resharding`` and ``dense_dispatch`` counted by the cost engine)
KINDS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
         "collective_permute")

#: jaxpr primitive -> canonical kind (psum_invariant is what a psum
#: traces to inside a checked shard_map; unchecked regions keep plain psum)
_PRIM_KIND = {
    "psum": "all_reduce",
    "psum_invariant": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "collective_permute",
}

_MLIR_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8E4M3FN": 1,
                     "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2,
                     "ui16": 2, "i8": 1, "ui8": 1, "i1": 1}
_HLO_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                    "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}


@dataclasses.dataclass
class CollectiveOp:
    """One collective (or logical-collective) site in one inventory
    layer."""

    kind: str
    layer: str  # jaxpr | stablehlo | compiled
    bytes_in: int
    bytes_out: int
    group_size: int  # participants per replica group (0 = unknown)
    n_groups: int
    axes: str  # mesh-axis attribution ("pipe", "data+fsdp", "g4", "unknown")
    scope: str = ""  # jaxpr scope path or HLO op name

    def bytes_moved(self) -> int:
        """Analytic wire bytes per participant (module docstring table).
        Unknown group size conservatively uses the g->inf factor of 1."""
        g = self.group_size
        f = (g - 1) / g if g > 1 else (0.0 if g == 1 else 1.0)
        if self.kind == "all_reduce":
            return int(2 * self.bytes_in * f)
        if self.kind == "all_gather":
            return int(self.bytes_out * f)
        if self.kind in ("reduce_scatter", "all_to_all"):
            return int(self.bytes_in * f)
        if self.kind == "collective_permute":
            return self.bytes_in
        return self.bytes_in  # resharding: whole-buffer upper bound

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["bytes_moved"] = self.bytes_moved()
        return d


# ---------------------------------------------------------------------------
# jaxpr layer
# ---------------------------------------------------------------------------
def _axis_names(params: dict) -> Tuple[str, ...]:
    axes = params.get("axes") or params.get("axis_name") or ()
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _group_size(axes: Tuple[str, ...], mesh_axes: Dict[str, int]) -> int:
    if not axes:
        return 0
    size = 1
    for a in axes:
        if a not in mesh_axes:
            return 0
        size *= int(mesh_axes[a])
    return size


def jaxpr_collectives(analyzer: ProgramAnalyzer,
                      mesh_axes: Optional[Dict[str, int]] = None) -> List[CollectiveOp]:
    """Explicit collective primitives + chained-constraint logical
    reshardings from the shared analyzer walk."""
    mesh_axes = dict(mesh_axes or {})
    total_devices = int(np.prod(list(mesh_axes.values()))) if mesh_axes else 0
    ops: List[CollectiveOp] = []
    producer = {}
    for rec in analyzer.records():
        for v in rec.eqn.outvars:
            producer[id(v)] = rec
    for rec in analyzer.records():
        prim = rec.primitive
        kind = _PRIM_KIND.get(prim)
        if kind is not None:
            bytes_in = sum(aval_bytes(getattr(v, "aval", None))
                           for v in rec.eqn.invars if hasattr(v, "aval"))
            bytes_out = sum(aval_bytes(v.aval) for v in rec.eqn.outvars
                            if hasattr(v, "aval"))
            axes = _axis_names(rec.eqn.params)
            g = _group_size(axes, mesh_axes) or int(rec.eqn.params.get("axis_size", 0) or 0)
            ops.append(CollectiveOp(
                kind=kind, layer="jaxpr", bytes_in=bytes_in, bytes_out=bytes_out,
                group_size=g,
                n_groups=(total_devices // g) if (g and total_devices) else 0,
                axes="+".join(axes) or "unknown", scope=rec.scope))
        elif prim == "sharding_constraint":
            # a constraint whose operand is itself a fresh constraint output
            # is an explicit reshard: the MoE dispatch/combine a2a idiom
            src = rec.eqn.invars[0] if rec.eqn.invars else None
            src_rec = producer.get(id(src))
            if src_rec is not None and src_rec.primitive == "sharding_constraint":
                nbytes = aval_bytes(getattr(src, "aval", None))
                ops.append(CollectiveOp(
                    kind="resharding", layer="jaxpr", bytes_in=nbytes,
                    bytes_out=nbytes, group_size=0, n_groups=0,
                    axes="reshard", scope=rec.scope))
    return ops


# ---------------------------------------------------------------------------
# StableHLO layer
# ---------------------------------------------------------------------------
def _mlir_tensor_bytes(spec: str) -> int:
    """``"2x4xf32"`` (or ``"f32"`` for rank 0) -> bytes."""
    parts = spec.split("x")
    dtype = parts[-1]
    n = 1
    for p in parts[:-1]:
        if not p.isdigit():
            return 0  # dynamic dims: not our programs
        n *= int(p)
    return n * _MLIR_DTYPE_BYTES.get(dtype, 0)


_STABLEHLO_OP = re.compile(r"stablehlo\.(all_reduce|all_gather|all_to_all|"
                           r"reduce_scatter|collective_permute)\W")
_MLIR_GROUPS = re.compile(r"replica_groups\s*=\s*dense<[^>]*>\s*:\s*"
                          r"tensor<(\d+)x(\d+)xi64>")
_MLIR_PAIRS = re.compile(r"source_target_pairs\s*=\s*dense<[^>]*>\s*:\s*"
                         r"tensor<(\d+)x2xi64>")
_MLIR_SIG = re.compile(r":\s*\(tensor<([^>]+)>[^)]*\)\s*->\s*\(?tensor<([^>]+)>")


def stablehlo_collectives(text: str) -> List[CollectiveOp]:
    """Parse collective ops out of lowered StableHLO text. The reduction
    region of ``all_reduce`` spans lines, so each op is judged on a
    bounded window from its mnemonic to its type signature."""
    ops = []
    for m in _STABLEHLO_OP.finditer(text):
        window = text[m.start():m.start() + 6000]
        kind = m.group(1)
        groups = _MLIR_GROUPS.search(window)
        pairs = _MLIR_PAIRS.search(window)
        sig = _MLIR_SIG.search(window)
        bytes_in = _mlir_tensor_bytes(sig.group(1)) if sig else 0
        bytes_out = _mlir_tensor_bytes(sig.group(2)) if sig else 0
        if kind == "collective_permute":
            g, n = 2, int(pairs.group(1)) if pairs else 0
        else:
            n, g = (int(groups.group(1)), int(groups.group(2))) if groups else (0, 0)
        ops.append(CollectiveOp(kind=kind, layer="stablehlo", bytes_in=bytes_in,
                                bytes_out=bytes_out, group_size=g, n_groups=n,
                                axes="unknown"))
    return ops


# ---------------------------------------------------------------------------
# compiled (post-SPMD) layer
# ---------------------------------------------------------------------------
#: result type is either one array type or a tuple (async -start pairs on
#: TPU: "(f32[8]{0}, f32[64]{0}) all-gather-start(...)")
_HLO_OP = re.compile(r"%(\S+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[^\]=]*\]\S*)\s+"
                     r"(all-reduce|all-gather|all-to-all|reduce-scatter|"
                     r"collective-permute)(-start)?\(")
_HLO_OPERAND = re.compile(r"([a-z0-9]+)\[([\d,]*)\]\S*\s+%")
_HLO_GROUPS_EXPLICIT = re.compile(r"replica_groups=\{(\{[\d,]*\}(?:,\{[\d,]*\})*)\}")
_HLO_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                              r"(?:T\(([\d,]+)\))?")
_HLO_PAIRS = re.compile(r"source_target_pairs=\{(\{[\d,]+\}(?:,\{[\d,]+\})*)\}")


def _hlo_type_bytes(spec: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", spec)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * _HLO_DTYPE_BYTES.get(m.group(1), 0)


def parse_replica_groups(line: str) -> Tuple[List[Tuple[int, ...]], int, int]:
    """(explicit groups, n_groups, group_size) from either HLO syntax;
    groups may be empty when only the iota shape was recoverable."""
    m = _HLO_GROUPS_EXPLICIT.search(line)
    if m:
        groups = [tuple(int(x) for x in grp.split(",") if x)
                  for grp in re.findall(r"\{([\d,]*)\}", m.group(0))]
        groups = [g for g in groups if g]
        if groups:
            return groups, len(groups), len(groups[0])
    m = _HLO_GROUPS_IOTA.search(line)
    if m:
        n, g = int(m.group(1)), int(m.group(2))
        reshape = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(reshape))).reshape(reshape)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        groups = [tuple(int(x) for x in row) for row in ids.reshape(n, g)]
        return groups, n, g
    return [], 0, 0


def infer_axes(groups: List[Tuple[int, ...]],
               mesh_axes: Optional[Dict[str, int]]) -> str:
    """Name the mesh axis (or axis pair) a replica-group set communicates
    over, by regenerating each candidate's groups from the row-major mesh
    layout. Falls back to ``"full"`` / ``"g<size>"``."""
    if not groups:
        return "unknown"
    if not mesh_axes:
        return f"g{len(groups[0])}"
    names = list(mesh_axes)
    shape = [int(mesh_axes[a]) for a in names]
    n = int(np.prod(shape))
    if sum(len(g) for g in groups) != n:
        return f"g{len(groups[0])}"
    want = {frozenset(g) for g in groups}
    if want == {frozenset(range(n))}:
        return "full"
    ids = np.arange(n).reshape(shape)

    def groups_over(axis_idxs):
        moved = np.moveaxis(ids, axis_idxs, range(-len(axis_idxs), 0))
        rows = moved.reshape(-1, int(np.prod([shape[i] for i in axis_idxs])))
        return {frozenset(int(x) for x in row) for row in rows}

    for i, name in enumerate(names):
        if shape[i] > 1 and groups_over([i]) == want:
            return name
    for i in range(len(names)):
        for j in range(i + 1, len(names)):
            if shape[i] * shape[j] > 1 and groups_over([i, j]) == want:
                return f"{names[i]}+{names[j]}"
    return f"g{len(groups[0])}"


def compiled_collectives(text: str,
                         mesh_axes: Optional[Dict[str, int]] = None) -> List[CollectiveOp]:
    """Inventory the post-optimization HLO — the collectives that actually
    run on this backend (module docstring caveat: CPU decomposes RS/A2A)."""
    ops = []
    for line in text.splitlines():
        m = _HLO_OP.search(line)
        if m:
            kind = m.group(3).replace("-", "_")
            call = line[m.end():]
            bytes_in = 0
            for t, dims in _HLO_OPERAND.findall(call):
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                bytes_in += n * _HLO_DTYPE_BYTES.get(t, 0)
            result = m.group(2)
            if result.startswith("("):
                # async tuple (operand alias, result, ...): the largest
                # element is the gathered/reduced payload
                bytes_out = max((_hlo_type_bytes(t) for t in
                                 re.findall(r"[a-z0-9]+\[[\d,]*\]", result)),
                                default=0)
            else:
                bytes_out = _hlo_type_bytes(result)
            if kind == "collective_permute":
                p = _HLO_PAIRS.search(line)
                n_pairs = len(re.findall(r"\{[\d,]+\}", p.group(1))) if p else 0
                ops.append(CollectiveOp(kind=kind, layer="compiled",
                                        bytes_in=bytes_in or bytes_out,
                                        bytes_out=bytes_out, group_size=2,
                                        n_groups=n_pairs, axes=_permute_axes(mesh_axes),
                                        scope=m.group(1)))
            else:
                groups, n, g = parse_replica_groups(line)
                ops.append(CollectiveOp(kind=kind, layer="compiled",
                                        bytes_in=bytes_in or bytes_out,
                                        bytes_out=bytes_out, group_size=g,
                                        n_groups=n,
                                        axes=infer_axes(groups, mesh_axes),
                                        scope=m.group(1)))
    return ops


def _permute_axes(mesh_axes):
    return "permute" if mesh_axes else "unknown"


# ---------------------------------------------------------------------------
def inventory(ops: Iterable[CollectiveOp]) -> Dict[str, Dict[str, Any]]:
    """Per-layer summary: op counts per kind + total analytic wire bytes —
    the shape R013 ratchets."""
    out: Dict[str, Dict[str, Any]] = {}
    for op in ops:
        layer = out.setdefault(op.layer, {"counts": {}, "bytes_moved": 0,
                                          "bytes_by_axis": {}})
        layer["counts"][op.kind] = layer["counts"].get(op.kind, 0) + 1
        moved = op.bytes_moved()
        layer["bytes_moved"] += moved
        layer["bytes_by_axis"][op.axes] = layer["bytes_by_axis"].get(op.axes, 0) + moved
    for layer in out.values():
        layer["counts"] = dict(sorted(layer["counts"].items()))
        layer["bytes_by_axis"] = dict(sorted(layer["bytes_by_axis"].items(),
                                             key=lambda kv: -kv[1]))
    return out
