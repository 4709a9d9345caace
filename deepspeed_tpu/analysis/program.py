"""ProgramAnalyzer: one shared walk over a traced program.

A :class:`ProgramInfo` bundles what graft-lint knows about one traced
program: its closed jaxpr, optionally the lowered StableHLO text (the
layer where donation/aliasing is visible — jaxpr-level ``donated_invars``
only exist on pjit eqns), and free-form ``metadata`` the scenario
builder supplies (the MoE ``[S,E,C]`` signature, whether the program is
the parity path, whether it runs on a multi-device mesh, size
thresholds).

:class:`ProgramAnalyzer` walks the jaxpr ONCE — recursing into every
sub-jaxpr it can find in eqn params (``pjit``/``scan``/``while``/
``cond`` branches/``remat2``/``custom_vjp``/``shard_map``), whether
stored as ``ClosedJaxpr``, open ``Jaxpr``, or tuples of either — and
caches flat :class:`EqnRecord`s that every rule then iterates. Scope
paths (``pjit:train_step/scan/remat2``) give findings a human-readable
location and give the precision rule its attribution key.
"""

import itertools
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

import jax

from jax.extend.core import ClosedJaxpr, Jaxpr


class EqnRecord(NamedTuple):
    eqn: Any  # JaxprEqn
    path: Tuple[str, ...]  # enclosing sub-jaxpr scopes, outermost first
    in_remat: bool  # inside a remat/checkpoint region

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    @property
    def scope(self) -> str:
        return "/".join(self.path) or "<top>"


class ProgramInfo:
    """One traced program + everything a rule may need to judge it.

    ``lower`` is an optional zero-arg thunk returning the program's
    ``jax.stages.Lowered`` — the cost engine (``analysis/cost.py``) calls
    it (then ``.compile()``) only in the ``--cost`` pass, so plain lint
    runs stay trace-only. The compiled executable is cached: its
    ``as_text()`` is the post-SPMD collective inventory and its
    ``cost_analysis()``/``memory_analysis()`` cross-check the static
    memory estimate."""

    def __init__(self, name: str, jaxpr: Optional[ClosedJaxpr] = None,
                 hlo_text: Optional[str] = None, kind: str = "fwd_bwd",
                 metadata: Optional[Dict[str, Any]] = None,
                 lower=None):
        assert jaxpr is not None or hlo_text is not None, name
        self.name = name
        self.jaxpr = jaxpr
        self.hlo_text = hlo_text
        self.kind = kind  # fwd_bwd | train_step | layer | fixture
        self.metadata = dict(metadata or {})
        self.lower = lower
        self._compiled = None

    def compiled(self):
        """The compiled executable, or None when no lowering thunk was
        attached. Exceptions propagate — the caller records them as the
        program's ``compile_error`` evidence."""
        if self._compiled is None and self.lower is not None:
            self._compiled = self.lower().compile()
        return self._compiled


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:  # extended dtypes (typed PRNG keys) aren't numpy dtypes
        itemsize = getattr(dtype, "itemsize", 0) or 0
    return int(np.prod(shape, dtype=np.int64)) * itemsize if shape else itemsize


def _iter_sub_jaxprs(value) -> Iterator[Tuple[Jaxpr, Optional[Any]]]:
    """Yield (open_jaxpr, consts_or_None) for every jaxpr nested in an eqn
    param value, whatever container it hides in (cond stores a tuple of
    ClosedJaxprs under ``branches``; remat2 stores an open Jaxpr)."""
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr, value.consts
    elif isinstance(value, Jaxpr):
        yield value, None
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _iter_sub_jaxprs(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _iter_sub_jaxprs(v)


_REMAT_PRIMS = ("remat", "remat2", "checkpoint")


def _scope_label(eqn) -> str:
    name = eqn.primitive.name
    inner = eqn.params.get("name")
    return f"{name}:{inner}" if isinstance(inner, str) and inner else name


class ProgramAnalyzer:
    """The cached single walk; rules share one instance per program."""

    def __init__(self, program: ProgramInfo):
        self.program = program
        self._records: List[EqnRecord] = []
        self.metrics: Dict[str, Any] = {}  # rules may deposit attribution here
        if program.jaxpr is not None:
            self._walk(program.jaxpr.jaxpr, (), False)

    def _walk(self, jaxpr: Jaxpr, path: Tuple[str, ...], in_remat: bool):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            self._records.append(EqnRecord(eqn, path, in_remat))
            sub_remat = in_remat or any(prim.startswith(r) for r in _REMAT_PRIMS)
            for key, value in eqn.params.items():
                for sub, _ in _iter_sub_jaxprs(value):
                    self._walk(sub, path + (_scope_label(eqn),), sub_remat)

    # ------------------------------------------------------------------
    def records(self) -> List[EqnRecord]:
        return self._records

    def iter_avals(self, outputs_only: bool = False) -> Iterator[Tuple[EqnRecord, Any]]:
        """(record, aval) over eqn outvars (and invars unless
        ``outputs_only``) — invars included so rules see top-level-input
        shapes flowing into eqns, deduped per eqn by identity."""
        for rec in self._records:
            vs = rec.eqn.outvars if outputs_only else itertools.chain(rec.eqn.invars, rec.eqn.outvars)
            for v in vs:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "shape", None) is not None:
                    yield rec, aval

    def count_primitive(self, name: str) -> int:
        return sum(1 for r in self._records if r.primitive == name)

    def top_invars(self):
        return list(self.program.jaxpr.jaxpr.invars) if self.program.jaxpr is not None else []

    # ------------------------------------------------------------------
    def has_sharding_evidence(self) -> bool:
        """True when the program visibly participates in SPMD placement:
        an explicit ``sharding_constraint``, a ``shard_map`` region, or a
        pjit whose in/out shardings are not all unspecified."""
        for rec in self._records:
            if rec.primitive in ("sharding_constraint", "shard_map"):
                return True
            if rec.primitive == "pjit":
                for key in ("in_shardings", "out_shardings"):
                    for s in rec.eqn.params.get(key) or ():
                        if s is not None and "Unspecified" not in type(s).__name__:
                            return True
        return False


def run_program_rules(program: ProgramInfo, rules=None,
                      analyzer: Optional["ProgramAnalyzer"] = None) -> Tuple[List, Dict[str, Any]]:
    """Run every (or the given) jaxpr/hlo-layer rule against one program.
    Returns ``(findings, metrics)`` — metrics carry rule attributions
    (e.g. R002's per-scope precision-upcast counts) into the report.
    Pass ``analyzer`` to share one cached walk with the cost pass."""
    from deepspeed_tpu.analysis import rules as _rules  # noqa: F401 — registers on import
    from deepspeed_tpu.analysis.core import RULES, program_rules

    selected = program_rules() if rules is None else [RULES[r] for r in rules]
    bad = [r.id for r in selected if r.layer not in ("jaxpr", "hlo")]
    if bad:
        raise ValueError(f"{bad} are {'an ' if len(bad) == 1 else ''}non-program-layer rule(s) — "
                         f"ast rules take source files (tools/graft_lint.py --ast-only), "
                         f"cost rules need the cost engine (tools/graft_lint.py --cost)")
    analyzer = analyzer or ProgramAnalyzer(program)
    findings = []
    for r in selected:
        if r.layer == "jaxpr" and program.jaxpr is None:
            continue
        findings.extend(r.check(program, analyzer))
    return findings, analyzer.metrics
