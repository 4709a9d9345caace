"""Static memory estimator: jaxpr-level buffer liveness -> peak live bytes.

graft-lint's boolean rules (R001..R008) can say *whether* a program does
something; the ROADMAP's open items are quantitative — "the chunked-wave
pipe schedule holds ~2x the 1F1B activation bound", "donation halves peak
state HBM" — and until now those numbers were only checkable on chip via
``compiled.memory_analysis()`` during scarce chip windows. This module
computes a backend-independent estimate from the traced jaxpr alone, so
the activation-bound gate (R010) and the cost ratchet (R013) run on every
CPU tier-1 pass.

Model
-----
A closed jaxpr is a linear schedule of eqns. Every variable is a buffer:
defined by one eqn (or as a program input), dead after its last consumer
(program outputs stay live to the end). Peak live bytes is the max over
schedule slots of the sum of live buffer sizes, plus — at the slot of an
eqn that carries sub-jaxprs (``pjit``/``scan``/``cond``/``remat2``/...)
— the sub-program's *internal transient peak* (its own peak minus its
boundary buffers, which the outer level already counts).

Two headline numbers per program:

* ``peak_bytes`` — everything live at the worst slot, inputs included.
  An **undonated upper bound**: donation (an HLO-layer property) aliases
  old state into new and is deliberately ignored, so the estimate cannot
  be gamed by aliasing it away.
* ``peak_transient_bytes`` — the same walk with top-level inputs
  (params, optimizer state, batch) excluded: the activations and temps
  the *schedule* controls. This is the number R010 judges against a
  declared activation budget, and the number the 1F1B refactor must
  drive down; donation does not move it.

One value is no buffer: a ``transpose`` whose every consumer is a
``dot_general``. A dot's dimension numbers name its operand's axes in any
order, so XLA folds such a transpose into the dot (on the TPU, into the
operand's layout); the estimate counts it as a view that keeps its
operand alive. Any other transpose is a copy and counts as one.

Accuracy contract: this is a *scheduling* estimate, not a simulator —
XLA fuses, rematerializes and buffer-shares below this level. The
cross-check against ``compiled.memory_analysis()`` (where the backend
provides it) is tolerance-banded, not exact; see
``tests/unit/analysis/test_memory.py``.
"""

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.analysis.program import _scope_label, aval_bytes

#: how many of the largest live buffers to name in the peak attribution
_TOP_LIVE = 8


@dataclasses.dataclass
class MemoryEstimate:
    """Static peak-liveness estimate for one traced program."""

    peak_bytes: int
    peak_transient_bytes: int
    input_bytes: int  # top-level invars + consts
    output_bytes: int
    eqns: int  # total eqns walked (all nesting levels)
    by_scope: Dict[str, int]  # live bytes at the peak slot, per defining scope
    top_live: List[Dict[str, Any]]  # largest live buffers at the peak slot
    #: largest non-input buffers at the TRANSIENT peak slot (R010's
    #: attribution — can be a different schedule slot than top_live's)
    top_transient: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _is_tracked(v) -> bool:
    """Real jaxpr Vars only: Literals are inline constants (no buffer of
    their own worth tracking), DropVars are dead on arrival (XLA DCEs
    them)."""
    return hasattr(v, "aval") and type(v).__name__ not in ("Literal", "DropVar")


def _dot_operand_views(jaxpr) -> Dict[Any, Any]:
    """``{view: viewed}`` for every ``transpose`` result of ``jaxpr`` that
    only ``dot_general`` eqns consume (and the program does not return): a
    reordering of axes that the dot's dimension numbers absorb. A view of a
    view resolves to the buffer underneath."""
    consumers: Dict[Any, set] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if _is_tracked(v):
                consumers.setdefault(v, set()).add(eqn.primitive.name)
    returned = {v for v in jaxpr.outvars if _is_tracked(v)}
    views: Dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "transpose":
            continue
        (viewed,), (out,) = eqn.invars, eqn.outvars
        if (_is_tracked(out) and _is_tracked(viewed) and out not in returned
                and consumers.get(out) == {"dot_general"}):
            views[out] = views.get(viewed, viewed)
    return views


class _Liveness:
    """One liveness walk over one (sub-)jaxpr.

    Schedule slots: slot 0 = program entry (inputs become live), slot i+1
    = eqn i (operands must be live, outputs become live), slot T+1 =
    program exit (outputs still live). ``sub_peaks(cache)`` recursion
    bottoms out because jaxprs are acyclic.
    """

    def __init__(self, jaxpr, scope_path: Tuple[str, ...] = (),
                 cache: Optional[dict] = None):
        self.jaxpr = jaxpr
        self.scope_path = scope_path
        self.cache = cache if cache is not None else {}
        self.T = len(jaxpr.eqns)
        # var -> [def_slot, last_slot, nbytes, scope, is_input]
        self.vars: Dict[Any, list] = {}
        self.inner_extra = [0] * (self.T + 2)
        self.total_eqns = self.T
        self._walk()

    def _walk(self):
        scope = "/".join(self.scope_path) or "<top>"
        for v in itertools.chain(self.jaxpr.constvars, self.jaxpr.invars):
            if _is_tracked(v):
                self.vars[v] = [0, 0, aval_bytes(v.aval), "<inputs>", True]
        from deepspeed_tpu.analysis.program import _iter_sub_jaxprs
        views = _dot_operand_views(self.jaxpr)  # view var -> the var it views
        for i, eqn in enumerate(self.jaxpr.eqns):
            slot = i + 1
            for v in eqn.invars:
                if not _is_tracked(v):
                    continue
                v = views.get(v, v)  # reading a view reads what it views
                if v in self.vars:
                    self.vars[v][1] = max(self.vars[v][1], slot)
            for v in eqn.outvars:
                if _is_tracked(v) and v not in views:
                    self.vars[v] = [slot, slot, aval_bytes(v.aval), scope, False]
            # sub-jaxprs run *inside* this slot; alternatives (cond
            # branches) and single bodies both take the max internal
            # transient peak
            extra = 0
            for key, value in eqn.params.items():
                for sub, _ in _iter_sub_jaxprs(value):
                    sub_peak, sub_io, sub_eqns = self._sub_summary(
                        sub, self.scope_path + (_scope_label(eqn),))
                    extra = max(extra, max(0, sub_peak - sub_io))
                    self.total_eqns += sub_eqns
            self.inner_extra[slot] = extra
        for v in self.jaxpr.outvars:
            if _is_tracked(v) and v in self.vars:
                self.vars[v][1] = self.T + 1

    def _sub_summary(self, sub, sub_path) -> Tuple[int, int, int]:
        """(peak, boundary io bytes, eqn count) for a nested jaxpr.
        Cached by identity — pjit bodies repeat across call sites."""
        hit = self.cache.get(id(sub))
        if hit is not None:
            return hit
        lv = _Liveness(sub, sub_path, self.cache)
        peak, _ = lv.peaks()
        io = sum(aval_bytes(v.aval)
                 for v in itertools.chain(sub.constvars, sub.invars, sub.outvars)
                 if _is_tracked(v))
        self.cache[id(sub)] = (peak, io, lv.total_eqns)
        return self.cache[id(sub)]

    # ------------------------------------------------------------------
    def _timeline(self, include_inputs: bool) -> List[int]:
        diff = [0] * (self.T + 3)
        for def_slot, last_slot, nbytes, _, is_input in self.vars.values():
            if is_input and not include_inputs:
                continue
            diff[def_slot] += nbytes
            diff[last_slot + 1] -= nbytes
        live, acc = [], 0
        for s in range(self.T + 2):
            acc += diff[s]
            live.append(acc + self.inner_extra[s])
        return live

    def peaks(self) -> Tuple[int, int]:
        """(peak slot value, argmax slot) over the inputs-included
        timeline."""
        live = self._timeline(include_inputs=True)
        peak = max(live)
        return peak, live.index(peak)

    def transient_peak(self) -> Tuple[int, int]:
        """(peak, argmax slot) over the inputs-excluded timeline. The
        argmax can differ from the total timeline's (params dominate
        early, activations late) — R010's attribution must read THIS
        slot."""
        live = self._timeline(include_inputs=False)
        peak = max(live)
        return peak, live.index(peak)

    def live_at(self, slot: int):
        """The buffers live at ``slot``, largest first."""
        out = []
        for v, (d, l, nbytes, scope, is_input) in self.vars.items():
            if d <= slot <= l and nbytes > 0:
                out.append((nbytes, tuple(getattr(v.aval, "shape", ())),
                            str(getattr(v.aval, "dtype", "?")), scope, is_input))
        out.sort(key=lambda t: -t[0])
        return out


def estimate_memory(program_or_jaxpr) -> MemoryEstimate:
    """Estimate peak live bytes for a :class:`ProgramInfo` (or a bare
    ``ClosedJaxpr``). The per-scope attribution names where the bytes at
    the peak slot were *defined* — the handle the remat-policy and
    1F1B levers need."""
    closed = getattr(program_or_jaxpr, "jaxpr", program_or_jaxpr)
    if hasattr(closed, "jaxpr"):  # ClosedJaxpr -> open jaxpr
        open_jaxpr = closed.jaxpr
    else:
        open_jaxpr = closed
    lv = _Liveness(open_jaxpr)
    peak, peak_slot = lv.peaks()
    transient_peak, transient_slot = lv.transient_peak()
    live = lv.live_at(peak_slot)
    by_scope: Dict[str, int] = {}
    for nbytes, _, _, scope, _ in live:
        by_scope[scope] = by_scope.get(scope, 0) + nbytes
    if lv.inner_extra[peak_slot]:
        by_scope["<nested transients>"] = lv.inner_extra[peak_slot]
    top = [{"bytes": n, "shape": list(shape), "dtype": dt, "scope": scope}
           for n, shape, dt, scope, _ in live[:_TOP_LIVE]]
    top_transient = [{"bytes": n, "shape": list(shape), "dtype": dt, "scope": scope}
                     for n, shape, dt, scope, is_input
                     in lv.live_at(transient_slot) if not is_input][:_TOP_LIVE]
    input_bytes = sum(aval_bytes(v.aval)
                      for v in itertools.chain(open_jaxpr.constvars, open_jaxpr.invars)
                      if _is_tracked(v))
    output_bytes = sum(aval_bytes(v.aval) for v in open_jaxpr.outvars if _is_tracked(v))
    return MemoryEstimate(
        peak_bytes=peak,
        peak_transient_bytes=transient_peak,
        input_bytes=input_bytes,
        output_bytes=output_bytes,
        eqns=lv.total_eqns,
        by_scope=dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        top_live=top,
        top_transient=top_transient,
    )
