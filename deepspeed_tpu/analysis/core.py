"""graft-lint core: the rule registry, findings, and waivers.

PR 5 and PR 6 each hardened a program-level invariant by hand ("no
``[*,S,E,C]`` tensor in the sorted-route jaxpr", "owned_device_put for
donated host trees", pinned matmul precision on the parity path) — one-off
assertions that protect nothing outside their own test. This package
turns those invariants into a *registry of named rules* checked
mechanically against every traced program, the same role the reference's
op-builder compatibility checks play for its CUDA ops
(``/root/reference/op_builder/builder.py``): convention becomes
enforcement.

A :class:`Rule` declares an id (``R001``..), severity, and the layer it
inspects (``jaxpr`` — walked closed jaxprs; ``hlo`` — lowered StableHLO
text; ``ast`` — repo source). Rules yield :class:`Finding`s; a
:class:`Waiver` (from ``analysis_results/waivers.json`` or an inline
``# graft-lint: waive R00X reason`` comment for AST rules) marks a
finding as acknowledged so it reports but does not gate. The CLI
(``tools/graft_lint.py``) gates on *new* ERROR findings against a
committed baseline.
"""

import dataclasses
import fnmatch
import hashlib
from typing import Callable, Dict, Iterable, List, Optional

ERROR = "ERROR"
WARN = "WARN"
INFO = "INFO"

_SEVERITIES = (ERROR, WARN, INFO)

#: layers a rule can inspect
LAYER_JAXPR = "jaxpr"
LAYER_HLO = "hlo"
LAYER_AST = "ast"
LAYER_COST = "cost"  # quantitative rules fed by the cost engine (analysis/cost.py)


@dataclasses.dataclass
class Finding:
    """One rule violation (or waived acknowledgement) at one site."""

    rule: str
    severity: str
    scenario: str  # program name (jaxpr/hlo rules) or repo-relative file (ast)
    message: str
    location: str = ""  # scope path inside the program, or file:line
    waived: bool = False
    waiver_reason: str = ""

    def fingerprint(self) -> str:
        """Stable identity for baseline comparison. The full location
        (including AST line numbers) is part of the identity: two raw
        ``device_put`` calls in one file are two findings, and a new one
        must not hide behind an old one's fingerprint. Line-shift churn is
        handled by inline waiver comments (which move with the code), not
        by the baseline."""
        raw = f"{self.rule}|{self.scenario}|{self.location}|{self.message}"
        return hashlib.sha1(raw.encode()).hexdigest()[:16]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["fingerprint"] = self.fingerprint()
        return d


@dataclasses.dataclass(frozen=True)
class Rule:
    """A named invariant. ``check`` signature depends on ``layer``:

    * ``jaxpr`` / ``hlo`` rules: ``check(program, analyzer) -> Iterable[Finding]``
      (``program``: :class:`~deepspeed_tpu.analysis.program.ProgramInfo`,
      ``analyzer``: the shared :class:`ProgramAnalyzer` walk);
    * ``ast`` rules: ``check(files) -> Iterable[Finding]`` where ``files``
      is ``[(relpath, source_text, ast_module)]``.
    """

    id: str
    title: str
    severity: str
    layer: str
    doc: str
    check: Callable

    def __post_init__(self):
        assert self.severity in _SEVERITIES, self.severity


RULES: Dict[str, Rule] = {}


def register(rule: Rule) -> Rule:
    assert rule.id not in RULES, f"duplicate rule id {rule.id}"
    RULES[rule.id] = rule
    return rule


def rule(id: str, title: str, severity: str, layer: str):  # noqa: A002 — rule id
    """Decorator: ``@rule("R001", "...", ERROR, LAYER_JAXPR)`` over a check
    function registers it; the function's docstring becomes the rule doc."""

    def wrap(fn):
        register(Rule(id=id, title=title, severity=severity, layer=layer,
                      doc=(fn.__doc__ or "").strip(), check=fn))
        return fn

    return wrap


def program_rules() -> List[Rule]:
    return [r for r in RULES.values() if r.layer in (LAYER_JAXPR, LAYER_HLO)]


def ast_rules() -> List[Rule]:
    return [r for r in RULES.values() if r.layer == LAYER_AST]


def cost_rules() -> List[Rule]:
    """Cost-layer rules run only in the ``--cost`` pass: they need the
    memory estimate + collective inventory a plain trace walk doesn't
    build (R013 additionally needs the committed cost baseline)."""
    return [r for r in RULES.values() if r.layer == LAYER_COST]


@dataclasses.dataclass(frozen=True)
class Waiver:
    """Acknowledge a finding without fixing it. ``scenario`` is an fnmatch
    pattern against ``Finding.scenario`` (program name or file path);
    ``match`` optionally narrows to findings whose message contains it."""

    rule: str
    scenario: str = "*"
    match: str = ""
    reason: str = ""

    def covers(self, f: Finding) -> bool:
        return (self.rule == f.rule
                and fnmatch.fnmatch(f.scenario, self.scenario)
                and (not self.match or self.match in f.message))


def apply_waivers(findings: Iterable[Finding], waivers: Iterable[Waiver]) -> List[Finding]:
    out = []
    for f in findings:
        for w in waivers:
            if not f.waived and w.covers(f):
                f.waived = True
                f.waiver_reason = w.reason or f"waived by {w.rule}/{w.scenario}"
        out.append(f)
    return out


def stale_config_waivers(findings: Iterable[Finding],
                         waivers: Iterable[Waiver]) -> List[Waiver]:
    """Waivers that cover no current finding. A waiver is an
    acknowledged debt; once the debt is paid (or the rule/scenario
    renamed) the entry keeps matching nothing forever — the CLI WARNs so
    dead waivers get pruned instead of silently accumulating into a
    blanket that could swallow a future real finding."""
    findings = list(findings)
    return [w for w in waivers if not any(w.covers(f) for f in findings)]


def load_waivers(entries: Optional[Iterable[dict]]) -> List[Waiver]:
    """Parse the ``waivers.json`` list-of-dicts form (unknown keys rejected
    so a typo'd waiver fails loudly instead of silently not waiving)."""
    out = []
    for e in entries or []:
        unknown = set(e) - {"rule", "scenario", "match", "reason"}
        if unknown:
            raise ValueError(f"waiver {e!r} has unknown keys {sorted(unknown)}")
        if "rule" not in e:
            raise ValueError(f"waiver {e!r} missing 'rule'")
        out.append(Waiver(**e))
    return out
