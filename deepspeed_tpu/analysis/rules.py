"""Program-layer rules R001–R007 and R015.

Each rule converts one piece of this repo's accumulated perf/correctness
folklore into an enforced check (ISSUE 7; the per-rule history is cited
inline). Severities: ERROR findings gate the CLI against the baseline;
WARN findings report (and feed evidence rows) without gating.
"""

import itertools
from typing import List

import numpy as np

import jax.numpy as jnp

from deepspeed_tpu.analysis.core import ERROR, INFO, LAYER_HLO, LAYER_JAXPR, WARN, Finding, rule
from deepspeed_tpu.analysis.program import aval_bytes

_MAX_SITES = 8  # per-rule per-program cap: first N deduped sites + a summary line


def _cap(findings: List[Finding], rule_id: str, scenario: str, suppressed: int) -> List[Finding]:
    """Append one INFO marker when deduped sites were dropped at the cap.
    INFO (never gates) with a count-free message: a count would make the
    fingerprint churn with unrelated edits and trip the baseline ratchet
    on noise."""
    if suppressed > 0:
        findings.append(Finding(rule=rule_id, severity=INFO, scenario=scenario,
                                message=f"additional sites suppressed (cap {_MAX_SITES})"))
    return findings


# ---------------------------------------------------------------------------
@rule("R001", "no dense [*,S,E,C] intermediate in MoE programs", ERROR, LAYER_JAXPR)
def r001_dense_sec(program, analyzer):
    """The dense GShard einsum route materializes a ``[G,S,E,C]``
    combine-weights tensor and pays O(S*E*C*M) in fwd AND bwd for what is
    a gather of <=k*S rows (PR 6 measured 49x dispatch+combine and 11.6x
    peak-bytes CPU wins from eliminating it). Any aval whose trailing
    shape matches a declared ``(S, E, C)`` signature — scenario metadata
    ``moe_sec``, from ``sharded_moe.sec_signature`` — anywhere in the
    program (including sub-jaxprs under remat/scan/pjit) is a
    reintroduction of the dense route."""
    sigs = [tuple(s) for s in program.metadata.get("moe_sec", ())]
    if not sigs:
        return []
    findings, seen, suppressed = [], set(), 0
    for rec, aval in analyzer.iter_avals():
        tail = tuple(aval.shape)[-3:]
        if tail in sigs:
            key = (tuple(aval.shape), rec.scope)
            if key in seen:
                continue
            seen.add(key)
            if len(findings) >= _MAX_SITES:
                suppressed += 1
                continue
            findings.append(Finding(
                rule="R001", severity=ERROR, scenario=program.name,
                message=f"dense [*,S,E,C] intermediate {tuple(aval.shape)} "
                        f"matches MoE signature (S,E,C)={tail}",
                location=rec.scope))
    return _cap(findings, "R001", program.name, suppressed)


# ---------------------------------------------------------------------------
_FLOAT_WIDTH = {"bfloat16": 16, "float16": 16, "float32": 32, "float64": 64}
_DEFAULT_PRECISION_ALLOWLIST = (
    # scopes where a local fp32 upcast is the *intended* numerics (mirrors
    # the pinned-precision parity levers, SURVEY.md:338): normalization
    # statistics, softmax/logsumexp, loss accumulation, optimizer moments
    "norm", "softmax", "logsumexp", "lse", "loss", "xent", "l_aux", "adam",
    "scale", "logits",
)


@rule("R002", "no silent precision widening on the parity path", ERROR, LAYER_JAXPR)
def r002_precision(program, analyzer):
    """The bit-identical parity envelope (ROADMAP item 4, 47-ULP gap)
    dies by a thousand silent dtype widenings. Two checks: (a) float64
    anywhere is an ERROR — no TPU path wants f64, it is always a leaked
    python float or numpy default; (b) on programs marked
    ``parity: True``, each 16->32-bit float upcast outside the allowlist
    scopes is a WARN, and ALL upcasts are tallied per (src->dst, scope)
    into the report's ``precision_attribution`` metric — the per-op
    attribution that feeds the ULP hunt."""
    allow_f64 = program.metadata.get("allow_f64", False)
    allowlist = tuple(program.metadata.get("precision_allowlist",
                                           _DEFAULT_PRECISION_ALLOWLIST))
    parity = program.metadata.get("parity", False)
    findings, seen64, suppressed64 = [], set(), 0
    attribution = {}
    for rec, aval in analyzer.iter_avals(outputs_only=True):
        if not allow_f64 and getattr(aval, "dtype", None) == jnp.float64:
            key = (tuple(aval.shape), rec.scope)
            if key in seen64:
                continue
            seen64.add(key)
            if len(findings) >= _MAX_SITES:
                suppressed64 += 1
                continue
            findings.append(Finding(
                rule="R002", severity=ERROR, scenario=program.name,
                message=f"float64 value {tuple(aval.shape)} in traced program",
                location=rec.scope))
    findings = _cap(findings, "R002", program.name, suppressed64)

    warned = set()
    for rec in analyzer.records():
        if rec.primitive != "convert_element_type":
            continue
        src = getattr(rec.eqn.invars[0].aval, "dtype", None)
        dst = rec.eqn.params.get("new_dtype")
        if src is None or dst is None:
            continue
        sw, dw = _FLOAT_WIDTH.get(str(src)), _FLOAT_WIDTH.get(str(np.dtype(dst)))
        if sw is None or dw is None or dw <= sw:
            continue  # not a float upcast
        key = f"{src}->{np.dtype(dst)} @ {rec.scope}"
        attribution[key] = attribution.get(key, 0) + 1
        scope_l = rec.scope.lower()
        if parity and not any(a in scope_l for a in allowlist) and key not in warned:
            warned.add(key)
            if sum(1 for f in findings if f.severity == WARN) < _MAX_SITES:
                findings.append(Finding(
                    rule="R002", severity=WARN, scenario=program.name,
                    message=f"silent float upcast {src}->{np.dtype(dst)} outside "
                            f"precision allowlist on parity path",
                    location=rec.scope))
    if attribution:
        analyzer.metrics["precision_attribution"] = dict(
            sorted(attribution.items(), key=lambda kv: -kv[1]))
    return findings


# ---------------------------------------------------------------------------
_HOST_PRIMS = {
    "device_put": ERROR,  # host<->device copy inside the step: a sync + a
    # transfer every dispatch
    "io_callback": ERROR,
    "pure_callback": ERROR,
    "outside_call": ERROR,
    "infeed": ERROR,
    "outfeed": ERROR,
    "debug_callback": WARN,  # jax.debug.print/callback: host sync per step
}


@rule("R003", "no host transfer/callback inside a jitted step", ERROR, LAYER_JAXPR)
def r003_host_transfer(program, analyzer):
    """A ``device_put`` or host callback traced INTO the step program
    forces a host round-trip every dispatch — the exact class of silent
    step-time regression the MFU campaign (ROADMAP item 3) cannot afford.
    Host staging belongs outside the step (``_shard_batch``), not inside
    it. ``metadata["allow_callbacks"]`` exempts named primitives for
    programs that intentionally stream (e.g. offload paths)."""
    allowed = set(program.metadata.get("allow_callbacks", ()))
    findings, suppressed = [], 0
    for rec in analyzer.records():
        sev = _HOST_PRIMS.get(rec.primitive)
        if sev is None or rec.primitive in allowed:
            continue
        if len(findings) >= _MAX_SITES:
            suppressed += 1
            continue
        findings.append(Finding(
            rule="R003", severity=sev, scenario=program.name,
            message=f"host primitive '{rec.primitive}' inside traced step",
            location=rec.scope))
    return _cap(findings, "R003", program.name, suppressed)


# ---------------------------------------------------------------------------
@rule("R004", "large fwd activation outside the remat policy", WARN, LAYER_JAXPR)
def r004_remat_coverage(program, analyzer):
    """When a program uses remat at all (or the scenario declares
    ``expect_remat``), every activation above ``remat_threshold_bytes``
    (default 16 MiB) produced OUTSIDE a remat region is a residual the
    autodiff must hold live across the backward — exactly the non-matmul
    HBM sink the MFU campaign's remat-policy lever targets (ROADMAP 3a).
    Inside-remat values are rematerialized, not saved. Judged on the
    FORWARD program: under ``grad``'s partial-eval the covered primal is
    inlined to the top level, so coverage is only visible pre-transform
    (scenario builders hand R004 fwd jaxprs; on fwd+bwd programs the rule
    still flags genuinely uncovered fwd activations, plus their inlined
    shadows — same shapes, same fix)."""
    threshold = int(program.metadata.get("remat_threshold_bytes", 16 << 20))
    uses_remat = any(r.in_remat or r.primitive.startswith(("remat", "checkpoint"))
                     for r in analyzer.records())
    if not uses_remat and not program.metadata.get("expect_remat"):
        return []
    findings, seen, suppressed = [], set(), 0
    for rec, aval in analyzer.iter_avals(outputs_only=True):
        if rec.in_remat or rec.primitive.startswith(("remat", "checkpoint")):
            continue
        nbytes = aval_bytes(aval)
        if nbytes <= threshold:
            continue
        key = tuple(aval.shape)
        if key in seen:
            continue
        seen.add(key)
        if len(findings) >= _MAX_SITES:
            suppressed += 1
            continue
        findings.append(Finding(
            rule="R004", severity=WARN, scenario=program.name,
            message=f"activation {tuple(aval.shape)} ({nbytes >> 20} MiB) produced "
                    f"outside remat coverage (threshold {threshold >> 20} MiB)",
            location=rec.scope))
    return _cap(findings, "R004", program.name, suppressed)


# ---------------------------------------------------------------------------
@rule("R005", "step programs must donate their state buffers", ERROR, LAYER_HLO)
def r005_donation(program, analyzer):
    """A train step that does not donate its state doubles peak HBM (old
    + new TrainState live across the update) — the single largest static
    memory lever the engine owns (``donate_argnums`` on every step fn).
    Checked at the HLO layer, where donation is visible as
    ``tf.aliasing_output``/``jax.buffer_donor`` argument attributes; a
    duplicate output alias (two args donated into one output) would be
    the aliased-donation corruption class from utils/device.py."""
    if not program.metadata.get("expect_donation"):
        return []
    hlo = program.hlo_text
    if hlo is None:
        return [Finding(rule="R005", severity=INFO, scenario=program.name,
                        message="expect_donation set but no lowered HLO attached; "
                                "donation not verifiable at the jaxpr layer alone")]
    findings = []
    if "tf.aliasing_output" not in hlo and "jax.buffer_donor" not in hlo:
        findings.append(Finding(
            rule="R005", severity=ERROR, scenario=program.name,
            message="no donated buffers in lowered step program "
                    "(missing tf.aliasing_output/jax.buffer_donor): "
                    "old+new state both live across the update"))
    else:
        import re
        targets = re.findall(r"tf\.aliasing_output\s*=\s*(\d+)", hlo)
        dupes = {t for t in targets if targets.count(t) > 1}
        if dupes:
            findings.append(Finding(
                rule="R005", severity=ERROR, scenario=program.name,
                message=f"multiple arguments donate into output(s) {sorted(dupes)} — "
                        f"aliased donation"))
    return findings


# ---------------------------------------------------------------------------
@rule("R006", "no weak-typed (python scalar) program inputs", WARN, LAYER_JAXPR)
def r006_weak_types(program, analyzer):
    """A weak-typed top-level input means a raw python scalar reached the
    traced signature: the jit cache then keys on the scalar's *value
    class*, and a later call with a numpy/jnp scalar (or a different
    python type) silently recompiles the whole step — the recompilation
    hazard class behind 'why did step 1000 take 40 s'."""
    findings = []
    for i, v in enumerate(analyzer.top_invars()):
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            findings.append(Finding(
                rule="R006", severity=WARN, scenario=program.name,
                message=f"program input {i} is weak-typed "
                        f"({getattr(aval, 'dtype', '?')}) — python scalar leaked "
                        f"into the traced signature",
                location=f"invar[{i}]"))
    return findings


# ---------------------------------------------------------------------------
@rule("R007", "large intermediates need sharding on multi-device meshes", WARN, LAYER_JAXPR)
def r007_sharding_coverage(program, analyzer):
    """On a >1-device mesh, a program with NO sharding evidence anywhere
    (no ``sharding_constraint``, no ``shard_map``, no sharded pjit
    binding) leaves GSPMD free to replicate every large intermediate —
    an implicit all-gather per step. Declared via scenario metadata
    ``multi_device``; ``shard_threshold_bytes`` (default 8 MiB) bounds
    what counts as large."""
    if not program.metadata.get("multi_device"):
        return []
    if analyzer.has_sharding_evidence():
        return []
    threshold = int(program.metadata.get("shard_threshold_bytes", 8 << 20))
    findings, seen, suppressed = [], set(), 0
    for rec, aval in analyzer.iter_avals(outputs_only=True):
        nbytes = aval_bytes(aval)
        if nbytes <= threshold:
            continue
        key = tuple(aval.shape)
        if key in seen:
            continue
        seen.add(key)
        if len(findings) >= _MAX_SITES:
            suppressed += 1
            continue
        findings.append(Finding(
            rule="R007", severity=WARN, scenario=program.name,
            message=f"unsharded intermediate {tuple(aval.shape)} ({nbytes >> 20} MiB) "
                    f"in a multi-device program with no sharding constraints",
            location=rec.scope))
    return _cap(findings, "R007", program.name, suppressed)


# ---------------------------------------------------------------------------
@rule("R015", "telemetry must not enter the traced step program", ERROR, LAYER_JAXPR)
def r015_telemetry_identity(program, analyzer):
    """graft-trace (runtime/telemetry) instruments HOST phases only: spans
    wrap staging/dispatch/wait around the jitted step, never inside it. A
    single stray ``io_callback``/``debug_print``/eager sync traced into
    the step would silently tax every dispatch (the R003 class) — so the
    ``train_batch_telemetry`` scenario stamps ``expect_eqn_count``, the
    recursive eqn count of the SAME engine program traced telemetry-off,
    and this rule fails on any divergence. Zero tolerance on purpose: the
    two traces differ only by the telemetry config block, so any eqn
    delta IS instrumentation leaking into the compiled program."""
    expected = program.metadata.get("expect_eqn_count")
    if expected is None:
        return []
    actual = len(analyzer.records())
    if actual != int(expected):
        return [Finding(
            rule="R015", severity=ERROR, scenario=program.name,
            message=f"traced step has {actual} eqns but its telemetry-off twin "
                    f"has {expected} — instrumentation entered the compiled program",
            location="<jaxpr>")]
    return []
