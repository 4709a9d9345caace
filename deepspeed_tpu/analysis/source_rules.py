"""Source-layer (AST) rule R008 + the inline-waiver comment scanner.

The jaxpr rules see what got traced; this pass sees what *can't* be
traced — host-side API misuse. Two bans, both from hard-won container
folklore:

* raw ``jax.device_put`` anywhere in the package (outside
  ``utils/device.py`` itself): placement of host data that a step will
  donate goes through one audited seam, ``owned_device_put`` (a zero-copy
  ``device_put`` aliases host memory on the CPU backend; the seam's
  docstring records what was checked about donating it). Audited-safe
  sites (jax-owned sources, device->device
  resharding) carry an inline waiver:

      jax.device_put(x, sharding)  # graft-lint: waive R008 jax-owned source

* ``time.time()``/``time.perf_counter()``/``np.random``/``random.*``
  inside a ``@jax.jit``-decorated body: traced once at compile time,
  frozen forever after — the classic "my timestamps/noise never change"
  bug.
"""

import ast
import re
from typing import Iterable, List, Tuple

from deepspeed_tpu.analysis.core import ERROR, LAYER_AST, Finding, rule

WAIVE_RE = re.compile(r"#\s*graft-lint:\s*waive\s+(R\d{3})(?:\s+(.*))?")

#: files allowed to call jax.device_put directly (the safe wrapper itself)
DEVICE_PUT_ALLOWED = ("utils/device.py",)


def line_waivers(source: str):
    """{lineno: (rule_id, reason)} for inline waiver comments."""
    out = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = WAIVE_RE.search(line)
        if m:
            out[i] = (m.group(1), (m.group(2) or "").strip())
    return out


def _string_literal_lines(tree) -> set:
    """Line numbers covered by string constants (docstrings): a waiver
    pattern in there is documentation of the syntax, not a waiver."""
    lines = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            end = getattr(node, "end_lineno", node.lineno)
            lines.update(range(node.lineno, end + 1))
    return lines


def stale_inline_waivers(files, findings) -> List[dict]:
    """Inline ``# graft-lint: waive`` comments that sit on a line no
    current finding points at — the code they excused moved or was fixed,
    and a stale comment on the wrong line could silently excuse the NEXT
    edit. Reported as WARNs by the CLI (mirror of
    :func:`core.stale_config_waivers` for the AST layer)."""
    locations = {f.location for f in findings}
    out = []
    for rel, source, tree in files:
        doc_lines = _string_literal_lines(tree)
        for line, (rule_id, reason) in line_waivers(source).items():
            if line not in doc_lines and f"{rel}:{line}" not in locations:
                out.append({"kind": "inline", "file": rel, "line": line,
                            "rule": rule_id, "reason": reason})
    return out


def _dotted(node) -> str:
    """'jax.device_put' for Attribute/Name chains, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_decorator(dec) -> bool:
    """Matches @jax.jit, @jit, @partial(jax.jit, ...), @functools.partial(jit, ...),
    and @jax.jit(...) call forms."""
    if isinstance(dec, ast.Call):
        name = _dotted(dec.func)
        if name.endswith("partial"):
            return any(_is_jit_decorator(a) for a in dec.args)
        dec_name = name
    else:
        dec_name = _dotted(dec)
    return dec_name in ("jit", "jax.jit", "pjit", "jax.pjit")


_FROZEN_HOST_CALLS = ("time.time", "time.perf_counter", "time.monotonic",
                      "datetime.now", "datetime.datetime.now")
_FROZEN_HOST_PREFIXES = ("np.random.", "numpy.random.", "random.")


def _frozen_host_call(name: str) -> bool:
    return name in _FROZEN_HOST_CALLS or any(name.startswith(p) for p in _FROZEN_HOST_PREFIXES)


@rule("R008", "raw jax.device_put / frozen host state in jitted bodies", ERROR, LAYER_AST)
def r008_source(files: Iterable[Tuple[str, str, ast.Module]]) -> List[Finding]:
    """See module docstring. ``files``: (relpath, source, parsed module)."""
    findings = []
    for relpath, source, tree in files:
        waivers = line_waivers(source)

        def emit(lineno, message, _rel=relpath, _w=waivers):
            w = _w.get(lineno)
            waived = bool(w and w[0] == "R008")
            findings.append(Finding(
                rule="R008", severity=ERROR, scenario=_rel, message=message,
                location=f"{_rel}:{lineno}", waived=waived,
                waiver_reason=w[1] if waived else ""))

        device_put_ok = any(relpath.endswith(a) for a in DEVICE_PUT_ALLOWED)
        # names bound by `from jax import device_put [as alias]`
        dp_aliases = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "jax":
                for a in node.names:
                    if a.name == "device_put":
                        dp_aliases.add(a.asname or a.name)

        jit_stack: List[bool] = []

        class V(ast.NodeVisitor):
            def _visit_fn(self, node):
                jitted = any(_is_jit_decorator(d) for d in node.decorator_list)
                jit_stack.append(bool(jitted or (jit_stack and jit_stack[-1])))
                self.generic_visit(node)
                jit_stack.pop()

            visit_FunctionDef = _visit_fn
            visit_AsyncFunctionDef = _visit_fn

            def visit_Call(self, node):
                name = _dotted(node.func)
                if not device_put_ok and (name == "jax.device_put" or name in dp_aliases):
                    emit(node.lineno,
                         "raw jax.device_put — use "
                         "deepspeed_tpu.utils.device.owned_device_put (the "
                         "audited placement seam) or waive with an audit note")
                if jit_stack and jit_stack[-1] and _frozen_host_call(name):
                    emit(node.lineno,
                         f"'{name}' inside a @jit-decorated body is evaluated "
                         f"once at trace time and frozen into the compiled program")
                self.generic_visit(node)

        V().visit(tree)
    return findings
