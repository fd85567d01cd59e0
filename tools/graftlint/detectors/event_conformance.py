"""Detector 6: flight-recorder event-kind conformance.

Every lifecycle event the flight recorder may journal is declared once, in
``dynamo_tpu/utils/events.py`` (``DECLARED_EVENT_KINDS``) — the same tuple
``emit()`` enforces at runtime (ValueError on an unknown kind). This detector
is the *static* half of that contract, the exact mirror of
metric-conformance:

  - every ``*.emit("<kind>")`` string-literal kind at an emitting site must
    be a declared kind — a typo'd kind would otherwise only surface as a
    runtime ValueError on the one code path that emits it;
  - vice versa, every declared kind must have at least one emitting literal
    in the scanned code — a kind nobody emits is dashboard/forensics drift
    waiting to happen.

Only dotted ``<plane>.<decision>`` literals in the first positional argument
of an ``.emit(...)`` call are considered (other emit-like APIs with free-text
arguments don't look like kinds); non-event strings that still collide carry
``# graftlint: event-ok <reason>``. The vice-versa direction only runs when
the declaring module is part of the scan.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass

from tools.graftlint.core import (
    Finding,
    ScanContext,
    SourceFile,
    enclosing_func,
    make_finding,
)

RULE = "event-conformance"

DECLARATION_NAME = "DECLARED_EVENT_KINDS"
DECLARING_MODULE = "dynamo_tpu/utils/events.py"

#: the shape of a kind: ``<plane>.<decision>`` (one dot, snake_case halves)
_KIND_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")


@dataclass
class _Literal:
    sf: SourceFile
    node: ast.Constant
    value: str


def _find_declaration(tree: ast.AST) -> tuple[list[tuple[str, ast.Constant]], set[int]]:
    """(declared (kind, node) pairs, ids of every Constant inside the
    declaration assignment) — declaration literals are not emitting sites."""
    declared: list[tuple[str, ast.Constant]] = []
    decl_ids: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            if not any(
                isinstance(t, ast.Name) and t.id == DECLARATION_NAME for t in targets
            ):
                continue
            if node.value is None:
                continue
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Constant):
                    decl_ids.add(id(sub))
                    if isinstance(sub.value, str) and _KIND_RE.match(sub.value):
                        declared.append((sub.value, sub))
    return declared, decl_ids


def _emit_literals(tree: ast.AST, decl_ids: set[int]) -> list[ast.Constant]:
    """First-positional string literals of ``<anything>.emit(...)`` calls
    that look like event kinds."""
    out: list[ast.Constant] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
            continue
        if node.func.attr != "emit" or not node.args:
            continue
        # the kind argument is usually one literal, but decision sites pick
        # between kinds inline ('prefix_fetch.timeout' if timed_out else
        # 'prefix_fetch.fallback') — every literal inside the argument is an
        # emitting reference
        for arg in ast.walk(node.args[0]):
            if (
                isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)
                and id(arg) not in decl_ids
                and _KIND_RE.match(arg.value)
            ):
                out.append(arg)
    return out


class EventConformanceDetector:
    """Whole-scan detector: literals are collected per file, cross-checked in
    finalize (the vice-versa direction needs the full file set)."""

    rule = RULE

    def scan(self, sf: SourceFile, ctx: ScanContext) -> list[Finding]:
        return []

    def finalize(self, files: list[SourceFile], ctx: ScanContext) -> list[Finding]:
        findings: list[Finding] = []
        declared: dict[str, tuple[SourceFile, ast.Constant]] = {}
        declaring_file_scanned = False
        usages: list[_Literal] = []

        for sf in files:
            decl_pairs, decl_ids = _find_declaration(sf.tree)
            if decl_pairs:
                declaring_file_scanned = True
            for kind, node in decl_pairs:
                declared.setdefault(kind, (sf, node))
            for node in _emit_literals(sf.tree, decl_ids):
                usages.append(_Literal(sf, node, node.value))

        kinds = set(declared)
        referenced: set[str] = set()
        for use in usages:
            if use.value in kinds:
                referenced.add(use.value)
            elif kinds:  # with no declaration in scope, skip direction 1
                findings.extend(
                    make_finding(
                        use.sf,
                        RULE,
                        use.node,
                        f"event kind literal {use.value!r} is not in "
                        f"{DECLARATION_NAME} (utils/events.py) — emit() would "
                        "raise ValueError at runtime; declare the kind or "
                        "mark the call event-ok if it is not a journal emit",
                        enclosing_func(use.sf, use.node),
                    )
                )

        # vice versa: only meaningful when the declaring module was scanned
        if declaring_file_scanned:
            for kind in sorted(kinds - referenced):
                sf, node = declared[kind]
                findings.extend(
                    make_finding(
                        sf,
                        RULE,
                        node,
                        f"declared event kind {kind!r} is emitted by no site "
                        "in the scanned code — dead declaration or missing "
                        "instrumentation",
                        DECLARATION_NAME,
                    )
                )
        return findings
