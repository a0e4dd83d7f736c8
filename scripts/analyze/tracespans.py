"""Checker 6: the trace-span name contract.

Span names are string literals minted at C++ ``ScopedSpan``/``RecordSpan``
sites and at Python ``telemetry.span(...)``/``telemetry.record_span(...)``
sites.  They are the vocabulary the job-trace merge and the Perfetto
recipes in doc/observability.md are written against, so — like metric
names — they are a cross-layer contract.  Checked both directions, the
same discipline as the telemetry checker:

  * every span name used in code appears in the "Trace span contract"
    table in doc/observability.md
  * every documented span name has a code usage site (no stale rows)
  * span names share the metric-name shape (dotted lowercase) so trace
    tooling can group them by stage prefix

Device scopes (``jax.named_scope("...")`` literals in the package) are the
same kind of contract for the device's side of a profile: an XLA instruction
carries its scope path in the trace, and the benchmark's per-layer metrics
(``benchmark/layer_metrics/*.json``, reader ``trace_scope``) select device
time by it.  Checked three ways against the "Device scope contract" table
(first cell of each row) in doc/observability.md:

  * every scope opened in code is a row, and has the dotted-lowercase shape
  * every row has a code site
  * every alternative of a layer metric's ``scope`` / ``scoped`` pattern is
    a row — or a program selector (it starts ``^jit``), which names no scope
"""
from __future__ import annotations

import json
import re
from pathlib import Path

from .common import (Finding, line_of, read_text, rel, strip_cxx_comments,
                     table_backticks)

DOC = "doc/observability.md"
DOC_SECTION = "Trace span contract"
SPAN_SHAPE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

CPP_SCOPED_RE = re.compile(r'ScopedSpan\s+\w+\s*\(\s*"([^"]+)"\s*\)')
CPP_RECORD_RE = re.compile(r'\bRecordSpan\s*\(\s*"([^"]+)"')
PY_SPAN_RE = re.compile(r'\b(?:telemetry\.)?(?:span|record_span)\(\s*'
                        r'"([^"]+)"')


SCOPE_SECTION = "Device scope contract"
PY_SCOPE_RE = re.compile(r'\bnamed_scope\(\s*"([^"]+)"')
LAYER_METRICS = "benchmark/layer_metrics"
PROGRAM_SELECTOR = "^jit"


def harvest(root: Path) -> dict[str, list[tuple[str, int]]]:
    """span name -> [(relpath, line)] over every code-side usage site."""
    uses: dict[str, list[tuple[str, int]]] = {}

    def add(name: str, path: str, line: int) -> None:
        uses.setdefault(name, []).append((path, line))

    cpp_files = sorted((root / "cpp").rglob("*.h")) + \
        sorted((root / "cpp").rglob("*.cc")) if (root / "cpp").is_dir() else []
    for p in cpp_files:
        if "tests" in p.parts:
            continue  # test-local span names are not the public contract
        text = strip_cxx_comments(read_text(p))
        for regex in (CPP_SCOPED_RE, CPP_RECORD_RE):
            for m in regex.finditer(text):
                add(m.group(1), rel(root, p), line_of(text, m.start()))

    pkg = root / "dmlc_core_tpu"
    py_files = sorted(pkg.rglob("*.py")) if pkg.is_dir() else []
    for p in py_files:
        if "__pycache__" in p.parts:
            continue
        text = read_text(p)
        for m in PY_SPAN_RE.finditer(text):
            add(m.group(1), rel(root, p), line_of(text, m.start()))
    return uses


def documented(root: Path) -> dict[str, int]:
    doc = root / DOC
    if not doc.is_file():
        return {}
    names: dict[str, int] = {}
    for line, tok in table_backticks(read_text(doc), DOC_SECTION):
        if SPAN_SHAPE.match(tok) and not tok.endswith((".h", ".py", ".cc",
                                                       ".md")):
            names.setdefault(tok, line)
    return names


def harvest_scopes(root: Path) -> dict[str, tuple[str, int]]:
    """scope name -> first (relpath, line) that opens it."""
    uses: dict[str, tuple[str, int]] = {}
    pkg = root / "dmlc_core_tpu"
    for p in sorted(pkg.rglob("*.py")) if pkg.is_dir() else []:
        if "__pycache__" in p.parts:
            continue
        text = read_text(p)
        for m in PY_SCOPE_RE.finditer(text):
            uses.setdefault(m.group(1),
                            (rel(root, p), line_of(text, m.start())))
    return uses


def documented_scopes(root: Path) -> dict[str, int]:
    """First backticked cell of each row of the scope table -> line."""
    doc = root / DOC
    names: dict[str, int] = {}
    seen_lines: set[int] = set()
    if doc.is_file():
        for line, tok in table_backticks(read_text(doc), SCOPE_SECTION):
            if line not in seen_lines:
                seen_lines.add(line)
                names.setdefault(tok, line)
    return names


def metric_scopes(root: Path) -> list[tuple[str, str]]:
    """(relpath, scope) for every alternative a ``trace_scope`` layer
    metric selects by, unescaped; program selectors left out."""
    out = []
    base = root / LAYER_METRICS
    for p in sorted(base.glob("*.json")) if base.is_dir() else []:
        spec = json.loads(read_text(p))
        if spec.get("reader") != "trace_scope":
            continue
        args = spec.get("args", {})
        for pattern in [args.get("scope", "")] + list(args.get("scoped", [])):
            for alt in pattern.split("|"):
                if alt and not alt.startswith(PROGRAM_SELECTOR):
                    out.append((rel(root, p), alt.replace("\\.", ".")))
    return out


def check_scopes(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    uses = harvest_scopes(root)
    docs = documented_scopes(root)
    wanted = metric_scopes(root)
    if (uses or wanted) and not docs:
        return [Finding(DOC, 1, "tracespans",
                        f'no "{SCOPE_SECTION}" table found in {DOC}')]
    for name, (path, line) in sorted(uses.items()):
        if not SPAN_SHAPE.match(name):
            findings.append(Finding(
                path, line, "tracespans",
                f'scope "{name}" does not match the dotted-lowercase '
                f'name shape'))
        elif name not in docs:
            findings.append(Finding(
                path, line, "tracespans",
                f'scope "{name}" is opened here but missing from the '
                f'"{SCOPE_SECTION}" table in {DOC}'))
    for name, line in sorted(docs.items()):
        if name not in uses:
            findings.append(Finding(
                DOC, line, "tracespans",
                f'documented scope "{name}" has no code site '
                f'(stale contract row)'))
    for path, name in wanted:
        if name not in docs:
            findings.append(Finding(
                path, 1, "tracespans",
                f'layer metric selects the scope "{name}", which is no row '
                f'of the "{SCOPE_SECTION}" table in {DOC}'))
    return findings


def check(root: Path) -> list[Finding]:
    findings: list[Finding] = check_scopes(root)
    uses = harvest(root)
    docs = documented(root)
    if not docs and not (root / DOC).is_file():
        return [Finding(DOC, 1, "tracespans", f"{DOC} not found")]
    if not docs:
        return [Finding(DOC, 1, "tracespans",
                        f'no "{DOC_SECTION}" table found in {DOC}')]

    for name in sorted(uses):
        path, line = uses[name][0]
        if not SPAN_SHAPE.match(name):
            findings.append(Finding(
                path, line, "tracespans",
                f'span "{name}" does not match the dotted-lowercase '
                f'name shape'))
            continue
        if name not in docs:
            findings.append(Finding(
                path, line, "tracespans",
                f'span "{name}" is recorded here but missing from the '
                f'"{DOC_SECTION}" table in {DOC}'))
    for name, line in sorted(docs.items()):
        if name not in uses:
            findings.append(Finding(
                DOC, line, "tracespans",
                f'documented span "{name}" has no code usage site '
                f'(stale contract row)'))
    return findings
