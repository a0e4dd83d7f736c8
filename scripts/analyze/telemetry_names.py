"""Checker 2: the telemetry-name contract.

Metric names are string literals minted in C++ (`telemetry.h` stage
accessors, direct `Registry::Get()->counter("...")` sites) and in Python
(`telemetry.counter_add("...")`, `depth_gauge="..."` and a span's
`total="..."` kwargs, the stall-attribution read sites).  The public contract is the "Metric name
contract" table in doc/observability.md, and every name must also survive
the mechanical Prometheus mapping in telemetry_http.py.  Checked:

  * every name used in code is documented (doc/observability.md table)
  * every documented name is used in code (no stale rows)
  * one name is never used as two different kinds (counter vs gauge)
  * no two names collide after the Prometheus sanitize+suffix mapping,
    and every mapped family is a valid Prometheus metric name
"""
from __future__ import annotations

import re
from pathlib import Path

from .common import (Finding, line_of, read_text, rel, table_backticks)

TELEMETRY_HEADER = "cpp/include/dmlctpu/telemetry.h"
DOC = "doc/observability.md"
DOC_SECTION = "Metric name contract"
METRIC_SHAPE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

STAGE_MACRO_RE = re.compile(
    r'DMLCTPU_STAGE_(COUNTER|GAUGE|HISTOGRAM)\(\s*\w+\s*,\s*"([^"]+)"\s*\)')
CPP_DIRECT_RE = re.compile(r'->\s*(counter|gauge|histogram)\(\s*"([^"]+)"\s*\)')
# telemetry.py's public helpers; names may wrap to the next line
PY_CALL_RE = re.compile(
    r'\b(counter_add|counter_get|gauge_set|gauge_add|gauge_get)\(\s*'
    r'"([^"]+)"', re.S)
PY_KWARG_RE = re.compile(r'depth_gauge\s*=\s*"([^"]+)"')
# the counter a span keeps its total in: span(..., total="x.y_us")
PY_TOTAL_RE = re.compile(r'\btotal\s*=\s*"([^"]+)"')
# stall_attribution read sites in telemetry.py: d.get("x.y"), us("x.y"),
# and the ("stage", "busy", "wait") contract tuples
PY_READ_RE = re.compile(r'(?:\.get|\bus)\(\s*"([a-z0-9_.]+)"')
PY_TUPLE_RE = re.compile(r'\(\s*"\w+"\s*,\s*"([a-z0-9_.]+)"\s*,\s*'
                         r'"([a-z0-9_.]+)"\s*\)')

KIND = {"COUNTER": "counter", "GAUGE": "gauge", "HISTOGRAM": "histogram",
        "counter": "counter", "gauge": "gauge", "histogram": "histogram",
        "counter_add": "counter", "counter_get": "counter",
        "gauge_set": "gauge", "gauge_add": "gauge", "gauge_get": "gauge"}


def _sanitize(name: str) -> str:
    """Mirror of telemetry_http._sanitize — keep in lockstep."""
    out = [ch if ch.isalnum() or ch == "_" else "_" for ch in name]
    base = "".join(out)
    return base if not base or not base[0].isdigit() else "_" + base


def harvest(root: Path) -> dict[str, list[tuple[str, int, str]]]:
    """name -> [(relpath, line, kind)] over every code-side usage site."""
    uses: dict[str, list[tuple[str, int, str]]] = {}

    def add(name: str, path: str, line: int, kind: str) -> None:
        if METRIC_SHAPE.match(name):
            uses.setdefault(name, []).append((path, line, kind))

    cpp_files = sorted((root / "cpp").rglob("*.h")) + \
        sorted((root / "cpp").rglob("*.cc")) if (root / "cpp").is_dir() else []
    for p in cpp_files:
        if "tests" in p.parts:
            continue  # test-local fixture names are not the public contract
        text = read_text(p)
        for m in STAGE_MACRO_RE.finditer(text):
            add(m.group(2), rel(root, p), line_of(text, m.start()),
                KIND[m.group(1)])
        for m in CPP_DIRECT_RE.finditer(text):
            add(m.group(2), rel(root, p), line_of(text, m.start()),
                KIND[m.group(1)])

    pkg = root / "dmlc_core_tpu"
    py_files = sorted(pkg.rglob("*.py")) if pkg.is_dir() else []
    for p in py_files:
        if "__pycache__" in p.parts:
            continue
        text = read_text(p)
        rpath = rel(root, p)
        for m in PY_CALL_RE.finditer(text):
            add(m.group(2), rpath, line_of(text, m.start()), KIND[m.group(1)])
        for m in PY_KWARG_RE.finditer(text):
            add(m.group(1), rpath, line_of(text, m.start()), "gauge")
        for m in PY_TOTAL_RE.finditer(text):
            add(m.group(1), rpath, line_of(text, m.start()), "counter")
        if p.name == "telemetry.py":
            for m in PY_READ_RE.finditer(text):
                add(m.group(1), rpath, line_of(text, m.start()), "read")
            for m in PY_TUPLE_RE.finditer(text):
                add(m.group(1), rpath, line_of(text, m.start()), "read")
                add(m.group(2), rpath, line_of(text, m.start()), "read")
    return uses


def documented(root: Path) -> dict[str, int]:
    doc = root / DOC
    if not doc.is_file():
        return {}
    names: dict[str, int] = {}
    for line, tok in table_backticks(read_text(doc), DOC_SECTION):
        if METRIC_SHAPE.match(tok) and not tok.endswith((".h", ".py", ".cc",
                                                         ".md")):
            names.setdefault(tok, line)
    return names


def check(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    uses = harvest(root)
    docs = documented(root)
    if not docs and not (root / DOC).is_file():
        return [Finding(DOC, 1, "telemetry", f"{DOC} not found")]

    for name in sorted(uses):
        if name not in docs:
            path, line, _ = uses[name][0]
            findings.append(Finding(
                path, line, "telemetry",
                f'metric "{name}" is used here but missing from the '
                f'"{DOC_SECTION}" table in {DOC}'))
        kinds = {k for _, _, k in uses[name] if k != "read"}
        if len(kinds) > 1:
            path, line, _ = uses[name][0]
            findings.append(Finding(
                path, line, "telemetry",
                f'metric "{name}" is used as conflicting kinds: '
                f'{sorted(kinds)}'))
    for name, line in sorted(docs.items()):
        if name not in uses:
            findings.append(Finding(
                DOC, line, "telemetry",
                f'documented metric "{name}" has no code usage site '
                f'(stale contract row)'))

    # Prometheus mapping: family names must be unique and well-formed
    prom_name = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    fams: dict[str, str] = {}
    for name in sorted(uses):
        kinds = {k for _, _, k in uses[name] if k != "read"} or {"counter"}
        kind = sorted(kinds)[0]
        fam = "dmlctpu_" + _sanitize(name)
        if kind == "counter":
            fam += "_total"
        if not prom_name.match(fam):
            path, line, _ = uses[name][0]
            findings.append(Finding(
                path, line, "telemetry",
                f'metric "{name}" maps to invalid Prometheus family '
                f'"{fam}"'))
        if fam in fams and fams[fam] != name:
            path, line, _ = uses[name][0]
            findings.append(Finding(
                path, line, "telemetry",
                f'metrics "{fams[fam]}" and "{name}" collide on Prometheus '
                f'family "{fam}"'))
        fams.setdefault(fam, name)
    return findings
