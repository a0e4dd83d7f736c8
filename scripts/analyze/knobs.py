"""Checker 3: the fault-point registry and the DMLCTPU_* env-knob registry.

Fault points are armed BY NAME from spec strings ("shard.worker.chunk=err@
0.02;seed=3") in tests, check.sh tiers, and docs; the registration site is a
DMLCTPU_FAULT_POINT macro in cpp/.  Env knobs are read by name via getenv /
GetEnv / env_i64 / os.environ and set by name in scripts, tests, and docs.
Both directions are enforced:

  * every fault point named in a spec anywhere must be registered in cpp/
  * the fault-point table in doc/robustness.md must list exactly the
    registered set
  * every DMLCTPU_* token used anywhere (read, set, or documented) must be
    a row of the canonical knob registry in doc/analysis.md
  * every `env` registry row must have a real read site; every `build` row
    must appear in the build system; rows with neither are stale
"""
from __future__ import annotations

import re
from pathlib import Path

from .common import (Finding, iter_source_files, line_of, read_text, rel,
                     table_backticks)

ROBUSTNESS_DOC = "doc/robustness.md"
REGISTRY_DOC = "doc/analysis.md"
REGISTRY_SECTION = "Env knob registry"

SCAN_DIRS = ["cpp", "dmlc_core_tpu", "tests", "scripts", "doc", "examples"]
SCAN_SUFFIXES = (".h", ".cc", ".py", ".sh", ".md")
SCAN_EXTRA = ["CMakeLists.txt", "Makefile"]

FAULT_POINT_REG_RE = re.compile(r'DMLCTPU_FAULT_POINT\(\s*\w+\s*,\s*"([^"]+)"')
FAULT_SPEC_USE_RE = re.compile(
    r'([a-z][a-z0-9_.]*)=(?:err|eof|503|5xx|corrupt)@')

# A DMLCTPU_* token only counts as a knob USE in an env-read, env-set, or
# build-define context.  Bare identifier mentions — code macros like
# DMLCTPU_LIKELY, include guards, CMake list variables — are not knobs.
ENV_READ_RES = [
    re.compile(r'getenv\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),          # C getenv
    re.compile(r'GetEnv\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),          # util helper
    re.compile(r'\b_?env_\w+\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),     # env_i64 etc.
    re.compile(r'os\.environ\.get\(\s*\n?\s*"(DMLCTPU_[A-Z0-9_]+)"', re.S),
    re.compile(r'os\.getenv\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),
    re.compile(r'os\.environ\[\s*"(DMLCTPU_[A-Z0-9_]+)"\s*\](?!\s*=[^=])'),
]
# the ${X} form is a read only in shell; in CMakeLists it is variable deref
SH_READ_RE = re.compile(r'\$\{(DMLCTPU_[A-Z0-9_]+)[:\-\}]')
ENV_SET_RES = [
    re.compile(r'os\.environ\[\s*"(DMLCTPU_[A-Z0-9_]+)"\s*\]\s*=[^=]'),
    re.compile(r'os\.environ\.setdefault\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),
    re.compile(r'monkeypatch\.setenv\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),
    re.compile(r'setenv\(\s*"(DMLCTPU_[A-Z0-9_]+)"'),          # C setenv
    # shell / docs: a `VAR=value cmd` prefix or an `export VAR=value`
    re.compile(r'(?:^|\s)(?:export\s+)?(DMLCTPU_[A-Z0-9_]+)=', re.M),
]
BUILD_USE_RES = [
    re.compile(r'-D\s*(DMLCTPU_[A-Z0-9_]+)'),                  # compiler/cmake
    re.compile(r'\b(?:option|set)\(\s*(DMLCTPU_[A-Z0-9_]+)'),  # CMake knobs
]


def registered_fault_points(root: Path) -> dict[str, tuple[str, int]]:
    points: dict[str, tuple[str, int]] = {}
    cpp = root / "cpp"
    files = sorted(cpp.rglob("*.h")) + sorted(cpp.rglob("*.cc")) \
        if cpp.is_dir() else []
    for p in files:
        if p.name == "fault.h":
            continue  # the macro's own definition, not a registration
        text = read_text(p)
        for m in FAULT_POINT_REG_RE.finditer(text):
            points.setdefault(m.group(1),
                              (rel(root, p), line_of(text, m.start())))
    return points


def knob_registry(root: Path) -> dict[str, tuple[int, str]]:
    """knob -> (line, kind) from the doc/analysis.md registry table.  Kind is
    the second backticked token of the row (`env`, `build`, `env+build`)."""
    doc = root / REGISTRY_DOC
    if not doc.is_file():
        return {}
    rows: dict[str, tuple[int, str]] = {}
    by_line: dict[int, list[str]] = {}
    for line, tok in table_backticks(read_text(doc), REGISTRY_SECTION):
        by_line.setdefault(line, []).append(tok)
    for line, toks in by_line.items():
        knobs = [t for t in toks if t.startswith("DMLCTPU_")]
        kinds = [t for t in toks if t in ("env", "build", "env+build")]
        for k in knobs:
            rows[k] = (line, kinds[0] if kinds else "env")
    return rows


def check(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    files = iter_source_files(root, SCAN_DIRS, SCAN_SUFFIXES, SCAN_EXTRA)
    texts = {p: read_text(p) for p in files}

    # ---- fault points -------------------------------------------------------
    registered = registered_fault_points(root)
    for p, text in texts.items():
        for m in FAULT_SPEC_USE_RE.finditer(text):
            point = m.group(1)
            if "." not in point:
                continue  # spec-grammar examples like "<point>=err@..."
            if point not in registered:
                findings.append(Finding(
                    rel(root, p), line_of(text, m.start()), "knobs",
                    f'fault point "{point}" is armed here but never '
                    f'registered via DMLCTPU_FAULT_POINT in cpp/'))
    rb = root / ROBUSTNESS_DOC
    if rb.is_file():
        doc_points = {tok: line for line, tok in
                      table_backticks(read_text(rb),
                                      "Deterministic fault injection")
                      if re.match(r"^[a-z][a-z0-9_.]*$", tok)
                      and "." in tok and "=" not in tok}
        for name, (path, line) in sorted(registered.items()):
            if name not in doc_points:
                findings.append(Finding(
                    path, line, "knobs",
                    f'fault point "{name}" is registered here but missing '
                    f'from the fault-point table in {ROBUSTNESS_DOC}'))
        for name, line in sorted(doc_points.items()):
            if name not in registered:
                findings.append(Finding(
                    ROBUSTNESS_DOC, line, "knobs",
                    f'documented fault point "{name}" has no '
                    f'DMLCTPU_FAULT_POINT registration in cpp/'))

    # ---- env knobs ----------------------------------------------------------
    registry = knob_registry(root)
    if not registry:
        findings.append(Finding(
            REGISTRY_DOC, 1, "knobs",
            f'no "{REGISTRY_SECTION}" table found in {REGISTRY_DOC}'))
        return findings

    reads: dict[str, tuple[str, int]] = {}
    seen: dict[str, tuple[str, int]] = {}
    for p, text in texts.items():
        if "tests" in p.parts:
            continue  # test fixtures (DMLCTPU_TEST_*, fuzz seeds) are local
        rpath = rel(root, p)
        read_res = list(ENV_READ_RES)
        if p.suffix == ".sh":
            read_res.append(SH_READ_RE)
        for regex in read_res:
            for m in regex.finditer(text):
                reads.setdefault(m.group(1), (rpath, line_of(text, m.start())))
                seen.setdefault(m.group(1), (rpath, line_of(text, m.start())))
        for regex in ENV_SET_RES + BUILD_USE_RES:
            for m in regex.finditer(text):
                seen.setdefault(m.group(1), (rpath, line_of(text, m.start())))

    build_files = [root / "CMakeLists.txt", root / "Makefile"]
    build_text = "\n".join(read_text(p) for p in build_files if p.is_file())
    cpp_macro_text = "\n".join(
        t for p, t in texts.items() if p.suffix in (".h", ".cc"))

    for tok, (path, line) in sorted(seen.items()):
        if tok not in registry:
            findings.append(Finding(
                path, line, "knobs",
                f'`{tok}` is used here but is not a row of the '
                f'"{REGISTRY_SECTION}" table in {REGISTRY_DOC}'))
    for tok, (line, kind) in sorted(registry.items()):
        env_ok = tok in reads
        build_ok = tok in build_text or f"ifndef {tok}" in cpp_macro_text \
            or f"defined({tok})" in cpp_macro_text
        if kind == "env" and not env_ok:
            findings.append(Finding(
                REGISTRY_DOC, line, "knobs",
                f'registry row `{tok}` (kind env) has no read site '
                f'(getenv/GetEnv/env_i64/os.environ/bash) — stale row?'))
        elif kind == "build" and not build_ok:
            findings.append(Finding(
                REGISTRY_DOC, line, "knobs",
                f'registry row `{tok}` (kind build) does not appear in the '
                f'build system or as a cpp macro — stale row?'))
        elif kind == "env+build" and not (env_ok or build_ok):
            findings.append(Finding(
                REGISTRY_DOC, line, "knobs",
                f'registry row `{tok}` has neither a read site nor a build '
                f'definition — stale row?'))
    return findings
