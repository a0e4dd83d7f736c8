"""Red-path tests for the cross-layer contract analyzer (doc/analysis.md).

Each checker gets a synthetic repo tree containing exactly one planted
violation and must report it at the right file:line; the final test runs
the whole analyzer against this repo and must come back empty — the
contract tables ship in lockstep with the code.

No jax / native library needed: the analyzer is pure text analysis.
"""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "scripts"))

from analyze import (capi, concurrency, knobs, stubparity,  # noqa: E402
                     telemetry_names, tracespans)
from analyze.main import run  # noqa: E402


def _tree(tmp_path: Path, files: dict) -> Path:
    for relpath, content in files.items():
        p = tmp_path / relpath
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
    return tmp_path


def _line(content: str, needle: str) -> int:
    for i, ln in enumerate(content.splitlines(), 1):
        if needle in ln:
            return i
    raise AssertionError(f"needle {needle!r} not in synthetic file")


def _find(findings, path: str, line: int, fragment: str):
    hits = [f for f in findings
            if f.path == path and f.line == line and fragment in f.message]
    assert hits, (
        f"expected a finding at {path}:{line} containing {fragment!r}, "
        f"got: {[f.render() for f in findings]}")
    return hits[0]


def test_capi_arity_mismatch(tmp_path):
    header = (
        "typedef void* DmlcTpuParserHandle;\n"
        "int DmlcTpuFoo(DmlcTpuParserHandle handle, int nrows);\n")
    binding = (
        "import ctypes\n"
        "_LIB = None\n"
        "_LIB.DmlcTpuFoo.argtypes = [ctypes.c_void_p]\n")
    root = _tree(tmp_path, {
        "cpp/include/dmlctpu/c_api.h": header,
        "dmlc_core_tpu/_native.py": binding,
        "doc/api/cpp.md": "DmlcTpuFoo\n",
    })
    findings = capi.check(root)
    _find(findings, "dmlc_core_tpu/_native.py",
          _line(binding, "argtypes"), "arity 1 != header arity 2")


def test_capi_type_mismatch(tmp_path):
    header = "int DmlcTpuBar(const char* uri);\n"
    binding = (
        "import ctypes\n"
        "_LIB = None\n"
        "_LIB.DmlcTpuBar.argtypes = [ctypes.c_int]\n")
    root = _tree(tmp_path, {
        "cpp/include/dmlctpu/c_api.h": header,
        "dmlc_core_tpu/_native.py": binding,
        "doc/api/cpp.md": "DmlcTpuBar\n",
    })
    findings = capi.check(root)
    _find(findings, "dmlc_core_tpu/_native.py",
          _line(binding, "argtypes"), "`const char*` in the header")


def test_telemetry_undocumented_metric(tmp_path):
    src = "void F(Registry* r) {\n  r->counter(\"ghost.metric\");\n}\n"
    doc = ("## Metric name contract\n\n"
           "| Stage | Metrics |\n|---|---|\n| x | `some.other` |\n")
    root = _tree(tmp_path, {
        "cpp/src/metrics.cc": src,
        "doc/observability.md": doc,
    })
    findings = telemetry_names.check(root)
    _find(findings, "cpp/src/metrics.cc", _line(src, "ghost.metric"),
          '"ghost.metric" is used here but missing')
    # and the stale direction: the documented-but-unused row
    _find(findings, "doc/observability.md", _line(doc, "some.other"),
          "stale contract row")


def test_knobs_unregistered_env_var(tmp_path):
    conf = ("import os\n"
            "GOOD = os.environ.get(\"DMLCTPU_GOOD\", \"\")\n"
            "ROGUE = os.environ.get(\"DMLCTPU_ROGUE\", \"\")\n")
    registry = ("## Env knob registry\n\n"
                "| knob | kind | meaning |\n|---|---|---|\n"
                "| `DMLCTPU_GOOD` | `env` | test |\n")
    root = _tree(tmp_path, {
        "dmlc_core_tpu/conf.py": conf,
        "doc/analysis.md": registry,
    })
    findings = knobs.check(root)
    _find(findings, "dmlc_core_tpu/conf.py", _line(conf, "ROGUE"),
          "`DMLCTPU_ROGUE` is used here but is not a row")
    assert not any("DMLCTPU_GOOD" in f.message for f in findings)


def test_knobs_unregistered_fault_point(tmp_path):
    # split so the repo-wide scan doesn't match the literal in THIS file
    test_src = "SPEC = \"ghost.point=" + "err@0.5;seed=1\"\n"
    root = _tree(tmp_path, {"tests/test_x.py": test_src})
    findings = knobs.check(root)
    _find(findings, "tests/test_x.py", 1,
          '"ghost.point" is armed here but never registered')


def test_stubparity_missing_stub(tmp_path):
    header = ("#if DMLCTPU_TELEMETRY\n"
              "void RealOnly();\n"
              "void Both();\n"
              "#else\n"
              "inline void Both() {}\n"
              "#endif\n")
    root = _tree(tmp_path, {"cpp/include/dmlctpu/telemetry.h": header})
    findings = stubparity.check(root)
    _find(findings, "cpp/include/dmlctpu/telemetry.h",
          _line(header, "#else") + 1, "`RealOnly` is declared")
    assert not any("Both" in f.message for f in findings)


def test_concurrency_seqcst_and_bare_wait(tmp_path):
    header = ("struct Q {\n"
              "  void Push() { head_.fetch_add(1); }\n"
              "  void Ok() { head_.fetch_add(1, std::memory_order_relaxed); }\n"
              "  void Wait() { cv_.wait(lk); }\n"
              "  void WaitOk() { cv_.wait(lk, [&] { return ready_; }); }\n"
              "};\n")
    root = _tree(tmp_path, {"cpp/include/dmlctpu/lockfree_queue.h": header})
    findings = concurrency.check(root)
    _find(findings, "cpp/include/dmlctpu/lockfree_queue.h",
          _line(header, "void Push"), "without an explicit memory_order")
    _find(findings, "cpp/include/dmlctpu/lockfree_queue.h",
          _line(header, "void Wait()"), "without a predicate")
    assert len(findings) == 2, [f.render() for f in findings]


def test_tracespans_both_directions(tmp_path):
    src = ('#include "dmlctpu/telemetry.h"\n'
           "void F() {\n"
           '  ScopedSpan sp("ghost.span");\n'
           "}\n")
    pysrc = ("from . import telemetry\n"
             "def g():\n"
             "    with telemetry.span(\"BadShape\"):\n"
             "        pass\n"
             "    with telemetry.span(\"good.span\"):\n"
             "        pass\n")
    doc = ("## Trace spans\n\n"
           "### Trace span contract\n\n"
           "| span | where | meaning |\n|---|---|---|\n"
           "| `good.span` | `x.py` | test |\n"
           "| `stale.span` | `x.py` | never recorded |\n")
    root = _tree(tmp_path, {
        "cpp/src/spans.cc": src,
        "dmlc_core_tpu/work.py": pysrc,
        "doc/observability.md": doc,
    })
    findings = tracespans.check(root)
    _find(findings, "cpp/src/spans.cc", _line(src, "ghost.span"),
          '"ghost.span" is recorded here but missing')
    _find(findings, "dmlc_core_tpu/work.py", _line(pysrc, "BadShape"),
          "dotted-lowercase")
    _find(findings, "doc/observability.md", _line(doc, "stale.span"),
          "stale contract row")
    assert not any("good.span" in f.message for f in findings), \
        [f.render() for f in findings]


def test_tracespans_green_tree(tmp_path):
    pysrc = ("from . import telemetry\n"
             "def g():\n"
             "    with telemetry.span(\"good.span\"):\n"
             "        pass\n")
    doc = ("### Trace span contract\n\n"
           "| span | where | meaning |\n|---|---|---|\n"
           "| `good.span` | `work.py` | test |\n")
    root = _tree(tmp_path, {
        "dmlc_core_tpu/work.py": pysrc,
        "doc/observability.md": doc,
    })
    assert tracespans.check(root) == []


SCOPE_DOC = ("### Trace span contract\n\n"
             "| span | where | meaning |\n|---|---|---|\n"
             "| `good.span` | `work.py` | test |\n\n"
             "### Device scope contract\n\n"
             "| scope | where | covers | find it as |\n|---|---|---|---|\n"
             "| `good.scope` | `work.py` | test | `jit(f)/good.scope/add` |\n"
             "| `stale.scope` | `work.py` | never opened | |\n")
SCOPE_SRC = ("import jax\nfrom . import telemetry\n"
             "def g(x):\n"
             "    with telemetry.span(\"good.span\"):\n"
             "        with jax.named_scope(\"good.scope\"):\n"
             "            x = x + 1\n"
             "        with jax.named_scope(\"ghost.scope\"):\n"
             "            x = x + 1\n"
             "        with jax.named_scope(\"BadShape\"):\n"
             "            return x + 1\n")


def _scope_tree(tmp_path, metric_args):
    metric = {"name": "m", "layer": "l", "reader": "trace_scope",
              "args": metric_args}
    other = {"name": "o", "layer": "l", "reader": "trace_events",
             "args": {"pattern": "unlisted\\.name"}}
    return _tree(tmp_path, {
        "dmlc_core_tpu/work.py": SCOPE_SRC,
        "doc/observability.md": SCOPE_DOC,
        "benchmark/layer_metrics/m.json": json.dumps(metric),
        "benchmark/layer_metrics/o.json": json.dumps(other),
    })


@pytest.mark.parametrize("path,needle,fragment", [
    ("dmlc_core_tpu/work.py", "ghost.scope",
     '"ghost.scope" is opened here but missing'),
    ("dmlc_core_tpu/work.py", "BadShape", "dotted-lowercase"),
    ("doc/observability.md", "stale.scope", "stale contract row"),
])
def test_device_scopes_both_directions(tmp_path, path, needle, fragment):
    root = _scope_tree(tmp_path, {"scope": "good\\.scope"})
    findings = tracespans.check(root)
    text = SCOPE_SRC if path.endswith(".py") else SCOPE_DOC
    _find(findings, path, _line(text, needle), fragment)
    assert not any("good.scope" in f.message or "good.span" in f.message
                   for f in findings), [f.render() for f in findings]
    assert len(findings) == 3, [f.render() for f in findings]


@pytest.mark.parametrize("args,unknown", [
    ({"scope": "good\\.scope|^jit\\((?!_build_tree\\))"}, []),
    ({"scope": "good\\.scope|lost\\.scope"}, ["lost.scope"]),
    ({"what": "unscoped_pct", "scoped": ["good\\.scope", "gone\\.scope"]},
     ["gone.scope"]),
])
def test_layer_metrics_select_only_contract_scopes(tmp_path, args, unknown):
    findings = [f for f in tracespans.check(_scope_tree(tmp_path, args))
                if f.path.startswith("benchmark/")]
    assert [f.path for f in findings] == [
        "benchmark/layer_metrics/m.json"] * len(unknown)
    for name, f in zip(unknown, findings):
        assert f'selects the scope "{name}"' in f.message


def test_scopes_without_a_contract_table_are_a_finding(tmp_path):
    root = _tree(tmp_path, {
        "dmlc_core_tpu/work.py": SCOPE_SRC,
        "doc/observability.md": SCOPE_DOC.split("### Device")[0],
    })
    findings = tracespans.check(root)
    assert any('no "Device scope contract" table' in f.message
               for f in findings), [f.render() for f in findings]


def test_repo_is_green():
    """The shipped repo satisfies every contract the analyzer proves."""
    findings = run(REPO)
    assert findings == [], "\n".join(f.render() for f in findings)
