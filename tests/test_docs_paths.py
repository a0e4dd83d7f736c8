"""Every file a document names exists.

The hand-written pages (README.md, PARITY.md, doc/*.md, the verify skill)
send a reader to files by name.  A page that still cites a file after the
file is gone misleads; this test fails on it.  Checked: every word inside
back-ticks or a fenced block that ends in a source or record suffix and is
either a bare file name or a relative path.  A path resolves against the
repository root, the page's own directory, or as the tail of a file in the
tree (``models/gbdt.py``); a bare name resolves to any file of that name.
"""
import os
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SUFFIXES = (".py", ".sh", ".json", ".jsonl", ".md", ".h", ".cc")
# not files of this tree: a user's own script and outputs in the pages'
# examples, and the upstream dmlc-core files the mapping pages map from
NOT_OURS = {"train.py", "job.json", "trace.json", "lua.h"}
UPSTREAM = ("include/dmlc/", "test/", "tracker/dmlc_tracker/")
SKIP_DIRS = {"chiprun_out", "__pycache__", "_site"}

DOCS = sorted(
    [ROOT / "README.md", ROOT / "PARITY.md",
     ROOT / ".claude" / "skills" / "verify" / "SKILL.md"]
    + list((ROOT / "doc").glob("*.md")))

_FENCE = re.compile(r"^```.*?$(.*?)^```", re.S | re.M)
_TICKS = re.compile(r"`([^`\n]+)`")


@pytest.fixture(scope="module")
def tree_files():
    files = []
    for base, dirs, names in os.walk(ROOT):
        rel = Path(base).relative_to(ROOT)
        dirs[:] = [d for d in dirs
                   if d not in SKIP_DIRS and not d.startswith("build")
                   and (not d.startswith(".") or d in (".claude", ".github"))]
        files += [(rel / n).as_posix() for n in names]
    return files


def _named_files(text):
    spans = _FENCE.findall(text) + _TICKS.findall(_FENCE.sub("", text))
    for span in spans:
        for word in re.split(r"[\s\"'()\[\],;=]+", span):
            word = re.sub(r"(::[\w\[\]-]+|:\d+(-\d+)?(,\d+(-\d+)?)*)+$", "",
                          word).rstrip(".:")
            if not word.endswith(SUFFIXES) or word in SUFFIXES:
                continue
            if re.search(r"[*<>{}$|]", word) or "://" in word:
                continue  # globs, <placeholders>, URLs
            if word.startswith(("/", "~", "-") + UPSTREAM):
                continue  # outside the checkout
            yield word


@pytest.mark.parametrize("doc", DOCS,
                         ids=lambda p: p.relative_to(ROOT).as_posix())
def test_every_file_a_document_names_exists(doc, tree_files):
    files = tree_files
    names = {f.rsplit("/", 1)[-1] for f in files}
    missing = []
    for word in _named_files(doc.read_text()):
        if "/" not in word:
            ok = word in names or word in NOT_OURS
        else:
            path = word.removeprefix("./")
            ok = ((ROOT / path).exists() or (doc.parent / path).exists()
                  or any(f.endswith("/" + path) for f in files))
        if not ok:
            missing.append(word)
    assert not missing, (
        f"{doc.relative_to(ROOT)} names files that are not in the tree: "
        f"{sorted(set(missing))}")
