"""BENCHMARK.json and the data files keep to the contract's characters and
limits, every name in it has its files, one reading has one name, and the
merge of PR 52 reads what its parent read.  ``cell_entries`` is what a cell's
own test file holds its metrics by: membership and order, never position."""
import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# tests/test_chip_names.py:365 takes the dense kernel's event pattern from
# this file and a ``benchmark`` PR may not edit tests/: the file stays, with
# no entry, until that line opens hist_ms_per_round.json (PERF.md section 7)
FILES_WITHOUT_AN_ENTRY = {"mesh_hist_ms_per_round": "hist_ms_per_round"}


def line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def layer_metric(name: str) -> dict:
    return json.loads((HERE / "layer_metrics" / f"{name}.json").read_text())


def cell_entries(cell: str, names: list, after: str | None = None) -> list:
    """The ``per_layer`` entries called ``names``, for a cell's own test:
    each is there, reports in ``cell`` (lists it, or lists nothing and moves
    an end-to-end metric every cell reports), has its file and its reader,
    and they stand in this order, after the entry ``after``.  Whatever other
    PRs put before, between or after them, and whichever other cells an entry
    lists, is none of the cell's business."""
    every = [m["name"] for m in BENCH["per_layer"]]
    assert [n for n in every if n in names] == names
    if after is not None:
        assert every.index(names[0]) > every.index(after)
    entries = [BENCH["per_layer"][every.index(n)] for n in names]
    for m in entries:
        assert cell in m.get("workloads", [cell]), m["name"]
        spec = layer_metric(m["name"])
        assert spec["name"] == m["name"] and spec["layer"] == m["layer"]
        assert (HERE / "readers" / f"{spec['reader']}.py").is_file()
    return entries


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert all(line_ok(w) for w in BENCH["command"])


def test_configs_have_their_files_and_cut_no_width():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"])
        assert line_ok(c["why"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert data["reduced"] == c["reduced"] and line_ok(data["source"])
        assert (HERE / "references" / f"{c['name']}.py").is_file()
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|width|factors|fields|bins)$",
                                 key)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_cells_have_their_files():
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        cell = json.loads(
            (HERE / "workloads" / f"{w['name']}.json").read_text())
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert (HERE / "traffic" / f"{cell['generator']}.py").is_file()
        assert (HERE / "references" / f"{cell['reference']}.py").is_file()
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_metrics_names_units_sources_and_files():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and line_ok(m["layer"])
        spec = json.loads(
            (HERE / "layer_metrics" / f"{m['name']}.json").read_text())
        assert spec["layer"] == m["layer"]
        assert (HERE / "readers" / f"{spec['reader']}.py").is_file()
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:      # setup_s, one more end-to-end, one per-layer
        mine = [m for m in BENCH["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        reported = {m["name"] for m in mine}
        assert any(cell in m["workloads"] if "workloads" in m
                   else m["moves"] in reported for m in BENCH["per_layer"])


def test_one_reading_has_one_name_and_every_entry_its_file():
    """The list cannot fill with copies again: an entry is a reading (a
    reader and its ``args``), a cell that takes a reading an entry already
    takes joins that entry's ``workloads`` (benchmark/README.md)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert 1 <= len(names) <= 128
    files = {p.stem for p in (HERE / "layer_metrics").glob("*.json")}
    assert files - set(names) == set(FILES_WITHOUT_AN_ENTRY)
    assert set(names) <= files
    cells = {w["name"] for w in BENCH["workloads"]}
    readings = {}
    for m in BENCH["per_layer"]:
        spec = layer_metric(m["name"])
        assert spec["name"] == m["name"]
        if "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= cells
        readings.setdefault((spec["reader"], json.dumps(
            spec.get("args", {}), sort_keys=True)), []).append(m["name"])
    assert not [same for same in readings.values() if len(same) > 1]
    for name, entry in FILES_WITHOUT_AN_ENTRY.items():
        left, merged = layer_metric(name), layer_metric(entry)
        assert (left["reader"], left["args"]) == (merged["reader"],
                                                  merged["args"])


ROUND = {"per": "rounds", "scale": 1000.0}
STEP = {"per": "steps", "scale": 1000.0}
RESIDENT, SPARSE, MESH4, LEAFWISE, PAGED = (
    "higgs-gbdt.fit-resident", "bosch-gbdt.fit-sparse",
    "airline-gbdt.fit-mesh4", "epsilon-lgbm.fit-leafwise",
    "criteo-xgb-extmem.fit-paged")
FFM, FTRL, DIFACTO, PS4 = (
    "criteo-ffm.stream-train", "criteo-tb-ftrl.stream-train",
    "criteo-tb-difacto.stream-train",
    "criteo-tb-difacto-ps4.stream-train-mesh4")
# PR 52 made 47 entries that were 16 readings 16.  The reader and ``args`` of
# the parent's files, written out, and the cells that stood on the reading
# under their own prefixes, in the order of BENCHMARK.json's workloads
MERGED = {
    "round_device_ms": (
        "trace_events", {"what": "busy_per", **ROUND},
        [RESIDENT, SPARSE, MESH4, LEAFWISE, PAGED]),
    "hist_ms_per_round": (
        "trace_events", {"what": "pattern_per",
                         "pattern": "^%_histogram_gh_pallas", **ROUND},
        [RESIDENT, MESH4, PAGED]),
    "route_ms_per_round": (
        "trace_scope", {"scope": "gbdt\\.route", **ROUND},
        [RESIDENT, SPARSE, MESH4]),
    "split_ms_per_round": (
        "trace_scope", {"scope": "gbdt\\.split", **ROUND},
        [RESIDENT, SPARSE]),
    "leaf_ms_per_round": (
        "trace_scope", {"scope": "gbdt\\.leaf", **ROUND}, [RESIDENT, MESH4]),
    "boost_ms_per_round": (
        "trace_scope",
        {"scope": "gbdt\\.boost|^jit\\((?!_build_tree\\))", **ROUND},
        [RESIDENT, MESH4]),
    "margin_ms_per_round": (
        "trace_scope", {"scope": "gbdt\\.margin", **ROUND},
        [RESIDENT, SPARSE, MESH4, LEAFWISE, PAGED]),
    "sgd_step_device_ms": (
        "trace_events", {"what": "busy_per", **STEP},
        [FFM, FTRL, DIFACTO, PS4]),
    "sgd_unique_ms_per_step": (
        "trace_scope", {"scope": "sgd\\.unique", **STEP},
        [FFM, FTRL, DIFACTO, PS4]),
    "sgd_gather_ms_per_step": (
        "trace_scope", {"scope": "sgd\\.gather_rows", **STEP},
        [FFM, FTRL, DIFACTO, PS4]),
    "sgd_scatter_ms_per_step": (
        "trace_scope", {"scope": "sgd\\.scatter_rows", **STEP},
        [FFM, FTRL, DIFACTO, PS4]),
    "sgd_touched_rows_per_step": (
        "counter_delta", {"num": ["sgd.touched_rows"], "den": ["sgd.steps"]},
        [FFM, FTRL, DIFACTO, PS4]),
    "sgd_margins_ms_per_step": (
        "trace_scope", {"scope": "fm\\.margins|linear\\.margins", **STEP},
        [DIFACTO, PS4]),
    "sgd_update_ms_per_step": (
        "trace_scope",
        {"scope": "sgd\\.ftrl|sgd\\.adagrad|sgd\\.count", **STEP},
        [DIFACTO, PS4]),
    "sgd_active_rows_per_step": (
        "counter_delta", {"num": ["sgd.active_rows"], "den": ["sgd.steps"]},
        [DIFACTO, PS4]),
    "sgd_scatter_tiles_per_step": (
        "counter_delta", {"num": ["sgd.scatter_tiles"], "den": ["sgd.steps"]},
        [FTRL, DIFACTO]),
}


@pytest.mark.parametrize("name", sorted(MERGED))
def test_a_merged_metric_reads_what_its_parents_files_read(name):
    reader, args, cells = MERGED[name]
    spec = layer_metric(name)
    assert (spec["reader"], spec["args"]) == (reader, args)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    # a later cell joins after them
    assert entry["workloads"][:len(cells)] == cells
    assert spec["what"] and "\n" not in spec["what"]


@pytest.mark.parametrize("path", sorted(
    str(p.relative_to(ROOT)) for p in HERE.rglob("*")
    if p.is_file() and ".cache" not in p.parts
    and "__pycache__" not in p.parts))
def test_file_names_use_the_allowed_characters(path):
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", path) and len(path) <= 200
