"""BENCHMARK.json and the data files keep to the contract's characters and
limits, and every name in it has its files."""
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


def line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


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


@pytest.mark.parametrize("path", sorted(
    str(p.relative_to(ROOT)) for p in HERE.rglob("*")
    if p.is_file() and ".cache" not in p.parts
    and "__pycache__" not in p.parts))
def test_file_names_use_the_allowed_characters(path):
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", path) and len(path) <= 200
