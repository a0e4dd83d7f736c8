"""The ``bosch-gbdt`` configuration and its cell ``bosch-gbdt.fit-sparse``:
the names resolve to their files, the generator's rows and file are a
function of the seed and come back through the parser as drawn, the opcount
is what its docstring says, the rehearsal walks, ``check`` breaks when the
timed call is broken, and the cell's entries in ``BENCHMARK.json`` are there
in their order (membership and order, never that they are the last)."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import harness, opcount, opcount_sparse_histogram, run
from test_names import cell_entries

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "bosch-gbdt.fit-sparse"
SEED = 2 ** 31 + 27
# the tree's round and the parts the dense tree has too, then PR 27's own
MINE = ["round_device_ms", "route_ms_per_round", "split_ms_per_round",
        "sparse_hist_ms_per_round", "sparse_hist_roofline",
        "entry_gather_ms_per_round", "sparse_leaf_ms_per_round",
        "sparse_prepare_ms_per_round", "sparse_boost_ms_per_round"]


def sparse_fit():
    return run.load_module("traffic", "sparse_fit")


def test_the_cell_and_its_configuration_resolve():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == "bosch-gbdt")
    assert cell["config"] == "bosch-gbdt" and cell["chips"] == 1
    spec = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    assert spec["generator"] == "sparse_fit"
    assert spec["reference"] == "bosch-gbdt"
    assert spec["params"]["num_trees"] == 1
    assert spec["params"]["histogram"] == "auto"
    assert spec["params"]["regret_levels"] == [[0, 0], [0, 4], [0, 7]]
    data = json.loads((ROOT / config["file"]).read_text())
    sizes = data["sizes"]
    # the source's shapes, none of them cut
    assert (sizes["num_features"], sizes["num_bins"], sizes["max_depth"],
            sizes["present_share"]) == (968, 256, 8, 0.19)
    assert data["assumed"]["label_rate"] == 0.0058
    assert sizes["train_rows"] == 1183747
    if spec["params"]["rows"] != sizes["train_rows"]:
        assert config["reduced"] == ["train_rows"] == data["reduced"]
        assert spec["params"]["rows"] in (1048576, 524288)
    else:
        assert config["reduced"] == [] == data["reduced"]
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_rows_per_s")
    assert CELL in rate["workloads"]
    assert set(data["tolerance"]["limits"]) == {
        "base_abs_err", "gain_rel_err", "cover_rel_err", "leaf_rel_err",
        "split_regret", "trees_missing", "cuts_rank_err"}


def test_every_new_layer_metric_has_its_file_and_reader():
    for m in cell_entries(CELL, MINE):
        assert m["moves"] == "train_rows_per_s"
    roofline = json.loads(
        (HERE / "layer_metrics" / "sparse_hist_roofline.json").read_text())
    assert roofline["args"]["opcount"] == \
        "opcount_sparse_histogram:sparse_histogram"
    assert roofline["args"]["pattern"] == "^%_histogram_gh_sparse_pallas"


def test_sparse_histogram_is_16_bytes_an_entry_a_level_plus_the_histograms():
    counts = {"entries": 1000, "levels": 6, "rounds": 2, "max_depth": 3,
              "features": 10, "bins": 16}
    work = opcount_sparse_histogram.sparse_histogram(counts)
    assert work["flops"] == 2.0 * 1000 * 6
    assert work["bytes"] == 16.0 * 1000 * 6 + 2 * 7 * 10 * 16 * 8
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.least_seconds(work, peaks)[1] == "bytes"


def test_station_plan_fills_the_features_at_the_present_share():
    g = sparse_fit()
    sizes, prob = g.station_plan(968, 52, 0.19)
    assert sizes.sum() == 968 and sizes.min() >= 1 and len(sizes) == 52
    assert abs((sizes * prob).sum() / 968 - 0.19) < 1e-6
    assert prob.max() == pytest.approx(0.95) and prob.min() < 0.02
    again = g.station_plan(968, 52, 0.19)
    assert np.array_equal(sizes, again[0]) and np.array_equal(prob, again[1])


def test_rows_are_a_function_of_the_seed_with_the_sources_shape():
    g = sparse_fit()
    a = g.draw_rows(SEED, 20000, 968, 52, 0.19, 0.0058)
    b = g.draw_rows(SEED, 20000, 968, 52, 0.19, 0.0058)
    c = g.draw_rows(SEED + 1, 20000, 968, 52, 0.19, 0.0058)
    for k in ("row_ptr", "fi", "q", "label"):
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["q"][:1000], c["q"][:1000])
    entries = int(a["row_ptr"][-1])
    assert abs(entries / (20000 * 968) - 0.19) < 0.004
    assert int(a["label"].sum()) == round(0.0058 * 20000)
    assert not (a["q"] == 0).any() and np.abs(a["q"]).max() <= 9999
    # features ascend inside a row, and a visited station is there whole
    rid = np.repeat(np.arange(20000), np.diff(a["row_ptr"]))
    assert np.all((np.diff(a["fi"].astype(int)) > 0) | (np.diff(rid) > 0))
    # every seed is the same work to a fraction of a percent
    assert abs(int(c["row_ptr"][-1]) / entries - 1) < 0.01


def test_the_file_is_libsvm_text_the_parser_reads_back_as_drawn(tmp_path):
    g = sparse_fit()
    data = g.draw_rows(SEED, 3000, 64, 8, 0.19, 0.05)
    path = tmp_path / "train.libsvm"
    size = g.write_libsvm(path, data)
    entries = int(data["row_ptr"][-1])
    assert size == path.stat().st_size == g.libsvm_bytes(3000, entries)
    lines = path.read_bytes().decode().split("\n")
    assert lines[-1] == "" and len(lines) == 3001
    first = lines[0].split()
    assert first[0] == str(int(data["label"][0]))
    n0 = int(data["row_ptr"][1])
    assert [t.split(":")[0] for t in first[1:]] == [
        str(f) for f in data["fi"][:n0]]
    assert [float(t.split(":")[1]) for t in first[1:]] == [
        q / 1000.0 for q in data["q"][:n0]]
    batch = g.stage(path, 3000, 2, 4096)
    assert int(batch.num_rows) == 3000
    assert np.array_equal(np.asarray(batch.index)[:entries], data["fi"])
    assert np.array_equal(np.asarray(batch.value)[:entries],
                          (data["q"] / 1000.0).astype(np.float32))
    assert np.array_equal(np.asarray(batch.row_ptr), data["row_ptr"])


def test_tile_counts_follow_the_kernels_layout():
    from dmlc_core_tpu.ops.pallas_segment import sparse_hist_layout
    g = sparse_fit()
    data = g.draw_rows(SEED, 6000, 64, 8, 0.19, 0.05)
    fi = data["fi"].astype(np.int32)
    rid = np.repeat(np.arange(6000, dtype=np.int32), np.diff(data["row_ptr"]))
    layout = sparse_hist_layout(rid, fi, np.ones_like(fi),
                                np.ones(len(fi), bool), 64, 32)
    tiles = g.tile_counts(data["fi"], 64, 32)
    assert tiles["key_tiles"] == layout.num_kt
    assert tiles["executed_tiles"] == int(np.asarray(layout.tcount).sum())
    # the layout rounds its grid's inner extent up a little, so that two
    # seeds' draws share a compiled program
    assert tiles["max_tiles"] == int(np.asarray(layout.tcount).max())
    assert tiles["max_tiles"] <= layout.max_tiles <= tiles["max_tiles"] * 1.07


def walk(tmp_path, seed=SEED):
    cell = harness.load_cell(HERE, CELL, seed, rehearse=True)
    cell.cache_dir = tmp_path
    generator = sparse_fit()
    reference = run.load_module("references", cell.reference)
    spans = harness.Spans()
    state = generator.setup(cell, spans)
    measured = generator.window(state, 0.2, spans)
    return cell, generator, reference, state, measured


def verdict(cell, comparisons) -> dict:
    limits = cell.config["tolerance"]["limits"]
    return {c["name"]: c["value"] <= limits[c["name"]] for c in comparisons
            if not c["name"].startswith("control.")}


@pytest.mark.parametrize("seed", (SEED, 99))
def test_the_rehearsal_walks_and_the_control_fails(tmp_path, seed):
    cell, generator, reference, state, measured = walk(tmp_path, seed)
    assert measured["attempted"] >= 1 and measured["failed"] == 0
    assert set(measured["counts"]) >= {
        "entries", "rows", "rounds", "levels", "features", "key_tiles",
        "max_tiles", "executed_tiles"}
    assert measured["counts"]["levels"] == measured["counts"]["rounds"] * 3
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    limits = cell.config["tolerance"]["limits"]
    failed = [c["name"] for c in sound if c["name"].startswith("control.")
              and c["value"] > limits[c["name"][len("control."):]]]
    assert failed, sound
    # the seed's file is found again, not written again
    stamp = (tmp_path / "train.libsvm").stat().st_mtime_ns
    generator.teardown(state)
    state = generator.setup(cell, harness.Spans())
    assert (tmp_path / "train.libsvm").stat().st_mtime_ns == stamp
    generator.teardown(state)


def test_the_cells_fit_routes_by_the_layout_alone(tmp_path, monkeypatch):
    """The timed fit is the one-copy form: the drawn rows' layout says
    ``rows_ascend``, so the tree program is handed no unsorted entries."""
    from dmlc_core_tpu.models import GBDT
    cell, generator, reference, state, measured = walk(tmp_path)
    real, seen = GBDT._build_tree_sparse, []

    def spy(self, entries, layout, *rest):
        seen.append((entries is None, layout.rows_ascend))
        return real(self, entries, layout, *rest)
    monkeypatch.setattr(GBDT, "_build_tree_sparse", spy)
    generator.fit_once(state)
    assert seen == [(True, True)]
    generator.teardown(state)


def run_cell(capsys, trace: int = 0) -> dict:
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "0.3", "--trace", str(trace), "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct_and_prints_the_contract_keys(capsys, trace):
    line = run_cell(capsys, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["metrics"] == {}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}


def patched(monkeypatch, patch):
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if (kind, name) == ("traffic", "sparse_fit"):
            patch(module)
        return module
    monkeypatch.setattr(run, "load_module", load)


def test_fit_that_returns_its_state_unchanged_is_not_correct(capsys,
                                                             monkeypatch):
    def patch(module):
        def unchanged(state):
            state["forest"] = state["model"].init()
        module.fit_once = unchanged
    patched(monkeypatch, patch)
    assert run_cell(capsys)["correct"] is False


def test_fit_that_flips_a_default_direction_is_not_correct(capsys,
                                                           monkeypatch):
    def patch(module):
        real = module.fit_once

        def flipped(state):
            real(state)
            forest = dict(state["forest"])
            forest["default_right"] = 1 - forest["default_right"]
            state["forest"] = forest
        module.fit_once = flipped
    patched(monkeypatch, patch)
    assert run_cell(capsys)["correct"] is False


def test_fit_that_leaves_out_half_the_rows_is_not_correct(capsys,
                                                          monkeypatch):
    def patch(module):
        import dataclasses

        import jax

        def half(state):
            b = state["batch"]
            state["forest"] = jax.block_until_ready(
                state["model"].fit_batch(
                    dataclasses.replace(b, weight=b.weight.at[::2].set(0.0)),
                    state["binner"]))
        module.fit_once = half
    patched(monkeypatch, patch)
    assert run_cell(capsys)["correct"] is False
