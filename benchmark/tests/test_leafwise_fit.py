"""The ``leafwise_fit`` generator and the ``epsilon-lgbm`` reference at the
cell's rehearsal size: the walk is sound and its control is not, each number
the check brings catches the fault it is there for (a swapped expansion, a
dangling pointer, a miscounted row, a full pass counted as a segment, values
through bfloat16), the counts the roofline reads come off the forest, and the
cell's entries in ``BENCHMARK.json`` are there in their order (membership and
order, never that they are the last: the next cell's come after them)."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import opcount, opcount_leafwise_histogram, run
from test_names import cell_entries
from test_references import (SEED, control_fails, through_bf16, verdict,
                             walk)

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "epsilon-lgbm.fit-leafwise"
CONFIG = "epsilon-lgbm"
# the round is the other GBDT cells' reading, under their name
MINE = ["leafwise_hist_ms_per_round",
        "leafwise_partition_ms_per_round", "leafwise_split_ms_per_round",
        "leafwise_pick_ms_per_round", "leafwise_rows_visited_per_round",
        "leafwise_expansions_per_round", "leafwise_depth_max",
        "leafwise_hist_roofline"]
EXACT = ("constraint_violations", "stopped_early", "pointer_errors",
         "rows_visited_mismatch", "trees_missing")


def test_the_cell_and_its_configuration_resolve():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert "GPU-Performance.rst" in config["source"]
    assert "arXiv:1706.08359" in config["source"]
    spec = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    assert spec["generator"] == "leafwise_fit" and spec["reference"] == CONFIG
    p = spec["params"]
    assert (p["rows"], p["num_trees"], p["max_leaves"]) == (400000, 2, 255)
    assert p["regret_expansions"] == [[0, 0], [0, 1], [0, 16], [0, 127],
                                      [1, 253]]
    data = json.loads((ROOT / config["file"]).read_text())
    s = data["sizes"]
    # the source's shapes, none of them cut, and every row of it
    assert (s["num_features"], s["train_rows"], s["max_bin"], s["num_leaves"],
            s["learning_rate"], s["min_sum_hessian_in_leaf"],
            s["min_data_in_leaf"], s["lambda"], s["max_depth"]) == (
                2000, 400000, 255, 255, 0.1, 100.0, 1, 0.0, 0)
    assert p["rows"] == s["train_rows"] and not s["missing_aware"]
    assert config["reduced"] == [] == data["reduced"]
    assert len(data["guarantees"]) == 3 and "deployment" in data
    limits = data["tolerance"]["limits"]
    assert all(limits[name] == 0 for name in EXACT)
    assert set(limits) - set(EXACT) == {
        "base_abs_err", "gain_rel_err", "cover_rel_err", "leaf_rel_err",
        "split_regret", "order_regret"}
    # after the DiFacto cell's, whatever follows
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index("criteo-tb-difacto.stream-train")
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_rows_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "criteo-tb-difacto.stream-train")


def test_every_new_layer_metric_has_its_file_and_reader():
    for entry in cell_entries(CELL, MINE, after="difacto_scatter_roofline"):
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_rows_per_s"
    cell_entries(CELL, ["round_device_ms", "margin_ms_per_round"])
    scopes = {json.loads((HERE / "layer_metrics" / f"{n}.json").read_text())[
        "args"].get("scope") for n in MINE} - {None}
    assert scopes == {"gbdt\\.leafwise\\.hist", "gbdt\\.leafwise\\.partition",
                      "gbdt\\.leafwise\\.split", "gbdt\\.leafwise\\.pick"}
    roof = json.loads(
        (HERE / "layer_metrics" / "leafwise_hist_roofline.json").read_text())
    # the kernel the other dense cells read, under its own name
    assert roof["args"]["pattern"] == "^%_histogram_gh_pallas"
    assert roof["args"]["opcount"] == (
        "opcount_leafwise_histogram:leafwise_histogram")


def test_opcount_is_rows_visited_and_every_histogram_written_once():
    counts = {"rows_visited": 1_000, "features": 2000, "num_bins": 255,
              "histograms_built": 3}
    work = opcount_leafwise_histogram.leafwise_histogram(counts)
    assert work["bytes"] == 1_000 * (2000 + 8 + 4) + 3 * 2000 * 255 * 2 * 4
    assert work["flops"] == 2 * 1_000 * 2000
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"][
        "TPU v5 lite"]
    seconds, bound = opcount.least_seconds(work, peaks)
    assert bound == "bytes" and seconds == pytest.approx(
        work["bytes"] / 819e9)


def test_forest_counts_read_the_smaller_child_of_every_expansion():
    generator = run.load_module("traffic", "leafwise_fit")
    ids = np.arange(7)
    forest = {"left": np.stack([np.array([1, 1, 3, 3, 4, 5, 6]), ids]),
              "right": np.stack([np.array([2, 1, 4, 3, 4, 5, 6]), ids]),
              "node_rows": np.stack([np.array([100, 30, 70, 60, 10, 0, 0]),
                                     np.array([100, 0, 0, 0, 0, 0, 0])])}
    # tree 0: 100 rows, then 30 of (30, 70), then 10 of (60, 10); tree 1
    # never split: its rows once
    assert generator.forest_counts(forest) == {
        "expansions": 2, "rows_visited": 100 + 30 + 10 + 100}


def test_data_is_the_seeds_and_half_positive():
    generator = run.load_module("traffic", "leafwise_fit")
    x, y = generator.make_data(2 ** 31 + 5, 4096, 64)
    x2, y2 = generator.make_data(2 ** 31 + 5, 4096, 64)
    other, _ = generator.make_data(2 ** 31 + 6, 4096, 64)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    assert not np.array_equal(np.asarray(x), np.asarray(other))
    assert abs(float(np.mean(np.asarray(y))) - 0.5) < 0.04
    # a unit-norm row's scale
    assert float(np.std(np.asarray(x))) == pytest.approx(64 ** -0.5, rel=0.02)
    cuts = np.quantile(np.asarray(x[:2048]), np.linspace(0, 1, 33)[1:-1],
                       axis=0).T
    codes = np.asarray(generator.bin_codes(x, np.asarray(cuts)))
    assert codes.dtype == np.uint8 and codes.min() == 0 and codes.max() == 31


def test_reference_agrees_and_its_control_does_not(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    assert state["visited"] > 0
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    got = {c["name"]: c["value"] for c in sound}
    assert all(got[name] == 0 for name in EXACT), got
    assert {"control.gain_rel_err", "control.cover_rel_err",
            "control.leaf_rel_err"} <= set(control_fails(cell, sound)), sound
    # the program's own result, rounded through bfloat16 where it is stored
    forest = dict(state["forest"])
    for key in ("split_gain", "split_cover", "leaf"):
        forest[key] = through_bf16(forest[key])
    state["forest"] = forest
    rounded = verdict(cell, generator.check(state, reference))
    assert not (rounded["gain_rel_err"] or rounded["cover_rel_err"]
                or rounded["leaf_rel_err"]), rounded
    generator.teardown(state)


def numbers(generator, reference, state) -> dict:
    return {c["name"]: c["value"] for c in generator.check(state, reference)}


def with_forest(state, **tables):
    forest = {k: np.array(v) for k, v in state["forest"].items()}
    for key, edit in tables.items():
        edit(forest[key])
    state["forest"] = forest


def test_a_swapped_expansion_shows_as_order_regret(tmp_path):
    """Expansions 1 and 2 of tree 0 exchanged (both split children of the
    root here, so the tree is the same tree): the leaf expanded second was
    not the frontier's best."""
    cell, generator, reference, state = walk(CELL, tmp_path)
    forest = {k: np.array(v) for k, v in state["forest"].items()}
    left = forest["left"][0]
    parents = {int(np.flatnonzero(left == 2 * e + 1)[0]) for e in (1, 2)}
    assert parents == {1, 2}, "the rehearsal's second and third expansions " \
        "are the root's children at this seed"
    swap = np.arange(left.shape[0])
    swap[[3, 4, 5, 6]] = [5, 6, 3, 4]
    for key, table in forest.items():
        if table.ndim == 2:
            table[0] = table[0][np.argsort(swap)]
    for key in ("left", "right"):
        forest[key][0] = swap[forest[key][0]]
    state["forest"] = forest
    cell.params["regret_expansions"] = [[0, 1]]
    got = numbers(generator, reference, state)
    limits = cell.config["tolerance"]["limits"]
    assert got["order_regret"] > limits["order_regret"], got
    assert got["pointer_errors"] == 0 == got["rows_visited_mismatch"], got
    assert got["gain_rel_err"] <= limits["gain_rel_err"]
    generator.teardown(state)


@pytest.mark.parametrize("fault", ["dangling", "backwards", "miscounted"])
def test_a_broken_pointer_or_count_is_a_pointer_error(tmp_path, fault):
    cell, generator, reference, state = walk(CELL, tmp_path)

    def dangling(right):
        right[1, 0] = right.shape[1] + 3        # past the node table

    def backwards(left):
        node = int(np.flatnonzero(left[0] != np.arange(left.shape[1]))[-1])
        left[0, node] = 0                       # a child that is the root

    def miscounted(count):
        count[0, 1] += 1
        count[0, 2] -= 1

    with_forest(state, **{"dangling": {"right": dangling},
                          "backwards": {"left": backwards},
                          "miscounted": {"node_rows": miscounted}}[fault])
    got = numbers(generator, reference, state)
    assert got["pointer_errors"] >= 1, got
    generator.teardown(state)


def test_a_full_pass_counted_as_a_segment_is_a_mismatch(tmp_path):
    """A builder that read every row at every expansion and counted the
    smaller child all the same: the counter is held to the forest's counts,
    so it has to count what the backend was handed."""
    cell, generator, reference, state = walk(CELL, tmp_path)
    sound = state["visited"]
    one = generator.forest_counts(state["forest"])
    assert sound == one["rows_visited"]
    state["visited"] = state["rows"] * (
        one["expansions"] + state["model"].num_trees)
    got = numbers(generator, reference, state)
    assert got["rows_visited_mismatch"] == state["visited"] - sound > 0
    generator.teardown(state)


def test_a_worse_cut_a_starved_child_and_a_missing_tree_are_caught(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    kept = {k: np.array(v) for k, v in state["forest"].items()}
    limits = cell.config["tolerance"]["limits"]

    def other_cut(threshold):
        threshold[0, 0] = (threshold[0, 0] + 9) % 30
    with_forest(state, threshold=other_cut)
    got = numbers(generator, reference, state)
    assert got["split_regret"] > limits["split_regret"], got
    assert got["pointer_errors"] >= 1       # and the counts no longer fit

    state["forest"] = kept
    state["rule"] = dict(state["rule"], min_child_weight=10 ** 6)
    got = numbers(generator, reference, state)
    assert got["constraint_violations"] >= 1, got

    state["rule"] = dict(state["rule"], min_child_weight=4.0, max_leaves=31)
    got = numbers(generator, reference, state)
    assert got["stopped_early"] == 2, got    # both trees stopped at 15

    ids = np.arange(kept["left"].shape[1])
    state["rule"] = dict(state["rule"], max_leaves=15)
    with_forest(state, left=lambda a: a.__setitem__(1, ids),
                right=lambda a: a.__setitem__(1, ids))
    got = numbers(generator, reference, state)
    assert got["trees_missing"] == 1, got
    generator.teardown(state)


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearses_under_run_py(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                     "0.3", "--rehearse-cpu", "--control", "1"]) == 0
    out = last_line(capsys)
    assert out["correct"] is True and out["rehearsal"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["metrics"] == {}
