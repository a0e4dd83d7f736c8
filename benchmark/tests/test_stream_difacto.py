"""The ``stream_difacto`` generator and the ``criteo-tb-difacto`` reference at
the cell's rehearsal size: the walk is sound and its control is not, state
rounded through bfloat16 fails, a live step that writes nothing back fails,
the gate must be exercised on both sides, the window's distinct keys are
counted on the host, and the cell's entries in ``BENCHMARK.json`` are there in their
order (membership and order, never that they are the last: the next cell's
entries come after them)."""
import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

from benchmark import opcount, opcount_rows_scatter, run
from test_names import cell_entries
from test_references import SEED, control_fails, verdict, walk

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "criteo-tb-difacto.stream-train"
CONFIG = "criteo-tb-difacto"
# the touched-rows step's readings, which the FTRL cell's (and since PR 42 the
# FFM cell's) step shares under the spans' own names, then the three this
# cell's gate and wide kernel brought
STEP = ["sgd_step_device_ms", "sgd_unique_ms_per_step",
        "sgd_gather_ms_per_step", "sgd_scatter_ms_per_step",
        "sgd_touched_rows_per_step", "sgd_scatter_tiles_per_step"]
MINE = ["sgd_margins_ms_per_step", "sgd_update_ms_per_step",
        "sgd_active_rows_per_step", "difacto_scatter_roofline"]
FEED = ["parse_us_per_row.train", "feed_wait_pct.train",
        "feed_wait_us_per_row.train", "h2d_host_wait_us_per_batch.train",
        "h2d_emit_wait_us_per_batch.train", "pack_input_wait_us_per_row.train",
        "native_spans_dropped.train", "feed_wait_h2d_pct.train",
        "feed_wait_native_pct.train", "feed_wait_handoff_pct.train",
        "feed_lead_ms.train", "h2d_device_put_us_per_batch.train",
        "clock_sync_err_us.train"]


def test_the_cell_and_its_configuration_resolve():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    spec = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    assert spec["generator"] == "stream_difacto"
    assert spec["reference"] == CONFIG
    assert spec["params"]["file_rows"] == 8388608
    assert spec["params"]["loss_every"] == 8
    data = json.loads((ROOT / config["file"]).read_text())
    s = data["sizes"]
    # the source's shapes, none of them cut
    assert (s["num_features"], s["num_factors"], s["entries_per_row"],
            s["batch_size"], s["threshold"]) == (2 ** 26, 16, 39, 16384, 16)
    assert config["reduced"] == ["rows"] == data["reduced"]
    assert len(data["guarantees"]) == 7 and "deployment" in data
    limits = data["tolerance"]["limits"]
    assert set(limits) == set(data["tolerance"]["limits_why"]) - {
        "loss_rel_err"}
    for exact in ("count_mismatch", "live_count_mismatch", "gate_unexercised",
                  "active_set_mismatch", "live_active_set_mismatch",
                  "untouched_changed", "delivery_mismatch"):
        assert limits[exact] == 0
    # after the FTRL cell's, whatever follows
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index("criteo-tb-ftrl.stream-train")
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_rows_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "criteo-tb-ftrl.stream-train")


def test_every_new_layer_metric_has_its_file_and_reader():
    for entry in cell_entries(CELL, MINE, after="sgd_scatter_tiles_per_step"):
        assert entry["workloads"][0] == CELL        # this cell brought them
        assert entry["moves"] == "train_rows_per_s"
    for entry in cell_entries(CELL, STEP):
        assert entry["workloads"].index(CELL) > entry["workloads"].index(
            "criteo-tb-ftrl.stream-train")
    for name in FEED:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"].index(CELL) > entry["workloads"].index(
            "criteo-tb-ftrl.stream-train")


def test_the_scatter_roofline_is_taken_against_the_rows_alone():
    spec = json.loads((HERE / "layer_metrics"
                       / "difacto_scatter_roofline.json").read_text())
    assert spec["args"]["pattern"] == "^%_scatter_rows_inplace_pallas"
    assert spec["args"]["opcount"] == "opcount_rows_scatter:difacto_rows"
    work = opcount_rows_scatter.difacto_rows(
        {"distinct_keys": 1000, "num_factors": 16})
    assert work == {"flops": 0.0, "bytes": 2.0 * 1000 * 128}
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    least, bound = opcount.least_seconds(work, next(iter(peaks.values())))
    assert bound == "bytes" and least > 0


@pytest.mark.parametrize("seed", (SEED, 99))
def test_difacto_reference_agrees_and_its_control_does_not(tmp_path, seed):
    cell, generator, reference, state = walk(CELL, tmp_path, seed)
    at, bias = state["compared"]
    want = cell.params["sample_features"]
    assert at["v"].shape == (want, 16) and at["c"].shape == (want,)
    assert bias.shape == (3,)
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    assert {"control.z_rel_err", "control.n_rel_err", "control.nv_rel_err",
            "control.live_z_rel_err", "control.live_n_rel_err",
            "control.live_nv_rel_err"} <= set(control_fails(cell, sound))
    assert not [c for c in sound if "loss" in c["name"]]
    generator.teardown(state)


def test_the_kernel_visits_the_tables_the_roofline_counts(monkeypatch):
    """At the cell's sizes the embedding rows and their sums take the rows
    kernel and the tables of one element a key take XLA's scatter: the split
    ``opcount_rows_scatter`` counts by.  A crossover or a visit that moves
    it fails here, not silently in the share."""
    from dmlc_core_tpu.models.common import TOUCHED_ROWS_VISITS
    from dmlc_core_tpu.ops import pallas_rows
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    s = json.loads((HERE / "configs" / f"{CONFIG}.json").read_text())["sizes"]
    # a minibatch names 70,200 to 70,400 distinct keys (PERF.md section 4)
    lanes = min(v for v in TOUCHED_ROWS_VISITS if v >= 70400)
    assert pallas_rows.engages(s["num_features"], lanes, np.float32,
                               s["num_factors"])
    assert not pallas_rows.engages(s["num_features"], lanes, np.float32)
    assert not pallas_rows.engages(s["num_features"], lanes, np.int32)


def test_the_windows_distinct_keys_are_counted_on_the_host(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    from benchmark import harness
    before = state["steps"]
    out = generator.window(state, 0.1, harness.Spans())
    counts = out["counts"]
    assert counts["num_factors"] == 16 and counts["steps"] >= 1
    assert "distinct_keys" not in counts    # nothing of it inside the window
    generator.check(state, reference)
    # the same batches, drawn again from the seed
    s = cell.sizes
    _label, index = generator.base.draw_rows(
        cell.seed, 0, cell.params["file_rows"], s["num_features"],
        s["entries_per_row"], cell.config["assumed"]["label_rate"])
    per = state["per_epoch"]
    want = sum(len(np.unique(index[(t % per) * s["batch_size"]:
                                   (t % per + 1) * s["batch_size"]]))
               for t in range(before, before + counts["steps"]))
    assert counts["distinct_keys"] == want
    generator.teardown(state)


def test_state_rounded_through_bfloat16_fails(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    at, bias = state["compared"]
    state["compared"] = ({k: v if k == "c" else v.astype(
        ml_dtypes.bfloat16).astype(np.float32) for k, v in at.items()}, bias)
    got = verdict(cell, generator.check(state, reference))
    assert not got["z_rel_err"] and not got["n_rel_err"]
    assert not got["nv_rel_err"] and not got["v_abs_err"]
    assert got["count_mismatch"] and got["delivery_mismatch"]
    generator.teardown(state)


def test_a_live_step_that_writes_nothing_back_fails(tmp_path):
    import jax.numpy as jnp
    cell, generator, reference, state = walk(CELL, tmp_path)
    state["model"].train_step = lambda params, batch: (params,
                                                       jnp.float32(0.6))
    got = verdict(cell, generator.check(state, reference))
    assert not got["live_z_rel_err"] and not got["live_nv_rel_err"]
    assert not got["live_count_mismatch"]
    assert not got["live_active_set_mismatch"]
    assert got["z_rel_err"] and got["untouched_changed"]
    generator.teardown(state)


def test_a_gate_that_never_opens_is_unexercised(tmp_path):
    """A threshold no key passes: nothing else may differ (the reference
    follows the same sizes), and the check still fails."""
    cell, generator, reference, state = walk(CELL, tmp_path)
    generator.teardown(state)
    cell.config["sizes"]["threshold"] = 10 ** 6
    state = generator.setup(cell, __import__(
        "benchmark.harness", fromlist=["Spans"]).Spans())
    got = {c["name"]: c["value"] for c in generator.check(state, reference)}
    assert got["gate_unexercised"] == 2     # nothing crossed, nothing opened
    assert got["active_set_mismatch"] == 0 == got["live_v_abs_err"]
    generator.teardown(state)


def test_a_written_untouched_row_is_noticed(tmp_path):
    cell, generator, reference, state = walk(CELL, tmp_path)
    ids = state["untouched_ids"]
    state["params"] = dict(state["params"], v=state["params"]["v"].at[
        ids[3], 2].add(1e-9 + 1e-3))
    got = {c["name"]: c["value"] for c in generator.check(state, reference)}
    assert got["untouched_changed"] == 1
    generator.teardown(state)


def test_reference_on_a_hand_worked_key():
    """One row, two keys over the threshold with unit values, from a state
    in which both weights are non-zero: every number by hand."""
    reference = run.load_module("references", CONFIG)
    sizes = {"alpha": 0.5, "beta": 1.0, "l1": 1.0, "l2": 0.0,
             "objective": "logistic", "num_factors": 2, "alpha_v": 0.1,
             "beta_v": 1.0, "l2_v": 0.0, "threshold": 1,
             "batch_size": 1}
    start = {"keys": [-1, 4, 9], "z": [0.0, 3.0, -2.0], "n": [0.0, 1.0, 4.0],
             "c": [0, 1, 5], "v": [[0, 0], [1.0, 2.0], [0.5, -1.0]],
             "nv": np.zeros((3, 2))}
    batch = {"row": [0, 0], "index": [4, 9], "value": [1.0, 1.0],
             "label": [1], "weight": [1.0]}
    out = reference.difacto_steps([batch], sizes, None, start=start)
    w4 = -(3.0 - 1.0) / ((1.0 + 1.0) / 0.5)             # -0.5
    w9 = -(-2.0 + 1.0) / ((1.0 + 2.0) / 0.5)            # 1/6
    pooled = np.array([1.5, 1.0])
    second = 0.5 * (pooled @ pooled - (1 + 4 + 0.25 + 1))
    margin = w4 + w9 + second
    slope = 1 / (1 + np.exp(-margin)) - 1
    assert out["losses"][0] == pytest.approx(np.log1p(np.exp(-margin)))
    assert list(out["c"]) == [0, 2, 6] and out["opened"] == [2]
    g4 = slope * (pooled - np.array([1.0, 2.0]))        # s (P - v x)
    want_n = g4 ** 2
    np.testing.assert_allclose(out["nv"][1], want_n)
    np.testing.assert_allclose(
        out["v"][1], np.array([1.0, 2.0]) - 0.1 * g4 / (1 + np.sqrt(want_n)))
    assert out["n"][1] == pytest.approx(1.0 + slope ** 2)
    sigma = (np.sqrt(1.0 + slope ** 2) - 1.0) / 0.5
    assert out["z"][1] == pytest.approx(3.0 + slope - sigma * w4)
