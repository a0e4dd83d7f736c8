"""The ``airline-gbdt`` configuration and its cell ``airline-gbdt.fit-mesh4``:
the names resolve to their files, the generator's rows are a function of
(seed, shard) with the source's columns, the roofline's work is one chip's,
the skew reader reads a trace's chips, the rehearsal walks on a CPU mesh of
four devices, ``check`` breaks when the timed call is broken, and the cell's
entries in ``BENCHMARK.json`` are there in their order (membership and order,
never that they are the last)."""
import json
import os
from pathlib import Path

# the cell holds four chips; its rehearsal wants as many CPU devices, asked
# for before anything initialises a JAX backend (tests/conftest.py does the
# same for the program's tests, with eight)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import (harness, opcount, opcount_mesh_histogram,  # noqa: E402
                       run, trace_reduce)
from test_names import cell_entries  # noqa: E402

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "airline-gbdt.fit-mesh4"
SEED = 2 ** 31 + 31
# the tree's round and its parts, the readings the resident tree has too
SHARED = ["hist_ms_per_round", "round_device_ms", "route_ms_per_round",
          "leaf_ms_per_round", "boost_ms_per_round"]
MINE = ["mesh_hist_roofline", "allreduce_ms_per_round",
        "collective_bytes_per_round", "chip_busy_skew_pct.train"]


def mesh_fit():
    return run.load_module("traffic", "mesh_fit")


@pytest.fixture(scope="module")
def four_devices():
    import jax
    if jax.device_count() < 4:
        pytest.skip("the cell's rehearsal needs 4 CPU devices: run with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")


def test_the_cell_and_its_configuration_resolve():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == "airline-gbdt")
    assert cell["config"] == "airline-gbdt" and cell["chips"] == 4
    assert CELL in [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    spec = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    assert spec["generator"] == "mesh_fit"
    assert spec["reference"] == "airline-gbdt"
    p = spec["params"]
    assert (p["num_trees"], p["histogram"], p["collective"],
            p["overlap_chunks"]) == (2, "auto", "flat", 1)
    assert p["regret_levels"] == [[0, 0], [0, 4], [0, 7]]
    data = json.loads((ROOT / config["file"]).read_text())
    sizes = data["sizes"]
    # the source's shapes, none of them cut, and every row of it
    assert (sizes["num_features"], sizes["num_bins"], sizes["max_depth"],
            sizes["train_rows"]) == (13, 256, 8, 115_000_000)
    assert p["rows"] == sizes["train_rows"] and p["rows"] % cell["chips"] == 0
    assert config["reduced"] == [] == data["reduced"]
    assert "chips" not in p         # only the builder's control sets it
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_rows_per_s")
    # after the sparse cell's, whatever follows
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        "bosch-gbdt.fit-sparse")
    assert set(data["tolerance"]["limits"]) == {
        "base_abs_err", "gain_rel_err", "cover_rel_err", "leaf_rel_err",
        "split_regret", "trees_missing", "root_cover_rel_err"}
    assert len(data["guarantees"]) == 3 and "deployment" in data


def test_every_new_layer_metric_has_its_file_and_reader():
    # the mesh's own come after the sparse cell's
    for m in cell_entries(CELL, MINE, after="sparse_boost_ms_per_round"):
        assert m["workloads"][0] == CELL and m["moves"] == "train_rows_per_s"
    for m in cell_entries(CELL, SHARED):
        assert m["workloads"].index(CELL) > m["workloads"].index(
            "higgs-gbdt.fit-resident")
    roofline = json.loads(
        (HERE / "layer_metrics" / "mesh_hist_roofline.json").read_text())
    assert roofline["args"]["opcount"] == \
        "opcount_mesh_histogram:dense_histogram_shard"
    one_chip = json.loads(
        (HERE / "layer_metrics" / "hist_roofline.json").read_text())
    assert roofline["args"]["pattern"] == one_chip["args"]["pattern"]
    reduce = json.loads(
        (HERE / "layer_metrics" / "allreduce_ms_per_round.json").read_text())
    assert reduce["args"]["scope"] == "mesh\\.allreduce"


def test_the_rooflines_work_is_one_chips_rows():
    counts = {"rows": 4 * 1000 * 2, "rounds": 2, "levels": 16,
              "rows_per_chip": 1000, "features": 13, "chips": 4}
    work = opcount_mesh_histogram.dense_histogram_shard(counts)
    assert work["bytes"] == 1000 * (13 + 12) * 16
    assert work["flops"] == 2.0 * 1000 * 13 * 16
    # a quarter of what the one-chip function says of all four shards' rows
    whole = opcount.dense_histogram(dict(counts, data_rows=4000))
    assert whole["bytes"] == 4 * work["bytes"]
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert opcount.least_seconds(work, peaks)[1] == "bytes"


def record(chips: list) -> harness.RunRecord:
    trace = trace_reduce.Trace((0, 1000), chips, [])
    return harness.RunRecord(cell=None, peaks=None, counters={}, spans={},
                             trace=trace, setup={}, native={}, window_s=1e-6,
                             counts={})


def test_chip_skew_is_the_spread_of_the_chips_busy_unions():
    read = run.load_module("readers", "chip_skew").read
    even = [[("%a", 0, 100), ("%b", 50, 200)]] * 4          # 200 each
    assert read({}, record(even)) == 0.0
    uneven = [[("%a", 0, 100)], [("%a", 0, 120)], [("%a", 0, 80)],
              [("%a", 0, 50), ("%b", 50, 100)]]             # 100 120 80 100
    assert read({}, record(uneven)) == pytest.approx(100.0 * 40 / 100)
    assert read({}, record([[("%a", 0, 100)]])) is None     # one chip
    assert read({}, record([])) is None
    no_trace = record([])
    no_trace.trace = None
    assert read({}, no_trace) is None


def test_rows_are_a_function_of_seed_and_shard_with_the_sources_columns():
    import jax
    g = mesh_fit()
    draw = jax.jit(g.shard_columns, static_argnums=1)
    key = jax.random.PRNGKey(harness.seed31(SEED))
    a, score = draw(jax.random.fold_in(key, 0), 1 << 16)
    again, _ = draw(jax.random.fold_in(key, 0), 1 << 16)
    other, _ = draw(jax.random.fold_in(key, 1), 1 << 16)
    a, again, other = (np.asarray(x) for x in (a, again, other))
    assert a.shape == (13, 1 << 16) == other.shape
    assert np.array_equal(a, again) and not np.array_equal(a, other)
    assert np.array_equal(a, np.round(a))           # codes and whole minutes
    distinct = dict(zip(g.COLUMNS, (len(np.unique(c)) for c in a)))
    assert (distinct["Year"], distinct["Month"], distinct["DayofMonth"],
            distinct["DayOfWeek"], distinct["UniqueCarrier"],
            distinct["Diverted"]) == (22, 12, 31, 7, 30, 2)
    assert 300 <= distinct["Origin"] <= 340 and distinct["FlightNum"] > 2000
    assert a[4].min() >= 0 and a[5].max() <= 1439
    rate = float((np.asarray(score) > g.SCORE_CUT).mean())
    assert abs(rate - 0.45) < 0.01
    # the binner's shortcut is QuantileBinner.transform
    from dmlc_core_tpu.models import QuantileBinner
    binner = QuantileBinner(num_bins=256).fit(a[:, :8192].T)
    assert np.array_equal(np.asarray(g.bin_columns(a, binner.cuts)),
                          np.asarray(binner.transform(a.T)))


def walk(tmp_path, seed=SEED):
    cell = harness.load_cell(HERE, CELL, seed, rehearse=True)
    cell.cache_dir = tmp_path
    generator = mesh_fit()
    reference = run.load_module("references", cell.reference)
    spans = harness.Spans()
    state = generator.setup(cell, spans)
    measured = generator.window(state, 0.2, spans)
    return cell, generator, reference, state, measured


@pytest.mark.parametrize("seed", (SEED, 99))
def test_the_rehearsal_walks_and_the_control_fails(tmp_path, four_devices,
                                                   seed):
    cell, generator, reference, state, measured = walk(tmp_path, seed)
    assert measured["attempted"] >= 1 and measured["failed"] == 0
    counts = measured["counts"]
    assert set(counts) == {"rows", "rounds", "levels", "rows_per_chip",
                           "features", "chips"}
    assert counts["chips"] == 4 and counts["rows_per_chip"] * 4 == 16384
    assert counts["levels"] == counts["rounds"] * 3
    assert counts["rows"] == 16384 * counts["rounds"]
    plan = state["plan"]
    assert plan.num_shards == 4 and plan.collective == "flat"
    assert state["model"].mesh_plan is plan
    assert state["bins"].sharding == plan.data_sharding()
    got = generator.check(state, reference, control=1)
    limits = cell.config["tolerance"]["limits"]
    sound = {c["name"]: c["value"] <= limits[c["name"]] for c in got
             if not c["name"].startswith("control.")}
    assert set(sound) == set(limits) and all(sound.values()), got
    failed = [c["name"] for c in got if c["name"].startswith("control.")
              and c["value"] > limits[c["name"][len("control."):]]]
    assert failed, got
    generator.teardown(state)


def test_the_one_chip_control_is_the_same_program_on_one_shard(tmp_path,
                                                               four_devices):
    cell = harness.load_cell(HERE, CELL, SEED, rehearse=True)
    cell.cache_dir = tmp_path
    cell.params.update(chips=1, rows=4096)
    generator = mesh_fit()
    state = generator.setup(cell, harness.Spans())
    assert state["plan"].num_shards == 1 and state["rows_chip"] == 4096
    assert state["model"].mesh_plan is state["plan"]
    generator.teardown(state)


def run_cell(capsys, trace: int = 0) -> dict:
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "0.3", "--trace", str(trace), "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", (0, 1))
def test_sound_run_is_correct_and_prints_the_contract_keys(capsys,
                                                           four_devices,
                                                           trace):
    line = run_cell(capsys, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["metrics"] == {}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["device"]["count"] >= 4


def patched(monkeypatch, patch):
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if (kind, name) == ("traffic", "mesh_fit"):
            patch(module)
        return module
    monkeypatch.setattr(run, "load_module", load)


def test_fit_that_returns_its_state_unchanged_is_not_correct(
        capsys, four_devices, monkeypatch):
    def patch(module):
        def unchanged(state):
            state["forest"] = state["model"].init()
        module.fit_once = unchanged
    patched(monkeypatch, patch)
    assert run_cell(capsys)["correct"] is False


def test_fit_that_leaves_one_shard_out_is_not_correct(capsys, four_devices,
                                                      monkeypatch):
    def patch(module):
        import jax
        import jax.numpy as jnp

        def three_shards(state):
            rows = state["rows"]
            weight = (jnp.arange(rows) >= state["rows_chip"]
                      ).astype(jnp.float32)
            state["forest"] = jax.block_until_ready(state["model"].fit(
                state["bins"], state["label"], weight=weight))
        module.fit_once = three_shards
    patched(monkeypatch, patch)
    assert run_cell(capsys)["correct"] is False
