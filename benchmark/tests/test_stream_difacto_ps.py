"""The ``stream_difacto_ps`` generator and the ``criteo-tb-difacto-ps4``
reference at the cell's rehearsal size, on a CPU mesh of four: the walk ends
``correct`` and its control does not; a fault planted in the program — a key
dropped at the exchange, a gradient summed over three senders, a row written on
a chip that does not own it — fails the limit that names it; a program without
``mesh=`` fails at once; the roofline's work is one chip's; and the cell's
entries in ``BENCHMARK.json`` are there in their order (membership and order,
never that they are the last)."""
import json
import os
from pathlib import Path

# the cell holds four chips; its rehearsal wants as many CPU devices, asked
# for before anything initialises a JAX backend
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import (harness, opcount, opcount_sharded_rows,  # noqa: E402
                       run)
from test_names import cell_entries  # noqa: E402
from test_references import SEED, control_fails, verdict, walk  # noqa: E402

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "criteo-tb-difacto-ps4.stream-train-mesh4"
CONFIG = "criteo-tb-difacto-ps4"
SIBLING = "criteo-tb-difacto"
# the step's readings that the one-chip sibling's step has too, under the
# spans' own names, then what the exchange and the owner brought
STEP = ["sgd_step_device_ms", "sgd_unique_ms_per_step",
        "sgd_gather_ms_per_step", "sgd_scatter_ms_per_step",
        "sgd_touched_rows_per_step", "sgd_margins_ms_per_step",
        "sgd_update_ms_per_step", "sgd_active_rows_per_step"]
MINE = ["ps4_exchange_ms_per_step", "ps4_owner_merge_ms_per_step",
        "ps4_exchange_bytes_per_step", "ps4_owner_rows_per_step",
        "ps4_exchange_overflow_per_step", "ps4_scatter_roofline"]
JOINED = ["parse_us_per_row.train", "feed_wait_pct.train",
          "feed_wait_us_per_row.train", "h2d_host_wait_us_per_batch.train",
          "h2d_emit_wait_us_per_batch.train",
          "pack_input_wait_us_per_row.train", "native_spans_dropped.train",
          "feed_wait_h2d_pct.train", "feed_wait_native_pct.train",
          "feed_wait_handoff_pct.train", "feed_lead_ms.train",
          "h2d_device_put_us_per_batch.train", "clock_sync_err_us.train"]


@pytest.fixture(scope="module")
def four_devices():
    import jax
    if jax.device_count() < 4:
        pytest.skip("the cell's rehearsal needs 4 CPU devices: run with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")


def test_the_cell_and_its_configuration_resolve():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert cell["config"] == CONFIG and cell["chips"] == 4
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(BENCH["workloads"]) // 4
    spec = json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
    assert spec["generator"] == "stream_difacto_ps"
    assert spec["reference"] == CONFIG
    assert spec["params"] == {"file_rows": 8388608, "num_workers": 8,
                              "loss_every": 8, "compare_steps": 3,
                              "sample_rows": 256, "sample_features": 4096}
    data = json.loads((ROOT / config["file"]).read_text())
    sibling = json.loads((HERE / "configs" / f"{SIBLING}.json").read_text())
    s = data["sizes"]
    # the sibling's and the source's shapes, none of them cut: only the
    # table is the size four chips hold, and the workers are four
    assert s["num_features"] == 2 ** 28 == 4 * sibling["sizes"]["num_features"]
    assert (s["workers"], s["servers"]) == (4, 4)
    assert {k: v for k, v in s.items() if k not in (
        "num_features", "workers", "servers")} == {
            k: v for k, v in sibling["sizes"].items() if k != "num_features"}
    assert config["reduced"] == ["rows"] == data["reduced"]
    assert len(data["source"]) <= 200 and "ps-lite" in data["source"]
    assert set(sibling["assumed"]) <= set(data["assumed"])
    assert {"synchronous_steps", "workers_rows"} <= set(data["assumed"])
    assert len(data["guarantees"]) == 8 and "deployment" in data
    limits = data["tolerance"]["limits"]
    assert set(limits) == set(data["tolerance"]["limits_why"]) - {
        "loss_rel_err"}
    assert set(limits) == set(sibling["tolerance"]["limits"]) | {
        "exchange_dropped", "owner_mismatch"}
    for exact in ("count_mismatch", "live_count_mismatch", "gate_unexercised",
                  "active_set_mismatch", "live_active_set_mismatch",
                  "untouched_changed", "delivery_mismatch",
                  "exchange_dropped", "owner_mismatch"):
        assert limits[exact] == 0
    # after the one-chip sibling's and the first four-chip cell's
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) > names.index(f"{SIBLING}.stream-train")
    assert names.index(CELL) > names.index("airline-gbdt.fit-mesh4")
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_rows_per_s")
    assert rate["workloads"].index(CELL) > rate["workloads"].index(
        f"{SIBLING}.stream-train")


def test_every_new_layer_metric_has_its_file_and_reader():
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in MINE}
    for entry in cell_entries(CELL, MINE, after="difacto_scatter_roofline"):
        assert entry["workloads"] == [CELL] and entry["layer"] in layers
        assert entry["moves"] == "train_rows_per_s"
    for entry in cell_entries(CELL, STEP):
        assert entry["workloads"].index(CELL) > entry["workloads"].index(
            f"{SIBLING}.stream-train")
    # the feed's metrics and the chips' skew hold for a batch laid over four
    # chips: the cell joins their lists, after the cells that were there
    for name in JOINED:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"].index(CELL) > entry["workloads"].index(
            f"{SIBLING}.stream-train")
    skew = next(m for m in BENCH["per_layer"]
                if m["name"] == "chip_busy_skew_pct.train")
    assert skew["workloads"].index(CELL) > skew["workloads"].index(
        "airline-gbdt.fit-mesh4")


def test_the_scatter_roofline_is_one_chips_share_of_the_global_keys():
    spec = json.loads((HERE / "layer_metrics"
                       / "ps4_scatter_roofline.json").read_text())
    assert spec["args"]["pattern"] == "^%_scatter_rows_inplace_pallas"
    assert spec["args"]["opcount"] == (
        "opcount_sharded_rows:difacto_rows_shard")
    work = opcount_sharded_rows.difacto_rows_shard(
        {"distinct_keys": 4000, "num_factors": 16, "chips": 4})
    # a chip's share of the keys, (v, N) 64 B each, read and written
    assert work == {"flops": 0.0, "bytes": 2.0 * 1000 * 128}
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    least, bound = opcount.least_seconds(work, next(iter(peaks.values())))
    assert bound == "bytes" and least > 0


def test_the_kernel_visits_the_tables_the_roofline_counts(monkeypatch):
    """At the cell's sizes an owner's shard is the sibling's whole table and
    its lanes the sibling's candidate: the rows and their sums take the rows
    kernel, the tables of one element a key XLA's scatter."""
    from dmlc_core_tpu.models.common import EXCHANGE_LANES
    from dmlc_core_tpu.ops import pallas_rows
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    s = json.loads((HERE / "configs" / f"{CONFIG}.json").read_text())["sizes"]
    shard, lanes = s["num_features"] // s["servers"], (
        s["servers"] * EXCHANGE_LANES[0])
    assert (shard, lanes) == (2 ** 26, 131072)
    assert pallas_rows.engages(shard, lanes, np.float32, s["num_factors"])
    assert not pallas_rows.engages(shard, lanes, np.float32)
    assert not pallas_rows.engages(shard, lanes, np.int32)


def test_a_program_whose_model_takes_no_plan_fails_at_once(tmp_path,
                                                           monkeypatch,
                                                           four_devices):
    """Before a byte of the file is drawn, with the generator's own
    message: what the parent commit does with this cell."""
    from dmlc_core_tpu.models import fm
    real = fm.FactorizationMachine.__init__

    def parents(self, *args, **kwargs):
        if "mesh" in kwargs:
            raise TypeError("__init__() got an unexpected keyword argument "
                            "'mesh'")
        real(self, *args, **kwargs)

    monkeypatch.setattr(fm.FactorizationMachine, "__init__", parents)
    cell = harness.load_cell(HERE, CELL, SEED, rehearse=True)
    cell.cache_dir = tmp_path
    generator = run.load_module("traffic", cell.generator)
    with pytest.raises(harness.BenchFailure, match="takes no plan"):
        generator.setup(cell, harness.Spans())
    assert not list(tmp_path.iterdir())


@pytest.mark.parametrize("seed", (SEED, 99))
def test_the_rehearsal_ends_correct_and_its_control_does_not(
        tmp_path, seed, four_devices):
    cell, generator, reference, state = walk(CELL, tmp_path, seed)
    assert state["plan"].num_shards == 4
    assert state["batch"] == 4 * cell.sizes["batch_size"]
    for name in ("w", "v", "count"):
        assert len(state["params"][name].addressable_shards) == 4
    at, bias = state["compared"]
    want = cell.params["sample_features"]
    assert at["v"].shape == (want, 16) and at["c"].shape == (want,)
    assert bias.shape == (3,)
    sound = generator.check(state, reference, control=1)
    assert all(verdict(cell, sound).values()), sound
    names = {c["name"] for c in sound}
    assert {"exchange_dropped", "owner_mismatch", "delivery_mismatch",
            "count_mismatch", "untouched_changed"} <= names
    assert {"control.z_rel_err", "control.n_rel_err", "control.nv_rel_err",
            "control.live_z_rel_err", "control.live_n_rel_err",
            "control.live_nv_rel_err"} <= set(control_fails(cell, sound))
    assert not [c for c in sound if "loss" in c["name"]]
    generator.teardown(state)


def faulty_exchange(monkeypatch, fault):
    """``MeshPlan.alltoall`` with ``fault(x, received)`` applied to what an
    exchange hands back; programs traced from here on hold it."""
    from dmlc_core_tpu.parallel import MeshPlan
    real = MeshPlan.alltoall

    def alltoall(self, x, noted=True):
        return fault(x, real(self, x, noted))

    monkeypatch.setattr(MeshPlan, "alltoall", alltoall)


def checked(tmp_path, seed=SEED):
    cell, generator, reference, state = walk(CELL, tmp_path, seed)
    got = verdict(cell, generator.check(state, reference))
    generator.teardown(state)
    return got


def test_a_key_dropped_at_the_exchange_fails_exchange_dropped(
        tmp_path, monkeypatch, four_devices):
    """The first key every sender has for every owner never arrives: the
    owner neither counts nor updates it."""
    import jax.numpy as jnp

    def fault(x, received):
        if received.dtype == jnp.int32:         # the keys and their counts
            return received.at[:, 0, 0].set(2 ** 30)
        return received

    faulty_exchange(monkeypatch, fault)
    got = checked(tmp_path)
    assert not got["exchange_dropped"]
    assert not got["count_mismatch"] and not got["live_count_mismatch"]
    assert got["owner_mismatch"] and got["delivery_mismatch"]
    assert got["untouched_changed"]


def test_a_gradient_summed_over_three_senders_fails_the_states_limits(
        tmp_path, monkeypatch, four_devices):
    """What the first sender pushed is lost on the way: every key and count
    arrives, the sums are short."""
    def fault(x, received):
        if received.shape[1] == 1 + 16:         # the gradient sums
            return received.at[0].set(0)
        return received

    faulty_exchange(monkeypatch, fault)
    got = checked(tmp_path)
    assert not got["z_rel_err"] and not got["live_z_rel_err"]
    assert not got["live_n_rel_err"] and not got["live_nv_rel_err"]
    assert got["exchange_dropped"] and got["owner_mismatch"]
    assert got["count_mismatch"] and got["live_count_mismatch"]
    assert got["delivery_mismatch"] and got["untouched_changed"]


def test_a_row_written_on_a_chip_that_does_not_own_it_fails_owner_mismatch(
        tmp_path, four_devices):
    """The live step also writes, on the chip after each owner, the row a key
    of its minibatch would have there: an id no row of the step names."""
    cell, generator, reference, state = walk(CELL, tmp_path)
    features = cell.sizes["num_features"]
    owned = features // 4
    real = state["model"].train_step
    # the step the check takes from the window's state
    at = state["steps"] % state["per_epoch"] * state["batch"]
    _label, index = generator.base.draw_rows(
        cell.seed, 0, cell.params["file_rows"], features,
        cell.sizes["entries_per_row"], cell.config["assumed"]["label_rate"])
    keys = np.unique(index[at:at + state["batch"]])
    key = int(next(k for k in keys if (k + owned) % features not in keys))

    def misplaced(params, batch):
        params, loss = real(params, batch)
        z = params["ftrl"]["z"]["w"]
        params["ftrl"]["z"]["w"] = z.at[(key + owned) % features].add(1.0)
        return params, loss

    state["model"].train_step = misplaced
    got = {c["name"]: c["value"] for c in generator.check(state, reference)}
    assert got["owner_mismatch"] == 1
    assert got["exchange_dropped"] == 0 == got["live_count_mismatch"]
    assert got["live_z_rel_err"] <= cell.config["tolerance"]["limits"][
        "live_z_rel_err"]
    generator.teardown(state)


def test_the_windows_distinct_keys_are_the_global_minibatches(tmp_path,
                                                              four_devices):
    cell, generator, reference, state = walk(CELL, tmp_path)
    before = state["steps"]
    out = generator.window(state, 0.1, harness.Spans())
    counts = out["counts"]
    assert counts["chips"] == 4 and counts["num_factors"] == 16
    assert counts["rows"] == counts["steps"] * 4 * cell.sizes["batch_size"]
    assert "distinct_keys" not in counts    # nothing of it inside the window
    generator.check(state, reference)
    s = cell.sizes
    _label, index = generator.base.draw_rows(
        cell.seed, 0, cell.params["file_rows"], s["num_features"],
        s["entries_per_row"], cell.config["assumed"]["label_rate"])
    per, rows = state["per_epoch"], 4 * s["batch_size"]
    want = sum(len(np.unique(index[(t % per) * rows:(t % per + 1) * rows]))
               for t in range(before, before + counts["steps"]))
    assert counts["distinct_keys"] == want
    generator.teardown(state)


def test_the_tables_come_first_and_the_loop_hands_no_one_chip_scalar(
        tmp_path, monkeypatch, four_devices):
    """What kept the cell's windows in one mode on the chip (PERF.md, PR 45):
    set-up puts nothing on a chip before the tables (a chip that holds more
    than the others lays its tables out elsewhere, its reads take other
    times, and the others wait at every exchange), and the step's place goes
    to the tally as a host scalar (a device scalar lies on the first chip
    alone, and the tally waits on the others for its copy)."""
    import jax

    from dmlc_core_tpu.models.fm import FactorizationMachine
    real, made_before = FactorizationMachine.init, []
    there = {id(a) for a in jax.live_arrays()}      # what other tests left

    def init(self, seed=0):
        made_before.append([a.shape for a in jax.live_arrays()
                            if id(a) not in there])
        return real(self, seed)

    monkeypatch.setattr(FactorizationMachine, "init", init)
    cell, generator, reference, state = walk(CELL, tmp_path)
    assert made_before == [[]]
    handed, tally_add = [], state["tally_add"]

    def recorded(acc, place, batch):
        handed.append(place)
        return tally_add(acc, place, batch)

    state["tally_add"] = recorded
    generator.step(state, harness.Spans())
    assert [type(place) for place in handed] == [np.uint32]
    assert all(verdict(cell, generator.check(state, reference)).values())
    generator.teardown(state)


def test_misplaced_writes_are_looked_for_on_every_other_chip():
    generator = run.load_module("traffic", "stream_difacto_ps")
    keys = np.array([3, 17, 64 + 3, 200])
    # shards of 64 keys: 3 and 67 are each other's aliases and both named
    got = generator.aliases(keys, 256, 4)
    assert list(got) == sorted({131, 195, 81, 145, 209, 8, 72, 136} - set(keys))
