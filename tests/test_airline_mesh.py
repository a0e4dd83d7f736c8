"""`GBDT.fit` under a `MeshPlan`, as the `airline-gbdt` configuration runs it,
held against that configuration's plain reference (numpy float64, taken in
row blocks) at a small size on the CPU mesh: the sharded fit's forest lies
inside the rehearsal's limits on 4 and on 8 shards and has the one-device
fit's splits; the shards' local histograms add up to the histogram of all
rows; a fit that loses one shard's gradients fails `root_cover_rel_err`; a
column of few distinct values keeps them through the binner; and the mesh
counters count calls of the tree program, not compiles of it."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import run  # noqa: E402
from dmlc_core_tpu import telemetry  # noqa: E402
from dmlc_core_tpu.models import GBDT, QuantileBinner  # noqa: E402
from dmlc_core_tpu.ops.pallas_segment import histogram_gh  # noqa: E402
from dmlc_core_tpu.parallel import MeshPlan  # noqa: E402

CELL = json.loads((ROOT / "benchmark" / "workloads"
                   / "airline-gbdt.fit-mesh4.json").read_text())
CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "airline-gbdt.json").read_text())
ROWS, BINS, DEPTH, TREES = 16384, 32, 3, 2
SIZES = dict(CONFIG["sizes"], num_bins=BINS, max_depth=DEPTH)
# the rehearsal's limits: the cell's own, with the leaf and regret limits
# that the cell's file gives its tiny size
LIMITS = dict(CONFIG["tolerance"]["limits"], **CELL["rehearse"]["limits"])
REGRET = [[0, 0], [0, 2], [1, 1]]
WEEKDAY = 3     # DayOfWeek: 7 distinct values


@pytest.fixture(scope="module")
def reference():
    return run.load_module("references", "airline-gbdt")


@pytest.fixture(scope="module")
def traffic():
    return run.load_module("traffic", "mesh_fit")


def drawn(traffic, seed: int, shards: int, rows: int = ROWS):
    """The generator's rows, shard by shard from (seed, shard), binned under
    cuts from a sample that takes as much from each shard: numpy arrays."""
    key = jax.random.PRNGKey(seed)
    per = rows // shards
    made = [traffic.shard_columns(jax.random.fold_in(key, s), per)
            for s in range(shards)]
    cols = jnp.concatenate([m[0] for m in made], axis=1)
    label = jnp.concatenate([(m[1] > traffic.SCORE_CUT) for m in made]
                            ).astype(jnp.float32)
    take = 4096 // shards
    sample = np.concatenate([np.asarray(m[0][:, :take]) for m in made],
                            axis=1).T
    binner = QuantileBinner(num_bins=BINS).fit(sample)
    bins = traffic.bin_columns(cols, binner.cuts)
    return np.asarray(bins), np.asarray(label)


def model(plan=None, depth: int = DEPTH, trees: int = TREES) -> GBDT:
    return GBDT(num_features=13, num_trees=trees, max_depth=depth,
                num_bins=BINS, learning_rate=SIZES["learning_rate"],
                lambda_=SIZES["lambda"],
                min_child_weight=SIZES["min_child_weight"],
                objective="logistic", missing_aware=False,
                histogram="pallas", histogram_mesh=plan)


def plan_of(shards: int) -> MeshPlan:
    return MeshPlan.build(devices=jax.devices()[:shards], collective="flat",
                          overlap_chunks=1)


def mesh_fit(bins, label, shards: int, weight=None, **kw):
    plan = plan_of(shards)
    put = lambda a: jax.device_put(a, plan.data_sharding())  # noqa: E731
    m = model(plan, **kw)
    assert set(m.level_backends()) == {"pallas"}
    forest = m.fit(put(bins), put(label),
                   weight=None if weight is None else put(weight))
    return {k: np.asarray(v) for k, v in forest.items()}


def numbers(reference, bins, label, forest, **kw) -> dict:
    got = reference.compare(bins, label, forest, SIZES, TREES, REGRET, **kw)
    return {c["name"]: c["value"] for c in got}


# (a) the mesh fit's forest against the reference, inside the rehearsal's
# limits, with the rows taken in blocks that no shard boundary respects
@pytest.mark.parametrize("shards", (4, 8))
def test_mesh_forest_lies_inside_the_rehearsal_limits(reference, traffic,
                                                      shards):
    bins, label = drawn(traffic, 11 + shards, shards)
    forest = mesh_fit(bins, label, shards)
    got = numbers(reference, bins, label, forest, block_rows=3000)
    assert set(got) == set(LIMITS)
    for name, value in got.items():
        assert value <= LIMITS[name], (name, value, LIMITS[name])


# (b) the mesh fit's splits are the one-device fit's on the same rows
@pytest.mark.parametrize("shards", (4, 8))
def test_mesh_splits_equal_the_one_device_splits(traffic, shards):
    bins, label = drawn(traffic, 23, shards)
    sharded = mesh_fit(bins, label, shards)
    whole = model().fit(jnp.asarray(bins), jnp.asarray(label))
    for name in ("feature", "threshold", "default_right"):
        np.testing.assert_array_equal(sharded[name], np.asarray(whole[name]))
    np.testing.assert_allclose(sharded["split_gain"],
                               np.asarray(whole["split_gain"]), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(sharded["leaf"], np.asarray(whole["leaf"]),
                               rtol=1e-4, atol=1e-6)


# (c) the shares add up: every shard's local histogram, summed, is the
# reference's float64 histogram of all rows
@pytest.mark.parametrize("n_nodes", (1, 16, 128))
def test_shard_histograms_add_up_to_the_whole(reference, traffic, n_nodes):
    shards = 4
    bins, label = drawn(traffic, 31, shards)
    rng = np.random.default_rng(n_nodes)
    rel = rng.integers(0, n_nodes, ROWS).astype(np.int32)
    g = rng.standard_normal(ROWS).astype(np.float32)
    h = rng.uniform(0.05, 0.25, ROWS).astype(np.float32)
    per = ROWS // shards
    parts = [np.asarray(histogram_gh(
        jnp.asarray(bins[lo:lo + per]), jnp.asarray(rel[lo:lo + per]),
        jnp.stack([g[lo:lo + per], h[lo:lo + per]], axis=-1), n_nodes, BINS,
        force="pallas"), np.float64) for lo in range(0, ROWS, per)]
    total = sum(parts)                                   # [nodes, F, B, 2]
    want = reference.level_histogram(
        np.ascontiguousarray(bins.T), rel, n_nodes, BINS,
        (g.astype(np.float64), h.astype(np.float64)))    # [2, F, nodes, B]
    want = want.transpose(2, 1, 3, 0)
    assert total.shape == want.shape
    assert np.abs(total - want).max() <= 2e-6 * np.abs(want).max()
    # and no shard alone is the whole
    assert np.abs(parts[0] - want).max() > 0.1 * np.abs(want).max()


# (d) a fit that loses one shard's gradients and hessians
def test_fit_without_one_shard_fails_root_cover(reference, traffic):
    shards = 4
    bins, label = drawn(traffic, 41, shards)
    weight = np.ones(ROWS, np.float32)
    weight[:ROWS // shards] = 0.0
    forest = mesh_fit(bins, label, shards, weight=weight)
    got = numbers(reference, bins, label, forest)
    assert got["root_cover_rel_err"] > 0.2
    assert got["root_cover_rel_err"] > 100 * LIMITS["root_cover_rel_err"]
    sound = numbers(reference, bins, label, mesh_fit(bins, label, shards))
    assert sound["root_cover_rel_err"] <= LIMITS["root_cover_rel_err"]


# the control: the same pass one precision down fails a limit
def test_bfloat16_control_fails_a_limit(reference, traffic):
    bins, label = drawn(traffic, 43, 4)
    forest = mesh_fit(bins, label, 4)
    got = numbers(reference, bins, label, forest, control=True)
    failed = [k for k in LIMITS if got.get(f"control.{k}", 0) > LIMITS[k]]
    assert {"gain_rel_err", "cover_rel_err"} <= set(failed), got


# the reference in blocks is the reference in one piece
@pytest.mark.parametrize("block_rows", (1000, 4096))
def test_reference_in_blocks_is_the_reference_whole(reference, traffic,
                                                    block_rows):
    bins, label = drawn(traffic, 47, 4)
    forest = mesh_fit(bins, label, 4)
    whole = numbers(reference, bins, label, forest, block_rows=ROWS)
    blocks = numbers(reference, bins, label, forest, block_rows=block_rows)
    for name, value in whole.items():
        assert blocks[name] == pytest.approx(value, rel=1e-6, abs=1e-12), name


# (e) a column of 7 distinct values keeps 7 codes, and the best split on it
# is found
def test_low_cardinality_column_keeps_its_values_and_its_split(reference,
                                                               traffic):
    shards = 4
    bins, _ = drawn(traffic, 53, shards)
    codes = np.unique(bins[:, WEEKDAY])
    assert len(codes) == 7
    # weekdays 6 and 7 against the rest: codes 5 and 6 of the seven
    label = (bins[:, WEEKDAY] >= codes[5]).astype(np.float32)
    flip = np.random.default_rng(5).random(ROWS) < 0.1
    label = np.where(flip, 1.0 - label, label).astype(np.float32)
    forest = mesh_fit(bins, label, shards)
    assert forest["feature"][0, 0] == WEEKDAY
    assert codes[4] <= forest["threshold"][0, 0] < codes[5]
    got = numbers(reference, bins, label, forest)
    assert got["split_regret"] <= LIMITS["split_regret"]


# (f) the counters count calls, not compiles
def test_mesh_counters_grow_alike_on_every_fit(traffic):
    shards = 4
    bins, label = drawn(traffic, 59, shards)
    plan = plan_of(shards)
    put = lambda a: jax.device_put(a, plan.data_sharding())  # noqa: E731
    m = model(plan)
    b, y = put(bins), put(label)

    def fit_delta() -> tuple:
        before = telemetry.snapshot()
        jax.block_until_ready(m.fit(b, y))
        d = telemetry.counters_delta(before, telemetry.snapshot())
        return d.get("mesh.allreduce_calls", 0), d.get(
            "mesh.collective_bytes", 0)

    first, second, third = fit_delta(), fit_delta(), fit_delta()
    # a level reduces its built node histograms only: the root, then one
    # child of every parent, 1 + sum of 2^(d-1) a tree
    built = 1 + sum(2 ** (d - 1) for d in range(1, DEPTH))
    level_bytes = built * 13 * BINS * 2 * 4
    assert first == (DEPTH * TREES, level_bytes * TREES)
    assert second == first and third == first


# fit places what the caller has not, and names shard_map's even-rows rule
@pytest.mark.parametrize("placed", (False, True))
def test_fit_shards_its_inputs_once_and_leaves_placed_ones_alone(traffic,
                                                                 placed):
    shards = 4
    bins, label = drawn(traffic, 61, shards)
    plan = plan_of(shards)
    m = model(plan)
    if placed:
        bins, label = (jax.device_put(a, plan.data_sharding())
                       for a in (bins, label))
    got = m._shard_inputs(bins, label, None)
    assert got[2] is None
    assert all(a.sharding == plan.data_sharding() for a in got[:2])
    if placed:
        assert got[0] is bins and got[1] is label
    forest = m.fit(bins, label)
    want = mesh_fit(np.asarray(bins), np.asarray(label), shards)
    np.testing.assert_array_equal(np.asarray(forest["feature"]),
                                  want["feature"])
    np.testing.assert_array_equal(np.asarray(forest["leaf"]), want["leaf"])


def test_fit_names_rows_that_do_not_divide_over_the_shards(traffic):
    bins, label = drawn(traffic, 67, 4)
    with pytest.raises(ValueError, match="do not divide over the mesh"):
        model(plan_of(4)).fit(bins[:-2], label[:-2])
