"""The margin update (`models/gbdt.py:_add_leaf_values`): every row takes its
leaf's value by selects, not by a gather a row, and what it takes is the
float the gather took.  Forests and training margins of every builder are
those of a control that puts the gather back; under a mesh plan the program
holds no collective; the counter ``gbdt.margin_select`` says how often the
select path ran."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models import GBDT, QuantileBinner
from dmlc_core_tpu.models import gbdt as gbdt_module
from dmlc_core_tpu.parallel import MeshPlan

from test_gbdt import _sparse_identity_fixture


def bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


@contextlib.contextmanager
def select_ceiling(leaves: int):
    """The most leaves the select path takes, set to ``leaves`` for a block.
    The jitted program reads the constant while it is traced, so what it
    traced goes with the constant, both ways."""
    before = gbdt_module._MARGIN_SELECT_LEAVES
    gbdt_module._MARGIN_SELECT_LEAVES = leaves
    gbdt_module._leaf_values.clear_cache()
    try:
        yield
    finally:
        gbdt_module._MARGIN_SELECT_LEAVES = before
        gbdt_module._leaf_values.clear_cache()


def gathered(margin, leaf, leaf_rel):
    """The control: the lines `_boost` and `_boost_multi` held before."""
    value = leaf[leaf_rel]
    return value if margin is None else margin + value


def leaves_and_rows(leaves: int, rows: int = 3001):
    rng = np.random.default_rng(leaves)
    leaf = rng.normal(size=leaves).astype(np.float32)
    leaf[rng.integers(0, leaves)] = -0.0
    rel = rng.integers(0, leaves, size=rows).astype(np.int32)
    rel[:2] = 0, leaves - 1
    rel[2:2 + leaves] = np.arange(leaves)[:rows - 2]
    margin = rng.normal(size=rows).astype(np.float32)
    margin[::7] = -0.0              # -0.0 + -0.0 keeps its sign, + 0.0 not
    return leaf, rel, margin


@pytest.mark.parametrize("side", ["select", "gather"])
@pytest.mark.parametrize("leaves", [1, 2, 3, 64, 255, 256, 509, 1024])
def test_margin_update_takes_the_float_the_gather_takes(leaves, side):
    """``margin + leaf[leaf_rel]`` bit for bit, a ``-0.0`` leaf under a
    ``-0.0`` margin included, at every cell's leaf count (the leaf-wise
    tree hands over its 2 * 255 - 1 nodes), at counts that are no power of
    two, and on both sides of the crossover."""
    leaf, rel, margin = leaves_and_rows(leaves)
    ceiling = (gbdt_module._MARGIN_SELECT_LEAVES if side == "select"
               else leaves - 1)
    with select_ceiling(ceiling):
        assert gbdt_module._margin_selects(jnp.zeros(leaves)) == (
            side == "select")
        got = gbdt_module._add_leaf_values(
            jnp.asarray(margin), jnp.asarray(leaf), jnp.asarray(rel))
        values = gbdt_module._add_leaf_values(None, jnp.asarray(leaf),
                                              jnp.asarray(rel))
    np.testing.assert_array_equal(bits(got), bits(margin + leaf[rel]))
    assert values.shape == rel.shape and values.dtype == jnp.float32
    np.testing.assert_array_equal(bits(values), bits(leaf[rel]))
    assert (bits(values) == np.int32(-2 ** 31)).any(), "no -0.0 was taken"


# ---- whole fits against the control that gathers ----------------------------


def dense_data(seed: int, rows: int, features: int, classes: int = 2):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1, 1, size=(rows, features)).astype(np.float32)
    score = x[:, 0] + x[:, 1] * x[:, 2]
    y = (np.digitize(score, np.quantile(score, np.arange(1, classes)
                                        / classes))).astype(np.float32)
    return QuantileBinner(num_bins=16).fit_transform(x), jnp.asarray(y)


def dense_fit():
    bins, y = dense_data(5, 900, 5)
    model = GBDT(num_features=5, num_trees=4, max_depth=4, num_bins=16,
                 learning_rate=0.3, histogram="xla")
    return lambda: model.fit(bins, y)


def softmax_fit():
    bins, y = dense_data(6, 700, 4, classes=3)
    model = GBDT(num_features=4, num_trees=3, max_depth=3, num_bins=16,
                 learning_rate=0.4, objective="softmax", num_class=3,
                 histogram="xla")
    return lambda: model.fit(bins, y)


def sparse_fit():
    batch, binner, *_ = _sparse_identity_fixture(np.random.default_rng(40),
                                                 rows=200, feats=4)
    model = GBDT(num_features=4, num_trees=3, max_depth=3, num_bins=8,
                 learning_rate=0.5, missing_aware=True, histogram="xla")
    return lambda: model.fit_batch(batch, binner)


def leafwise_fit():
    bins, y = dense_data(8, 600, 6)
    model = GBDT(num_features=6, num_trees=3, num_bins=16, learning_rate=0.2,
                 grow_policy="lossguide", max_leaves=7, histogram="xla")
    return lambda: model.fit(bins, y)


def mesh_fit():
    bins, y = dense_data(9, 1024, 5)
    plan = MeshPlan.build(jax.devices()[:4])
    model = GBDT(num_features=5, num_trees=3, max_depth=3, num_bins=16,
                 histogram="xla", histogram_mesh=plan)
    bins, y = (jax.device_put(a, plan.data_sharding()) for a in (bins, y))
    return lambda: model.fit(bins, y)


FITS = {"fit": dense_fit, "softmax_fit": softmax_fit,
        "fit_batch": sparse_fit, "leafwise_fit": leafwise_fit,
        "mesh_fit": mesh_fit}


def recorded(monkeypatch, fit, update) -> tuple:
    """The forest of ``fit()`` with ``update`` as the margin update, and
    what every call of it returned: the training margins, a round each."""
    seen = []

    def recording(margin, leaf, leaf_rel):
        out = update(margin, leaf, leaf_rel)
        seen.append(np.asarray(out))    # a copy: the next round donates it
        return out
    monkeypatch.setattr(gbdt_module, "_add_leaf_values", recording)
    return fit(), seen


@pytest.mark.parametrize("builder", sorted(FITS))
def test_fits_equal_the_gathering_control_bit_for_bit(builder, monkeypatch):
    """Every builder goes through `_boost` or `_boost_multi`: the forest and
    each round's training margins equal, bit for bit, those of a fit whose
    margin update is the gather again."""
    fit = FITS[builder]()
    got, margins = recorded(monkeypatch, fit, gbdt_module._add_leaf_values)
    want, control = recorded(monkeypatch, fit, gathered)
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), key)
    assert len(margins) == len(control) >= 3
    for a, b in zip(margins, control):
        np.testing.assert_array_equal(bits(a), bits(b))
    assert len(np.unique(np.asarray(margins[-1]))) > 2


# ---- under a mesh plan ------------------------------------------------------


@pytest.mark.parametrize("leaves", [64, 256])
def test_margin_program_of_a_sharded_fit_holds_no_collective(leaves):
    """The Airline cell's layout on four CPU devices: margins and
    ``leaf_rel`` sharded by rows, the leaves replicated.  The compiled
    program holds no collective and hands the margins back row-sharded."""
    plan = MeshPlan.build(jax.devices()[:4])
    rows = 4 * 1000
    by_rows, whole = plan.data_sharding(), plan.replicated_sharding()
    args = (jax.ShapeDtypeStruct((leaves,), jnp.float32, sharding=whole),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=by_rows),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=by_rows))
    compiled = gbdt_module._leaf_values.lower(*args).compile()
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert op not in text, op
    assert compiled.output_shardings.is_equivalent_to(by_rows, 1)
    # a shard's program works on its own 1,000 rows and never on all 4,000
    assert "f32[1000]" in text and "f32[4000]" not in text
    leaf, rel, margin = leaves_and_rows(leaves, rows)
    got = gbdt_module._add_leaf_values(
        jax.device_put(margin, by_rows), jax.device_put(leaf, whole),
        jax.device_put(rel, by_rows))
    assert got.sharding.is_equivalent_to(by_rows, 1)
    np.testing.assert_array_equal(bits(got), bits(margin + leaf[rel]))


# ---- how often the mechanism engages ----------------------------------------


def selects_counted(fit) -> int:
    before = telemetry.snapshot()
    fit()
    return telemetry.counters_delta(before, telemetry.snapshot()).get(
        "gbdt.margin_select", 0)


@pytest.mark.skipif(not telemetry.enabled(), reason="no native telemetry")
@pytest.mark.parametrize("builder,updates", [
    ("fit", 4), ("fit_batch", 3), ("leafwise_fit", 3), ("mesh_fit", 3),
    ("softmax_fit", 3 * 3)])
def test_select_path_counts_once_a_round_and_the_gather_never(builder,
                                                              updates):
    """``gbdt.margin_select`` rises by one a boosting round whose margin
    update took the select path (a softmax round updates a class a tree),
    and not at all past the crossover."""
    fit = FITS[builder]()
    assert selects_counted(fit) == updates
    with select_ceiling(1):
        assert selects_counted(fit) == 0
