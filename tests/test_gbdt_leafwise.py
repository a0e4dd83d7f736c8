"""Leaf-wise (best-first) growth, ``GBDT(grow_policy="lossguide")``: the
builder against the plain float64 reference the benchmark keeps
(``benchmark/references/epsilon-lgbm.py``: the same rule, sequential, numpy),
against the depth-wise builder where the two policies coincide, and the
pointer forest through ``predict``, snapshots and checkpoints."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu import checkpoint, telemetry
from dmlc_core_tpu.data.staging import PaddedBatch
from dmlc_core_tpu.models import GBDT, QuantileBinner, gbdt_leafwise
from dmlc_core_tpu.models.gbdt_leafwise import LeafwiseGBDT
from dmlc_core_tpu.serving import ScoringEngine, pack_snapshot

ROOT = Path(__file__).resolve().parents[1]


def _reference():
    spec = importlib.util.spec_from_file_location(
        "epsilon_lgbm_reference",
        ROOT / "benchmark" / "references" / "epsilon-lgbm.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

TABLES_I = ("feature", "threshold", "default_right", "left", "right",
            "node_rows")


def make_data(seed, rows, features, bins, missing=False):
    """A label whose signal is spread over every column with decaying
    weights, one product of two columns and noise: a frontier of many leaves
    of comparable gain."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, features)).astype(np.float32)
    w = rng.normal(size=features) * 0.85 ** np.arange(features)
    y = (x @ w + 0.7 * x[:, 0] * x[:, 1] + rng.normal(size=rows) > 0)
    if missing:
        x[rng.random(x.shape) < 0.1] = np.nan
    binner = QuantileBinner(num_bins=bins, missing_aware=missing)
    return np.asarray(binner.fit_transform(x)), y.astype(np.float32)


def rule(model: LeafwiseGBDT) -> dict:
    """The model's settings under the names the reference reads."""
    return {"num_bins": model.num_bins, "lambda": model.lambda_,
            "learning_rate": model.learning_rate,
            "min_child_weight": model.min_child_weight,
            "max_leaves": model.max_leaves, "max_depth": model.max_depth,
            "missing_aware": model.missing_aware}


def leafwise(features, bins, leaves, **kw):
    kw.setdefault("num_trees", 2)
    kw.setdefault("learning_rate", 0.1)
    kw.setdefault("lambda_", 0.0)
    return GBDT(num_features=features, num_bins=bins,
                grow_policy="lossguide", max_leaves=leaves, **kw)


def host(params) -> dict:
    return {k: np.asarray(v) for k, v in params.items()}


def rows_visited(left, right, count) -> int:
    """What a tree's histograms must visit: its rows once, then the smaller
    child's of every expansion, off the tree's own tables."""
    split = left != np.arange(left.shape[0])
    return int(count[0] + np.minimum(count[left], count[right])[split].sum())


def depth_of(left, right) -> np.ndarray:
    depth = np.zeros(left.shape[0], int)
    for node in range(left.shape[0]):
        if left[node] != node:
            depth[left[node]] = depth[right[node]] = depth[node] + 1
    return depth


SHAPES = {
    # seed, rows, features, bins, leaves, min_child_weight, missing
    "narrow": (1, 3000, 10, 32, 15, 5.0, False),
    "wide-missing": (2, 5000, 24, 64, 31, 2.0, True),
    "few-bins": (3, 1500, 7, 16, 8, 10.0, False),
}


@pytest.mark.parametrize("histogram", ["xla", "pallas"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_builder_matches_float64_reference(shape, histogram):
    """Same expansion order (the pointers are it: children take the next two
    ids), same (feature, bin, direction) and row counts at every node; gains,
    covers and leaves within float32 histograms' reach of float64."""
    seed, rows, F, B, L, mcw, missing = SHAPES[shape]
    bins, y = make_data(seed, rows, F, B, missing)
    model = leafwise(F, B, L, min_child_weight=mcw, missing_aware=missing,
                     histogram=histogram)
    assert model.level_backends() == [histogram]
    got = host(model.fit(jnp.asarray(bins), jnp.asarray(y)))
    want = ref.fit(bins, y, rule(model), model.num_trees)
    assert abs(got["base"] - want["base"]) < 1e-6
    for t, tree in enumerate(want["trees"]):
        for key in TABLES_I:
            np.testing.assert_array_equal(got[key][t], tree[key],
                                          err_msg=f"tree {t} {key}")
        split = tree["left"] != np.arange(tree["left"].shape[0])
        assert split.sum() == L - 1
        np.testing.assert_allclose(got["split_gain"][t][split],
                                   tree["split_gain"][split], rtol=2e-4)
        np.testing.assert_allclose(got["split_cover"][t], tree["split_cover"],
                                   rtol=2e-5)
        leaf = ~split & (tree["node_rows"] > 0)
        np.testing.assert_allclose(got["leaf"][t][leaf], tree["leaf"][leaf],
                                   rtol=2e-4, atol=1e-7)
    numbers = {c["name"]: c["value"] for c in ref.compare(
        bins, y, got, rule(model), model.num_trees,
        [[0, 0], [0, 3], [1, L - 2]], rows_visited=sum(
            rows_visited(t["left"], t["right"], t["node_rows"])
            for t in want["trees"]))}
    for name in ("constraint_violations", "stopped_early", "pointer_errors",
                 "rows_visited_mismatch", "trees_missing", "order_regret"):
        assert numbers[name] == 0, (name, numbers)
    assert numbers["split_regret"] < 1e-9


def heap_walk(params, t, depth):
    """A depth-wise tree as ``{path: (feature, threshold, default_right)}``
    and ``{path: leaf}``; a path is the string of turns from the root."""
    splits, leaves = {}, {}
    for level in range(depth + 1):
        for k in range(2 ** level):
            path = format(k, f"0{level}b") if level else ""
            heap = 2 ** level - 1 + k
            if level == depth:
                leaves[path] = params["leaf"][t][k]
            else:
                splits[path] = tuple(int(params[key][t][heap]) for key in (
                    "feature", "threshold", "default_right"))
    return splits, leaves


def pointer_walk(params, t):
    splits, leaves = {}, {}
    todo = [(0, "")]
    while todo:
        node, path = todo.pop()
        left, right = params["left"][t][node], params["right"][t][node]
        if left == node:
            leaves[path] = params["leaf"][t][node]
            continue
        splits[path] = tuple(int(params[key][t][node]) for key in (
            "feature", "threshold", "default_right"))
        todo += [(left, path + "0"), (right, path + "1")]
    return splits, leaves


@pytest.mark.parametrize("missing", [False, True])
def test_coincides_with_depthwise_when_every_node_splits(missing):
    """``max_leaves = 2 ** d`` under a depth cap ``d``, constraints loose
    enough that every node splits: best-first reaches the complete tree the
    depth-wise builder grows, split for split and leaf for leaf."""
    depth, F, B = 3, 9, 32
    bins, y = make_data(5, 4000, F, B, missing)
    shared = dict(num_trees=2, learning_rate=0.3, lambda_=1.0,
                  min_child_weight=1e-3, missing_aware=missing,
                  histogram="xla")
    level = GBDT(num_features=F, num_bins=B, max_depth=depth, **shared)
    best = leafwise(F, B, 2 ** depth, max_depth=depth, **shared)
    level_forest = level.fit(jnp.asarray(bins), jnp.asarray(y))
    best_forest = best.fit(jnp.asarray(bins), jnp.asarray(y))
    want, got = host(level_forest), host(best_forest)
    for t in range(2):
        want_splits, want_leaves = heap_walk(want, t, depth)
        got_splits, got_leaves = pointer_walk(got, t)
        assert got_splits == want_splits
        assert sorted(got_leaves) == sorted(want_leaves)
        for path, value in want_leaves.items():
            assert got_leaves[path] == pytest.approx(value, rel=1e-4,
                                                     abs=1e-7)
    np.testing.assert_allclose(
        np.asarray(best.predict(best_forest, jnp.asarray(bins))),
        np.asarray(level.predict(level_forest, jnp.asarray(bins))),
        rtol=1e-4)


def test_histogram_rows_visited_is_rows_plus_smaller_children():
    """The counter ``gbdt.hist_rows_visited`` is exactly the root's rows plus
    the smaller child's of every expansion: no pass over rows outside the
    expanded leaf.  ``gbdt.expansions``, ``gbdt.leaves`` and
    ``gbdt.depth_max`` are the forest's own."""
    rows, F, B, L = 3000, 8, 32, 12
    bins, y = make_data(7, rows, F, B)
    model = leafwise(F, B, L, min_child_weight=3.0, histogram="xla")
    before = telemetry.snapshot()
    got = host(model.fit(jnp.asarray(bins), jnp.asarray(y)))
    moved = telemetry.counters_delta(before, telemetry.snapshot())
    ids = np.arange(2 * L - 1)
    visited = splits = depth = 0
    for t in range(model.num_trees):
        left, right, count = (got[k][t] for k in (
            "left", "right", "node_rows"))
        visited += rows_visited(left, right, count)
        splits += (left != ids).sum()
        depth += depth_of(left, right).max()
    assert moved["gbdt.hist_rows_visited"] == visited
    assert moved["gbdt.expansions"] == splits == 2 * (L - 1)
    assert moved["gbdt.leaves"] == splits + model.num_trees
    assert moved["gbdt.depth_max"] == depth
    assert visited < model.num_trees * rows * (1 + np.log2(L))


@pytest.mark.parametrize("histogram, F, dead", [
    ("pallas", 67, 5), ("pallas", 24, 0), ("xla", 67, 0)])
def test_dead_key_tiles_are_told_a_segment(histogram, F, dead):
    """``gbdt.hist_dead_key_tiles``: the padding key tiles the dense kernel's
    plan has at the fit's width (67 features of 256 bins: 5 of 72; none
    where every tile holds a feature) once a segment histogram, the root's
    and one an expansion; nothing where no kernel runs."""
    rows, B, L = 700, 256, 4
    bins, y = make_data(51, rows, F, B)
    model = leafwise(F, B, L, num_trees=1, min_child_weight=3.0,
                     histogram=histogram)
    before = telemetry.snapshot()
    model.fit(jnp.asarray(bins), jnp.asarray(y))
    moved = telemetry.counters_delta(before, telemetry.snapshot())
    assert moved["gbdt.expansions"] == L - 1
    assert moved.get("gbdt.hist_dead_key_tiles", 0) == dead * L


def test_constraints_hold_and_growth_stops_on_an_empty_frontier():
    """A minimum hessian mass that a few hundred rows cannot meet twice:
    growth stops short of ``max_leaves`` with no child under the minimum,
    and the reference finds no split the builder missed."""
    rows, F, B, L, mcw = 1200, 6, 32, 64, 40.0
    bins, y = make_data(11, rows, F, B)
    model = leafwise(F, B, L, min_child_weight=mcw, histogram="xla")
    got = host(model.fit(jnp.asarray(bins), jnp.asarray(y)))
    ids = np.arange(2 * L - 1)
    for t in range(model.num_trees):
        split = got["left"][t] != ids
        leaves = split.sum() + 1
        assert 2 <= leaves < L
        kids = np.concatenate([got["left"][t][split], got["right"][t][split]])
        assert got["split_cover"][t][kids].min() >= mcw * (1 - 1e-5)
        assert (got["split_gain"][t][split] > 0).all()
        assert got["node_rows"][t][2 * leaves - 1:].sum() == 0
    numbers = {c["name"]: c["value"] for c in ref.compare(
        bins, y, got, rule(model), model.num_trees, [], rows_visited=0)}
    assert numbers["stopped_early"] == 0
    assert numbers["constraint_violations"] == 0
    # and max_leaves binds where the frontier does not run out
    full = leafwise(F, B, 8, min_child_weight=1.0, histogram="xla")
    grown = host(full.fit(jnp.asarray(bins), jnp.asarray(y)))
    assert ((grown["left"] != np.arange(15)).sum(axis=1) == 7).all()


def chain_forest(model: LeafwiseGBDT, rng) -> dict:
    """A forest whose every expansion split the right child of the one
    before: ``max_leaves - 1`` levels deep, cuts and values drawn."""
    params = {k: np.array(v) for k, v in model.init().items()}
    nodes = model.num_nodes
    for t in range(model.num_trees):
        for e in range(model.max_leaves - 1):
            node = 2 * e
            params["left"][t, node], params["right"][t, node] = (
                node + 1, node + 2)
            params["feature"][t, node] = rng.integers(model.num_features)
            params["threshold"][t, node] = rng.integers(
                model.num_bins // 4, 3 * model.num_bins // 4)
            params["default_right"][t, node] = rng.integers(2)
        params["leaf"][t] = rng.normal(size=nodes)
    params["base"] = np.float32(0.25)
    params["trees_used"] = np.int32(model.num_trees)
    return params


@pytest.mark.parametrize("missing", [False, True])
def test_predict_follows_pointers_on_a_tree_deeper_than_16(missing):
    rng = np.random.default_rng(23)
    F, B, rows = 5, 16, 700
    model = leafwise(F, B, 24, missing_aware=missing)
    got = chain_forest(model, rng)
    assert depth_of(got["left"][0], got["right"][0]).max() == 23
    bins = rng.integers(0, B, (rows, F)).astype(np.uint8)
    bins_t = np.ascontiguousarray(bins.T)
    want = np.full(rows, got["base"], np.float64)
    deepest = 0
    for t in range(model.num_trees):
        tree = {k: got[k][t] for k in TABLES_I + ("leaf",)}
        _, leaf_of_row, bad = ref.route(bins_t, tree, missing)
        assert bad == 0
        deepest = max(deepest, leaf_of_row.max())
        want += tree["leaf"][leaf_of_row]
    assert deepest >= 33        # some row walks 17 levels down at least
    params = {k: jnp.asarray(v) for k, v in got.items()}
    np.testing.assert_allclose(
        np.asarray(model.margins(params, jnp.asarray(bins))), want,
        rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(model.predict_bucketed(params, jnp.asarray(bins[:37]))),
        np.asarray(model.predict(params, jnp.asarray(bins)))[:37])


def sparse_rows(seed, rows, features):
    """Rows with 40% of their cells present, dense with NaN for the absent
    ones and as the CSR batch a scoring request is."""
    rng = np.random.default_rng(seed)
    x = (rng.random((rows, features)) + 0.1).astype(np.float32)
    x[rng.random(x.shape) > 0.4] = np.nan
    x[:, 0] = np.where(np.isnan(x).all(axis=1), 0.5, x[:, 0])
    present = ~np.isnan(x)
    batch = PaddedBatch(
        label=jnp.zeros(rows, jnp.float32),
        weight=jnp.ones(rows, jnp.float32),
        row_ptr=jnp.asarray(np.concatenate(
            [[0], np.cumsum(present.sum(axis=1))]).astype(np.int32)),
        index=jnp.asarray(np.nonzero(present)[1].astype(np.int32)),
        value=jnp.asarray(x[present]), num_rows=jnp.int32(rows))
    return x, batch


def test_pointer_forest_round_trips_snapshot_engine_and_checkpoint(tmp_path):
    rows, F, B, L = 600, 12, 16, 10
    x, batch = sparse_rows(3, rows, F)
    y = (np.nansum(x[:, :4], axis=1) + 0.3 * np.nan_to_num(x[:, 5])
         > 1.1).astype(np.float32)
    binner = QuantileBinner(num_bins=B, missing_aware=True)
    bins = binner.fit_transform(x)
    config = {"num_features": F, "num_trees": 3, "num_bins": B,
              "max_depth": 0, "missing_aware": True, "learning_rate": 0.2,
              "min_child_weight": 1.0, "grow_policy": "lossguide",
              "max_leaves": L}
    model = GBDT(**config)
    assert type(model) is LeafwiseGBDT
    params = model.fit(bins, jnp.asarray(y))
    want = np.asarray(model.predict(params, bins))
    assert want.std() > 0.05

    uri = str(tmp_path / "leafwise.ckpt")
    checkpoint.save(params, uri)
    restored = checkpoint.load(uri, like=model.init())
    assert sorted(restored) == sorted(params)
    for key in params:
        np.testing.assert_array_equal(np.asarray(restored[key]),
                                      np.asarray(params[key]), err_msg=key)
        assert restored[key].dtype == params[key].dtype

    engine = ScoringEngine.from_snapshot_bytes(
        pack_snapshot("gbdt", config, restored, binner=binner))
    assert type(engine.model) is LeafwiseGBDT
    for key in params:
        np.testing.assert_array_equal(np.asarray(engine.params[key]),
                                      np.asarray(params[key]), err_msg=key)
    np.testing.assert_array_equal(engine.score(batch), want)


def test_a_depthwise_forest_still_loads_and_scores_unchanged():
    """The default policy is the class and the forest it was: a snapshot
    whose configuration names no policy scores as ``predict_batch`` does."""
    rows, F, B = 300, 12, 16
    x, batch = sparse_rows(4, rows, F)
    y = (np.nansum(x[:, :3], axis=1) > 0.8).astype(np.float32)
    binner = QuantileBinner(num_bins=B, missing_aware=True)
    bins = binner.fit_transform(x)
    config = {"num_features": F, "num_trees": 2, "num_bins": B,
              "max_depth": 3, "missing_aware": True}
    model = GBDT(**config)
    assert type(model) is GBDT and model.grow_policy == "depthwise"
    assert type(GBDT(grow_policy="depthwise", **config)) is GBDT
    params = model.fit(bins, jnp.asarray(y))
    assert sorted(params) == sorted(GBDT(**config).init())
    assert params["feature"].shape == (2, 7) and "left" not in params
    engine = ScoringEngine.from_snapshot_bytes(
        pack_snapshot("gbdt", config, params, binner=binner))
    assert type(engine.model) is GBDT
    np.testing.assert_array_equal(
        engine.score(batch), np.asarray(model.predict(params, bins)))


def test_segments_past_a_chunk_go_through_the_backend_in_chunks(monkeypatch):
    """A chunk of 2,048 rows: the root and the first expansions take the
    chunked branch, and the forest is the unchunked one's tables."""
    bins, y = make_data(13, 6000, 6, 16)
    model = leafwise(6, 16, 9, min_child_weight=2.0, histogram="xla")
    whole = host(model.fit(jnp.asarray(bins), jnp.asarray(y)))
    monkeypatch.setattr(gbdt_leafwise, "_SEGMENT_CHUNK", 2048)
    again = leafwise(6, 16, 9, min_child_weight=2.0, histogram="xla")
    chunked = host(again.fit(jnp.asarray(bins), jnp.asarray(y)))
    for key in TABLES_I:
        np.testing.assert_array_equal(chunked[key], whole[key], err_msg=key)
    np.testing.assert_allclose(chunked["leaf"], whole["leaf"], rtol=1e-4,
                               atol=1e-7)


@pytest.mark.parametrize("features", [1, 6, 7, 8])
def test_packed_rows_unpack_to_the_bins(features):
    bins = np.random.default_rng(features).integers(
        0, 256, (50, features)).astype(np.uint8)
    packed = gbdt_leafwise._pack_rows(jnp.asarray(bins))
    assert packed.shape == (50, -(-features // 4))
    np.testing.assert_array_equal(
        np.asarray(gbdt_leafwise._unpack_rows(packed[::-1], features)),
        bins[::-1])
    for f in range(features):
        np.testing.assert_array_equal(
            np.asarray(gbdt_leafwise._packed_column(packed, jnp.int32(f))),
            bins[:, f])


def test_sampling_early_stopping_and_importance_ride_the_shared_driver():
    bins, y = make_data(17, 2500, 10, 32)
    train, held = slice(0, 2000), slice(2000, None)
    model = leafwise(10, 32, 8, num_trees=12, learning_rate=0.5,
                     min_child_weight=1.0, subsample=0.7,
                     colsample_bytree=0.5, seed=3, histogram="xla")
    params = model.fit(jnp.asarray(bins[train]), jnp.asarray(y[train]),
                       eval_set=(jnp.asarray(bins[held]),
                                 jnp.asarray(y[held])),
                       early_stopping_rounds=2)
    used = int(params["trees_used"])
    assert 1 <= used <= 12
    got = host(params)
    ids = np.arange(15)
    assert (got["left"][used:] == ids).all() and (got["leaf"][used:] == 0).all()
    for t in range(used):       # half the columns a tree, at most
        assert len(set(got["feature"][t][got["left"][t] != ids])) <= 5
    loss = float(model.loss(params, jnp.asarray(bins[held]),
                            jnp.asarray(y[held])))
    assert loss < np.log(2.0)
    weight = np.asarray(model.feature_importance(params, "weight"))
    assert weight.sum() == (got["left"] != ids).sum()
    assert np.asarray(model.feature_importance(params, "gain")).max() > 0


@pytest.mark.parametrize("kwargs, message", [
    (dict(grow_policy="bestfirst"), "grow_policy"),
    (dict(max_leaves=8), "max_leaves belongs"),
    (dict(grow_policy="lossguide"), "max_leaves >= 2"),
    (dict(grow_policy="lossguide", max_leaves=8, min_child_weight=0.0),
     "min_child_weight"),
    (dict(grow_policy="lossguide", max_leaves=8, objective="softmax",
          num_class=3), "objective"),
    (dict(grow_policy="lossguide", max_leaves=8, colsample_bylevel=0.5),
     "colsample_bylevel"),
    (dict(grow_policy="lossguide", max_leaves=8,
          monotone_constraints=[1, 0, 0, 0]), "monotone_constraints"),
])
def test_constructor_refuses_what_the_policy_does_not_take(kwargs, message):
    with pytest.raises(ValueError, match=message):
        GBDT(num_features=4, **kwargs)


def test_sparse_fits_are_refused_by_name():
    model = leafwise(4, 16, 4)
    with pytest.raises(NotImplementedError, match="sparse entries"):
        model.fit_batch(None, None)
    with pytest.raises(NotImplementedError, match="streamed"):
        model.fit_streamed(None, None)
    with pytest.raises(NotImplementedError, match="sparse entries"):
        model.level_backends(sparse=True)
