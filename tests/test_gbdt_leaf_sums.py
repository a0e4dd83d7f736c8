"""A dense tree's leaves are read off its last level's split sums (PR 38):
`_build_tree` sums nothing over the rows after its last histogram.  Held
against float64 sums over the rows that ``leaf_rel`` sends to each leaf, for
every option that shapes a leaf, and against the tree program itself, which
may hold no scatter of a row array outside its histograms."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.models import GBDT
from dmlc_core_tpu.models import gbdt as gbdt_module
from dmlc_core_tpu.parallel import MeshPlan

from test_gbdt import TREE_OUTPUTS, tree_inputs

BINS = 16

# A leaf's float32 sums come off a histogram, its right child's through one
# subtraction, so the error is measured in the ABSOLUTE mass of its parent and
# not in the leaf's own (G cancels): eta * (sum |g| of the two siblings) /
# (H + lambda), plus the leaf.  The cases read 9e-8 to 5.6e-7 of that (1,500
# to 2,048 rows; the row sums this replaced, 2e-8 to 1.6e-6); 3.5x of room.
LEAF_TOL = 2e-6


def weights_f64(model, tree, grad, hess, clamp=True):
    """``(leaf, scale)``: the leaves `_build_tree` should return, float64
    ``[2 ** max_depth]``, from the rows ``tree['leaf_rel']`` sends to each:
    ``learning_rate * clip(-G / (H + lambda), lo, hi)``, the bounds
    propagated from the root as `_child_bounds` does (midpoints of the
    clipped child weights along the tree's own splits; ``clamp=False``
    leaves them out); and the scale an error of the sums is measured by."""
    depth, lam = model.max_depth, model.lambda_
    n_leaves = 2 ** depth
    rel = np.asarray(tree["leaf_rel"])
    g, h = np.asarray(grad, np.float64), np.asarray(hess, np.float64)
    G = np.bincount(rel, g, n_leaves)
    H = np.bincount(rel, h, n_leaves)
    absG = np.bincount(rel, np.abs(g), n_leaves)
    with np.errstate(invalid="ignore", divide="ignore"):
        w = -G / (H + lam)
        if clamp and model.monotone_constraints is not None:
            c_of = np.asarray(model.monotone_constraints)
            lo, hi = np.full(1, -np.inf), np.full(1, np.inf)
            for d in range(depth):
                first, n = 2 ** d - 1, 2 ** d
                kids = (-G.reshape(2 * n, -1).sum(1)
                        / (H.reshape(2 * n, -1).sum(1) + lam))
                wl = np.clip(kids[0::2], lo, hi)
                wr = np.clip(kids[1::2], lo, hi)
                mid = 0.5 * (wl + wr)
                f = np.asarray(tree["feature"])[first:first + n]
                live = np.asarray(tree["threshold"])[first:first + n] < BINS
                up, down = live & (c_of[f] > 0), live & (c_of[f] < 0)
                lo, hi = (np.stack([np.where(down, np.maximum(lo, mid), lo),
                                    np.where(up, np.maximum(lo, mid), lo)],
                                   1).reshape(-1),
                          np.stack([np.where(up, np.minimum(hi, mid), hi),
                                    np.where(down, np.minimum(hi, mid), hi)],
                                   1).reshape(-1))
            w = np.clip(w, lo, hi)
        pair = absG.reshape(-1, 2).sum(1).repeat(2)
        scale = model.learning_rate * (pair / (H + lam) + np.abs(w))
    return model.learning_rate * w, scale


def recorded_fit(monkeypatch, model, bins, label, **fit_args):
    """``fit``, and every tree it built: ``[(grad, hess, tree)]`` in call
    order, the (grad, hess) `_boost` handed `_build_tree` (sampled rows
    zeroed, one class's pair a softmax tree)."""
    real, calls = GBDT._build_tree, []

    def recording(self, b, grad, hess, col_mask, col_key):
        out = real(self, b, grad, hess, col_mask, col_key)
        calls.append((grad, hess, dict(zip(TREE_OUTPUTS, out, strict=True))))
        return out

    monkeypatch.setattr(GBDT, "_build_tree", recording)
    forest = model.fit(bins, label, **fit_args)
    return forest, calls


def labelled(seed, rows, features, classes=2):
    """``(bins, label)`` for a ``fit``: `tree_inputs`' bins, and classes cut
    at the quantiles of a noisy score of the first column."""
    bins = tree_inputs(seed, rows, features, BINS)[0]
    rng = np.random.default_rng(seed + 1)
    score = np.asarray(bins[:, 0], np.float32) + rng.normal(0, 4, rows)
    cuts = np.quantile(score, np.arange(1, classes) / classes)
    return bins, jnp.asarray(np.searchsorted(cuts, score), jnp.float32)


def one_tree(model, seed=5, rows=1500, bins=None):
    args = tree_inputs(seed, rows, model.num_features, BINS)
    if bins is not None:
        args = (jnp.asarray(bins, jnp.uint8),) + args[1:]
    tree = dict(zip(TREE_OUTPUTS, model._build_tree(*args),
                    strict=True))
    return [(args[1], args[2], tree)]


def gbdt(**kw):
    kw = {"num_features": 5, "num_trees": 2, "max_depth": 4,
          "num_bins": BINS, "histogram": "xla", **kw}
    return GBDT(**kw)


def null_split_case(_monkeypatch):
    """One feature of two values: the root divides them, and no cut may
    divide either child again, so every node below is a null split — its
    left leaf is the node, its right leaf holds no row and weighs 0."""
    model = gbdt(num_features=1, max_depth=3, missing_aware=True)
    bins = np.where(np.arange(1500) % 3 == 0, 3, 9)[:, None]
    trees = one_tree(model, bins=bins)
    tree = trees[0][2]
    assert np.asarray(tree["threshold"]).tolist() == [3] + [BINS] * 6
    leaf = np.asarray(tree["leaf"])
    assert sorted(set(np.asarray(tree["leaf_rel"]).tolist())) == [0, 4]
    assert np.all(leaf[[1, 2, 3, 5, 6, 7]] == 0.0) and np.all(leaf[[0, 4]])
    return model, trees


def min_child_weight_case(_monkeypatch):
    """A floor of 0.35 of the hessian mass: the root may be cut, near its
    middle, and nothing below it."""
    args = tree_inputs(5, 1500, 5, BINS)
    model = gbdt(max_depth=3,
                 min_child_weight=0.35 * float(jnp.sum(args[2])))
    trees = one_tree(model)
    thr = np.asarray(trees[0][2]["threshold"])
    assert thr[0] < BINS and np.all(thr[1:] == BINS)
    return model, trees


def monotone_case(_monkeypatch):
    model = gbdt(missing_aware=True, monotone_constraints=[1, -1, 0, 1, 0])
    trees = one_tree(model)
    grad, hess, tree = trees[0]
    leaf, _ = weights_f64(model, tree, grad, hess)
    free, _ = weights_f64(model, tree, grad, hess, clamp=False)
    assert np.sum(leaf != free) >= 2, "no leaf of the case is clamped"
    return model, trees


def subsample_case(monkeypatch):
    model = gbdt(subsample=0.5, num_trees=3, seed=11)
    _, calls = recorded_fit(monkeypatch, model, *labelled(7, 1500, 5))
    dropped = [float(jnp.mean(h == 0)) for _, h, _ in calls]
    assert all(0.4 < d < 0.6 for d in dropped), dropped
    return model, calls


def softmax_case(monkeypatch):
    model = gbdt(objective="softmax", num_class=3, max_depth=3)
    forest, calls = recorded_fit(monkeypatch, model,
                                 *labelled(7, 1500, 5, classes=3))
    assert len(calls) == 6
    # tree i of the forest is call i, class i % 3 (`_boost_multi`)
    for i, (_, _, tree) in enumerate(calls):
        np.testing.assert_array_equal(np.asarray(forest["leaf"][i]),
                                      np.asarray(tree["leaf"]))
    return model, calls


def mesh_case(monkeypatch):
    """Four CPU devices under a plan against one: the leaves come off the
    reduced histograms, so they are global sums with no collective of their
    own, and the forests are equal as tests/test_meshplan.py compares them."""
    bins, label = labelled(7, 2048, 5)
    alone = gbdt(missing_aware=True).fit(bins, label)
    plan = MeshPlan.build(jax.devices()[:4])
    model = gbdt(missing_aware=True, histogram_mesh=plan)
    forest, calls = recorded_fit(
        monkeypatch, model, jax.device_put(bins, plan.data_sharding()),
        jax.device_put(label, plan.data_sharding()))
    assert len(forest["leaf"].sharding.device_set) == 4
    for key in ("feature", "threshold", "default_right"):
        np.testing.assert_array_equal(np.asarray(alone[key]),
                                      np.asarray(forest[key]), key)
    np.testing.assert_allclose(np.asarray(alone["leaf"]),
                               np.asarray(forest["leaf"]),
                               rtol=1e-4, atol=1e-6)
    return model, calls


def plain(**kw):
    """One tree of `tree_inputs`' normal gradients by a model of ``kw``."""
    def case(_monkeypatch):
        model = gbdt(**kw)
        return model, one_tree(model)
    return case


CASES = {
    "missing_aware_off": plain(missing_aware=False),
    "missing_aware_on": plain(missing_aware=True),
    "lambda_0": plain(lambda_=0.0, missing_aware=True),
    "lambda_1_eta_0.1": plain(lambda_=1.0, learning_rate=0.1),
    "depth_6": plain(max_depth=6, missing_aware=True),
    "null_splits": null_split_case,
    "min_child_weight": min_child_weight_case,
    "monotone": monotone_case,
    "subsample": subsample_case,
    "softmax": softmax_case,
    "pallas_interpreted": plain(histogram="pallas", missing_aware=True),
    "pallas_interpreted_depth_1": plain(histogram="pallas", max_depth=1),
    "mesh_of_four": mesh_case,
}


@pytest.mark.parametrize("case", CASES)
def test_a_leaf_is_the_float64_weight_of_the_rows_it_holds(case, monkeypatch):
    """``-G / (H + lambda) * eta`` of the rows ``leaf_rel`` sends to a leaf,
    in float64, is the leaf the tree returns, to ``LEAF_TOL`` of the
    siblings' absolute mass; a leaf no row reaches weighs exactly 0."""
    model, trees = CASES[case](monkeypatch)
    assert trees
    for grad, hess, tree in trees:
        want, scale = weights_f64(model, tree, grad, hess)
        got = np.asarray(tree["leaf"], np.float64)
        assert np.all(np.isfinite(got)) or model.lambda_ == 0.0
        empty = np.bincount(np.asarray(tree["leaf_rel"]),
                            minlength=got.size) == 0
        if model.lambda_ > 0.0:
            assert np.all(got[empty] == 0.0)
        err = np.abs(got - want)[~empty] / scale[~empty]
        assert err.max() < LEAF_TOL, (case, err.max())


# ---- the scatter is gone, and stays gone ------------------------------------

ROWS = 1733     # a prime: a dimension it divides is made of the rows


def row_scatters(jaxpr) -> list:
    """Every combining scatter of the program (``scatter-add`` and its kin:
    what ``segment_sum`` and ``.at[].add`` are), sub-programs included, one
    of whose operands or results has the rows among its dimensions (the XLA
    histograms flatten rows x features into one).  The plain ``scatter`` is
    left out: the kernel's wrapper pads its operands by ``.at[:rows].set``,
    a copy of a static slice."""
    found = []
    for eqn in jaxpr.eqns:
        shapes = [tuple(getattr(v.aval, "shape", ()))
                  for v in list(eqn.invars) + list(eqn.outvars)]
        name = eqn.primitive.name
        if (name.startswith("scatter") and name != "scatter"
                and any(d and d % ROWS == 0 for s in shapes for d in s)):
            found.append((name, shapes))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += row_scatters(sub)
    return found


@pytest.mark.parametrize("missing_aware", [False, True])
def test_the_tree_program_scatters_no_row_array(missing_aware):
    """With the kernel building the histograms, `_build_tree` holds no
    scatter-add (``segment_sum`` is one) that reads or writes an array with
    the rows among its dimensions: the ``[rows, 2]`` pair that one relaid at
    512 B a row cannot come back by a quiet edit.  The XLA histograms ARE
    such scatters, one a level, which shows that the walk can see them."""
    args = tree_inputs(5, ROWS, 5, BINS)
    seen = {}
    for backend in ("pallas", "xla"):
        model = gbdt(histogram=backend, missing_aware=missing_aware,
                     max_depth=3)
        seen[backend] = row_scatters(
            jax.make_jaxpr(model._build_tree)(*args).jaxpr)
    assert seen["pallas"] == []
    assert len(seen["xla"]) == 3 and all(
        name == "scatter-add" for name, _ in seen["xla"]), seen["xla"]


def test_split_child_sums_reads_what_the_split_search_holds():
    """The helper alone, on histograms whose sums are small integers (exact
    in float32): a cut with the missing mass on either side, and a null
    split, whose left child is the node and whose right child is (0, 0)."""
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 9, size=(3, 2, 4, 2)).astype(np.float32)
    gl, hl = np.cumsum(hist[..., 0], 2), np.cumsum(hist[..., 1], 2)
    dirs = [(gl, hl), (gl - hist[:, :, 0:1, 0], hl - hist[:, :, 0:1, 1])]
    split_f = np.asarray([1, 0, 0], np.int32)
    split_b = np.asarray([2, 1, 4], np.int32)       # node 2: a null split
    split_d = np.asarray([0, 1, 0], np.int32)
    got = np.asarray(gbdt_module._split_child_sums(
        [tuple(map(jnp.asarray, d)) for d in dirs],
        *map(jnp.asarray, (split_f, split_b, split_d))))
    want = np.stack([
        hist[0, 1, :3].sum(0), hist[0, 1, 3:].sum(0),
        hist[1, 0, 1:2].sum(0), hist[1, 0, [0, 2, 3]].sum(0),
        hist[2, 0].sum(0), np.zeros(2)])
    np.testing.assert_array_equal(got, want)
