"""Sibling subtraction (PR 32): below the root a level builds one child of
every parent — the one with the smaller hessian mass — and derives the other
as parent - built.  Held against float64 histograms of every node, against
the sentinel every backend must drop, against a chain of lopsided splits,
and against tree builders patched back to building every node."""
import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models import GBDT
from dmlc_core_tpu.models import gbdt as gbdt_module
from dmlc_core_tpu.ops.pallas_segment import (histogram_gh,
                                              histogram_gh_sparse,
                                              sparse_hist_layout)

from test_gbdt import (ROUTE_CASES, TREE_OUTPUTS, _sparse_identity_fixture,
                       tree_inputs)
from test_pallas import _hist_case, _hist_f64

ROWS, FEATURES, BINS = 700, 4, 16


# ---- one level after another, by the functions the builders call ------------


class Backend:
    """One way of summing the histograms of the rows that carry a slot:
    ``built(slot, cols)`` is float32 ``[cols, F, B, 2]``.  The sparse ones
    see the cells whose bin is not 0 (bin 0 is the absent cell there)."""

    def __init__(self, name: str, bins: np.ndarray, gh: np.ndarray):
        self.sparse = name.startswith("sparse")
        self.force = "pallas" if name.endswith("pallas") else "xla"
        self.bins, self.gh = jnp.asarray(bins), jnp.asarray(gh)
        if self.sparse:
            rid, fi = np.nonzero(bins)
            self.entries = tuple(jnp.asarray(a.astype(np.int32)) for a in
                                 (rid, fi, bins[rid, fi]))
            self.live = jnp.ones(rid.shape, bool)
            self.layout = sparse_hist_layout(*self.entries, self.live,
                                             bins.shape[1], BINS)

    def built(self, slot, cols: int):
        slot = jnp.asarray(slot, jnp.int32)
        if not self.sparse:
            return histogram_gh(self.bins, slot, self.gh, cols, BINS,
                                force=self.force)
        return histogram_gh_sparse(
            *self.entries, self.live, slot, self.gh, cols,
            self.bins.shape[1], BINS, force=self.force,
            layout=self.layout if self.force == "pallas" else None)


def hist_f64(bins, rel, gh, n_nodes, sparse):
    """Every node's histogram summed in float64, bucket by bucket; the
    sparse backends hold nothing in bin 0."""
    out = _hist_f64(bins, rel, gh, n_nodes, BINS)
    if sparse:
        out[:, :, 0] = 0.0
    return out


def left_mass_dirs(hist, h_tot, sparse, missing_aware):
    """The left hessian mass of every cut, one array a default direction, as
    the dense split block and `_level_splits_from_hist` form them."""
    hl = jnp.cumsum(hist[..., 1], axis=2)
    if sparse:
        miss = h_tot[:, None] - jnp.sum(hist[..., 1], axis=2)
        return [hl + miss[:, :, None], hl]
    return [hl, hl - hist[:, :, 0:1, 1]] if missing_aware else [hl]


def draw_splits(rng, n_nodes, features, lopsided):
    """A level's split tables: cuts over the whole range (so that some send
    every row one way), the last node's below the root always a null split;
    ``lopsided`` cuts each node between its two top bins instead, a level a
    feature."""
    split_f = (np.full(n_nodes, (n_nodes.bit_length() - 1) % features)
               if lopsided else rng.integers(0, features, n_nodes))
    split_b = (np.full(n_nodes, BINS - 2) if lopsided
               else rng.integers(0, BINS, n_nodes))
    split_d = rng.integers(0, 2, n_nodes)
    if not lopsided and n_nodes > 1:
        split_b[-1], split_f[-1], split_d[-1] = BINS, 0, 0
    return tuple(jnp.asarray(a.astype(np.int32))
                 for a in (split_f, split_b, split_d))


def walk(name, missing_aware, depth, bins, gh, seed, lopsided=False):
    """Levels 0 .. depth - 1 as the tree builders run them — built columns
    from the backend, siblings derived, the next level's built child chosen
    from the level's own histograms — and, for every level, every node's
    error against float64 as shares of its PARENT's largest bucket and of
    its own (the root is its own parent)."""
    rng = np.random.default_rng(seed)
    backend = Backend(name, bins, gh)
    sparse = backend.sparse
    rel = np.zeros(bins.shape[0], np.int64)
    hist, slot, right_built, want_up = None, rel, None, None
    out = []
    for d in range(depth):
        n_nodes = 2 ** d
        hist = gbdt_module._with_siblings(
            hist, backend.built(slot, gbdt_module._built_columns(d)),
            right_built)
        assert hist.shape == (n_nodes, bins.shape[1], BINS, 2)
        want = hist_f64(bins, rel, gh, n_nodes, sparse)
        err = np.abs(np.asarray(hist, np.float64) - want).max(axis=(1, 2))
        own = np.abs(want).max(axis=(1, 2))                   # [nodes, 2]
        up = own if d == 0 else np.repeat(
            np.abs(want_up).max(axis=(1, 2)), 2, axis=0)
        out.append({"of_parent": err / np.maximum(up, 1e-30),
                    "of_own": err / np.maximum(own, 1e-30),
                    "empty": own.max(axis=1) == 0,
                    "zero": ~np.asarray(hist).any(axis=(1, 2, 3)),
                    "rows": np.bincount(rel, minlength=n_nodes)})
        split_f, split_b, split_d = draw_splits(rng, n_nodes, bins.shape[1],
                                                lopsided)
        h_tot = jnp.asarray(np.bincount(rel, gh[:, 1].astype(np.float64),
                                        n_nodes).astype(np.float32))
        right_built = gbdt_module._smaller_child(
            left_mass_dirs(hist, h_tot, sparse, missing_aware), h_tot,
            split_f, split_b, split_d)
        f, b, dr = (np.asarray(a)[rel] for a in (split_f, split_b, split_d))
        row_bin = bins[np.arange(bins.shape[0]), f]
        go_right = row_bin > b
        if missing_aware or sparse:
            go_right = np.where(row_bin == 0, dr == 1, go_right)
        slot = gbdt_module._child_slot(jnp.asarray(rel, jnp.int32),
                                       jnp.asarray(go_right),
                                       right_built[jnp.asarray(rel)])
        rel, want_up = 2 * rel + go_right, want
    return out


def drawn(seed):
    """`test_pallas._hist_case`'s rows — bins over every code, 0 among them,
    (grad, hess) spread over six decades — with the hessians positive."""
    bins, _rel, gh = _hist_case(ROWS, FEATURES, BINS, 1, seed)
    gh[:, 1] = np.abs(gh[:, 1])
    return bins, gh


WALKS = [("dense-xla", False), ("dense-xla", True), ("dense-pallas", False),
         ("dense-pallas", True), ("sparse-xla", True), ("sparse-pallas", True)]


@functools.lru_cache(maxsize=None)
def walked(name, missing_aware):
    return walk(name, missing_aware, 8, *drawn(5), seed=6)


# A built node reads 1e-7 of its own largest bucket against float64, and a
# node derived k times in a row adds its ancestors' errors: of the PARENT's
# largest bucket the worst over these walks is 4.4e-7, at depth 7.  One
# bfloat16 part dropped reads 1e-5 and more (test_pallas.py's control).
PARENT_SHARE_LIMIT = 2e-6


@pytest.mark.parametrize("depth", range(1, 9))
@pytest.mark.parametrize("name,missing_aware", WALKS,
                         ids=[f"{n}-miss{int(m)}" for n, m in WALKS])
def test_built_and_derived_histograms_are_every_node_s(name, missing_aware,
                                                       depth):
    """The last level of a tree of ``depth``: each of its ``2 ** (depth -
    1)`` nodes' histograms, half of them never summed, against float64."""
    level = walked(name, missing_aware)[depth - 1]
    assert level["of_parent"].shape == (2 ** (depth - 1), 2)
    assert level["of_parent"].max() < PARENT_SHARE_LIMIT
    # a node no row reached holds zeros, not its ancestors' rounding: the
    # empty child of a null split is the one that is built
    assert level["zero"][level["empty"]].all()
    if depth > 2:
        assert level["rows"][-1] == 0       # the right child of a null split
    if depth >= 6:
        assert level["empty"].any() and not level["empty"].all()


# ---- the sentinel -----------------------------------------------------------


@pytest.mark.parametrize("name", ["dense-xla", "dense-pallas", "sparse-xla",
                                  "sparse-pallas"])
@pytest.mark.parametrize("cols", [1, 4, 64])
def test_rows_that_carry_the_sentinel_add_nothing(name, cols):
    """`_NO_SLOT` is -1 on every backend: the kernels match ids against
    their node columns (0 and up; the padded columns too), and
    ``jax.ops.segment_sum`` drops a negative key.  The rows that carry it
    could as well carry no mass."""
    assert gbdt_module._NO_SLOT == -1
    bins, gh = drawn(11 + cols)
    rng = np.random.default_rng(cols)
    slot = rng.integers(0, cols, ROWS)
    out = rng.uniform(size=ROWS) < 0.5
    backend = Backend(name, bins, gh)
    got = backend.built(np.where(out, gbdt_module._NO_SLOT, slot), cols)
    silenced = Backend(name, bins, np.where(out[:, None], 0.0, gh)
                       .astype(np.float32))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(silenced.built(slot, cols)))
    assert np.asarray(got).any()
    nothing = backend.built(np.full(ROWS, gbdt_module._NO_SLOT), cols)
    assert not np.asarray(nothing).any()


def test_child_slot_is_the_parent_s_index_or_the_sentinel():
    rel = jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32)
    go_right = jnp.asarray([False, True, False, True, False, True])
    right_built = jnp.asarray([True, False, True])
    slot = gbdt_module._child_slot(rel, go_right, right_built[rel])
    assert slot.dtype == jnp.int32
    assert list(np.asarray(slot)) == [-1, 0, 1, -1, -1, 2]
    assert [gbdt_module._built_columns(d) for d in range(5)] == [1, 1, 2, 4,
                                                                 8]


# ---- the lopsided chain -----------------------------------------------------

SKEW_ROWS = 40000
# built, the small child reads 6e-8 (the kernels) to 1.7e-7 (XLA) of its own
# largest bucket; derived from a parent of a thousand times its mass 1.3e-5
# to 1.8e-5
SKEW_LIMIT = 1e-6


def skew_drawn(seed):
    """Every column's top bin holds a thousandth of the rows: a cut under it
    sends 99.9% of a node's mass left, level after level."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(1, BINS - 1, (SKEW_ROWS, 3)).astype(np.int32)
    top = rng.uniform(size=bins.shape) < 1e-3
    bins[top] = BINS - 1
    gh = rng.uniform(0.5, 1.0, (SKEW_ROWS, 2)).astype(np.float32)
    return bins, gh


def skew_errors(name):
    """The worst error of a small (right) child with rows in it, as a share
    of its OWN largest bucket, over four levels of 99.9% / 0.1% splits."""
    levels = walk(name, True, 4, *skew_drawn(3), seed=4, lopsided=True)
    worst = 0.0
    for level in levels[1:]:
        small = (np.arange(len(level["rows"])) % 2 == 1) & (level["rows"] > 0)
        assert small.any()
        # the right children hold a thousandth of their parents' rows
        assert level["rows"][1] < 2e-3 * levels[0]["rows"][0]
        worst = max(worst, level["of_own"][small].max())
    return worst


@pytest.mark.parametrize("name", ["dense-xla", "dense-pallas",
                                  "sparse-pallas"])
def test_a_small_child_is_built_not_derived(name, monkeypatch):
    """Each split sends 99.9% of the mass left.  The small child is summed
    from its own rows, so it stays within the backend's own error of float64
    *relative to its own mass*; derived from a parent a thousand times its
    size it would carry the parent's rounding a thousand times over — which
    is what "always build the left child" does, and fails here."""
    assert skew_errors(name) < SKEW_LIMIT
    monkeypatch.setattr(
        gbdt_module, "_smaller_child",
        lambda hl_dirs, h_tot, split_f, split_b, split_d:
        jnp.zeros(split_f.shape, bool))
    assert skew_errors(name) > 3 * SKEW_LIMIT


def test_smaller_child_reads_the_chosen_cut():
    """Left mass at (feature, bin, direction); a null split builds its
    empty right child; an even split builds the left."""
    hl0 = jnp.asarray(np.arange(2 * 3 * 4, dtype=np.float32)
                      .reshape(2, 3, 4))            # node 1, f 2, b 3: 23
    dirs = [hl0, hl0 - 1.0]
    h_tot = jnp.asarray([30.0, 40.0])

    def pick(f, b, d):
        return list(np.asarray(gbdt_module._smaller_child(
            dirs, h_tot, jnp.asarray(f), jnp.asarray(b), jnp.asarray(d))))

    # node 0: left 5 of 30 -> left built; node 1: left 23 of 40 -> right
    assert pick([1, 2], [1, 3], [0, 0]) == [False, True]
    # direction 1 reads the other array: node 1's left is 20 of 40, a tie
    assert pick([1, 2], [1, 0], [0, 1]) == [False, False]
    assert pick([1, 2], [1, 1], [0, 1]) == [False, False]
    # null splits (bin == B) send everything left
    assert pick([0, 0], [4, 4], [0, 0]) == [True, True]


# ---- against builders that build every node ---------------------------------


@contextlib.contextmanager
def all_nodes_built():
    """The tree builders as they stood before PR 32: every level asks its
    backend for all of its nodes, and nothing is derived."""
    patch = pytest.MonkeyPatch()
    patch.setattr(gbdt_module, "_built_columns", lambda depth: 2 ** depth)
    patch.setattr(gbdt_module, "_child_slot",
                  lambda rel, go_right, right_built:
                  2 * rel + go_right.astype(jnp.int32))
    patch.setattr(gbdt_module, "_with_siblings",
                  lambda parent, built, right_built: built)
    try:
        yield
    finally:
        patch.undo()


def assert_same_tree(got: dict, want: dict, ties: int = 0) -> None:
    """The same splits in every node; gains, covers and leaves within 1e-5
    of their scale (a derived histogram is another rounding of the same
    sums).  ``ties``: at most so many nodes may choose another candidate
    whose gain is the same to 1e-5 — in a node of a few rows two cuts can
    part the rows alike, and no rounding resolves that; what lies under such
    a node is then not compared."""
    val = lambda t, key: np.asarray(t[key]).reshape(  # noqa: E731
        -1, np.asarray(t[key]).shape[-1])
    splits = ("feature", "threshold", "default_right")
    differs = np.any([val(got, k) != val(want, k) for k in splits], axis=0)
    gain, cover = val(want, "split_gain"), val(want, "split_cover")
    near = lambda a, b, scale: np.abs(a - b) <= 1e-5 * max(  # noqa: E731
        np.abs(scale).max(), 1e-30)
    under = np.zeros(differs.shape, bool)       # below a tied node
    n_internal = differs.shape[1]
    tied = 0
    for n in range(n_internal):
        up = (n - 1) // 2
        under[:, n] = n > 0 and (under[:, up] | differs[:, up])
        for t in np.flatnonzero(differs[:, n] & ~under[:, n]):
            tied += 1
            assert near(val(got, "split_gain")[t, n], gain[t, n],
                        gain[t, n]), (t, n)
    assert tied <= ties, f"{tied} nodes chose another split"
    known = ~under
    for key, ref in (("split_gain", gain), ("split_cover", cover)):
        assert near(val(got, key), ref, ref)[known].all(), key
    leaf_up = (np.arange(n_internal + 1) + n_internal - 1) // 2
    leaf_known = ~(under | differs)[:, leaf_up]
    leaf = val(want, "leaf")
    assert near(val(got, "leaf"), leaf, leaf)[leaf_known].all(), "leaf"
    if "leaf_rel" in want and not tied:
        np.testing.assert_array_equal(np.asarray(got["leaf_rel"]),
                                      np.asarray(want["leaf_rel"]))


@pytest.mark.parametrize(
    "missing_aware,max_depth,features,rows,extra", ROUTE_CASES,
    ids=[f"miss{int(m)}-d{d}-F{f}" + "".join(f"-{k.split('_')[0]}" for k in e)
         for m, d, f, _r, e in ROUTE_CASES])
def test_dense_tree_equals_the_all_nodes_tree(missing_aware, max_depth,
                                              features, rows, extra):
    num_bins = 16
    args = tree_inputs(1000 * max_depth + features, rows, features, num_bins)
    kw = dict(num_features=features, max_depth=max_depth, num_bins=num_bins,
              missing_aware=missing_aware, histogram="xla", **extra)
    got = GBDT(**kw)._build_tree(*args)
    with all_nodes_built():
        want = GBDT(**kw)._build_tree(*args)    # a new model traces anew
    # the two depth-8 fixtures end in nodes of three to ten rows, where 4 and
    # 6 of 255 nodes find two cuts that part their rows alike
    assert_same_tree(dict(zip(TREE_OUTPUTS, got)),
                     dict(zip(TREE_OUTPUTS, want)),
                     ties=6 if max_depth == 8 else 0)
    assert (np.asarray(got[1]) < num_bins).any()


SPARSE_KW = dict(num_features=4, num_trees=3, max_depth=3, num_bins=8,
                 learning_rate=0.5, missing_aware=True)
SPARSE_CASES = {
    "xla": dict(histogram="xla"),
    "pallas": dict(histogram="pallas"),
    "xla-deep": dict(histogram="xla", max_depth=5, num_trees=2),
    "xla-stochastic": dict(histogram="xla", subsample=0.8,
                           colsample_bytree=0.75, colsample_bylevel=0.75),
    "xla-monotone": dict(histogram="xla", monotone_constraints=[1, 0, -1, 0]),
    "xla-softmax": dict(histogram="xla", objective="softmax", num_class=3),
}


@pytest.mark.parametrize("case", sorted(SPARSE_CASES))
def test_sparse_tree_equals_the_all_nodes_tree(case):
    import dataclasses
    rng = np.random.default_rng(40)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=200, feats=4)
    kw = dict(SPARSE_KW, **SPARSE_CASES[case])
    if kw.get("num_class"):
        batch = dataclasses.replace(batch, label=jnp.asarray(
            rng.integers(0, 3, 200).astype(np.float32)))
    got = GBDT(**kw).fit_batch(batch, binner)
    with all_nodes_built():
        want = GBDT(**kw).fit_batch(batch, binner)
    assert_same_tree(got, want)
    assert np.any(np.asarray(got["threshold"]) < kw["num_bins"])


@pytest.mark.parametrize("histogram,max_depth", [("xla", 3), ("xla", 5),
                                                 ("pallas", 3)])
def test_streamed_tree_equals_the_all_nodes_tree(histogram, max_depth):
    from test_gbdt_streamed import FEATURES as STREAM_FEATURES
    from test_gbdt_streamed import _batch, _concat
    from dmlc_core_tpu.models import QuantileBinner
    from dmlc_core_tpu.ops.sparse import csr_to_dense_missing
    rng = np.random.default_rng(7)
    batches = [_batch(rng, rows) for rows in (60, 45, 70)]
    whole = _concat(batches)
    dense = np.asarray(csr_to_dense_missing(
        whole.index, whole.value, whole.row_ids(), int(whole.label.shape[0]),
        STREAM_FEATURES))
    binner = QuantileBinner(num_bins=8, missing_aware=True).fit(dense)
    kw = dict(num_features=STREAM_FEATURES, num_trees=2, max_depth=max_depth,
              num_bins=8, learning_rate=0.5, missing_aware=True,
              histogram=histogram)
    got = GBDT(**kw).fit_streamed(batches, binner)
    with all_nodes_built():
        want = GBDT(**kw).fit_streamed(batches, binner)
    assert_same_tree(got, want)
    assert np.any(np.asarray(got["threshold"]) < kw["num_bins"])
    # and the resident builder's forest, which the identity tests of
    # test_gbdt_streamed.py hold it to
    assert_same_tree(got, GBDT(**kw).fit_batch(whole, binner))


# ---- how often the mechanism engages ----------------------------------------


def counted(fit) -> tuple:
    before = telemetry.snapshot()
    fit()
    d = telemetry.counters_delta(before, telemetry.snapshot())
    return (d.get("gbdt.hist_nodes_built", 0),
            d.get("gbdt.hist_nodes_derived", 0))


@pytest.mark.skipif(not telemetry.enabled(), reason="no native telemetry")
def test_fits_count_the_nodes_they_build_and_derive():
    """A tree of depth D builds 2^(D-1) node histograms and derives
    2^(D-1) - 1; once a boosting round, a class a tree."""
    bins, grad, *_ = tree_inputs(3, 300, 5, 16)
    label = jnp.asarray(np.asarray(grad) > 0, jnp.float32)
    dense = GBDT(num_features=5, num_trees=3, max_depth=6, num_bins=16,
                 histogram="xla")
    assert counted(lambda: dense.fit(bins, label)) == (3 * 32, 3 * 31)
    rng = np.random.default_rng(40)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=200, feats=4)
    sparse = GBDT(**dict(SPARSE_KW, max_depth=8, num_trees=2,
                         histogram="xla"))
    assert counted(lambda: sparse.fit_batch(batch, binner)) == (2 * 128,
                                                                2 * 127)
    multi = GBDT(num_features=5, num_trees=2, max_depth=3, num_bins=16,
                 histogram="xla", objective="softmax", num_class=3)
    klass = jnp.asarray(np.arange(300) % 3, jnp.float32)
    assert counted(lambda: multi.fit(bins, klass)) == (6 * 4, 6 * 3)
