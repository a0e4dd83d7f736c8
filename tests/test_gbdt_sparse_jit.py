"""The sparse tree builder as one jitted program a tree (PR 27), held
against the op-by-op builder it replaced, and the device-built entry layout
held against the host sort it replaced."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.models import GBDT
from dmlc_core_tpu.parallel import MeshPlan
from dmlc_core_tpu.models.gbdt import (_built_columns, _child_slot,
                                       _entry_rels, _entry_slots,
                                       _with_siblings)
from dmlc_core_tpu.ops.pallas_segment import (_KEY_TILE, _NNZ_TILE,
                                              _round_up_some, segment_sum,
                                              sparse_hist_layout)

from test_gbdt import _sparse_identity_fixture


def build_tree_sparse_eager(self, entries, layout, grad, hess, col_mask,
                            col_key):
    """`GBDT._build_tree_sparse` as it stood before PR 27, kept as the
    reference: every level's gathers, scatters and kernel call dispatched
    op by op (the layout's weight lane ``w`` went with the host sort, so the
    per-tree entry gather is no longer multiplied by it; since PR 32 a level
    builds one child of each parent, through the functions the jitted
    builder calls)."""
    F, B = self.num_features, self.num_bins
    rows = grad.shape[0]
    mono = self.monotone_constraints is not None
    rid, fi, ebin, emask = entries
    emw = emask.astype(jnp.float32)[:, None]
    mesh = self.mesh_plan is not None
    gh_row = jnp.stack([grad, hess], axis=-1)
    gh_k = gh_e = None
    if layout is not None and not mesh:
        gh_e = gh_row[layout.rid].T
    node = jnp.zeros(rows, jnp.int32)
    lo = jnp.full(1, -jnp.inf)
    hi = jnp.full(1, jnp.inf)
    active = (jnp.ones((1, self._interaction_groups.shape[0]), bool)
              if self._interaction_groups is not None else None)
    features, thresholds, defaults, gains, covers = [], [], [], [], []
    hist, slot, right_built = None, node, None
    for depth in range(self.max_depth):
        first = 2 ** depth - 1
        n_nodes = 2 ** depth
        cols = _built_columns(depth)
        rel = node - first
        impl = (self._hist_impl_sparse(n_nodes)
                if layout is not None else "xla")
        if impl == "pallas":
            built = self._level_histogram_sparse(
                layout, slot, gh_row, gh_e,
                None if mesh else _entry_slots(layout, slot, depth),
                cols)
        else:
            if gh_k is None:
                gh_k = gh_row[rid] * emw
            keys = (slot[rid] * F + fi) * B + ebin
            built = jax.ops.segment_sum(
                gh_k, keys, num_segments=cols * F * B
            ).reshape(cols, F, B, 2)
        hist = _with_siblings(hist, built, right_built)
        gh_node = segment_sum(
            gh_row, rel, num_segments=n_nodes,
            force="pallas" if impl == "pallas" and not mesh else None)
        (split_f, split_b, split_d, split_g, lo, hi, active,
         right_built) = self._level_splits_from_hist(
            hist, gh_node, depth, col_mask, col_key, lo, hi, active)
        features.append(split_f)
        thresholds.append(split_b)
        defaults.append(split_d)
        gains.append(split_g)
        covers.append(gh_node[:, 1])
        go_right = self._route_sparse(fi, ebin, emask, rid, split_f[rel],
                                      split_b[rel], split_d[rel], rows)
        node = 2 * node + 1 + go_right.astype(jnp.int32)
        slot = _child_slot(rel, go_right, right_built[rel])
    n_leaves = 2 ** self.max_depth
    leaf_rel = node - (n_leaves - 1)
    leaf_force = ("pallas" if layout is not None and not mesh
                  and self._hist_impl_sparse(n_leaves) == "pallas"
                  else None)
    gh_leaf = segment_sum(gh_row, leaf_rel, num_segments=n_leaves,
                          force=leaf_force)
    leaf_w = -gh_leaf[:, 0] / (gh_leaf[:, 1] + self.lambda_)
    if mono:
        leaf_w = jnp.clip(leaf_w, lo, hi)
    leaf = self.learning_rate * leaf_w
    return (jnp.concatenate(features), jnp.concatenate(thresholds),
            jnp.concatenate(defaults), jnp.concatenate(gains),
            jnp.concatenate(covers), leaf, leaf_rel)


class EagerGBDT(GBDT):
    """The model with the reference builder in the jitted one's place.  The
    reference wants the unsorted entries, which `fit_batch` drops when the
    layout can stand for them: it gets them back from the layout."""

    def _build_tree_sparse(self, entries, layout, grad, hess, col_mask,
                           col_key):
        if entries is None:
            live = np.asarray(layout.gkey) >= 0
            gkey = np.asarray(layout.gkey)[live]
            entries = (jnp.asarray(np.asarray(layout.rid)[live]),
                       jnp.asarray(gkey // layout.nb),
                       jnp.asarray(gkey % layout.nb),
                       jnp.ones(gkey.shape, bool))
        return build_tree_sparse_eager(self, entries, layout, grad, hess,
                                       col_mask, col_key)


BASE = dict(num_features=4, num_trees=3, max_depth=3, num_bins=8,
            learning_rate=0.5, missing_aware=True)
CASES = {
    "plain": {},
    "stochastic": dict(subsample=0.8, colsample_bytree=0.75,
                       colsample_bylevel=0.75),
    "monotone": dict(monotone_constraints=[1, 0, -1, 0]),
    "interaction": dict(interaction_constraints=[[0, 1], [2, 3]]),
    "squared": dict(objective="squared"),
    "gamma_weighted": dict(gamma=0.05, scale_pos_weight=2.0,
                           min_child_weight=0.5),
}


@pytest.mark.parametrize("histogram", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_jitted_sparse_tree_equals_the_eager_one(case, histogram):
    """Bit for bit: same splits, gains, covers and leaves, on the fixture
    of test_gbdt.py's sparse identity tests and over the training controls
    its sparse tests use."""
    rng = np.random.default_rng(40)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=200, feats=4)
    kw = dict(BASE, histogram=histogram, **CASES[case])
    got = GBDT(**kw).fit_batch(batch, binner)
    want = EagerGBDT(**kw).fit_batch(batch, binner)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)
    assert np.any(np.asarray(got["threshold"]) < BASE["num_bins"])


def test_jitted_sparse_tree_softmax_equals_the_eager_one():
    rng = np.random.default_rng(44)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=200, feats=4)
    import dataclasses
    label = jnp.asarray(rng.integers(0, 3, 200).astype(np.float32))
    batch = dataclasses.replace(batch, label=label)
    kw = dict(BASE, num_trees=2, objective="softmax", num_class=3,
              histogram="pallas")
    got = GBDT(**kw).fit_batch(batch, binner)
    want = EagerGBDT(**kw).fit_batch(batch, binner)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def test_jitted_sparse_tree_under_a_mesh_equals_the_eager_one():
    """The mesh route keeps its unsorted entries, its XLA node totals and
    the shard_map'd kernel inside the one program."""
    from jax.sharding import Mesh
    rng = np.random.default_rng(43)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=256, feats=4)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    kw = dict(BASE, num_trees=2, histogram="pallas",
              histogram_mesh=MeshPlan(mesh, ("data",)))
    got = GBDT(**kw).fit_batch(batch, binner)
    want = EagerGBDT(**kw).fit_batch(batch, binner)
    # one program lets GSPMD add the shards' row sums in another order than
    # op by op: the same splits, the sums to rounding
    for k in want:
        if k in ("split_gain", "split_cover", "leaf"):
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-5,
                                       atol=1e-6, err_msg=k)
        else:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]), err_msg=k)


def test_fit_batch_keeps_one_copy_of_the_entries_when_the_kernel_runs():
    """Every level on the kernel, one device: the tree program is handed the
    layout and no other entry array; a level on XLA keeps them."""
    rng = np.random.default_rng(40)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=200, feats=4)
    seen = []

    class Spy(GBDT):
        def _build_tree_sparse(self, entries, layout, *a):
            seen.append((entries is None, layout is None))
            return GBDT._build_tree_sparse(self, entries, layout, *a)

    Spy(**dict(BASE, num_trees=1, histogram="pallas")).fit_batch(batch,
                                                                 binner)
    Spy(**dict(BASE, num_trees=1, histogram="xla")).fit_batch(batch, binner)
    assert seen == [(True, False), (False, True)]


@pytest.mark.parametrize("mesh", [False, True])
def test_a_fit_counts_what_a_kernel_level_of_its_layout_runs_and_launches(
        mesh):
    """Once a fit, whatever its trees and levels: the entry sub-tiles in
    the key tiles' spans to ``gbdt.sparse_hist_blocks``, the grid steps
    that a level's kernel calls launch (every shard's) to
    ``gbdt.sparse_hist_grid_steps``; nothing from a fit without a layout."""
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.ops import pallas_segment as ps
    rng = np.random.default_rng(43)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=256, feats=4)
    kw = dict(BASE, num_trees=2, histogram="pallas")
    if mesh:
        from jax.sharding import Mesh
        kw["histogram_mesh"] = MeshPlan(
            Mesh(np.asarray(jax.devices()[:8]), ("data",)))
    model, layouts = GBDT(**kw), []
    build = ps.sparse_hist_layout

    def spy(*a, **k):
        layouts.append(build(*a, **k))
        return layouts[-1]

    names = ("gbdt.sparse_hist_blocks", "gbdt.sparse_hist_grid_steps")
    before = [telemetry.counter_get(n) for n in names]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("dmlc_core_tpu.models.gbdt.sparse_hist_layout", spy)
        model.fit_batch(batch, binner)
        GBDT(**dict(BASE, num_trees=1, histogram="xla")).fit_batch(batch,
                                                                   binner)
    got = [telemetry.counter_get(n) - b for n, b in zip(names, before)]
    (layout,) = layouts
    shards = 8 if mesh else 1
    assert layout.num_shards == shards
    steps = ps._sparse_steps(layout.max_tiles)
    assert got == [int(np.asarray(layout.tcount).sum()),
                   shards * layout.num_kt * steps]
    # 4 features of 8 bins: one key tile a shard, its span one sub-tile
    assert got == [shards, shards] and steps == 1


def test_a_row_that_holds_a_feature_twice_is_routed_as_on_xla():
    """`_route_layout` finds one entry of a (row, feature) pair where
    `_route_sparse` takes the largest bin over all of them: a batch whose
    layout does not say ``rows_ascend`` keeps its entries and the latter,
    so the kernel's forest splits as XLA's does."""
    import dataclasses
    rng = np.random.default_rng(40)
    batch, binner, *_ = _sparse_identity_fixture(rng, rows=200, feats=4)
    ptr, index = np.asarray(batch.row_ptr), np.asarray(batch.index).copy()
    value = np.asarray(batch.value).copy()
    twice = [r for r in range(200) if ptr[r + 1] - ptr[r] >= 2][:20]
    for r in twice:     # the row's second entry repeats its first feature
        index[ptr[r] + 1] = index[ptr[r]]
        value[ptr[r] + 1] = value[ptr[r]] + 1.0
    batch = dataclasses.replace(batch, index=jnp.asarray(index),
                                value=jnp.asarray(value))
    seen = []

    class Spy(GBDT):
        def _build_tree_sparse(self, entries, layout, *a):
            seen.append((entries is None, layout.rows_ascend))
            return GBDT._build_tree_sparse(self, entries, layout, *a)

    kw = dict(BASE, num_trees=2)
    got = Spy(**dict(kw, histogram="pallas")).fit_batch(batch, binner)
    want = GBDT(**dict(kw, histogram="xla")).fit_batch(batch, binner)
    assert seen == [(False, False)] * 2
    for k in ("feature", "threshold", "default_right"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)


# ---- the layout ------------------------------------------------------------


def host_layout(rid, fi, eb, em, num_features, num_bins, num_shards=1,
                rows=None):
    """`sparse_hist_layout` as it stood before PR 27 (numpy, stable argsort
    by feature a shard), kept as the reference for the device sort; the
    padded sizes follow PR 27's rounding (`_round_up_some`: two draws of one
    data set compile once)."""
    nb = 1 << max(num_bins - 1, 1).bit_length()
    num_kt = -(-num_features * nb // _KEY_TILE)
    rid, fi, eb = (np.asarray(a).astype(np.int64) for a in (rid, fi, eb))
    em = np.asarray(em).astype(bool)
    local = rows // num_shards if num_shards > 1 else 0
    built = []
    for s in range(num_shards):
        sel = em & ((rid // local == s) if num_shards > 1 else True)
        r, f, e = rid[sel] - s * local, fi[sel], eb[sel]
        order = np.argsort(f, kind="stable")
        starts = np.zeros(num_features + 1, np.int64)
        np.cumsum(np.bincount(f, minlength=num_features), out=starts[1:])
        tstart = np.zeros(num_kt, np.int32)
        tcount = np.zeros(num_kt, np.int32)
        for kt in range(num_kt):
            flo = min((kt * _KEY_TILE) // nb, num_features)
            fhi = min(-(-((kt + 1) * _KEY_TILE) // nb), num_features)
            a, b = int(starts[flo]), int(starts[fhi])
            if b > a:
                tstart[kt] = a // _NNZ_TILE
                tcount[kt] = -(-b // _NNZ_TILE) - tstart[kt]
        built.append((r[order], f[order] * nb + e[order], tstart, tcount))
    nnz_pad = _round_up_some(max(len(b[0]) for b in built), _NNZ_TILE, 64)
    gkey = np.full(num_shards * nnz_pad, -1, np.int32)
    rid_p = np.zeros(num_shards * nnz_pad, np.int32)
    for s, (r, g, _, _) in enumerate(built):
        gkey[s * nnz_pad:s * nnz_pad + len(g)] = g
        rid_p[s * nnz_pad:s * nnz_pad + len(r)] = r
    tstart = np.concatenate([b[2] for b in built])
    tcount = np.concatenate([b[3] for b in built])
    return dict(gkey=gkey, rid=rid_p, tstart=tstart, tcount=tcount,
                nnz_pad=nnz_pad, nnz_live=sum(len(b[0]) for b in built),
                max_tiles=_round_up_some(int(tcount.max()), 1, 16), nb=nb,
                num_kt=num_kt)



def random_entries(rng, rows, num_features, num_bins, nnz, present=None):
    rid = np.sort(rng.integers(0, rows, nnz)).astype(np.int32)
    fi = rng.choice(present if present is not None
                    else np.arange(num_features), nnz).astype(np.int32)
    eb = rng.integers(1, num_bins, nnz).astype(np.int32)
    em = rng.random(nnz) < 0.9
    return rid, fi, eb, em


LAYOUTS = {
    # name: (rows, F, B, nnz, features present, shards)
    "small": (96, 5, 16, 700, None, 1),
    "empty_features": (64, 9, 16, 900, [1, 4, 8], 1),
    # 256 bins: a feature's keys are half a key tile; 512-wide strides
    # (num_bins 300 -> nb 512) give every feature a whole tile
    "feature_owns_a_key_tile": (128, 3, 300, 5000, None, 1),
    "several_blocks_a_tile": (256, 2, 256, 6000, None, 1),
    "no_live_entry": (16, 3, 8, 40, None, 1),
    "sharded": (8 * 32, 3, 8, 1800, None, 8),
    "sharded_with_an_empty_shard": (8 * 32, 4, 16, 900, None, 8),
}


@pytest.mark.parametrize("form", ["codes", "values"])
@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_device_layout_equals_the_host_one(name, form):
    """The layout by the entries' codes, and by their values and the cuts
    (binned after the sort, by the kernel, interpreted here): values that
    the cuts 1, 2, ... bin to the very codes give the codes' layout, every
    field of it."""
    rows, F, B, nnz, present, shards = LAYOUTS[name]
    rng = np.random.default_rng(sorted(LAYOUTS).index(name))
    rid, fi, eb, em = random_entries(rng, rows, F, B, nnz, present)
    if name == "no_live_entry":
        em[:] = False
    if name == "sharded_with_an_empty_shard":
        em &= rid // 32 != 5
    entries = dict(ebin=jnp.asarray(eb))
    if form == "values":    # code b: the value b - 0.5 among the cuts 1..B-2
        cuts = np.tile(np.arange(1, B - 1, dtype=np.float32), (F, 1))
        entries = dict(ebin=None, value=jnp.asarray(eb - 0.5, jnp.float32),
                       cuts=jnp.asarray(cuts))
    got = sparse_hist_layout(jnp.asarray(rid), jnp.asarray(fi),
                             emask=jnp.asarray(em), num_features=F,
                             num_bins=B, num_shards=shards, rows=rows,
                             **entries)
    want = host_layout(rid, fi, eb, em, F, B, shards, rows)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(getattr(got, k)), v,
                                      err_msg=k)
    if form == "values":
        by_codes = sparse_hist_layout(jnp.asarray(rid), jnp.asarray(fi),
                                      jnp.asarray(eb), jnp.asarray(em), F, B,
                                      num_shards=shards, rows=rows)
        flat_a, tree_a = jax.tree_util.tree_flatten(got)
        flat_b, tree_b = jax.tree_util.tree_flatten(by_codes)
        assert tree_a == tree_b             # every static field
        for a, b in zip(flat_a, flat_b):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_layout_refuses_entries_out_of_range():
    rid = jnp.zeros(4, jnp.int32)
    ok = jnp.ones(4, bool)
    with pytest.raises(ValueError, match="out of range"):
        sparse_hist_layout(rid, jnp.array([0, 1, 5, 2]), jnp.ones(4), ok,
                           5, 8)
    with pytest.raises(ValueError, match="out of range"):
        sparse_hist_layout(rid, jnp.array([0, 1, 2, 3]),
                           jnp.array([1, 8, 1, 1]), ok, 5, 8)
    # a dead entry may hold anything
    sparse_hist_layout(rid, jnp.array([0, 1, 9, 3]), jnp.ones(4),
                       jnp.array([True, True, False, True]), 5, 8)
    # by values: a feature out of range, and cuts of other features or of
    # more codes than the layout's keys hold
    value = jnp.ones(4)
    with pytest.raises(ValueError, match="out of range"):
        sparse_hist_layout(rid, jnp.array([0, 1, 5, 2]), None, ok, 5, 8,
                           value=value, cuts=jnp.zeros((5, 6)))
    for shape in ((4, 6), (5, 7)):
        with pytest.raises(ValueError, match="cuts"):
            sparse_hist_layout(rid, jnp.array([0, 1, 2, 3]), None, ok, 5, 8,
                               value=value, cuts=jnp.zeros(shape))


# ---- entry binning ----------------------------------------------------------


@pytest.mark.parametrize("features,bins,n", [(5, 8, 1000), (40, 256, 20000),
                                             (3, 3, 7), (968, 256, 50000)])
def test_binning_by_sort_equals_binning_by_bisection(features, bins, n):
    """`transform_entries`' two forms (chosen by the number of entries) give
    one code an entry: ties with a cut, values below and above every cut,
    signed zeros, NaN, features the sample never saw."""
    from dmlc_core_tpu.models import QuantileBinner
    from dmlc_core_tpu.models.gbdt import _bin_by_bisection, _bin_by_sort
    rng = np.random.default_rng(n)
    index = rng.integers(0, features, n)
    value = np.round(rng.standard_normal(n), 1).astype(np.float32)
    value[rng.random(n) < 0.01] = np.nan
    value[rng.random(n) < 0.01] = 0.0
    value[rng.random(n) < 0.005] = -0.0
    seen = ~np.isnan(value) & (index != features - 1)
    binner = QuantileBinner(num_bins=bins, missing_aware=True).fit_sparse(
        index[seen][: n // 2], value[seen][: n // 2], features)
    by_sort = np.asarray(_bin_by_sort(binner.cuts, jnp.asarray(index),
                                      jnp.asarray(value)))
    by_bisection = np.asarray(_bin_by_bisection(
        binner.cuts, jnp.asarray(index), jnp.asarray(value)))
    np.testing.assert_array_equal(by_sort, by_bisection)
    assert by_sort.min() == (0 if np.isnan(value).any() else 1)
    assert by_sort.max() <= bins - 1
    np.testing.assert_array_equal(
        np.asarray(binner.transform_entries(jnp.asarray(index),
                                            jnp.asarray(value))), by_sort)


def _bin_case(name, rng):
    """(features, num_bins, findex, value, cuts) of one case of the run-wise
    binning: entries in no order, the cuts a feature non-decreasing."""
    features, bins, n = 7, 16, 5000
    if name in ("bins_32", "bins_256"):
        features, bins, n = 40, int(name[5:]), 30000
    if name == "many_features_in_a_sub_tile":   # 300 runs in two sub-tiles
        features, n = 300, 2000
    cuts = np.sort(np.round(rng.standard_normal((features, bins - 2)), 1),
                   axis=1).astype(np.float32)
    index = rng.integers(0, features, n)
    value = np.round(rng.standard_normal(n), 1).astype(np.float32)
    value[value == 0] = 0.1
    if name == "ties_with_the_cuts":
        value = cuts[index, rng.integers(0, bins - 2, n)]
        value[value == 0] = 0.1
    elif name == "below_the_first_and_above_the_last":
        value = np.where(rng.random(n) < 0.5, cuts[index, 0] - 1,
                         cuts[index, -1] + 1).astype(np.float32)
        value[value == 0] = 0.1
    elif name == "infinities":
        value[rng.random(n) < 0.3] = np.inf
        value[rng.random(n) < 0.3] = -np.inf
        cuts[0, -1], cuts[1, 0] = np.inf, -np.inf
    elif name == "nan_and_zeros_are_dead":
        value[rng.random(n) < 0.2] = np.nan
        value[rng.random(n) < 0.2] = 0.0
        value[rng.random(n) < 0.1] = -0.0
    elif name == "an_empty_feature_and_one_of_a_single_entry":
        index = rng.choice([0, 1, 3, 4, 6], n)
        index[17] = 5                       # 2 has none, 5 this one
    elif name == "a_run_boundary_inside_a_sub_tile":
        index = np.where(np.arange(n) < 1500, 2, 4)     # 1,500 and 3,500
    return features, bins, index.astype(np.int32), value, cuts


BIN_CASES = ["ties_with_the_cuts", "below_the_first_and_above_the_last",
             "infinities", "nan_and_zeros_are_dead",
             "an_empty_feature_and_one_of_a_single_entry",
             "a_run_boundary_inside_a_sub_tile",
             "many_features_in_a_sub_tile", "bins_32", "bins_256"]


@pytest.mark.parametrize("name", BIN_CASES)
def test_codes_made_run_by_run_are_the_bisections(name):
    """`_bin_runs_pallas` (interpreted) on the lanes that the layout's sort
    leaves: a live entry's key is its feature's stride and the code that
    `_bin_by_bisection` gives the same entry, bit for bit; the dead entries
    (NaN, a stored zero) sort last and get none."""
    from dmlc_core_tpu.models.gbdt import _bin_by_bisection
    from dmlc_core_tpu.ops import pallas_segment as ps
    rng = np.random.default_rng(BIN_CASES.index(name))
    features, bins, index, value, cuts = _bin_case(name, rng)
    n = len(index)
    rid = np.arange(n, dtype=np.int32) // 3
    live = (value != 0) & ~np.isnan(value)
    assert live.all() == (name != "nan_and_zeros_are_dead")
    codes = np.asarray(_bin_by_bisection(jnp.asarray(cuts),
                                         jnp.asarray(index),
                                         jnp.asarray(value)))
    got = sparse_hist_layout(jnp.asarray(rid), jnp.asarray(index), None,
                             jnp.asarray(live), features, bins,
                             value=jnp.asarray(value), cuts=jnp.asarray(cuts))
    order = np.argsort(np.where(live, index, features), kind="stable")
    order = order[:live.sum()]
    gkey = np.asarray(got.gkey)
    np.testing.assert_array_equal(gkey[:len(order)],
                                  index[order] * got.nb + codes[order])
    assert (gkey[len(order):] == -1).all()
    assert 1 <= codes[live].min() and codes[live].max() <= bins - 1
    if name == "infinities":
        assert {1, bins - 1} <= set(codes[np.isinf(value)].tolist())
    # and the kernel alone, on the sorted values and the run starts
    lanes = got.nnz_pad
    rstart = np.append(np.asarray(got.fstart), lanes).astype(np.int32)
    held = np.diff(np.searchsorted(rstart, np.arange(0, lanes + 1, 1024)))
    if name == "many_features_in_a_sub_tile":
        assert held.max() > 128
    if name == "a_run_boundary_inside_a_sub_tile":
        assert rstart[4] == 1500 and rstart[5] == n
    alone = ps._bin_runs_pallas(
        jnp.asarray(np.pad(value[order], (0, lanes - len(order)))),
        jnp.asarray(rstart), jnp.asarray(cuts), got.nb, 1, True)
    np.testing.assert_array_equal(np.asarray(alone), gkey)


def test_the_layout_bins_where_the_rule_says_it_pays():
    """`layout_bin_engages`: the cuts are the features', a TPU compiles the
    kernel, the table fits its share of VMEM, and there are no more runs
    than the lanes hold sub-tiles."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    bosch = ((968, 254), 968, 218103808, 1)
    assert not ps.layout_bin_engages(*bosch)        # interpreted: a test's tool
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ps, "pallas_interpret", lambda: False)
        assert ps.layout_bin_engages(*bosch)
        assert ps.layout_bin_engages((968, 254), 968, 969 * 4 * 1024, 4)
        assert not ps.layout_bin_engages((968, 254), 968, 969 * 1024 - 1, 1)
        assert not ps.layout_bin_engages((967, 254), 968, 218103808, 1)
        assert ps.layout_bin_engages((16383, 254), 16383, 1 << 28, 1)
        assert not ps.layout_bin_engages((16384, 254), 16384, 1 << 28, 1)


FIT_CASES = {
    # name: (the rule forced on, counter a fit, the tree keeps the entries)
    "engaged": (True, 1, False),
    "off_a_tpu": (False, 0, False),
    "binned_batch": (True, 0, False),
    "rows_do_not_ascend": (True, 1, True),
    "a_level_on_xla": (True, 1, True),
    "under_a_mesh": (True, 1, True),
    "no_layout": (True, 0, True),
}


@pytest.mark.parametrize("name", sorted(FIT_CASES))
def test_fit_batch_binned_on_the_layouts_lanes_grows_the_same_forest(
        name, monkeypatch):
    """A `fit_batch` whose layout bins the batch's values after its sort
    (the rule forced on, the kernel interpreted) grows the forest of one
    that bins in entry order first, array for array; ``gbdt.layout_bin``
    says which it was, once a fit: 1 where it engaged, 0 off a TPU, for a
    `BinnedBatch` and without a layout.  Where the tree wants the entries in
    entry order too (rows that do not ascend, a level on XLA, a mesh) they
    are binned afterwards, as ever, and the fit trains."""
    import dataclasses
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.models import gbdt
    forced, counted, keeps = FIT_CASES[name]
    batch, binner = _thin_and_dense_batch(np.random.default_rng(49))
    kw = dict(num_features=4, num_trees=2, max_depth=4, num_bins=16,
              learning_rate=0.5, missing_aware=True, histogram="pallas")
    if name == "rows_do_not_ascend":    # some rows hold a feature twice
        ptr = np.asarray(batch.row_ptr)
        index = np.asarray(batch.index).copy()
        for r in [r for r in range(3000) if ptr[r + 1] - ptr[r] >= 2][:30]:
            index[ptr[r] + 1] = index[ptr[r]]
        batch = dataclasses.replace(batch, index=jnp.asarray(index))
    if name == "a_level_on_xla":        # the last, of eight nodes, as
        # "auto" resolves a level past the kernel's node limit on a chip
        monkeypatch.setattr(GBDT, "_hist_impl_sparse", lambda self, n_nodes: (
            "pallas" if n_nodes <= 4 else "xla"))
    if name == "under_a_mesh":
        from jax.sharding import Mesh
        kw["histogram_mesh"] = MeshPlan(
            Mesh(np.asarray(jax.devices()[:8]), ("data",)))
    if name == "no_layout":
        kw["histogram"] = "xla"
    want = GBDT(**kw).fit_batch(batch, binner)
    if name == "binned_batch":
        from dmlc_core_tpu.data.binned_cache import BinnedBatch
        rid, fi, ebin, emask = GBDT._entry_bins(batch, binner)
        batch = BinnedBatch(
            label=batch.label, weight=batch.weight, row_ptr=batch.row_ptr,
            index=batch.index, ebin=ebin.astype(jnp.uint8), emask=emask,
            num_rows=batch.num_rows, cuts_digest=binner.cuts_digest())
    seen, bins = [], []

    class Spy(GBDT):
        def _build_tree_sparse(self, entries, layout, *a):
            seen.append(entries is not None)
            return GBDT._build_tree_sparse(self, entries, layout, *a)

    binned = gbdt._bin_entries
    monkeypatch.setattr(gbdt, "_bin_entries",
                        lambda *a: bins.append(1) or binned(*a))
    if forced:
        real = gbdt.layout_bin_engages
        monkeypatch.setattr(gbdt, "layout_bin_engages", lambda *a: (
            real(*a) or a[1] == a[0][0] == 4))
    before = telemetry.counter_get("gbdt.layout_bin")
    got = Spy(**kw).fit_batch(batch, binner)
    assert telemetry.counter_get("gbdt.layout_bin") - before == counted
    assert seen == [keeps] * 2
    # binned in entry order: not at all where the layout did it and holds
    # every entry, once otherwise (a BinnedBatch comes binned)
    assert len(bins) == (0 if name in ("engaged", "binned_batch") else 1)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                                      err_msg=k)
    assert (np.asarray(got["feature"]) >= 0).any()


def _thin_and_dense_batch(rng, rows=3000):
    """A staged batch of four features over a few chunks' worth of rows:
    one present in 2% of them, one in 90%, two in between; the label leans
    on whether the thin one is there and on the dense one's value, so both
    split kinds occur below the root."""
    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models import QuantileBinner
    share = np.array([0.02, 0.9, 0.4, 0.6])
    present = rng.random((rows, 4)) < share
    present[:, 1] |= ~present.any(axis=1)           # no row is empty
    value = np.where(present, rng.uniform(-2, 2, (rows, 4)), np.nan
                     ).astype(np.float32)
    row_id, index = np.nonzero(present)
    row_ptr = np.concatenate([[0], np.cumsum(present.sum(1))]).astype(np.int32)
    score = (np.where(present[:, 0], 1.5, 0.0) + np.nan_to_num(value[:, 1])
             + 0.5 * np.nan_to_num(value[:, 2]) + rng.normal(0, 0.3, rows))
    pad = 9
    batch = PaddedBatch(
        label=jnp.asarray((score > 0.4).astype(np.float32)),
        weight=jnp.ones(rows, jnp.float32), row_ptr=jnp.asarray(row_ptr),
        index=jnp.asarray(np.pad(index.astype(np.int32), (0, pad))),
        value=jnp.asarray(np.pad(value[row_id, index], (0, pad))),
        num_rows=jnp.asarray(np.int32(rows)), field=None)
    return batch, QuantileBinner(num_bins=16, missing_aware=True).fit(value)


# ---- a level's routing by the entries' push (PR 47) --------------------------


def _route_case(rng, rows=40_000):
    """Entries of six features over a few chunks' worth of rows (one present
    in 0.2% of them, one in 90%), some of them a NaN's bin 0, some rows
    empty, and their feature-sorted layout."""
    share = np.array([0.01, 0.6, 0.3, 0.9, 0.002, 0.5])
    rid, fi = np.nonzero(rng.random((rows, 6)) < share)
    ebin = rng.integers(1, 16, len(rid)).astype(np.int32)
    ebin[rng.random(len(rid)) < 0.05] = 0
    entries = (jnp.asarray(rid, jnp.int32), jnp.asarray(fi, jnp.int32),
               jnp.asarray(ebin), jnp.ones(len(rid), bool))
    layout = sparse_hist_layout(*entries, 6, 16)
    assert layout.rows_ascend and len(set(rid.tolist())) < rows
    return entries, layout


ROUTE_SPLITS = {
    # depth, then the level's split_f / split_b / split_d tables
    "root": (0, [3], [7], [1]),
    "root_on_the_thin_feature_default_left": (0, [4], [3], [0]),
    "one_feature_by_four_nodes": (2, [1, 1, 1, 1], [2, 9, 9, 14],
                                  [0, 1, 1, 0]),
    "every_default_left": (3, None, None, 0),
    "every_default_right": (3, None, None, 1),
    "null_splits_among_them": (3, [0, 2, 0, 5, 0, 0, 3, 1],
                               [16, 4, 16, 15, 16, 0, 8, 16],
                               [0, 1, 0, 0, 0, 1, 1, 0]),
}


@pytest.mark.parametrize("name", sorted(ROUTE_SPLITS))
def test_the_entries_push_routes_as_the_bisection_and_the_maximum_do(name):
    """`_route_layout`'s two routes and `_route_sparse`: the same bool a
    row, whatever the level's tables say."""
    rng = np.random.default_rng(len(name))
    (rid, fi, ebin, emask), layout = _route_case(rng)
    rows = 40_000
    depth, split_f, split_b, split_d = ROUTE_SPLITS[name]
    n = 2 ** depth
    if split_f is None:
        split_f, split_b = rng.integers(0, 6, n), rng.integers(0, 17, n)
        split_d = np.full(n, split_d)
    split_f, split_b, split_d = (jnp.asarray(t, jnp.int32)
                                 for t in (split_f, split_b, split_d))
    rel = jnp.asarray(rng.integers(0, n, rows), jnp.int32)
    rel_e = rel[layout.rid] if depth else jnp.zeros_like(layout.rid)
    by_push = np.asarray(GBDT._route_layout(layout, rel, rel_e, split_f,
                                            split_b, split_d))
    by_bisection = np.asarray(GBDT._route_layout(layout, rel, None, split_f,
                                                 split_b, split_d))
    by_maximum = np.asarray(GBDT._route_sparse(
        fi, ebin, emask, rid, split_f[rel], split_b[rel], split_d[rel],
        rows))
    assert by_push.dtype == bool
    np.testing.assert_array_equal(by_push, by_bisection)
    np.testing.assert_array_equal(by_push, by_maximum)
    assert 0 < by_push.sum() < rows


@pytest.mark.parametrize("depth", [0, 1, 4, 8])
def test_slots_derived_on_the_entry_lanes_are_the_slots_gathered(depth):
    """`_entry_rels` lays ``rel`` on the lanes and derives the level's slots
    there from the level above's ``right_built``: the int32 values that
    `_entry_slots` gathers, `_NO_SLOT` and the padding lanes' too."""
    rng = np.random.default_rng(depth)
    _, layout = _route_case(rng)
    parents = 2 ** max(depth - 1, 0)
    parent = jnp.asarray(rng.integers(0, parents, 40_000), jnp.int32)
    went_right = jnp.asarray(rng.random(40_000) < 0.4)
    right_built = jnp.asarray(rng.random(parents) < 0.5)
    if depth == 0:
        rel, slot = parent, parent
    else:
        rel = 2 * parent + went_right.astype(jnp.int32)
        slot = _child_slot(parent, went_right, right_built[parent])
    rel_e, slot_e = _entry_rels(layout, rel, right_built, depth)
    np.testing.assert_array_equal(np.asarray(rel_e),
                                  np.asarray(rel)[np.asarray(layout.rid)])
    want = np.asarray(_entry_slots(layout, slot, depth))
    assert slot_e.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(slot_e), want)
    assert depth == 0 or (want == -1).any()


def test_fit_batch_by_the_entries_push_grows_the_bisections_forest(
        monkeypatch):
    """Rows routed by the push kernel (forced on through the rule's function,
    interpreted off the chip) or by the bisection: the same forest, bit for
    bit; the counter ``gbdt.route_push`` says which it was, a level a tree,
    and the lookup still runs once a level."""
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.ops import pallas_segment as ps
    from dmlc_core_tpu.models import gbdt
    batch, binner = _thin_and_dense_batch(np.random.default_rng(47))
    kw = dict(num_features=4, num_trees=2, max_depth=4, num_bins=16,
              learning_rate=0.5, missing_aware=True, histogram="pallas")
    calls = []
    kernel = ps._entry_push_pallas

    def spy(rid, span, val, rows, interpret):
        calls.append(rows)
        return kernel(rid, span, val, rows, interpret)

    monkeypatch.setattr(ps, "_entry_push_pallas", spy)
    counters = ("gbdt.route_push", "gbdt.entry_lookup")
    before = [telemetry.counter_get(c) for c in counters]
    by_bisection = GBDT(**kw).fit_batch(batch, binner)
    assert [telemetry.counter_get(c) for c in counters] == before
    assert not calls
    monkeypatch.setattr(gbdt, "route_push_engages",
                        lambda rows_ascend, rows: rows_ascend)
    monkeypatch.setattr(ps, "entry_lookup_engages",
                        lambda rows_ascend, plane_rows: rows_ascend)
    by_push = GBDT(**kw).fit_batch(batch, binner)
    assert calls == [3000] * 4          # one trace serves both trees
    assert [telemetry.counter_get(c) - b
            for c, b in zip(counters, before)] == [2 * 4, 2 * 4]
    for k in by_bisection:
        np.testing.assert_array_equal(np.asarray(by_push[k]),
                                      np.asarray(by_bisection[k]), err_msg=k)
    assert (np.asarray(by_push["feature"]) == 0).any()     # the thin one
    assert (np.asarray(by_push["default_right"]) == 1).any()


@pytest.mark.parametrize("mesh", [False, True])
def test_fit_batch_by_the_entry_lookup_grows_the_gathers_forest(mesh,
                                                                monkeypatch):
    """Values a row reach the sorted entries by the lookup kernel (forced on
    through the rule's function, interpreted off the chip) or by XLA's
    gather: the same forest, bit for bit, and the counter
    ``gbdt.entry_lookup`` says which it was, once a tree."""
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.ops import pallas_segment as ps
    batch, binner = _thin_and_dense_batch(np.random.default_rng(46))
    kw = dict(num_features=4, num_trees=2, max_depth=4, num_bins=16,
              learning_rate=0.5, missing_aware=True, histogram="pallas")
    if mesh:
        from jax.sharding import Mesh
        kw["histogram_mesh"] = MeshPlan(
            Mesh(np.asarray(jax.devices()[:8]), ("data",)))
    calls = []
    kernel = ps._entry_lookup_pallas

    def spy(rid, cspan, table, *static):
        calls.append(table.shape[0])
        return kernel(rid, cspan, table, *static)

    monkeypatch.setattr(ps, "_entry_lookup_pallas", spy)
    before = telemetry.counter_get("gbdt.entry_lookup")
    by_gather = GBDT(**kw).fit_batch(batch, binner)
    assert telemetry.counter_get("gbdt.entry_lookup") == before and not calls
    monkeypatch.setattr(ps, "entry_lookup_engages",
                        lambda rows_ascend, plane_rows: rows_ascend)
    by_lookup = GBDT(**kw).fit_batch(batch, binner)
    # a tree's program: on one device the slots of the three levels below
    # the root and the (grad, hess) once; under the mesh both at each of
    # the four levels, inside the shard_map body
    a_tree = [1, 6] * 4 if mesh else [6, 1, 1, 1]
    # (one trace serves both trees; the sharded program is traced again
    # for the second tree's margins)
    assert calls == a_tree * (2 if mesh else 1)
    assert (telemetry.counter_get("gbdt.entry_lookup") - before
            == 2 * len(a_tree))
    for k in by_gather:
        np.testing.assert_array_equal(np.asarray(by_lookup[k]),
                                      np.asarray(by_gather[k]), err_msg=k)
    assert (np.asarray(by_lookup["feature"]) == 0).any()   # the thin one
    assert (np.asarray(by_lookup["default_right"]) == 1).any()
