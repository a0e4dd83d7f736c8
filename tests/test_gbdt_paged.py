"""The paged dense fit (`GBDT.fit_paged`): the resident `fit`'s forest from
pages that are never all on the device.

The oracle is `fit` on the same seeded rows: split finding, sibling
subtraction, leaves and boosting are the same code, so the features,
thresholds and default directions must be the same and the gains, covers
and leaves agree to float32 rounding (the sums reorder by page).  Beside it:
the plain reference of the benchmark's configuration, the prefetcher's
residency, and every span and counter the paged fit tells.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.data import PagePrefetcher
from dmlc_core_tpu.models import GBDT, QuantileBinner
from dmlc_core_tpu.ops.pallas_segment import histogram_gh

ROOT = Path(__file__).resolve().parents[1]
FEATURES, BINS, DEPTH = 9, 32, 4
EXACT = ("feature", "threshold", "default_right", "trees_used")
# float32 sums in another order: relative to the largest stored value
CLOSE = {"split_gain": 3e-5, "split_cover": 1e-5, "leaf": 3e-5, "base": 1e-5}


def seeded_rows(rows: int, features: int = FEATURES, seed: int = 0,
                bins: int = BINS):
    """Binned rows with absent cells and a label that a few thresholds
    make; an absent cell counts as 0, so default directions matter.  (No
    term on absence itself: "absent right, every cut left" and "absent
    left, every cut right" are one partition, an exact tie that float32
    sums in another order break another way.)"""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, features)).astype(np.float32)
    x[rng.random((rows, features)) < 0.15] = np.nan
    y = ((x[:, 0] > 0.2) ^ (np.nan_to_num(x[:, 3]) < -0.3)
         | (np.nan_to_num(x[:, 5]) > 0.8) & (x[:, 1] > 0)
         | (rng.random(rows) < 0.05)).astype(np.float32)
    binner = QuantileBinner(num_bins=bins, missing_aware=True)
    return np.asarray(binner.fit_transform(x)), y


def model_of(features: int = FEATURES, **kwargs):
    # children of a few rows tie exactly among many cuts, and a float32 sum
    # in another order breaks an exact tie another way: ask for children
    # of some mass and splits of some gain, as a real fit does
    args = dict(num_features=features, num_trees=3, max_depth=DEPTH,
                num_bins=BINS, learning_rate=0.1, missing_aware=True,
                min_child_weight=25.0, gamma=1.0)
    args.update(kwargs)
    return GBDT(**args)


def pages_of(bins: np.ndarray, page_rows: int) -> list:
    return [bins[i:i + page_rows] for i in range(0, bins.shape[0], page_rows)]


def assert_same_forest(paged: dict, resident: dict):
    for key in EXACT:
        np.testing.assert_array_equal(np.asarray(paged[key]),
                                      np.asarray(resident[key]), err_msg=key)
    for key, rel in CLOSE.items():
        want = np.asarray(resident[key])
        np.testing.assert_allclose(np.asarray(paged[key]), want, rtol=0,
                                   atol=rel * float(np.max(np.abs(want))),
                                   err_msg=key)


@pytest.mark.parametrize("rows, page_rows", [
    (4096, 4096), (6144, 2048), (8192, 1024), (7000, 2048)],
    ids=["one-page", "three-pages", "eight-pages", "short-last-page"])
def test_paged_fit_grows_the_resident_fits_forest(rows, page_rows):
    bins, y = seeded_rows(rows)
    model = model_of()
    resident = model.fit(jnp.asarray(bins), jnp.asarray(y))
    paged = model.fit_paged(pages_of(bins, page_rows), jnp.asarray(y),
                            page_rows=page_rows)
    assert_same_forest(paged, resident)
    assert int(paged["trees_used"]) == 3
    # a real tree: the first levels split, on more than one feature
    assert np.all(np.asarray(paged["threshold"])[:, :3] < BINS)
    assert len(set(np.asarray(paged["feature"])[0, :7])) > 2


@pytest.mark.parametrize("kwargs", [
    dict(objective="squared"), dict(subsample=0.7, colsample_bytree=0.6),
    dict(monotone_constraints=[1, 0, 0, -1, 0, 0, 0, 0, 0]),
    dict(interaction_constraints=[[0, 1, 3], [2, 4, 5, 6]]),
    dict(base_score=0.3, scale_pos_weight=2.0)],
    ids=["squared", "sampling", "monotone", "interaction", "base-score"])
def test_paged_fit_rides_the_shared_drivers_controls(kwargs):
    bins, y = seeded_rows(5000, seed=1)
    model = model_of(**kwargs)
    resident = model.fit(jnp.asarray(bins), jnp.asarray(y))
    paged = model.fit_paged(pages_of(bins, 1024), jnp.asarray(y),
                            page_rows=1024)
    assert_same_forest(paged, resident)


def test_paged_fit_takes_weights_and_a_replayable_callable():
    bins, y = seeded_rows(5000, seed=2)
    w = np.random.default_rng(3).uniform(0.5, 2.0, 5000).astype(np.float32)
    model = model_of()
    resident = model.fit(jnp.asarray(bins), jnp.asarray(y), jnp.asarray(w))
    replays = []

    def source():
        replays.append(1)
        return iter(pages_of(bins, 2048))

    paged = model.fit_paged(source, jnp.asarray(y), jnp.asarray(w),
                            page_rows=2048, prefetch_pages=1)
    assert_same_forest(paged, resident)
    assert len(replays) == 3 * (DEPTH + 1)      # a replay a pass


def test_paged_fit_is_one_forest_a_seed():
    bins, y = seeded_rows(6000, seed=4)
    model = model_of(subsample=0.8)
    a = model.fit_paged(pages_of(bins, 1500), jnp.asarray(y), page_rows=1500)
    b = model.fit_paged(pages_of(bins, 1500), jnp.asarray(y), page_rows=1500)
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


def test_paged_fit_fetches_no_row_state_and_holds_few_pages():
    """Nothing comes to the host inside a fit, and the prefetcher's gauge
    reads the pages it may hold: the one under the kernel and those ahead."""
    bins, y = seeded_rows(8192, seed=5)
    model = model_of(num_trees=1)
    label = jnp.asarray(y)
    for ahead in (1, 2, 3):
        with jax.transfer_guard_device_to_host("disallow"):
            model.fit_paged(pages_of(bins, 1024), label, page_rows=1024,
                            prefetch_pages=ahead)
        assert telemetry.gauge_get("page.resident_max") == ahead + 1


def test_paged_fit_tells_its_passes_pages_and_rows():
    """Every counter and span of the paged fit, read over one fit."""
    rows, page_rows, trees = 7000, 2048, 2
    bins, y = seeded_rows(rows, seed=6)
    model = model_of(num_trees=trees)
    names = ("gbdt.page_passes", "gbdt.rows_streamed", "page.h2d_pages",
             "page.h2d_bytes", "page.h2d_busy_us", "page.wait_us",
             "gbdt.hist_nodes_built", "gbdt.margin_select")
    telemetry.trace_start()
    try:
        before = {k: telemetry.counter_get(k) for k in names}
        model.fit_paged(pages_of(bins, page_rows), jnp.asarray(y),
                        page_rows=page_rows)
        got = {k: telemetry.counter_get(k) - v for k, v in before.items()}
        events = telemetry.trace_dump()["traceEvents"]
    finally:
        telemetry.trace_stop()
    passes, pages = trees * (DEPTH + 1), 4
    assert got["gbdt.page_passes"] == passes
    assert got["gbdt.rows_streamed"] == rows * passes       # no padding
    assert got["page.h2d_pages"] == pages * passes
    # a short last page is put whole: padded on the host
    assert got["page.h2d_bytes"] == pages * passes * page_rows * FEATURES
    assert got["page.h2d_busy_us"] > 0
    assert got["gbdt.hist_nodes_built"] == trees * 8        # 1 + 1 + 2 + 4
    assert got["gbdt.margin_select"] == trees
    spans = [e["name"] for e in events]
    assert spans.count("gbdt.page_pass") == passes
    assert spans.count("page.h2d") == pages * passes
    assert spans.count("gbdt.fit") == 1 and spans.count("gbdt.tree") == trees
    waits = [e for e in events if e["name"] == "page.wait"]
    assert got["page.wait_us"] >= sum(e["dur"] for e in waits) - len(waits)
    assert (got["page.wait_us"] > 0) == bool(waits) or not waits


@pytest.mark.parametrize("what", ["dtype", "width", "short-middle",
                                  "too-long", "labels", "objective", "mesh"])
def test_paged_fit_refuses_by_name(what):
    bins, y = seeded_rows(4096, seed=7)
    pages, label, model = pages_of(bins, 1024), jnp.asarray(y), model_of()
    if what == "dtype":
        pages[1] = pages[1].astype(np.int32)
    elif what == "width":
        pages[2] = pages[2][:, :5]
    elif what == "short-middle":
        pages[1] = pages[1][:512]
    elif what == "too-long":
        pages = pages_of(bins, 2048)
    elif what == "labels":
        label = label[:4000]
    elif what == "objective":
        model = model_of(objective="softmax", num_class=3)
    elif what == "mesh":
        from dmlc_core_tpu.parallel import MeshPlan
        model = model_of(histogram_mesh=MeshPlan.build())
    with pytest.raises(ValueError, match="page|fit_paged"):
        model.fit_paged(pages, label, page_rows=1024)


def test_prefetcher_deletes_a_page_once_its_visit_has_run():
    """Pages come in order, a pass at a time; a released page is deleted
    before the page that takes its slot is put, and `close` leaves none."""
    host = [np.full((8, 3), i, np.uint8) for i in range(5)]
    seen, held = [], []
    with PagePrefetcher(host, passes=2, page_rows=8, num_features=3,
                        depth=1) as feed:
        for _ in range(2):
            for index, rows, page in feed.pages():
                assert rows == 8 and sum(p.shape[0] for p in page) == 8
                seen.append((index, int(page[0][0, 0])))
                held.append(page)
                feed.release(page, jnp.zeros(()))
            # two slots: by the time page k arrives, page k - 2 is gone
            assert all(p.is_deleted() for page in held[:-2] for p in page)
    assert seen == [(i, i) for i in range(5)] * 2
    assert all(p.is_deleted() for page in held for p in page)
    assert telemetry.gauge_get("page.resident_max") == 2


def test_prefetcher_relays_its_sources_failure():
    def source():
        yield np.zeros((4, 2), np.uint8)
        raise OSError("the page file is gone")

    with PagePrefetcher(source, passes=1, page_rows=4, num_features=2) as feed:
        with pytest.raises(OSError, match="page file"):
            for _index, _rows, page in feed.pages():
                feed.release(page, jnp.zeros(()))


@pytest.mark.parametrize("n_nodes", [1, 4])
def test_dense_kernel_at_67_features_matches_xla(n_nodes):
    """The paged cell's width: 67 features x 256 bins are 67 key tiles, more
    than a step takes, so the bins stream in blocks of 8 features and the
    last block is ragged (67 = 8 x 8 + 3).  Interpreted here; the chip's
    compiler sees the same call in tests/test_chip_names.py."""
    rng = np.random.default_rng(n_nodes)
    rows, features, bins = 1500, 67, 256
    codes = jnp.asarray(rng.integers(0, bins, (rows, features)), jnp.int32)
    rel = jnp.asarray(rng.integers(-1, n_nodes, rows), jnp.int32)
    gh = jnp.asarray(rng.normal(size=(rows, 2)), jnp.float32)
    want = histogram_gh(codes, rel, gh, n_nodes, bins, force="xla")
    got = histogram_gh(codes, rel, gh, n_nodes, bins, force="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5)
    assert float(jnp.abs(want).max()) > 1.0


def test_page_visit_through_the_kernel_grows_the_same_forest():
    """The route the chip takes (`histogram="pallas"`, interpreted here):
    the paged fit's forest is the resident kernel fit's."""
    bins, y = seeded_rows(3000, seed=8)
    model = model_of(num_trees=1, max_depth=3, histogram="pallas")
    resident = model.fit(jnp.asarray(bins), jnp.asarray(y))
    paged = model.fit_paged(pages_of(bins, 1024), jnp.asarray(y),
                            page_rows=1024)
    assert_same_forest(paged, resident)


@pytest.mark.parametrize("histogram, dead", [("pallas", 5), ("xla", 0)])
def test_paged_fit_at_a_short_last_group_of_key_tiles(histogram, dead):
    """The paged cell's plan (67 features of 256 bins: 72 key tiles in nine
    groups, 5 of them padding, which the kernel leaves out): the paged fit
    still grows the resident fit's forest, and both tell the tiles their
    kernel calls left out: 5 a call, one call a level and page (a level of
    the resident tree); none where no kernel runs."""
    rows, page_rows, depth = 2500, 1024, 3
    bins, y = seeded_rows(rows, features=67, seed=51, bins=256)
    model = model_of(features=67, num_trees=1, max_depth=depth, num_bins=256,
                     histogram=histogram)

    def told(fit):
        before = telemetry.counter_get("gbdt.hist_dead_key_tiles")
        forest = fit()
        return forest, telemetry.counter_get(
            "gbdt.hist_dead_key_tiles") - before

    resident, by_fit = told(lambda: model.fit(jnp.asarray(bins),
                                              jnp.asarray(y)))
    paged, by_pages = told(lambda: model.fit_paged(
        pages_of(bins, page_rows), jnp.asarray(y), page_rows=page_rows))
    assert_same_forest(paged, resident)
    assert by_fit == dead * depth
    assert by_pages == dead * depth * 3         # three pages a pass


def reference():
    path = ROOT / "benchmark" / "references" / "criteo-xgb-extmem.py"
    spec = importlib.util.spec_from_file_location("criteo_xgb_extmem", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_paged_fit_agrees_with_the_configurations_plain_reference():
    """The system against ``benchmark/references/criteo-xgb-extmem.py`` on
    seeded rows: float64 sums along the forest's own splits, page by page
    in blocks, and the three exact counts."""
    rows, page_rows, trees = 7000, 2048, 2
    bins, y = seeded_rows(rows, seed=9)
    model = model_of(num_trees=trees)
    pages = pages_of(bins, page_rows)
    names = ("gbdt.rows_streamed", "page.h2d_bytes")
    before = {k: telemetry.counter_get(k) for k in names}
    forest = model.fit_paged(pages, jnp.asarray(y), page_rows=page_rows)
    observed = {k: telemetry.counter_get(k) - v for k, v in before.items()}
    observed["page.resident_max"] = telemetry.gauge_get("page.resident_max")
    sizes = {"num_bins": BINS, "lambda": 1.0, "learning_rate": 0.1,
             "max_depth": DEPTH, "min_child_weight": 25.0,
             "missing_aware": True}
    numbers = {c["name"]: c["value"] for c in reference().compare(
        pages, y, {k: np.asarray(v) for k, v in forest.items()}, sizes,
        trees, [[0, 0], [1, DEPTH - 1]], observed=observed,
        page_rows=page_rows, control=True, block_rows=600)}
    assert numbers["trees_missing"] == 0
    assert numbers["rows_streamed_mismatch"] == 0
    assert numbers["page_bytes_mismatch"] == 0
    assert numbers["pages_resident_max"] == 3
    for name, limit in (("base_abs_err", 1e-6), ("gain_rel_err", 2e-6),
                        ("cover_rel_err", 2e-6), ("leaf_rel_err", 5e-6),
                        ("root_cover_rel_err", 1e-6), ("split_regret", 3e-4)):
        assert numbers[name] <= limit, (name, numbers[name])
    # one precision down is far outside what the program reads
    assert numbers["control.gain_rel_err"] > 20 * numbers["gain_rel_err"]
    assert numbers["control.cover_rel_err"] > 1e-4
