"""Traffic kind ``paged_fit``: a dense binned training set LARGER than the
chip's memory, its pages in host memory, and one GBDT instance whose
``fit_paged`` is called back to back until the window closes — XGBoost's
external-memory ``hist`` with its quantised pages ``on_host``.

What lies where.  On the device for the whole run: the labels (``[rows]``
float32) and, inside a fit, the row state ``fit_paged`` makes beside them
(weight, margin, grad, hess, node).  In host memory: the pages, ``uint8
[page_rows, F]`` each, the last one short when the rows do not divide.  A
page is on the device only for its visit; the generator wraps every fit in
``jax.transfer_guard_device_to_host("disallow")``, so a fit that fetches row
state to the host raises and the run gives no result.

Set-up draws, bins and lays down the pages one at a time, ON the device
from ``(--seed, page)``, and fetches each to the host as it is made (the
text and the first pass that would write the pages are not run: 2^28 rows
are some 140 GB of libsvm text).  A page is ``page_rows / block_rows``
blocks of rows, each from its own key, and ``block_rows`` is the binner's
sample a page: the cuts come from the first block of every page, the rows
the fit really sees, before any page is binned.  Then one whole warm-up fit:
every program of the window is a program of that fit (a fit over fewer
pages would be other programs: the row state's shape is the rows').

The window runs ``fit_paged`` from the base margin over all pages until
``seconds`` have passed, whole fits only (at 2^28 rows one fit outlasts the
window).  ``check`` hands the reference the window's last forest, the pages
and labels, and what the program counted in that fit: the rows it streamed
(``gbdt.rows_streamed``), the bytes its prefetcher put (``page.h2d_bytes``)
and the most pages resident at once (gauge ``page.resident_max``).

Parameters (the cell's ``params``): ``rows``, ``page_rows``,
``prefetch_pages``, ``num_trees`` a fit, ``histogram`` (the run fails unless
every level resolves to the Pallas kernel), ``regret_levels``.  The columns'
kinds and the label's rule are the configuration's ``assumed`` ``data``.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory, seed31

INTEGERS, CTRS, COUNTS = 13, 26, 28     # the 67 columns, in this order
# share of absent cells in the thirteen integer columns (the Criteo files'
# I1..I13, as remembered: two near a half, one three quarters, most small)
INT_MISSING = (0.45, 0.0, 0.21, 0.22, 0.03, 0.22, 0.04, 0.0005, 0.04, 0.45,
               0.04, 0.76, 0.22)
# The label: a click where the score passes SCORE_CUT.  The score is a fixed
# rule of nine columns, seven of them through a threshold at the column's
# own median (known from how the column is drawn, so every seed cuts alike
# and a tree's first levels find the same splits), plus logistic noise.
CTR_MEDIAN = 1.0 / (1.0 + math.exp(3.4))        # of every CTR column
COUNT_MEDIAN = 20.0                             # floor(e^3) of every count
INT_MEDIAN = 2.0                                # floor(e^1) where present
# SCORE_CUT: the score's quantile at 1 - 0.03, fixed (6.5019 - 6.5061 over five
# draws of 2^20 rows on the CPU; every run logs the rate it got)
SCORE_CUT = 6.505


def draw_block(key, rows: int):
    """One block's rows: float32 ``[67, rows]`` (a column a row of the
    array, rows on the lanes; absent cells NaN) and the label's score."""
    import jax
    import jax.numpy as jnp
    ki, km, kc, kn, ks = jax.random.split(key, 5)
    normal = jax.random.normal
    ints = jnp.floor(jnp.exp(
        1.0 + 1.5 * normal(ki, (INTEGERS, rows), jnp.float32)))
    absent = (jax.random.uniform(km, (INTEGERS, rows), jnp.float32)
              < jnp.asarray(INT_MISSING, jnp.float32)[:, None])
    ints = jnp.where(absent, jnp.nan, jnp.minimum(ints, 65535.0))
    ctrs = jax.nn.sigmoid(
        0.8 * normal(kc, (CTRS, rows), jnp.float32) - 3.4)
    counts = jnp.minimum(jnp.floor(jnp.exp(
        3.0 + 2.0 * normal(kn, (COUNTS, rows), jnp.float32))), 1e7)
    cols = jnp.concatenate([ints, ctrs, counts])

    def over(col, cut):             # an absent cell passes no threshold
        return (col > cut).astype(jnp.float32)

    c, n = ctrs, counts
    score = (1.3 * over(c[0], CTR_MEDIAN) + 1.1 * over(c[3], CTR_MEDIAN)
             + 0.9 * over(c[7], CTR_MEDIAN) * over(n[2], COUNT_MEDIAN)
             + 0.8 * over(ints[1], INT_MEDIAN) - 0.7 * over(n[5], COUNT_MEDIAN)
             + 0.6 * over(ints[4], INT_MEDIAN) * over(c[11], CTR_MEDIAN)
             + 0.5 * over(n[9], COUNT_MEDIAN)
             + 12.0 * (c[1] - CTR_MEDIAN) + 0.12 * jnp.log1p(n[0])
             + jax.random.logistic(ks, (rows,), jnp.float32))
    return cols, score


def bin_columns(cols, cuts):
    """``QuantileBinner.transform``'s codes for a ``[F, rows]`` array under a
    missing-aware binner (0 for an absent cell, else one more than the count
    of cuts at or below the value): uint8 ``[rows, F]``."""
    import jax.numpy as jnp
    codes = jnp.sum(cols[:, None, :] >= cuts[:, :, None], axis=1) + 1
    return jnp.where(jnp.isnan(cols), 0, codes).T.astype(jnp.uint8)


def page_maker(seed: int, page_rows: int, block_rows: int):
    """``(first_block(page) -> [67, block_rows]``, ``make(page, cuts) ->
    (uint8 [page_rows, 67], label [page_rows])``), both jitted, the page's
    number a traced scalar: one program each for all the pages."""
    import jax
    import jax.numpy as jnp
    if page_rows % block_rows:
        raise BenchFailure(f"a page of {page_rows} rows is no whole number "
                           f"of blocks of {block_rows}")
    blocks = page_rows // block_rows
    root = jax.random.PRNGKey(seed31(seed))

    def keys(page):
        return jax.random.split(jax.random.fold_in(root, page), blocks)

    @jax.jit
    def first_block(page):
        return draw_block(keys(page)[0], block_rows)[0]

    @jax.jit
    def make(page, cuts):
        cols, score = jax.vmap(lambda k: draw_block(k, block_rows))(
            keys(page))                     # [blocks, 67, block_rows]
        cols = cols.transpose(1, 0, 2).reshape(cols.shape[1], page_rows)
        label = (score.reshape(page_rows) > SCORE_CUT).astype(jnp.float32)
        return bin_columns(cols, cuts), label

    return first_block, make


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.models import GBDT, QuantileBinner
    if not hasattr(GBDT, "fit_paged"):
        # at once, before a page is drawn: the driver tries a new cell on
        # the parent first, and that has to fail soon and cleanly
        raise BenchFailure("this tree's GBDT has no fit_paged (it came "
                           "with the cell, PR 48)")
    sizes, p, assumed = cell.sizes, cell.params, cell.config["assumed"]
    features = int(sizes["num_features"])
    if features != INTEGERS + CTRS + COUNTS:
        raise BenchFailure(f"the columns are {INTEGERS + CTRS + COUNTS}, "
                           f"the configuration says {features}")
    if not sizes["missing_aware"]:
        raise BenchFailure("the integer columns hold absent cells")
    rows, page_rows = int(p["rows"]), int(p["page_rows"])
    n_pages = -(-rows // page_rows)
    block_rows = int(assumed["binner_sample_rows"]) // n_pages
    first_block, make = page_maker(cell.seed, page_rows, block_rows)

    t0 = time.perf_counter()
    sample = np.concatenate(
        [np.asarray(first_block(np.int32(i))) for i in range(n_pages)],
        axis=1)
    binner = QuantileBinner(num_bins=sizes["num_bins"], missing_aware=True)
    binner.fit(sample.T)
    cuts = jax.device_put(binner.cuts)
    log(f"cuts from {sample.shape[1]} rows, the first {block_rows} of each "
        f"of {n_pages} pages, after {time.perf_counter() - t0:.1f}s")

    # one page ahead: page i + 1 is drawn while page i comes to the host
    pages, labels = [], []
    made = make(np.int32(0), cuts)
    for i in range(n_pages):
        ahead = make(np.int32(i + 1), cuts) if i + 1 < n_pages else None
        held = min(page_rows, rows - i * page_rows)
        pages.append(np.asarray(made[0])[:held])
        labels.append(made[1][:held])
        made = ahead
    label = jax.block_until_ready(jnp.concatenate(labels))
    del labels, made
    rate = float(jnp.mean(label))
    log(f"{n_pages} pages of {page_rows} x {features} on the host "
        f"({sum(a.nbytes for a in pages)} B) after "
        f"{time.perf_counter() - t0:.1f}s; label rate {rate:.4f} "
        f"(assumed {assumed['label_rate']})")

    model = GBDT(num_features=features, num_trees=int(p["num_trees"]),
                 max_depth=sizes["max_depth"], num_bins=sizes["num_bins"],
                 learning_rate=sizes["learning_rate"],
                 lambda_=sizes["lambda"],
                 min_child_weight=sizes["min_child_weight"],
                 objective=sizes["objective"], missing_aware=True,
                 histogram=p["histogram"])
    levels = model.level_backends()
    if levels != ["pallas"] * model.max_depth:
        raise BenchFailure(f"histogram levels resolved to {levels}: this "
                           "cell times the Pallas kernel a page at a time "
                           "and nothing else")
    log_memory("labels on the device, no fit yet")
    state = {"cell": cell, "model": model, "pages": pages, "label": label,
             "rows": rows, "page_rows": page_rows, "n_pages": n_pages,
             "prefetch_pages": int(p["prefetch_pages"]), "forest": None,
             "observed": None}
    t0 = time.perf_counter()
    fit_once(state)     # every program of the window is one of this fit's
    log(f"warm-up fit took {time.perf_counter() - t0:.1f}s")
    return state


COUNTERS = ("gbdt.rows_streamed", "page.h2d_bytes")


def fit_once(state: dict) -> None:
    """The timed call: one ``fit_paged`` to its end, no row state fetched
    inside it, and what the program counted in it.  The tests break it
    here."""
    import jax

    from dmlc_core_tpu import telemetry
    before = {k: telemetry.counter_get(k) for k in COUNTERS}
    with jax.transfer_guard_device_to_host("disallow"):
        state["forest"] = jax.block_until_ready(state["model"].fit_paged(
            state["pages"], state["label"], page_rows=state["page_rows"],
            prefetch_pages=state["prefetch_pages"]))
    state["observed"] = {k: telemetry.counter_get(k) - v
                         for k, v in before.items()}
    state["observed"]["page.resident_max"] = telemetry.gauge_get(
        "page.resident_max")


def window(state: dict, seconds: float, spans) -> dict:
    model = state["model"]
    trees, rounds = model.num_trees, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with spans.span("fit"):
            fit_once(state)
        rounds += trees
    elapsed = time.perf_counter() - t0
    rows = state["rows"]
    return {"metrics": {"train_rows_per_s": rows * rounds / elapsed},
            "attempted": rounds // trees, "failed": 0,
            "counts": {"rows": rows * rounds, "rounds": rounds,
                       "levels": rounds * model.max_depth,
                       "passes": rounds * (model.max_depth + 1),
                       "data_rows": rows, "pages": state["n_pages"],
                       "window_us": int(elapsed * 1e6),
                       "features": model.num_features}}


def check(state: dict, reference, control: int = 0) -> list:
    """Hold the forest the window's last fit returned, at the timed size,
    against the float64 reference over every page, and that fit's counters
    against what the pages and the depth make them."""
    t0 = time.perf_counter()
    forest = {k: np.asarray(v) for k, v in state["forest"].items()}
    label = np.asarray(state["label"])
    cell = state["cell"]
    out = reference.compare(
        state["pages"], label, forest, cell.sizes,
        int(cell.params["num_trees"]), cell.params["regret_levels"],
        observed=state["observed"], page_rows=state["page_rows"],
        control=bool(control))
    log(f"reference took {time.perf_counter() - t0:.1f}s")
    return out


def teardown(state: dict) -> None:
    state.clear()
