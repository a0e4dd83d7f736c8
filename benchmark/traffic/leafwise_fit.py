"""Traffic kind ``leafwise_fit``: a wide dense binned training set resident in
HBM and one leaf-wise GBDT (``grow_policy="lossguide"``) whose ``fit`` is
called back to back, closed loop, until the window closes.

It calls what a user of LightGBM's policy calls here: ``QuantileBinner``,
``GBDT(grow_policy="lossguide", max_leaves=..., max_depth=0)``, ``fit(bins,
label)``.  The rows are drawn and binned on the device from ``--seed``: unit
variance columns scaled as unit-norm rows are, and a label whose signal is
spread over many columns (`make_data`), so that a frontier holds many leaves
of comparable gain.

Parameters (the cell's ``params``): ``rows``, ``num_trees`` a fit,
``max_leaves`` (must be the configuration's ``num_leaves``), ``histogram``
(the model's route; the run fails unless every expansion's histogram
resolves to the Pallas kernel), ``regret_expansions`` (the ``[tree,
expansion]`` pairs at which the reference builds the frontier's full
histograms and holds the chosen cut and the chosen leaf against its own).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory, seed31

# columns that carry the label's pairwise products, two by two
PRODUCT_COLUMNS = 8
# the dense direction's weight on column j: 1 / (1 + j / WEIGHT_DECAY)
WEIGHT_DECAY = 40.0


def make_data(seed: int, rows: int, features: int):
    """Features and labels on the device from the seed.  Columns are N(0, 1 /
    features) draws (a unit-norm row's scale; binning does not see the
    scale).  The label is the sign of a fixed rule in which every column
    acts through a threshold at its median (``s_j``: which side of it the
    row lies on): a dense direction over every column with decaying weights
    (unit variance), half a unit of products of the first columns' sides in
    pairs, 0.7 of noise; half positive.  A threshold pins a column's best cut
    (a linear effect leaves it wandering over a third of the column's range
    with the sample), so the seeds' trees differ in which columns they cut,
    hardly in how many rows stand on each side: the work a tree takes is
    the policy's and the shape's, not the draw's."""
    import jax
    import jax.numpy as jnp

    weight = 1.0 / (1.0 + np.arange(features) / WEIGHT_DECAY)
    weight = jnp.asarray(weight / np.sqrt(np.sum(weight ** 2)), jnp.float32)
    pairs = min(PRODUCT_COLUMNS, features - features % 2) // 2

    @jax.jit
    def make(key):
        kx, kn = jax.random.split(key)
        z = jax.random.normal(kx, (rows, features), jnp.float32)
        noise = jax.random.normal(kn, (rows,), jnp.float32)
        side = jnp.sign(z)
        products = sum(side[:, 2 * i] * side[:, 2 * i + 1]
                       for i in range(pairs))
        score = side @ weight + 0.5 * products / np.sqrt(pairs) + 0.7 * noise
        return z * (features ** -0.5), (score > 0).astype(jnp.float32)

    return make(jax.random.PRNGKey(seed31(seed)))


def bin_codes(x, cuts):
    """``QuantileBinner.transform``'s codes by comparing against every cut
    (the count of cuts at or below the value): uint8 ``[rows, F]``."""
    import jax.numpy as jnp
    return jnp.sum(x[:, :, None] >= cuts[None, :, :], axis=2
                   ).astype(jnp.uint8)


def rule_of(sizes: dict) -> dict:
    """The configuration's sizes (LightGBM's names) under the names the
    reference's rule reads."""
    return {"num_bins": int(sizes["max_bin"]),
            "max_leaves": int(sizes["num_leaves"]),
            "max_depth": int(sizes["max_depth"]),
            "learning_rate": sizes["learning_rate"],
            "lambda": sizes["lambda"],
            "min_child_weight": sizes["min_sum_hessian_in_leaf"],
            "missing_aware": sizes["missing_aware"]}


def setup(cell, spans) -> dict:
    import jax

    from dmlc_core_tpu.models import GBDT, QuantileBinner
    sizes, p = cell.sizes, cell.params
    rows, features = int(p["rows"]), int(sizes["num_features"])
    rule = rule_of(sizes)
    if int(p["max_leaves"]) != rule["max_leaves"]:
        raise BenchFailure(f"the cell grows {p['max_leaves']} leaves, its "
                           f"configuration {rule['max_leaves']}")
    if sizes["missing_aware"] or sizes["min_data_in_leaf"] != 1:
        raise BenchFailure("the columns hold nothing absent, and the "
                           "builder's only floor on a leaf is its hessian")
    # the model first: a program that lacks the policy fails here, at once
    model = GBDT(num_features=features, num_trees=int(p["num_trees"]),
                 num_bins=rule["num_bins"], grow_policy="lossguide",
                 max_leaves=rule["max_leaves"], max_depth=rule["max_depth"],
                 learning_rate=rule["learning_rate"], lambda_=rule["lambda"],
                 min_child_weight=rule["min_child_weight"],
                 objective=sizes["objective"], missing_aware=False,
                 histogram=p["histogram"])
    backends = model.level_backends()
    if set(backends) != {"pallas"}:
        raise BenchFailure(f"the histograms resolved to {backends}: this "
                           "cell times the Pallas kernel and nothing else")
    x, label = make_data(cell.seed, rows, features)
    binner = QuantileBinner(num_bins=rule["num_bins"], missing_aware=False)
    sample = int(cell.config["assumed"]["binner_sample_rows"])
    binner.fit(np.asarray(x[:sample]))
    bins = jax.block_until_ready(jax.jit(bin_codes)(x, binner.cuts))
    del x
    log(f"data ready: {rows} x {features} bins on the device, label rate "
        f"{float(label.mean()):.4f}; warm-up fit")
    log_memory("data ready, no fit yet")
    state = {"cell": cell, "model": model, "bins": bins, "label": label,
             "rows": rows, "rule": rule, "forest": None, "visited": 0}
    fit_once(state)     # compiles the tree program and the boosting ops
    return state


def fit_once(state: dict) -> None:
    """The timed call: one ``fit`` to its end, and what the program's counter
    of rows its histograms visited moved by.  The tests break it here."""
    import jax

    from dmlc_core_tpu import telemetry
    before = telemetry.counter_get("gbdt.hist_rows_visited")
    state["forest"] = jax.block_until_ready(
        state["model"].fit(state["bins"], state["label"]))
    state["visited"] = (telemetry.counter_get("gbdt.hist_rows_visited")
                        - before)


def forest_counts(forest: dict) -> dict:
    """Off the forest's own tables, a fit's expansions and the rows its
    histograms had to visit: every tree's rows once, then the smaller child
    of every expansion."""
    left, right, count = (np.asarray(forest[k]) for k in (
        "left", "right", "node_rows"))
    split = left != np.arange(left.shape[1])[None, :]
    smaller = np.minimum(np.take_along_axis(count, left, 1),
                         np.take_along_axis(count, right, 1))
    return {"expansions": int(split.sum()),
            "rows_visited": int(count[:, 0].sum() + smaller[split].sum())}


def window(state: dict, seconds: float, spans) -> dict:
    model = state["model"]
    trees, fits = model.num_trees, 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with spans.span("fit"):
            fit_once(state)
        fits += 1
    elapsed = time.perf_counter() - t0
    rows, rounds = state["rows"], fits * trees
    # every fit of the window saw the same rows: one forest, `fits` times
    one = forest_counts(state["forest"])
    return {"metrics": {"train_rows_per_s": rows * rounds / elapsed},
            "attempted": fits, "failed": 0,
            "counts": {"rows": rows * rounds, "rounds": rounds,
                       "data_rows": rows, "features": model.num_features,
                       "num_bins": model.num_bins,
                       "expansions": fits * one["expansions"],
                       "rows_visited": fits * one["rows_visited"],
                       "histograms_built": fits * (one["expansions"]
                                                   + trees)}}


def check(state: dict, reference, control: int = 0) -> list:
    """Hold the forest the window's last fit returned, at the timed size,
    against the float64 reference."""
    t0 = time.perf_counter()
    forest = {k: np.asarray(v) for k, v in state["forest"].items()}
    # feature-major off the device, handed over as its [rows, F] view: the
    # reference reads a column at a time, and a host transpose of 0.8 GB
    # takes 12 s
    bins = np.asarray(state["bins"].T).T
    label = np.asarray(state["label"])
    log(f"rows on the host after {time.perf_counter() - t0:.1f}s")
    cell = state["cell"]
    out = reference.compare(bins, label, forest, state["rule"],
                            int(cell.params["num_trees"]),
                            cell.params["regret_expansions"],
                            state["visited"], control=bool(control))
    log(f"reference took {time.perf_counter() - t0:.1f}s")
    return out


def teardown(state: dict) -> None:
    state.clear()
