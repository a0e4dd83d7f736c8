"""Traffic kind ``resident_fit``: a binned training set resident in HBM and one
GBDT instance whose ``fit`` is called back to back until the window closes.

Parameters (the cell's ``params``): ``rows``, ``num_trees`` a fit,
``histogram`` (the model's route; the run fails unless every level resolves
to the Pallas kernel), ``regret_levels`` (the ``[tree, depth]`` levels whose
chosen splits the reference holds against its own full histogram).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory, seed31


def make_data(seed: int, rows: int, features: int):
    """Features and labels on the device from the seed.  The label is a fixed
    nonlinear rule of six features plus noise, so that splits are not ties
    and gains fall off with depth as they do on real data."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kx, kn = jax.random.split(key)
        x = jax.random.normal(kx, (rows, features), jnp.float32)
        noise = jax.random.normal(kn, (rows,), jnp.float32)
        score = (x[:, 0] * x[:, 1] + jnp.sin(2.0 * x[:, 2])
                 + 0.5 * (x[:, 3] ** 2 - 1.0)
                 + 0.6 * x[:, 4] * (x[:, 5] > 0) + 0.8 * noise)
        return x, (score > 0).astype(jnp.float32)

    return make(jax.random.PRNGKey(seed31(seed)))


def bin_codes(x, cuts, missing_aware: bool):
    """``QuantileBinner.transform``'s codes by comparing against every cut
    (the count of cuts at or below the value; bin 0 kept for missing values
    when ``missing_aware``).  The binner's own ``searchsorted`` takes half a
    minute on 10.5M rows on the chip, and every run would pay it."""
    import jax.numpy as jnp
    codes = jnp.sum(x[:, :, None] >= cuts[None, :, :], axis=2)
    if missing_aware:
        codes = jnp.where(jnp.isnan(x), 0, codes + 1)
    return codes.astype(jnp.uint8)


def setup(cell, spans) -> dict:
    import jax

    from dmlc_core_tpu.models import GBDT, QuantileBinner
    sizes, p = cell.sizes, cell.params
    rows, features = int(p["rows"]), int(sizes["num_features"])
    x, label = make_data(cell.seed, rows, features)
    binner = QuantileBinner(num_bins=sizes["num_bins"],
                            missing_aware=sizes["missing_aware"])
    sample = int(cell.config["assumed"]["binner_sample_rows"])
    binner.fit(np.asarray(x[:sample]))
    bins = jax.block_until_ready(jax.jit(
        bin_codes, static_argnums=2)(x, binner.cuts, binner.missing_aware))
    del x
    model = GBDT(num_features=features, num_trees=int(p["num_trees"]),
                 max_depth=sizes["max_depth"], num_bins=sizes["num_bins"],
                 learning_rate=sizes["learning_rate"],
                 lambda_=sizes["lambda"],
                 min_child_weight=sizes["min_child_weight"],
                 objective=sizes["objective"],
                 missing_aware=sizes["missing_aware"],
                 histogram=p["histogram"])
    levels = model.level_backends()
    if set(levels) != {"pallas"}:
        raise BenchFailure(f"histogram levels resolved to {levels}: this "
                           "cell times the Pallas kernel and nothing else")
    log(f"data ready: {rows} x {features} bins on the device; warm-up fit")
    log_memory("data ready, no fit yet")
    state = {"cell": cell, "model": model, "bins": bins, "label": label,
             "rows": rows, "forest": None}
    fit_once(state)     # compiles the tree program and the boosting ops
    return state


def fit_once(state: dict) -> None:
    """The timed call: one ``fit`` to its end.  The tests break it here."""
    import jax
    state["forest"] = jax.block_until_ready(
        state["model"].fit(state["bins"], state["label"]))


def window(state: dict, seconds: float, spans) -> dict:
    trees = state["model"].num_trees
    rounds = 0
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
                       "levels": rounds * state["model"].max_depth,
                       "data_rows": rows,
                       "features": state["model"].num_features}}


def check(state: dict, reference, control: int = 0) -> list:
    """Hold the forest the window's last fit returned, at the timed size,
    against the float64 reference."""
    t0 = time.perf_counter()
    forest = {k: np.asarray(v) for k, v in state["forest"].items()}
    bins = np.asarray(state["bins"])
    label = np.asarray(state["label"])
    cell = state["cell"]
    out = reference.compare(bins, label, forest, cell.sizes,
                            int(cell.params["num_trees"]),
                            cell.params["regret_levels"],
                            control=bool(control))
    log(f"reference took {time.perf_counter() - t0:.1f}s")
    return out


def teardown(state: dict) -> None:
    state.clear()
