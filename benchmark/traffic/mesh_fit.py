"""Traffic kind ``mesh_fit``: a binned training set resident in HBM with its
rows divided over the cell's chips, and one GBDT instance under a
``MeshPlan`` whose ``fit`` is called back to back until the window closes.

It calls what a user of a sharded fit calls: ``MeshPlan.build`` over the
cell's chips, arrays placed with ``plan.data_sharding()``,
``GBDT(histogram=..., histogram_mesh=plan)``, ``QuantileBinner``,
``fit(bins, label)``.  Every chip draws its own rows from ``(seed, shard)``,
so no row ever crosses the host on its way in; only the binner's sample
does, a quarter of it from each shard.

Parameters (the cell's ``params``): ``rows`` (all shards together),
``num_trees`` a fit, ``histogram`` (the model's route; the run fails unless
every level resolves to the Pallas kernel), ``collective`` and
``overlap_chunks`` (the plan's), ``regret_levels``; optionally ``chips``, for
a run on fewer chips than the cell holds (the builder's one-chip control:
``rows`` is then what those chips hold).
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory, seed31

# the thirteen columns, in gbm-bench prepare_airline's order
COLUMNS = ("Year", "Month", "DayofMonth", "DayOfWeek", "CRSDepTime",
           "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
           "Origin", "Dest", "Distance", "Diverted")
CARRIERS, FLIGHTS, AIRPORTS = 30, 7000, 340


def shard_columns(key, rows: int):
    """One shard's rows: float32 ``[13, rows]`` (a column a row of the array,
    rows on the lanes: no ``[rows, 13]`` float array is ever laid out) and
    the score whose top 45% are the positives (``SCORE_CUT``).  The columns'
    kinds are the configuration's ``assumed`` ``data``; the score is a fixed
    rule of six of them plus noise."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 16)

    def uniform(k):
        return jax.random.uniform(k, (rows,), jnp.float32)

    def pick(k, n):                     # n codes, each as likely
        return jnp.floor(uniform(k) * n)

    def zipf(k, n):                     # n codes, log-uniform: mass ~ 1/rank
        return jnp.minimum(jnp.floor(jnp.exp(uniform(k) * math.log(n + 1.0)))
                           - 1.0, n - 1.0)

    year = 1987.0 + jnp.floor(22.0 * uniform(ks[0]) ** 0.8)
    month = 1.0 + pick(ks[1], 12)
    day = 1.0 + pick(ks[2], 31)
    weekday = 1.0 + pick(ks[3], 7)
    morning = uniform(ks[4]) < 0.45
    departure = jnp.clip(jnp.round(
        jnp.where(morning, 510.0, 1020.0)
        + jnp.where(morning, 110.0, 170.0)
        * jax.random.normal(ks[5], (rows,), jnp.float32)), 0.0, 1439.0)
    carrier = zipf(ks[6], CARRIERS)
    flight = 1.0 + zipf(ks[7], FLIGHTS)
    origin = zipf(ks[8], AIRPORTS)
    dest = zipf(ks[9], AIRPORTS)
    distance = jnp.clip(jnp.round(jnp.exp(
        6.3 + 0.75 * jax.random.normal(ks[10], (rows,), jnp.float32))),
        30.0, 5000.0)
    elapsed = jnp.maximum(jnp.round(
        distance / 7.6 + 32.0
        + 9.0 * jax.random.normal(ks[11], (rows,), jnp.float32)), 15.0)
    arrival = jnp.mod(departure + elapsed, 1440.0)
    diverted = (uniform(ks[12]) < 0.002).astype(jnp.float32)
    cols = jnp.stack([year, month, day, weekday, departure, arrival, carrier,
                      flight, elapsed, origin, dest, distance, diverted])
    # ArrDelay > 0 in the source: later departures, some carriers, summer
    # and December, short hops at the week's end, evening hub departures
    late = (departure - 360.0) / 1080.0
    score = (1.1 * late * late
             + 0.5 * jnp.sin(1.7 * carrier + 0.4)
             + 0.35 * jnp.cos((month - 7.0) * (math.pi / 3.0))
             + 0.45 * ((distance < 500.0) & (weekday >= 5.0))
             + 0.5 * ((origin < 20.0) & (departure > 900.0))
             - 0.15 * jnp.log(distance / 550.0)
             + 0.8 * jax.random.normal(ks[13], (rows,), jnp.float32))
    return cols, score


# score's quantile at 1 - label_rate, fixed so that every shard and every
# seed thresholds alike (0.6273 - 0.6288 over three draws of 2^22 rows; the rate is
# logged in every run)
SCORE_CUT = 0.628


def bin_columns(cols, cuts):
    """``QuantileBinner.transform``'s codes (the count of cuts at or below
    the value) for a ``[F, rows]`` array: uint8 ``[rows, F]``."""
    import jax.numpy as jnp
    codes = jnp.sum(cols[:, None, :] >= cuts[:, :, None], axis=1)
    return codes.T.astype(jnp.uint8)


def make_plan(cell):
    import jax

    from dmlc_core_tpu.parallel import MeshPlan
    p = cell.params
    chips = int(p.get("chips", cell.chips))
    return MeshPlan.build(devices=jax.devices()[:chips],
                          collective=p["collective"],
                          overlap_chunks=int(p["overlap_chunks"]))


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dmlc_core_tpu.models import GBDT, QuantileBinner
    sizes, p = cell.sizes, cell.params
    features = int(sizes["num_features"])
    if features != len(COLUMNS):
        raise BenchFailure(f"the airline columns are {len(COLUMNS)}, the "
                           f"configuration says {features}")
    if sizes["missing_aware"]:
        raise BenchFailure("the airline columns hold nothing absent")
    plan = make_plan(cell)
    shards = plan.num_shards
    rows = int(p["rows"])
    if rows % shards:
        raise BenchFailure(f"{rows} rows do not divide over {shards} chips")
    rows_chip = rows // shards
    axis = plan.axes if len(plan.axes) > 1 else plan.axes[0]
    label_rate = float(cell.config["assumed"]["label_rate"])

    def draw(key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        cols, score = shard_columns(key, rows_chip)
        return cols, (score > SCORE_CUT).astype(jnp.float32)

    by_rows = P(None, plan.axes)
    cols, label = jax.jit(plan.shard_map(
        draw, in_specs=P(), out_specs=(by_rows, plan.row_spec),
        check_replication=False))(jax.random.PRNGKey(seed31(cell.seed)))
    sample = int(cell.config["assumed"]["binner_sample_rows"]) // shards
    head = jax.jit(plan.shard_map(lambda c: c[:, :sample], in_specs=by_rows,
                                  out_specs=by_rows))(cols)
    binner = QuantileBinner(num_bins=sizes["num_bins"], missing_aware=False)
    binner.fit(np.asarray(head).T)
    bins = jax.block_until_ready(jax.jit(plan.shard_map(
        bin_columns, in_specs=(by_rows, P()), out_specs=plan.row_spec))(
            cols, jax.device_put(binner.cuts, plan.replicated_sharding())))
    del cols, head
    log(f"label rate {float(jnp.mean(label)):.4f} (assumed {label_rate})")

    model = GBDT(num_features=features, num_trees=int(p["num_trees"]),
                 max_depth=sizes["max_depth"], num_bins=sizes["num_bins"],
                 learning_rate=sizes["learning_rate"],
                 lambda_=sizes["lambda"],
                 min_child_weight=sizes["min_child_weight"],
                 objective=sizes["objective"], missing_aware=False,
                 histogram=p["histogram"], histogram_mesh=plan)
    levels = model.level_backends()
    if set(levels) != {"pallas"}:
        raise BenchFailure(f"histogram levels resolved to {levels}: this "
                           "cell times the Pallas kernel under shard_map "
                           "and nothing else")
    for name, a in (("bins", bins), ("label", label)):
        held = {s.data.shape[0] for s in a.addressable_shards}
        if (held != {rows_chip}
                or len({s.device for s in a.addressable_shards}) != shards):
            raise BenchFailure(f"{name} lies as {sorted(held)} rows on "
                               f"{len(a.addressable_shards)} shards")
    log(f"data ready: {rows} x {features} bins, {rows_chip} a chip on "
        f"{shards} chip(s), plan {plan.describe()}; warm-up fit")
    for d in jax.devices()[:shards]:
        log_memory(f"data ready, no fit yet, {d}", d)
    state = {"cell": cell, "model": model, "plan": plan, "bins": bins,
             "label": label, "rows": rows, "rows_chip": rows_chip,
             "forest": None}
    fit_once(state)     # compiles the tree program and the boosting ops
    return state


def fit_once(state: dict) -> None:
    """The timed call: one ``fit`` to its end.  The tests break it here."""
    import jax
    state["forest"] = jax.block_until_ready(
        state["model"].fit(state["bins"], state["label"]))


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
                       "rows_per_chip": state["rows_chip"],
                       "features": model.num_features,
                       "chips": state["plan"].num_shards}}


def check(state: dict, reference, control: int = 0) -> list:
    """Hold the forest the window's last fit returned, at the timed size,
    against the float64 reference over every shard's rows."""
    t0 = time.perf_counter()
    forest = {k: np.asarray(v) for k, v in state["forest"].items()}
    bins = np.asarray(state["bins"])
    label = np.asarray(state["label"])
    log(f"rows on the host after {time.perf_counter() - t0:.1f}s")
    cell = state["cell"]
    out = reference.compare(bins, label, forest, cell.sizes,
                            int(cell.params["num_trees"]),
                            cell.params["regret_levels"],
                            control=bool(control))
    log(f"reference took {time.perf_counter() - t0:.1f}s")
    return out


def teardown(state: dict) -> None:
    state.clear()
