"""The field-aware factorization machine on the touched-rows step
(``models/common.py:_touched_rows_step`` under the rule ``SGD``, a table
``[F, A, K]`` as rows of ``A * K`` floats, ``models/ffm.py:lay_entries`` and
``margins_of_rows``): held against ``SGDModelMixin._train_step``, the dense
step it replaces where the model has no penalty, on the same parameters."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.data.staging import PaddedBatch
from dmlc_core_tpu.models import (FieldAwareFactorizationMachine,
                                  SparseLinearModel)
from dmlc_core_tpu.models import common
from dmlc_core_tpu.models.common import SGD, SGDModelMixin

ROWS, FIELDS, FEATURES, FACTORS = 12, 5, 48, 4


def batch_of(rows: list, pad_lanes: int = 0, pad_rows: int = 0,
             seed: int = 0) -> PaddedBatch:
    """``rows``: a list of ``[(field, key, value), ...]`` a row."""
    rng = np.random.default_rng(seed)
    counts = [len(r) for r in rows] + [0] * pad_rows
    flat = [e for r in rows for e in r]
    fld, idx, val = (np.array([e[k] for e in flat] + [0] * pad_lanes)
                     for k in range(3))
    n = len(rows) + pad_rows
    return PaddedBatch(
        label=jnp.asarray(rng.integers(0, 2, n).astype(np.float32)),
        weight=jnp.asarray(np.r_[np.ones(len(rows)), np.zeros(pad_rows)]
                           .astype(np.float32)),
        row_ptr=jnp.asarray(np.r_[0, np.cumsum(counts)].astype(np.int32)),
        index=jnp.asarray(idx.astype(np.int32)),
        value=jnp.asarray(val.astype(np.float32)),
        num_rows=jnp.asarray(np.int32(len(rows))),
        field=jnp.asarray(fld.astype(np.int32)))


def drawn(seed: int, fields=lambda rng: range(FIELDS), keys: int = FEATURES,
          values=(1.0, 0.5, 2.0)) -> list:
    """``ROWS`` rows; ``fields(rng)`` gives a row's field ids."""
    rng = np.random.default_rng(seed)
    return [[(f, int(rng.integers(0, keys)), float(rng.choice(values)))
             for f in fields(rng)] for _ in range(ROWS)]


def cases() -> dict:
    one_a_field = drawn(1)
    return {
        # every slot of the (field, row) grid holds one entry, and the lanes
        # past them are dead: the entries are the grid as they lie
        "one_a_field": batch_of(one_a_field, pad_lanes=4),
        # ten keys in all: they repeat within rows and across them
        "keys_repeat": batch_of(drawn(2, keys=10), pad_lanes=3),
        "a_field_twice": batch_of(drawn(
            3, lambda rng: [0, 1, 1, 2, 3, 4, 4, 4][:int(rng.integers(5, 9))])),
        "a_field_absent": batch_of(drawn(
            4, lambda rng: rng.permutation(FIELDS)[:int(rng.integers(0, 5))])),
        # clamped into range, as ``margins`` clamps them
        "field_out_of_range": batch_of(drawn(
            5, lambda rng: rng.integers(-2, FIELDS + 3, FIELDS))),
        "padding": batch_of(drawn(6, lambda rng: range(int(rng.integers(1, 6)))),
                            pad_lanes=9, pad_rows=4),
        # fewer lanes than the grid has slots: summed onto it whatever lies
        "short_of_the_grid": batch_of(drawn(7, lambda rng: (0, 3))),
    }


@functools.lru_cache(maxsize=None)
def model(objective: str, l2: float = 0.0) -> FieldAwareFactorizationMachine:
    return FieldAwareFactorizationMachine(
        FEATURES, FIELDS, FACTORS, objective=objective, l2=l2,
        learning_rate=0.2, init_scale=0.3)


def start(m) -> dict:
    p = m.init(0)
    p["w"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), p["w"].shape)
    p["b"] = jnp.float32(0.2)
    return p


def named_keys(batch) -> np.ndarray:
    named = np.zeros(FEATURES, bool)
    named[np.asarray(batch.index)[np.asarray(batch.value) != 0]] = True
    return named


@pytest.mark.parametrize("chunk", (None, 8))
@pytest.mark.parametrize("objective", ("logistic", "squared"))
@pytest.mark.parametrize("case", sorted(cases()))
def test_step_is_the_dense_step_on_the_rows_the_batch_names(
        monkeypatch, case, objective, chunk):
    """``w``, ``v``, ``b`` and the loss are the dense step's to 1e-6, the
    parameters stay ``{"w", "v", "b"}`` as ``init`` shaped them, and a row
    no live entry names is bit for bit what it was.  ``chunk``: the distinct
    keys visited 8 at a time, several trips of the step's two loops, and the
    gradients' windows 4 lanes long (one chunk and one window hold every
    batch here otherwise)."""
    if chunk:
        monkeypatch.setattr(common, "WIDE_ROWS_CHUNK", chunk)
        monkeypatch.setattr(common, "RUN_WINDOW", 4)
        m = FieldAwareFactorizationMachine(
            FEATURES, FIELDS, FACTORS, objective=objective,
            learning_rate=0.2, init_scale=0.3)
    else:
        m = model(objective)
    batch, before = cases()[case], start(m)
    dense, dense_loss = SGDModelMixin._train_step(
        m, jax.tree.map(jnp.copy, before), batch)
    new, loss = m.train_step(jax.tree.map(jnp.copy, before), batch)
    assert set(new) == {"w", "v", "b"}
    np.testing.assert_allclose(float(loss), float(dense_loss), rtol=1e-6)
    for k in ("w", "v", "b"):
        assert new[k].shape == before[k].shape
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(dense[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
        moved = float(jnp.max(jnp.abs(dense[k] - before[k])))
        assert moved > 1e-4, f"{k} hardly moves in this case"
    off = ~named_keys(batch)
    assert off.any()
    for k in ("w", "v"):
        assert np.array_equal(np.asarray(new[k])[off],
                              np.asarray(before[k])[off]), k


@pytest.mark.parametrize("case", ("one_a_field", "a_field_twice"))
def test_three_steps_follow_the_dense_ones(case):
    m = model("logistic")
    dense = new = start(m)
    dense, new = (jax.tree.map(jnp.copy, p) for p in (dense, new))
    batch = cases()[case]
    for _ in range(3):
        dense, _ = SGDModelMixin._train_step(m, dense, batch)
        new, _ = m.train_step(new, batch)
    for k in ("w", "v", "b"):
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(dense[k]),
                                   rtol=3e-6, atol=3e-7, err_msg=k)


def test_a_penalty_keeps_the_dense_step():
    """``l2 > 0`` moves every row a step: the model names no rule, and
    ``train_step`` is ``_train_step``, to the bit."""
    m = model("logistic", l2=1e-3)
    assert m.optimizer is None and isinstance(model("logistic").optimizer, SGD)
    batch, before = cases()["keys_repeat"], start(m)
    counted = telemetry.snapshot()
    dense, dense_loss = SGDModelMixin._train_step(
        m, jax.tree.map(jnp.copy, before), batch)
    new, loss = m.train_step(jax.tree.map(jnp.copy, before), batch)
    assert float(loss) == float(dense_loss)
    for k in ("w", "v", "b"):
        assert np.array_equal(np.asarray(new[k]), np.asarray(dense[k])), k
    # every row moved, named or not
    assert np.all(np.asarray(new["v"]) != np.asarray(before["v"]))
    assert telemetry.counters_delta(
        counted, telemetry.snapshot()).get("sgd.steps", 0) == 0


@pytest.mark.parametrize("objective", ("logistic", "squared"))
def test_the_rule_alone_takes_the_mean_and_moves_the_bias_as_before(objective):
    """``SGD`` on the linear model (one flat table and a bias) against the
    same model's dense step: the rule sees the gradient of the weighted MEAN
    (half the rows' weight here is 0.5: a sum would be 9 times the mean),
    and ``b`` moves by ``learning_rate`` times its mean gradient."""
    rows = drawn(8, keys=20)
    batch = batch_of(rows, pad_lanes=5, pad_rows=2)
    weight = np.r_[np.where(np.arange(ROWS) % 2, 0.5, 1.0), 0, 0]
    batch = PaddedBatch(**{**{f: getattr(batch, f) for f in (
        "label", "row_ptr", "index", "value", "num_rows")},
        "weight": jnp.asarray(weight.astype(np.float32))})
    ruled = SparseLinearModel(FEATURES, objective=objective,
                              optimizer=SGD(learning_rate=0.3))
    plain = SparseLinearModel(FEATURES, objective=objective,
                              learning_rate=0.3)
    before = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(1), (FEATURES,)),
              "b": jnp.float32(-0.4)}
    assert set(ruled.init()) == {"w", "b"}
    dense, dense_loss = plain.train_step(jax.tree.map(jnp.copy, before), batch)
    new, loss = ruled.train_step(jax.tree.map(jnp.copy, before), batch)
    assert set(new) == {"w", "b"}
    np.testing.assert_allclose(float(loss), float(dense_loss), rtol=1e-6)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(dense[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    assert abs(float(new["b"] - before["b"])) > 1e-3
    assert SGD(0.5).apply(jnp.float32(1.0), jnp.float32(0.25)) == (0.875,)


def test_the_step_counts_its_distinct_keys_and_the_entries_it_served():
    m = model("logistic")
    batch = cases()["keys_repeat"]
    live = np.asarray(batch.value) != 0
    m.flush_step_counters()
    counted = telemetry.snapshot()
    params = start(m)
    for _ in range(2):
        params, _loss = m.train_step(params, batch)
    m.flush_step_counters()
    counters = telemetry.counters_delta(counted, telemetry.snapshot())
    assert counters["sgd.steps"] == 2
    assert counters["sgd.touched_rows"] == 2 * int(named_keys(batch).sum())
    assert counters["sgd.spread_entries"] == 2 * int(live.sum())
    assert counters.get("sgd.scatter_tiles", 0) == 0


def test_entries_are_laid_by_field_and_row_and_the_grid_is_found():
    """``lay_entries`` sorts the live entries by (field, row) and leaves
    the dead lanes last; a batch of one entry a field then holds slot ``q``
    on lane ``q``."""
    m = model("logistic")
    for case, as_laid in (("one_a_field", True), ("a_field_twice", False),
                          ("field_out_of_range", False)):
        batch = cases()[case]
        lanes = m.lay_entries(batch)
        slots = FIELDS * batch.batch_size
        slot = np.asarray(lanes.slot)
        live = int((np.asarray(batch.value) != 0).sum())
        assert np.all(np.diff(slot) >= 0) and np.all(slot[live:] == slots)
        assert np.all(slot[:live] < slots)
        assert np.all(np.asarray(lanes.value)[live:] == 0)
        fld = np.clip(np.asarray(batch.field), 0, FIELDS - 1)
        want = np.sort((fld * batch.batch_size
                        + np.asarray(batch.row_ids()))[
                            np.asarray(batch.value) != 0])
        assert np.array_equal(slot[:live], want)
        assert (live == slots and np.array_equal(
            slot[:slots], np.arange(slots))) == as_laid


def test_constructor_takes_no_new_argument_and_no_field_lane_is_an_error():
    import inspect
    assert list(inspect.signature(
        FieldAwareFactorizationMachine.__init__).parameters) == [
            "self", "num_features", "num_fields", "num_factors", "objective",
            "l2", "learning_rate", "init_scale", "sdot_backend"]
    m = model("logistic")
    batch = cases()["one_a_field"]
    bare = PaddedBatch(**{f: getattr(batch, f) for f in (
        "label", "weight", "row_ptr", "index", "value", "num_rows")})
    with pytest.raises(ValueError, match="field ids"):
        m.train_step(start(m), bare)


@pytest.mark.parametrize("window", (2, 16, 64))
@pytest.mark.parametrize("seed,keys", ((0, 7), (1, 40), (2, 300), (3, 1)))
def test_sums_at_two_levels_are_the_runs_sums(window, seed, keys):
    """``run_windows``, ``run_sums(below=)`` and ``window_totals``: the
    rows' sums at the distinct keys are what the whole runs' sums hold at the
    runs' ends, for runs shorter than a window, of many windows (a key that
    a quarter of the entries name) and of one lane, dead lanes among them,
    the keys taken 64 at a time as ``_wide_rows_step`` takes them."""
    from dmlc_core_tpu.ops.sparse import (run_sums, run_windows,
                                          window_totals)
    rng = np.random.default_rng(seed)
    n, bound, width, chunk = 1024, 1000, 5, 64
    index = rng.integers(0, keys, n) * (bound // keys)
    index[rng.random(n) < 0.25] = 0          # one long run
    live = rng.random(n) < 0.9
    rows = rng.normal(size=(n, width)).astype(np.float32)
    flat = rng.normal(size=n).astype(np.float32)
    order = np.argsort(np.where(live, index, bound), kind="stable")
    sk = np.where(live, index, bound)[order].astype(np.int32)
    alive = sk < bound
    distinct, first = np.unique(sk[alive], return_index=True)
    ends = np.r_[first[1:], alive.sum()] - 1
    ids, anchors = run_windows(jnp.asarray(sk), jnp.asarray(alive), window)
    # a window: at most ``window`` lanes of one run, from the run's start
    ids_, held = np.asarray(ids), np.asarray(anchors)
    held = held[held < n]
    assert np.all(np.diff(held) > 0) and np.all(alive[held])
    assert len(held) == sum(-(-c // window) for c in np.diff(
        np.r_[first, alive.sum()]))
    assert np.all(sk[ids_[alive]] == sk[alive])
    assert np.all(np.arange(n)[alive] - ids_[alive] < window)
    sums = run_sums(ids, jnp.asarray(rows[order]), jnp.asarray(flat[order]),
                    below=window)
    got = []
    for lo in range(0, len(distinct), chunk):
        hi = min(lo + chunk, len(distinct))
        at_hi = first[hi] if hi < len(distinct) else alive.sum()
        totals = window_totals(sums, jnp.asarray(sk), anchors,
                               jnp.int32(first[lo]), jnp.int32(at_hi),
                               min(n, chunk + n // window), chunk, bound)
        assert totals[0].shape == (chunk, width)
        assert totals[1].shape == (chunk,)
        got.append([np.asarray(t)[:hi - lo] for t in totals])
    exact = np.stack([rows[live & (index == k)].astype(np.float64).sum(0)
                      for k in distinct])
    np.testing.assert_allclose(np.concatenate([g[0] for g in got]), exact,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.concatenate([g[1] for g in got]),
        [flat[live & (index == k)].astype(np.float64).sum() for k in distinct],
        rtol=1e-5, atol=1e-5)
    whole = np.asarray(run_sums(jnp.asarray(sk), jnp.asarray(rows[order]))[0])
    np.testing.assert_allclose(np.concatenate([g[0] for g in got]),
                               whole[ends], rtol=2e-6, atol=2e-6)
