"""The touched-rows step for row-shaped tables, a rule a table and a count
table that gates them (``models/common.py:_touched_rows_step``), as
``FactorizationMachine`` runs it under DiFacto's rules: held against the
``criteo-tb-difacto`` configuration's plain reference (numpy float64) and
against ``jax.grad`` of the model's own loss at small sizes on the CPU; the
rows kernel at widths 1, 4 and 16; the scoring path's gate; the new cell's
rehearsal."""
import functools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness, run  # noqa: E402
from dmlc_core_tpu import checkpoint, telemetry  # noqa: E402
from dmlc_core_tpu.data.staging import PaddedBatch  # noqa: E402
from dmlc_core_tpu.models import (FactorizationMachine,  # noqa: E402
                                  SparseLinearModel)
from dmlc_core_tpu.models import common  # noqa: E402
from dmlc_core_tpu.models.common import FTRL, AdaGrad  # noqa: E402
from dmlc_core_tpu.ops import pallas_rows  # noqa: E402
from dmlc_core_tpu.ops.sparse import (csr_row_sums,  # noqa: E402
                                      reduce_by_key, run_sums)
from dmlc_core_tpu.serving.snapshot import (pack_snapshot,  # noqa: E402
                                            unpack_snapshot)

ROWS, FEATURES, FACTORS = 32, 256, 4
SIZES = {"alpha": 0.1, "beta": 1.0, "l1": 0.6, "l2": 0.01,
         "objective": "logistic", "batch_size": ROWS, "num_factors": FACTORS,
         "alpha_v": 0.05, "beta_v": 1.0, "l2_v": 1e-3, "threshold": 3}
CELL = "criteo-tb-difacto.stream-train"
#: a key no drawn row holds, put into rows 2.. of each step: (rows, label)
SPECIAL = 251
PLAN = ((3, 1), (3, 1), (3, 0), (4, 0), (2, 0))


@pytest.fixture(scope="module")
def reference():
    return run.load_module("references", "criteo-tb-difacto")


@functools.lru_cache(maxsize=None)
def model(**over) -> FactorizationMachine:
    """One model a set of sizes: a program is compiled a model object."""
    s = dict(SIZES, **over)
    return FactorizationMachine(
        FEATURES, FACTORS, optimizer={
            "w": FTRL(alpha=s["alpha"], beta=s["beta"], l1=s["l1"],
                      l2=s["l2"]),
            "v": AdaGrad(alpha=s["alpha_v"], beta=s["beta_v"], l2=s["l2_v"])},
        threshold=s["threshold"])


def padded(b: dict, pad: int = 37) -> PaddedBatch:
    """A reference batch (COO, rows in order) as the staged batch."""
    counts = np.bincount(b["row"], minlength=len(b["label"]))
    return PaddedBatch(
        label=jnp.asarray(b["label"], jnp.float32),
        weight=jnp.asarray(b["weight"], jnp.float32),
        row_ptr=jnp.asarray(np.concatenate([[0], np.cumsum(counts)]),
                            jnp.int32),
        index=jnp.asarray(np.pad(b["index"], (0, pad)), jnp.int32),
        value=jnp.asarray(np.pad(b["value"], (0, pad)), jnp.float32),
        num_rows=jnp.asarray(np.int32(len(b["label"]))))


def drawn(seed: int, steps: int = 5, keys: int = 120) -> list:
    """``test_ftrl.py``'s minibatches (keys repeat inside a row and across
    rows, some entries hold 0, some rows weigh 0, two hold nothing), and
    ``SPECIAL`` as the first entry of a few rows under ``PLAN``'s labels."""
    rng = np.random.default_rng(seed)
    out = []
    for t in range(steps):
        counts = rng.integers(0, 9, ROWS)
        counts[:2] = 0
        entries = int(counts.sum())
        b = {"row": np.repeat(np.arange(ROWS), counts),
             "index": rng.integers(0, keys, entries) * 2,
             "value": rng.choice([0.0, 1.0, 2.0, -0.5], entries),
             "label": rng.integers(0, 2, ROWS),
             "weight": rng.choice([0.0, 1.0, 2.0], ROWS)}
        many, label = PLAN[t]
        rows = np.arange(2, 2 + many)
        at = np.searchsorted(b["row"], rows)
        b["row"] = np.insert(b["row"], at, rows)
        b["index"] = np.insert(b["index"], at, SPECIAL)
        b["value"] = np.insert(b["value"], at, 1.0)
        b["label"][rows], b["weight"][rows] = label, 1.0
        out.append(b)
    return out


def state_of(params: dict) -> dict:
    """What the reference samples, the bias first."""
    f, width = params["ftrl"], params["v"].shape[1]
    out = {name: np.concatenate([[float(b)], np.asarray(t)]) for name, b, t
           in (("w", params["b"], params["w"]),
               ("z", f["z"]["b"], f["z"]["w"]),
               ("n", f["n"]["b"], f["n"]["w"]),
               ("c", 0, params["count"]))}
    for name, t in (("v", params["v"]), ("nv", params["adagrad"]["n"]["v"])):
        out[name] = np.concatenate([np.zeros((1, width)), np.asarray(t)])
    return out


def follow(batches, m=None, params=None, seed=3):
    m = m or model()
    params = m.init(seed) if params is None else params
    losses = []
    for b in batches:
        params, loss = m.train_step(params, padded(b))
        losses.append(float(loss))
    return m, params, losses


def rows_of(m, seed=3):
    drawn_rows = np.asarray(m.init(seed)["v"], np.float64)
    return lambda ids: drawn_rows[ids]


# (a) ------------------------------------------------------------------------
@pytest.mark.parametrize("seed", (0, 1, 2))
def test_step_follows_the_reference(reference, seed):
    """Five steps in which keys cross the count threshold, keys stay under
    it, and one key's weight leaves zero and returns to it, which shuts its
    embedding row again."""
    batches = drawn(seed)
    m, params, losses = follow(batches)
    ref = reference.difacto_steps(batches, SIZES, rows_of(m))
    want = reference.sampled(ref, np.arange(FEATURES), rows_of(m))
    np.testing.assert_allclose(losses, ref["losses"], rtol=3e-6)
    got = state_of(params)
    for name in ("z", "n", "nv"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
    np.testing.assert_allclose(got["w"], want["w"], atol=3e-7)
    np.testing.assert_allclose(got["v"], want["v"], atol=3e-7)
    assert np.array_equal(got["c"], want["c"])
    assert np.array_equal(got["w"] == 0, want["w"] == 0)
    moved = np.any(got["nv"] != 0, axis=1)
    assert np.array_equal(moved, np.any(want["nv"] != 0, axis=1))
    # some keys crossed the threshold and moved, some are over it and shut
    # by their zero weight, some stayed under it, and a key that never
    # moved holds its drawn row bit for bit
    over = got["c"][1:] > SIZES["threshold"]
    assert moved[1:].sum() > 3 and (over & ~moved[1:]).sum() > 0
    assert ((got["c"][1:] > 0) & ~over).sum() > 0
    assert np.array_equal(np.asarray(params["v"])[~moved[1:]],
                          np.asarray(m.init(3)["v"])[~moved[1:]])


def test_a_weight_that_returns_to_zero_shuts_its_row(reference):
    """``SPECIAL``: under the threshold in step 1, its row updated in steps
    2 to 4, its weight back at 0 after step 4, so step 5 leaves the row as
    step 4 left it although the count is far over the threshold."""
    batches = drawn(0)
    seen = []
    m, params = model(), None
    for b in batches:
        m, params, _ = follow([b], m, params)
        seen.append((float(params["w"][SPECIAL]), int(params["count"][SPECIAL]),
                     np.asarray(params["adagrad"]["n"]["v"][SPECIAL])))
    w, c, nv = zip(*seen)
    assert [x != 0 for x in w[:4]] == [True, True, True, False]
    assert list(c) == [3, 6, 9, 13, 15]
    assert not nv[0].any() and nv[1].all()
    assert np.all(nv[2] > nv[1]) and np.all(nv[3] > nv[2])
    assert np.array_equal(nv[4], nv[3])
    ref = reference.difacto_steps(batches, SIZES, rows_of(m))
    at = int(np.searchsorted(ref["keys"], SPECIAL))
    np.testing.assert_allclose(nv[4], ref["nv"][at], rtol=1e-5)


# (b) ------------------------------------------------------------------------
@pytest.mark.parametrize("seed,threshold", ((0, 3), (1, 3), (2, 0)))
def test_step_is_the_dense_rules_on_the_gradient_of_the_models_loss(
        seed, threshold):
    """The third step against ``jax.grad`` of ``loss`` (so of ``margins``)
    over the whole table, under FTRL and AdaGrad written densely; at
    threshold 0 a row is shut by its zero weight alone."""
    batches = drawn(seed)
    m, params, _ = follow(batches[:2], model(threshold=threshold))
    batch = padded(batches[2])
    live = np.asarray(batch.value) != 0
    named = np.zeros(FEATURES, bool)
    named[np.asarray(batch.index)[live]] = True
    seen = np.bincount(np.asarray(batch.index)[live], minlength=FEATURES)
    counted = dict(jax.tree.map(jnp.copy, params),
                   count=params["count"] + jnp.asarray(seen, jnp.int32))
    weights = ("w", "v", "b")
    loss, grads = jax.value_and_grad(
        lambda p: m.loss(dict(counted, **p), batch))(
            {k: counted[k] for k in weights})
    scale = max(float(jnp.sum(batch.weight)), 1.0)      # the SUM's gradient
    f, a = params["ftrl"], params["adagrad"]
    ftrl, ada = m.rule_of("w"), m.rule_of("v")
    want_w = ftrl.apply(f["z"]["w"], f["n"]["w"], grads["w"] * scale)
    want_b = ftrl.apply(f["z"]["b"], f["n"]["b"], grads["b"] * scale)
    on = named & np.asarray(m.active(counted["count"], params["w"]))
    new_v, new_n = ada.apply(params["v"], a["n"]["v"], grads["v"] * scale)
    want_v = np.where(on[:, None], new_v, params["v"])
    want_nv = np.where(on[:, None], new_n, a["n"]["v"])
    assert 3 < on.sum() < named.sum()
    got, got_loss = m.train_step(params, batch)
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
    for name, (g, w) in {
            "w": (got["w"], want_w[0]), "b": (got["b"], want_b[0]),
            "z": (got["ftrl"]["z"]["w"], want_w[1]),
            "n": (got["ftrl"]["n"]["w"], want_w[2]),
            "v": (got["v"], want_v),
            "nv": (got["adagrad"]["n"]["v"], want_nv)}.items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-7, err_msg=name)
    assert np.array_equal(np.asarray(got["count"]),
                          np.asarray(counted["count"]))


def test_a_linear_model_takes_the_step_ungated():
    """One FTRL, one flat table, no gate: no count table and no AdaGrad
    state beside the parameters, and a step counts its distinct keys and
    nothing of a gate."""
    batches = drawn(4)
    m = SparseLinearModel(FEATURES, optimizer=FTRL(l1=0.3, l2=0.01))
    params = m.init()
    assert set(params) == {"w", "b", "ftrl"}
    before = telemetry.snapshot()
    for b in batches:
        params, loss = m.train_step(params, padded(b))
        assert np.isfinite(float(loss))
    m.flush_step_counters()
    delta = telemetry.counters_delta(before, telemetry.snapshot())
    assert set(params) == {"w", "b", "ftrl"}
    assert delta["sgd.steps"] == len(batches)
    assert delta["sgd.touched_rows"] == sum(
        len(np.unique(b["index"][b["value"] != 0])) for b in batches)
    assert not delta.get("sgd.active_rows")
    assert not delta.get("sgd.activated_rows")


# (c) ------------------------------------------------------------------------
@pytest.mark.parametrize("width", (1, 4, 16))
@pytest.mark.parametrize("dtype", (np.float32, np.int32))
def test_rows_kernel_at_width_against_xla_set(width, dtype):
    """Interpreted: keys that share a tile (a block of 128 keys at width >
    1) and straddle chunks of 1,024 lanes, ``count`` short of the keys and
    of the lanes, ids past the table after them."""
    rng = np.random.default_rng(width)
    length, lanes, held, count = 4096, 2048, 1500, 1300
    shape = (length,) if width == 1 else (length, width)

    def table():
        return jnp.asarray(rng.normal(size=shape) * 100, dtype)
    tables = (table(), table())
    keys = np.sort(rng.choice(length, held, replace=False))
    keys[:6] = np.arange(6) + 120           # neighbours across a block's end
    keys = np.unique(keys)
    held = len(keys)
    keys = np.concatenate([keys, length + np.arange(lanes - held)]).astype(
        np.int32)
    rows = tuple(jnp.asarray(rng.normal(size=(lanes,) + shape[1:]) * 100,
                             dtype) for _ in tables)
    out, tiles = jax.jit(pallas_rows.scatter_rows_inplace)(
        tables, jnp.asarray(keys), rows, jnp.int32(count))
    for got, old, new in zip(out, tables, rows):
        want = old.at[keys[:count]].set(new[:count], mode="drop")
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert not np.array_equal(np.asarray(got), np.asarray(old))
    block = 1024 if width == 1 else 128
    per_block = 1 if width == 1 else -(-width // 8)
    assert int(tiles) == len(tables) * per_block * len(
        np.unique(keys[:count] // block))


@pytest.mark.parametrize("width,length,lanes,want", (
    (1, 1 << 29, 1 << 17, True), (1, 1 << 26, 1 << 17, False),
    (1, 1 << 26, 1 << 16, True), (16, 1 << 26, 1 << 19, True),
    (16, 1 << 26, 655360, True), (4, 1 << 26, 1 << 16, False),
    (16, 1 << 16, 1 << 16, False)))
def test_engages_is_stated_in_floats_of_table_a_lane(monkeypatch, width,
                                                      length, lanes, want):
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    assert pallas_rows.engages(length, lanes, jnp.float32, width) is want
    assert pallas_rows.engages(length, lanes, jnp.int32, width) is want
    assert not pallas_rows.engages(length, lanes, jnp.bfloat16, width)


@pytest.mark.parametrize("seed", (0, 1))
def test_reduce_by_key_sums_rows_and_scalars_once_a_live_key(seed):
    rng = np.random.default_rng(seed)
    n, bound, width = 300, 1000, 4
    index = rng.integers(0, 40, n) * 7
    live = rng.random(n) < 0.8
    scalar = rng.normal(size=n).astype(np.float32)
    rows = rng.normal(size=(n, width)).astype(np.float32)
    keys, (sums,), count, (running, ends) = jax.jit(
        reduce_by_key, static_argnums=3)(
        jnp.asarray(index, jnp.int32), jnp.asarray(live),
        (jnp.asarray(scalar),), bound, (jnp.asarray(rows),))
    want_keys = np.unique(index[live])
    count = int(count)
    assert count == len(want_keys)
    assert np.array_equal(np.asarray(keys)[:count], want_keys)
    assert np.all(np.diff(np.asarray(keys)) > 0)
    assert np.asarray(keys)[count:].min() >= bound
    plain = jax.jit(reduce_by_key, static_argnums=3)(
        jnp.asarray(index, jnp.int32), jnp.asarray(live),
        (jnp.asarray(scalar),), bound)
    assert len(plain) == 3
    assert np.array_equal(np.asarray(plain[0]), np.asarray(keys))
    got_rows = np.asarray(running[0])[np.asarray(ends)[:count]]
    for i, key in enumerate(want_keys):
        here = live & (index == key)
        assert float(sums[i]) == pytest.approx(scalar[here].sum(), abs=1e-5)
        np.testing.assert_allclose(got_rows[i], rows[here].sum(0), atol=1e-5)


def csr(rng, rows=40, pad=11):
    from dmlc_core_tpu.data.staging import csr_row_ids
    counts = rng.integers(0, 9, rows)
    counts[[0, 7, 8, rows - 1]] = 0
    row_ptr = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    real = int(counts.sum())
    return row_ptr, csr_row_ids(row_ptr, real + pad), real


@pytest.mark.parametrize("seed", (0, 1))
def test_csr_row_sums_of_rows_is_segment_sum_forward_and_backward(seed):
    rng = np.random.default_rng(seed)
    row_ptr, row_id, real = csr(rng)
    contrib = jnp.asarray(rng.normal(size=(len(row_id), 3)), jnp.float32).at[
        real:].set(0.0)
    want = jax.ops.segment_sum(contrib, row_id, num_segments=40)
    got = jax.jit(csr_row_sums)(contrib, row_id, row_ptr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    ct = jnp.asarray(rng.normal(size=(40, 3)), jnp.float32)
    grad = jax.grad(lambda c: jnp.sum(csr_row_sums(c, row_id, row_ptr) * ct))(
        contrib)
    assert np.array_equal(np.asarray(grad)[:real],
                          np.asarray(ct)[np.asarray(row_id)[:real]])


def test_a_column_of_rows_is_summed_as_its_elements_are_bit_for_bit():
    """One implementation for ``[lanes]`` and ``[lanes, K]``: each trailing
    element takes the passes a flat column takes, in the same order."""
    rng = np.random.default_rng(5)
    row_ptr, row_id, real = csr(rng)
    wide = jnp.asarray(rng.normal(size=(len(row_id), 3)), jnp.float32).at[
        real:].set(0.0)
    for k in range(3):
        assert np.array_equal(
            np.asarray(run_sums(row_id, wide)[0][:, k]),
            np.asarray(run_sums(row_id, wide[:, k])[0]))
        assert np.array_equal(
            np.asarray(csr_row_sums(wide, row_id, row_ptr)[:, k]),
            np.asarray(csr_row_sums(wide[:, k], row_id, row_ptr)))


# (d) ------------------------------------------------------------------------
def test_predict_applies_the_gate_and_scores_what_training_saw():
    batches = drawn(5)
    m, params, _ = follow(batches[:4])
    batch = padded(batches[4])
    rows = {k: params[k][batch.index] for k in ("w", "v")}
    on = (batch.value != 0) & m.active(params["count"][batch.index],
                                       rows["w"])
    seen = m.margins_of_rows(rows, params, batch, on)
    assert 0 < int(on.sum()) < int((batch.value != 0).sum())
    assert np.array_equal(np.asarray(m.margins(params, batch)),
                          np.asarray(seen))
    assert np.array_equal(np.asarray(m.predict(params, batch)),
                          np.asarray(jax.nn.sigmoid(seen)))
    # not what an ungated model scores from the same tables
    open_ = m.margins_of_rows(rows, params, batch, batch.value != 0)
    assert not np.array_equal(np.asarray(open_), np.asarray(seen))


def test_snapshot_and_checkpoint_round_trip_with_the_state(tmp_path):
    batches = drawn(6)
    m, params, _ = follow(batches[:3])
    batch = padded(batches[3])
    p = np.asarray(m.predict(params, batch))
    config = {"num_features": FEATURES, "num_factors": FACTORS,
              "threshold": SIZES["threshold"]}
    family, cfg, served, _ = unpack_snapshot(
        pack_snapshot("fm", config, params))
    assert family == "fm" and set(served) == {"w", "v", "b", "count"}
    scorer = FactorizationMachine(**cfg)
    assert np.array_equal(p, np.asarray(scorer.predict(served, batch)))
    assert np.array_equal(p, np.asarray(
        scorer.predict_bucketed(served, batch)))
    uri = str(tmp_path / "difacto.ckpt")
    assert checkpoint.save(params, uri) == len(jax.tree.leaves(params)) == 9
    loaded = checkpoint.load(uri, like=m.init())
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    _m, live, l1 = follow(batches[3:], m, params)
    _m, again, l2 = follow(batches[3:], m, loaded)
    assert l1 == l2
    for x, y in zip(jax.tree.leaves(live), jax.tree.leaves(again)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_optimizer_arguments_are_checked():
    rules = {"w": FTRL(), "v": AdaGrad()}
    with pytest.raises(ValueError, match="alpha"):
        AdaGrad(alpha=0.0)
    with pytest.raises(ValueError, match="optimizer"):
        FactorizationMachine(8, 4, optimizer=FTRL(), threshold=3)  # none for v
    with pytest.raises(ValueError, match="optimizer"):
        FactorizationMachine(8, 4, optimizer={"w": AdaGrad(), "v": AdaGrad()},
                             threshold=3)
    with pytest.raises(ValueError, match="optimizer"):
        FactorizationMachine(8, 4, optimizer={"w": FTRL(), "v": FTRL()},
                             threshold=3)
    with pytest.raises(ValueError, match="optimizer"):
        SparseLinearModel(8, optimizer=rules)
    with pytest.raises(ValueError, match="penalty"):
        FactorizationMachine(8, 4, l2=0.1, optimizer=rules, threshold=3)
    with pytest.raises(ValueError, match="threshold"):
        FactorizationMachine(8, 4, optimizer=rules, threshold=-1)
    with pytest.raises(ValueError, match="threshold"):
        FactorizationMachine(8, 4, optimizer=rules)     # rules want a gate
    assert set(FactorizationMachine(8, 4).init()) == {"w", "v", "b"}
    gated = FactorizationMachine(8, 4, optimizer=rules, threshold=0).init()
    assert set(gated) == {"w", "v", "b", "ftrl", "adagrad", "count"}
    assert gated["count"].dtype == jnp.int32 and gated["count"].shape == (8,)


def test_at_threshold_zero_a_row_opens_once_its_weight_has_left_zero(reference):
    """The gate's two halves apart: with every count over the threshold a
    key's row stays as drawn through the step that first moves its weight,
    and moves in the next step that names it."""
    batches = drawn(7)
    sizes = dict(SIZES, threshold=0, l1=0.0)
    m, params, _ = follow(batches[:3], model(threshold=0, l1=0.0))
    ref = reference.difacto_steps(batches[:3], sizes, rows_of(m))
    want = reference.sampled(ref, np.arange(FEATURES), rows_of(m))
    moved = np.any(np.asarray(params["adagrad"]["n"]["v"]) != 0, axis=1)
    assert np.array_equal(moved, np.any(want["nv"][1:] != 0, axis=1))
    first = np.unique(batches[0]["index"][batches[0]["value"] != 0])
    later = np.unique(np.concatenate(
        [b["index"][b["value"] != 0] for b in batches[1:3]]))
    assert moved.sum() > 3 and not moved[np.setdiff1d(first, later)].any()


def test_step_counters_count_the_open_and_the_opened_rows(reference):
    batches = drawn(8)
    model().flush_step_counters()       # another test's steps in flight
    before = telemetry.snapshot()
    m, params, _ = follow(batches)
    m.flush_step_counters()
    delta = telemetry.counters_delta(before, telemetry.snapshot())
    ref = reference.difacto_steps(batches, SIZES, rows_of(m))
    assert delta["sgd.steps"] == len(batches)
    assert delta["sgd.active_rows"] == sum(ref["opened"])
    assert delta["sgd.touched_rows"] == sum(
        len(np.unique(b["index"][b["value"] != 0])) for b in batches)
    # every key that ended over the threshold crossed it in some step
    assert delta["sgd.activated_rows"] == int(
        np.sum(np.asarray(params["count"]) > SIZES["threshold"]))


@pytest.mark.parametrize("visits", ((16, 32), (8, 24, 40), (1 << 16,)))
def test_one_visit_over_the_lanes_that_hold_the_distinct_keys(monkeypatch,
                                                              visits):
    batches = drawn(9)
    monkeypatch.setattr(common, "TOUCHED_ROWS_VISITS", (1 << 20,))
    _m, whole, losses = follow(batches, model.__wrapped__())
    monkeypatch.setattr(common, "TOUCHED_ROWS_VISITS", visits)
    _m, some, again = follow(batches, model.__wrapped__())
    assert losses == again
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(some)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# (e) ------------------------------------------------------------------------
def walk(tmp_path, seed=2 ** 31 + 77):
    cell = harness.load_cell(ROOT / "benchmark", CELL, seed, rehearse=True)
    cell.cache_dir = tmp_path
    generator = run.load_module("traffic", cell.generator)
    reference = run.load_module("references", cell.reference)
    spans = harness.Spans()
    state = generator.setup(cell, spans)
    generator.window(state, 0.2, spans)
    return cell, generator, reference, state


def test_new_cell_rehearses_on_the_cpu(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                   "--seconds", "0.3", "--rehearse-cpu", "--control", "1"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["attempted"] >= 1


@pytest.mark.parametrize("seed", (2 ** 31 + 77, 99))
def test_rehearsal_walk_is_sound_and_its_control_is_not(tmp_path, seed):
    cell, generator, reference, state = walk(tmp_path, seed)
    limits = cell.config["tolerance"]["limits"]
    out = generator.check(state, reference, control=1)
    sound = {c["name"]: c["value"] for c in out
             if not c["name"].startswith("control.")}
    assert set(sound) == set(limits)
    assert all(sound[k] <= limits[k] for k in limits), sound
    failed = {c["name"][len("control."):] for c in out
              if c["name"].startswith("control.")
              and c["value"] > limits[c["name"][len("control."):]]}
    assert {"z_rel_err", "n_rel_err", "nv_rel_err", "live_z_rel_err",
            "live_n_rel_err", "live_nv_rel_err"} <= failed, out
    generator.teardown(state)


def test_a_batch_delivered_twice_is_noticed(tmp_path):
    cell, generator, reference, state = walk(tmp_path)
    state["tally"] = dict(state["tally"],
                          rows=state["tally"]["rows"] + jnp.uint32(256))
    got = {c["name"]: c["value"] for c in generator.check(state, reference)}
    assert got["delivery_mismatch"] == 1 and got["count_mismatch"] == 0
    generator.teardown(state)


def test_a_step_taken_twice_is_a_count_mismatch(tmp_path):
    """The counts are the delivery's own books inside the model."""
    cell, generator, reference, state = walk(tmp_path)
    real = state["model"].train_step

    def twice(params, batch):
        params, _ = real(params, batch)
        return real(params, batch)
    state["model"].train_step = twice
    got = {c["name"]: c["value"] for c in generator.check(state, reference)}
    assert got["live_count_mismatch"] > 0 and got["count_mismatch"] == 0
    generator.teardown(state)


# (f) the spread ---------------------------------------------------------------


def plain_spread(features: int, calls: list):
    """``spread_by_key`` as the plain gather it replaces: a table rebuilt
    from the compact columns, read at every entry's key."""
    def spread(runs, columns):
        calls.append(len(columns))
        n = runs.order.shape[0]
        key = jnp.zeros(n, jnp.int32).at[runs.order].set(runs.sorted_keys)
        return tuple(jnp.where(key < features, jnp.zeros(
            features + 1, c.dtype).at[runs.keys[:c.shape[0]]].set(
                c, mode="drop")[key], 0) for c in columns)
    return spread


@pytest.mark.parametrize("lanes", common.TOUCHED_ROWS_VISITS + (655360,))
def test_rows_of_16_floats_reach_their_entries_by_the_rank_the_spread_carries(
        lanes):
    """At every candidate of ``TOUCHED_ROWS_VISITS``: a key's rank among
    the distinct keys rides the spread as one more column, and an entry's
    row, gathered by it out of the candidate's compact ``[lanes, 16]``
    rows, is ``table[index]`` bit for bit on every live lane."""
    from dmlc_core_tpu.ops.sparse import spread_by_key
    entries, features, width = 655360, 1 << 20, 16
    rng = np.random.default_rng(lanes)
    distinct = min(lanes - 1, entries * 5 // 6)
    ids = rng.choice(features, size=distinct, replace=False)
    pick = rng.integers(0, distinct // 3, entries)
    pick[:distinct] = np.arange(distinct)
    live = rng.random(entries) < 0.9
    live[:distinct] = True
    order = rng.permutation(entries)
    index, live = ids[pick][order].astype(np.int32), live[order]

    @jax.jit
    def both(index, live, seed):
        table = jax.random.normal(seed, (features, width), jnp.float32)
        keys, _, count, runs = reduce_by_key(index, live, (), features,
                                             runs=True)
        compact = table.at[keys[:lanes]].get(
            mode="fill", fill_value=0, unique_indices=True,
            indices_are_sorted=True)
        (rank,) = spread_by_key(runs, (jnp.arange(entries, dtype=jnp.int32),))
        got = compact.at[jnp.where(live, rank, entries)].get(
            mode="fill", fill_value=0)
        return count, rank, got, table[index]
    count, rank, got, want = both(index, live, jax.random.PRNGKey(lanes))
    assert int(count) == distinct
    assert np.array_equal(np.asarray(rank)[live], np.searchsorted(
        np.unique(index[live]), index[live]))
    got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got[live].view(np.uint32),
                          want[live].view(np.uint32))
    assert not got[~live].any() and (~live).sum() > 1000


def test_three_steps_with_the_spread_patched_to_the_plain_gather(monkeypatch):
    """``w``, the gate and the rank ride the spread; with a gather an entry
    in its place the same program leaves the same tables bit for bit, the
    counts and the shut rows among them."""
    from dmlc_core_tpu.ops import sparse
    batches = drawn(10)[:3]
    m, through, losses = follow(batches, model.__wrapped__())
    calls = []
    monkeypatch.setattr(sparse, "spread_by_key",
                        plain_spread(FEATURES, calls))
    _m, plain, again = follow(batches, model.__wrapped__())
    assert calls and set(calls) == {3} and losses == again
    assert np.any(np.asarray(through["adagrad"]["n"]["v"]) != 0)
    for a, b in zip(jax.tree.leaves(through), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a).view(np.uint32),
                              np.asarray(b).view(np.uint32))


def test_a_restored_w_without_its_state_enters_the_first_steps_margins():
    """A scorer's snapshot holds ``w``, ``v``, ``b`` and the counts, not the
    rules' state: training on from it, the first step's margins (and its
    gate, ``w != 0``) read the restored table a distinct key."""
    m = model.__wrapped__()
    batch = drawn(4)[0]
    params = m.init(3)
    rng = np.random.default_rng(5)
    params["w"] = jnp.asarray(rng.normal(size=FEATURES), jnp.float32)
    params["count"] = jnp.full(FEATURES, SIZES["threshold"], jnp.int32)
    after = dict(params, count=params["count"] + jnp.asarray(np.bincount(
        batch["index"][batch["value"] != 0], minlength=FEATURES), jnp.int32))
    want = float(m.loss(after, padded(batch)))
    before = telemetry.snapshot()
    _m, got, (loss,) = follow([batch], m, params)
    m.flush_step_counters()
    delta = telemetry.counters_delta(before, telemetry.snapshot())
    assert loss == pytest.approx(want, rel=1e-6)
    assert abs(loss - np.log(2)) > 0.05
    # every touched key's gate is open: its count passed, its weight is set
    assert delta["sgd.active_rows"] == delta["sgd.touched_rows"] > 10
    assert delta["sgd.spread_entries"] == int(np.sum(batch["value"] != 0))
