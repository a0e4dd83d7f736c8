"""The touched-rows step (``models/common.py``) under FTRL-Proximal, held
against the ``criteo-tb-ftrl`` configuration's plain reference (numpy
float64, Algorithm 1 of McMahan et al. 2013) at small sizes on the CPU; the
plain SGD step of the three margin families as it was; the new cell's
rehearsal."""
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

from benchmark import run  # noqa: E402
from dmlc_core_tpu import checkpoint, telemetry  # noqa: E402
from dmlc_core_tpu.data.staging import PaddedBatch  # noqa: E402
from dmlc_core_tpu.models import (FactorizationMachine,  # noqa: E402
                                  FieldAwareFactorizationMachine,
                                  SparseLinearModel)
from dmlc_core_tpu.models.common import FTRL  # noqa: E402
from dmlc_core_tpu.ops.sparse import reduce_by_key  # noqa: E402

ROWS, FEATURES = 32, 256
SIZES = {"alpha": 0.1, "beta": 1.0, "l1": 0.3, "l2": 0.01,
         "objective": "logistic", "batch_size": ROWS}
CELL = "criteo-tb-ftrl.stream-train"


@pytest.fixture(scope="module")
def reference():
    return run.load_module("references", "criteo-tb-ftrl")


def model(**over) -> SparseLinearModel:
    s = dict(SIZES, **over)
    return SparseLinearModel(FEATURES, optimizer=FTRL(
        alpha=s["alpha"], beta=s["beta"], l1=s["l1"], l2=s["l2"]))


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


def drawn(seed: int, steps: int = 4, keys: int = 40) -> list:
    """Minibatches in which keys repeat inside a row and across rows, some
    entries hold 0 and some rows weigh 0 (and two rows hold nothing)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        counts = rng.integers(0, 9, ROWS)
        counts[:2] = 0
        entries = int(counts.sum())
        out.append({"row": np.repeat(np.arange(ROWS), counts),
                    "index": rng.integers(0, keys, entries) * 5,
                    "value": rng.choice([0.0, 1.0, 2.0, -0.5], entries),
                    "label": rng.integers(0, 2, ROWS),
                    "weight": rng.choice([0.0, 1.0, 2.0], ROWS)})
    return out


def state_of(params: dict) -> dict:
    """``w``, ``z``, ``n`` with the bias first, as the reference samples."""
    f = params["ftrl"]
    return {name: np.concatenate([[float(b)], np.asarray(t)]) for name, b, t
            in (("w", params["b"], params["w"]),
                ("z", f["z"]["b"], f["z"]["w"]),
                ("n", f["n"]["b"], f["n"]["w"]))}


def follow(batches, m=None, params=None):
    m = m or model()
    params = m.init() if params is None else params
    losses = []
    for b in batches:
        params, loss = m.train_step(params, padded(b))
        losses.append(float(loss))
    return m, params, losses


@pytest.mark.parametrize("seed", (0, 1, 2))
def test_step_follows_the_reference(reference, seed):
    batches = drawn(seed)
    _m, params, losses = follow(batches)
    ref = reference.ftrl_steps(batches, SIZES)
    want = reference.sampled(ref, np.arange(FEATURES))
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-6)
    got = state_of(params)
    for name in ("z", "n"):
        np.testing.assert_allclose(got[name], want[name], rtol=5e-6,
                                   atol=1e-6, err_msg=name)
    np.testing.assert_allclose(got["w"], want["w"], atol=2e-7)
    assert np.array_equal(got["w"] == 0, want["w"] == 0)
    # more than the threshold's worth of keys moved, and some stayed at 0
    assert 5 < np.count_nonzero(got["w"]) < np.count_nonzero(got["n"])


def test_compare_passes_the_program_and_fails_its_control(reference):
    """The cell's own ``compare`` at a tiny size, three steps from zero
    state and a fourth from the state they left: sound against the step's
    numbers, and its bfloat16 control outside the configuration's limits."""
    rng = np.random.default_rng(7)
    index = (rng.integers(0, 60, (4 * ROWS, 5)) * 4).astype(np.int32)
    label = rng.integers(0, 2, 4 * ROWS)
    batches = list(reference.dense_batches(label, index, ROWS))
    m, params, losses = follow(batches[:3])
    ids = np.unique(index[:3 * ROWS])[:64]
    got = {k: v[np.concatenate([[0], ids + 1])]
           for k, v in state_of(params).items()}
    got["losses"] = losses
    got["untouched"] = np.zeros((8, 3))
    keys = np.unique(index[3 * ROWS:])

    def at_keys(params):
        return np.stack([state_of(params)[k][np.concatenate([[0], keys + 1])]
                         for k in ("w", "z", "n")], axis=1)
    live = {"label": label[3 * ROWS:], "index": index[3 * ROWS:],
            "keys": keys, "before": at_keys(params)}
    _m, params, (live["loss"],) = follow(batches[3:], m, params)
    live["after"] = at_keys(params)
    label, index = label[:3 * ROWS], index[:3 * ROWS]
    out = {c["name"]: c["value"] for c in reference.compare(
        got, label, index, ids, SIZES, control=True, live=live)}
    assert out["live_loss_rel_err"] < 1e-5 > out["loss_rel_err"]
    limits = json.loads((ROOT / "benchmark/configs/criteo-tb-ftrl.json")
                        .read_text())["tolerance"]["limits"]
    for name, limit in limits.items():
        if name != "delivery_mismatch":
            assert out[name] <= limit, (name, out[name])
    assert any(out[f"control.{name}"] > limit for name, limit in
               limits.items() if f"control.{name}" in out), out
    got["untouched"][3, 1] = 1e-30
    again = {c["name"]: c["value"] for c in reference.compare(
        got, label, index, ids, SIZES)}
    assert again["untouched_changed"] == 1


def test_repeated_key_is_updated_once_with_the_summed_gradient():
    """Two batches that differ only in how entries repeat (one entry of
    value 2, or two of value 1, in the same row; the lanes in another
    order) leave the same state; the counter counts distinct keys."""
    one = {"row": np.array([0, 0, 1, 1, 2]), "index": np.array([3, 9, 3, 7, 9]),
           "value": np.array([2.0, 1.0, 1.0, 4.0, 2.0]),
           "label": np.array([1, 0, 1]), "weight": np.ones(3)}
    two = {"row": np.array([0, 0, 0, 1, 1, 1, 1, 2, 2]),
           "index": np.array([9, 3, 3, 7, 3, 7, 7, 9, 9]),
           "value": np.array([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 2.0, 1.0, 1.0]),
           "label": np.array([1, 0, 1]), "weight": np.ones(3)}
    m = model()
    before = telemetry.counter_get("sgd.touched_rows")
    steps = telemetry.counter_get("sgd.steps")
    _m, a, la = follow([one, one], m)
    m.flush_step_counters()
    assert telemetry.counter_get("sgd.touched_rows") - before == 2 * 3
    assert telemetry.counter_get("sgd.steps") - steps == 2
    _m, b, lb = follow([two, two], m)
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    for name, x in state_of(a).items():
        np.testing.assert_allclose(x, state_of(b)[name], rtol=1e-6,
                                   err_msg=name)


def test_reduce_by_key_sums_each_distinct_live_key_once():
    rng = np.random.default_rng(3)
    index = rng.integers(0, 50, 700).astype(np.int32)
    live = rng.random(700) < 0.8
    g = rng.normal(size=700).astype(np.float32)
    keys, (sums,), count = jax.jit(
        lambda i, v, x: reduce_by_key(i, v, (x,), 1000))(index, live, g)
    keys, sums = np.asarray(keys), np.asarray(sums)
    held = keys < 1000
    assert int(count) == held.sum() == len(np.unique(index[live]))
    assert len(np.unique(keys)) == len(keys)        # the promise to scatter
    for k, s in zip(keys[held], sums[held]):
        np.testing.assert_allclose(s, g[live & (index == k)].sum(),
                                   rtol=1e-5, atol=1e-6)


def test_untouched_rows_stay_bit_for_bit():
    """From a state that is nowhere zero, a step leaves every coordinate no
    live entry names — zero-valued lanes and padding name none — exactly
    as it was, in all three tables."""
    m = model()
    rng = np.random.default_rng(11)
    z = rng.normal(size=FEATURES).astype(np.float32) * 3
    n = rng.random(FEATURES).astype(np.float32) * 9
    params = m.init()
    params["ftrl"]["z"]["w"], params["ftrl"]["n"]["w"] = map(jnp.asarray, (z, n))
    params["w"] = m.optimizer.weights(jnp.asarray(z), jnp.asarray(n))
    w = np.asarray(params["w"])
    batch = drawn(5, steps=1)[0]
    named = np.unique(batch["index"][batch["value"] != 0])
    _m, after, _ = follow([batch], m, params)
    rest = np.setdiff1d(np.arange(FEATURES), named)
    assert len(rest) > 100 and len(named) > 10
    for got, was in ((after["w"], w), (after["ftrl"]["z"]["w"], z),
                     (after["ftrl"]["n"]["w"], n)):
        got = np.asarray(got)
        assert np.array_equal(got[rest].view(np.uint32),
                              was[rest].view(np.uint32))
        assert not np.array_equal(got[named], was[named])


def test_w_is_the_closed_form_of_z_and_n_after_every_step(reference):
    m, params = model(), None
    for batch in drawn(9, steps=5):
        m, params, _ = follow([batch], m, params)
        got = state_of(params)
        np.testing.assert_allclose(
            got["w"], reference.weights(got["z"], got["n"], SIZES),
            rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(
            np.asarray(params["w"]), np.asarray(m.optimizer.weights(
                params["ftrl"]["z"]["w"], params["ftrl"]["n"]["w"])),
            rtol=1e-6, atol=1e-9)


def test_predict_and_checkpoint_round_trip_with_the_state(tmp_path):
    batches = drawn(4)
    m, params, _ = follow(batches[:2])
    p = np.asarray(m.predict(params, padded(batches[2])))
    assert np.all((p > 0) & (p < 1)) and p.std() > 0
    uri = str(tmp_path / "ftrl.ckpt")
    assert checkpoint.save(params, uri) == 6
    loaded = checkpoint.load(uri, like=m.init())
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        p, np.asarray(m.predict(loaded, padded(batches[2]))))
    # training goes on from the loaded state as from the live one
    _m, live, l1 = follow(batches[2:], m, params)
    _m, again, l2 = follow(batches[2:], m, loaded)
    assert l1 == l2
    assert np.array_equal(np.asarray(live["w"]), np.asarray(again["w"]))


def test_optimizer_arguments_are_checked():
    with pytest.raises(ValueError, match="alpha"):
        FTRL(alpha=0.0)
    with pytest.raises(ValueError, match="optimizer"):
        SparseLinearModel(8, optimizer="adagrad")
    with pytest.raises(ValueError, match="penalty"):
        SparseLinearModel(8, l2=0.1, optimizer=FTRL())
    assert set(SparseLinearModel(8).init()) == {"w", "b"}


def sgd_case(family: str):
    rows, per, features, fields = 16, 3, 64, 3
    rng = np.random.default_rng(21)
    batch = PaddedBatch(
        label=jnp.asarray(rng.integers(0, 2, rows), jnp.float32),
        weight=jnp.asarray(rng.choice([0.0, 1.0, 2.0], rows), jnp.float32),
        row_ptr=jnp.arange(rows + 1, dtype=jnp.int32) * per,
        index=jnp.asarray(rng.integers(0, features, rows * per), jnp.int32),
        value=jnp.asarray(rng.random(rows * per), jnp.float32),
        num_rows=jnp.asarray(np.int32(rows)),
        field=jnp.asarray(np.tile(np.arange(fields, dtype=np.int32), rows)))
    m = {"linear": lambda: SparseLinearModel(features, l2=0.01,
                                             learning_rate=0.2),
         "fm": lambda: FactorizationMachine(features, num_factors=4, l2=0.01,
                                            learning_rate=0.2),
         "ffm": lambda: FieldAwareFactorizationMachine(
             features, num_fields=fields, num_factors=2,
             learning_rate=0.2)}[family]()
    return m, batch


@pytest.mark.parametrize("family", ("linear", "fm", "ffm"))
def test_plain_sgd_step_is_what_it_was(family):
    """Without an optimizer ``train_step`` is ``p - lr * grad(loss)`` over
    every parameter, and the parameters carry no state."""
    m, batch = sgd_case(family)
    params = m.init(3)
    if family == "linear":       # zero margins sit on logistic_nll's kink
        params["w"] = params["w"] + 0.01
    assert "ftrl" not in params
    loss, grads = jax.value_and_grad(m.loss)(params, batch)
    want = jax.tree.map(lambda p, g: p - m.learning_rate * g, params, grads)
    got, got_loss = m.train_step(jax.tree.map(jnp.copy, params), batch)
    assert float(got_loss) == pytest.approx(float(loss), rel=1e-6)
    assert set(got) == set(params)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-8, err_msg=k)


def test_reference_on_a_hand_worked_coordinate(reference):
    """One key in two rows, from zero state, alpha 0.1, beta 1, l1 0.3,
    l2 0: the first step sees margin 0, so g = (0.5 - 1) + (0.5 - 0) * 2 =
    0.5 for the key (values 1 and 2), n = 0.25, z = 0.5, and
    w = -(0.5 - 0.3) / ((1 + 0.5) / 0.1) = -0.2 / 15."""
    sizes = dict(SIZES, l2=0.0)
    batch = {"row": np.array([0, 1]), "index": np.array([6, 6]),
             "value": np.array([1.0, 2.0]), "label": np.array([1, 0]),
             "weight": np.ones(2)}
    out = reference.ftrl_steps([batch], sizes)
    assert out["losses"] == [pytest.approx(np.log(2.0))]
    assert list(out["keys"]) == [reference.BIAS, 6]
    assert out["z"][1] == pytest.approx(0.5)
    assert out["n"][1] == pytest.approx(0.25)
    assert out["w"][1] == pytest.approx(-0.2 / 15)
    # the bias: g = 0, nothing moves
    assert (out["z"][0], out["n"][0], out["w"][0]) == (0.0, 0.0, 0.0)
    # a second step: the margins are w and 2 w, sigma = (sqrt(n + g^2) -
    # sqrt(n)) / alpha, z += g - sigma w
    w = -0.2 / 15
    p = 1 / (1 + np.exp(-np.array([w, 2 * w])))
    g = (p[0] - 1) + 2 * p[1]
    sigma = (np.sqrt(0.25 + g * g) - 0.5) / 0.1
    two = reference.ftrl_steps([batch, batch], sizes)
    assert two["z"][1] == pytest.approx(0.5 + g - sigma * w)
    assert two["n"][1] == pytest.approx(0.25 + g * g)
    # the program agrees on the same coordinate
    _m, params, _ = follow([batch, batch], model(l2=0.0))
    assert float(params["ftrl"]["z"]["w"][6]) == pytest.approx(
        two["z"][1], rel=1e-6)
    assert float(params["w"][6]) == pytest.approx(two["w"][1], rel=1e-5)


def test_new_cell_rehearses_on_the_cpu(capsys, monkeypatch):
    """``--rehearse-cpu`` walks the cell end to end: the file, the staging
    iterator, the step, the reference, the control; it reports no number."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                   "--seconds", "0.3", "--rehearse-cpu", "--control", "1"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["attempted"] >= 1


def test_generator_draws_its_columns_and_its_file():
    gen = run.load_module("traffic", "stream_ftrl")
    features = 2 ** 29
    vocab = gen.column_vocabulary(features, 39)
    assert vocab.sum() == features and list(vocab[:13]) == [64] * 13
    label, index = gen.draw_rows(2 ** 31 + 9, 0, 4096, features, 39, 0.26)
    again = gen.draw_rows(2 ** 31 + 9, 0, 4096, features, 39, 0.26)
    assert np.array_equal(index, again[1]) and np.array_equal(label, again[0])
    other = gen.draw_rows(2 ** 31 + 9, 1, 4096, features, 39, 0.26)
    assert not np.array_equal(index, other[1])
    assert index.min() >= 0 and index.max() < features
    assert 0.15 < label.mean() < 0.4
    # keys repeat inside a batch: the numeric columns hold 64 values each
    assert len(np.unique(index)) < 0.5 * index.size
    text = gen.libsvm_text(label[:3], index[:3], features).tobytes().decode()
    assert len(text) == gen.libsvm_bytes(3, 39, features)
    first = text.splitlines()[0].split()
    assert int(first[0]) == label[0] and len(first) == 40
    assert [int(e.split(":")[0]) for e in first[1:]] == list(index[0])
    assert all(e.endswith(":1") for e in first[1:])
    # the inverse CDF puts rank 0 first and never leaves the vocabulary
    ranks = gen.zipf_rank(np.linspace(0, 1, 1001)[:-1], 1000)
    assert ranks[0] == 0 and ranks.max() <= 999
    assert np.all(np.diff(ranks) >= 0)
    assert np.mean(ranks == 0) > np.mean(ranks == 1) > np.mean(ranks == 2)


@pytest.mark.parametrize("visits", ((16, 32), (8, 24, 40), (1 << 16,)))
def test_one_visit_over_the_lanes_that_hold_the_distinct_keys(
        reference, monkeypatch, visits):
    """The batches name 20-40 distinct keys on 80-odd lanes: whichever run
    of lanes the visit takes (the second of 16 and 32, the third of three,
    all of them), the state is the same bit for bit, and the reference's."""
    from dmlc_core_tpu.models import common
    batches = drawn(6)
    distinct = [len(np.unique(b["index"][b["value"] != 0])) for b in batches]
    assert min(distinct) > 16 and max(distinct) > 32
    monkeypatch.setattr(common, "TOUCHED_ROWS_VISITS", (1 << 20,))
    _m, whole, losses = follow(batches)
    monkeypatch.setattr(common, "TOUCHED_ROWS_VISITS", visits)
    _m, some, again = follow(batches)
    assert losses == again
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(some)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    want = reference.sampled(reference.ftrl_steps(batches, SIZES),
                             np.arange(FEATURES))
    np.testing.assert_allclose(state_of(some)["z"], want["z"], rtol=5e-6,
                               atol=1e-6)


@pytest.mark.parametrize("seed", (0, 1))
def test_csr_row_sums_is_segment_sum_forward_and_backward(seed):
    """Rows of 0 to 8 lanes (empty ones first, last and in between) and
    padding lanes after the last: the sums, and the gradient an entry."""
    from dmlc_core_tpu.data.staging import csr_row_ids
    from dmlc_core_tpu.ops.sparse import csr_row_spread, csr_row_sums
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 9, 40)
    counts[[0, 7, 8, 39]] = 0
    row_ptr = jnp.asarray(np.concatenate([[0], np.cumsum(counts)]), jnp.int32)
    lanes = int(counts.sum()) + 11
    row_id = csr_row_ids(row_ptr, lanes)
    contrib = jnp.asarray(rng.normal(size=lanes), jnp.float32).at[
        int(counts.sum()):].set(0.0)        # padding lanes hold 0
    want = jax.ops.segment_sum(contrib, row_id, num_segments=40)
    got = jax.jit(csr_row_sums)(contrib, row_id, row_ptr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    ct = jnp.asarray(rng.normal(size=40), jnp.float32)
    spread = np.asarray(csr_row_spread(ct, row_id, row_ptr))
    real = int(counts.sum())
    assert np.array_equal(spread[:real], np.asarray(ct)[np.asarray(row_id)[:real]])
    grad = jax.grad(lambda c: jnp.sum(csr_row_sums(c, row_id, row_ptr) * ct))(
        contrib)
    assert np.array_equal(np.asarray(grad)[:real], spread[:real])


# ---- the spread: a distinct key's values carried to its entries -------------


def lanes_that_name(rng, distinct: int, lanes: int, features: int):
    """``lanes`` entry lanes whose live ones name exactly ``distinct`` keys
    of ``features``: most keys on several entries, some on one, dead lanes
    in between (their index names a key that is live elsewhere)."""
    ids = rng.choice(features, size=distinct, replace=False)
    live = rng.random(lanes) < 0.9
    live[:distinct] = True
    pick = rng.integers(0, max(distinct // 2, 1), lanes)
    pick[:distinct] = np.arange(distinct)
    order = rng.permutation(lanes)
    return ids[pick][order].astype(np.int32), live[order]


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


def candidates():
    from dmlc_core_tpu.models.common import TOUCHED_ROWS_VISITS
    return TOUCHED_ROWS_VISITS + (655360,)


@pytest.mark.parametrize("lanes", candidates())
def test_spread_by_key_is_the_gather_of_the_table_bit_for_bit(lanes):
    """At every candidate of ``TOUCHED_ROWS_VISITS`` (and the entry lanes
    after them), with as many distinct keys as only that candidate holds:
    a float32 table (``-0.0`` among its values) and an int32 one, read at
    the candidate's lanes of distinct keys and spread, are ``table[index]``
    on every live lane and 0 on every dead one."""
    from dmlc_core_tpu.ops.sparse import spread_by_key
    entries, features = 655360, 1 << 20
    rng = np.random.default_rng(lanes)
    distinct = min(lanes - 3, entries * 5 // 6)
    index, live = lanes_that_name(rng, distinct, entries, features)
    table = rng.normal(size=features).astype(np.float32)
    table[rng.random(features) < 0.2] = -0.0
    counts = rng.integers(-5, 1 << 30, features).astype(np.int32)

    @jax.jit
    def both(index, live, table, counts):
        keys, _, count, runs = reduce_by_key(index, live, (), features,
                                             runs=True)
        read = dict(mode="fill", fill_value=0, unique_indices=True,
                    indices_are_sorted=True)
        return count, spread_by_key(runs, (
            table.at[keys[:lanes]].get(**read),
            counts.at[keys[:lanes]].get(**read))), (table[index],
                                                    counts[index])
    count, got, want = both(index, live, table, counts)
    assert int(count) == distinct == len(np.unique(index[live]))
    on_one = np.bincount(index[live], minlength=features)
    assert (on_one == 1).sum() > 100 and on_one.max() > 3
    assert np.signbit(np.asarray(want[0])[live]
                      [np.asarray(want[0])[live] == 0]).any()
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == (entries,)
        assert np.array_equal(g[live].view(np.uint32),
                              w[live].view(np.uint32))
        assert not g[~live].any() and (~live).sum() > 1000


def test_run_fill_carries_a_runs_last_lane_by_select():
    from dmlc_core_tpu.ops.sparse import run_fill
    keys = jnp.asarray([3, 3, 3, 3, 3, 7, 9, 9, 9, 9, 9, 9, 9, 12, 12])
    x = jnp.asarray([1, 2, 3, 4, -0.0, 5, 6, 6, 6, 6, 6, 6, 7, 8, np.inf],
                    jnp.float32)
    i = jnp.arange(15, dtype=jnp.int32)
    fx, fi = run_fill(keys, x, i)
    assert np.array_equal(np.asarray(fi), [4] * 5 + [5] + [12] * 7 + [14] * 2)
    assert np.array_equal(np.asarray(fx).view(np.uint32),
                          np.asarray(x)[np.asarray(fi)].view(np.uint32))


def test_three_steps_with_the_spread_patched_to_the_plain_gather(monkeypatch):
    """The rest of the step is the same program, so the tables after three
    steps are the same bit for bit whether the entries' rows came through
    the sorts or through a gather an entry."""
    from dmlc_core_tpu.ops import sparse
    batches = drawn(12, steps=3)
    _m, through, losses = follow(batches)
    calls = []
    monkeypatch.setattr(sparse, "spread_by_key",
                        plain_spread(FEATURES, calls))
    _m, plain, again = follow(batches, model())
    assert calls and set(calls) == {1}      # a trace a shape: ``w`` rides
    assert losses == again and losses[0] != losses[2]
    for a, b in zip(jax.tree.leaves(through), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a).view(np.uint32),
                              np.asarray(b).view(np.uint32))


def test_a_restored_w_without_its_state_enters_the_first_steps_margins():
    """A snapshot leaves the state out: the restored ``w`` is not the
    closed form of its zero ``(z, n)``, and the step's margins read the
    TABLE, a distinct key."""
    m = model()
    batch = drawn(21, steps=1)[0]
    params = m.init()
    rng = np.random.default_rng(2)
    w = rng.normal(size=FEATURES).astype(np.float32)
    params["w"] = jnp.asarray(w)
    want = float(m.loss(params, padded(batch)))
    _m, _p, (loss,) = follow([batch], m, params)
    assert loss == pytest.approx(want, rel=1e-6)
    assert abs(loss - np.log(2)) > 0.05      # what a zero table would read


def test_spread_entries_counts_the_live_entries():
    batches = drawn(14, steps=3)
    model().flush_step_counters()       # another test's steps in flight
    before = telemetry.snapshot()
    m, _p, _l = follow(batches)
    m.flush_step_counters()
    delta = telemetry.counters_delta(before, telemetry.snapshot())
    live = sum(int(np.sum(b["value"] != 0)) for b in batches)
    assert delta["sgd.spread_entries"] == live > 100
    assert delta["sgd.steps"] == 3
