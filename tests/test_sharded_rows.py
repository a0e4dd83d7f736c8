"""The touched-rows step over tables sharded by key
(``models/common.py:_sharded_rows_step``, ``parallel/meshplan.py``'s exchange
and key layout), on the virtual CPU mesh: against the
``criteo-tb-difacto-ps4`` configuration's plain reference (numpy float64 on
the global minibatch over an unsharded table) and against ONE
``_touched_rows_step`` on the same rows; the exchange's capacity candidates;
the linear model; scoring; the plan's exchange and its counters; the staging
iterator's deal of a global batch."""
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import run  # noqa: E402
from dmlc_core_tpu import DeviceStagingIter, telemetry  # noqa: E402
from dmlc_core_tpu.data.staging import PaddedBatch  # noqa: E402
from dmlc_core_tpu.models import (FactorizationMachine,  # noqa: E402
                                  SparseLinearModel)
from dmlc_core_tpu.models import common  # noqa: E402
from dmlc_core_tpu.models.common import FTRL, SGD, AdaGrad  # noqa: E402
from dmlc_core_tpu.parallel import MeshPlan  # noqa: E402

FEATURES, FACTORS, ROWS, COLUMNS = 1024, 4, 64, 5
SIZES = {"alpha": 0.1, "beta": 1.0, "l1": 0.3, "l2": 0.01,
         "objective": "logistic", "batch_size": ROWS, "workers": 1,
         "num_factors": FACTORS, "alpha_v": 0.05, "beta_v": 1.0,
         "l2_v": 1e-3, "threshold": 2}
#: (devices, hosts): a flat plan of four and of eight, and two hosts of four
PLANS = ((4, None), (8, None), (8, 2))


@pytest.fixture(scope="module")
def reference():
    return run.load_module("references", "criteo-tb-difacto-ps4")


def plan_of(devices: int, hosts=None) -> MeshPlan:
    return MeshPlan.build(jax.devices()[:devices], hosts=hosts)


def rules() -> dict:
    s = SIZES
    return {"w": FTRL(alpha=s["alpha"], beta=s["beta"], l1=s["l1"],
                      l2=s["l2"]),
            "v": AdaGrad(alpha=s["alpha_v"], beta=s["beta_v"], l2=s["l2_v"])}


def machine(plan=None) -> FactorizationMachine:
    return FactorizationMachine(FEATURES, FACTORS, optimizer=rules(),
                                threshold=SIZES["threshold"], mesh=plan)


def drawn(seed: int, steps: int = 5, lo: int = 0, hi: int = 200,
          stride: int = 5) -> list:
    """Global minibatches of ``ROWS`` rows of ``COLUMNS`` entries each, as the
    reference takes them: keys repeat inside a row, across rows and across
    the workers' shares; some entries hold 0, some rows weigh 0."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        out.append({
            "row": np.repeat(np.arange(ROWS), COLUMNS),
            "index": (rng.integers(lo, hi, ROWS * COLUMNS) * stride
                      % FEATURES),
            "value": rng.choice([0.0, 1.0, 1.0, 2.0, -0.5], ROWS * COLUMNS),
            "label": rng.integers(0, 2, ROWS),
            "weight": rng.choice([0.0, 1.0, 2.0], ROWS)})
    return out


def staged(b: dict, plan=None) -> PaddedBatch:
    """A reference batch as the staged batch; under a plan, laid over its
    chips as ``DeviceStagingIter(sharding=plan.data_sharding())`` lays it."""
    leaves = {"label": np.asarray(b["label"], np.float32),
              "weight": np.asarray(b["weight"], np.float32),
              "row_ptr": np.arange(ROWS + 1, dtype=np.int32) * COLUMNS,
              "index": np.asarray(b["index"], np.int32),
              "value": np.asarray(b["value"], np.float32),
              "num_rows": np.int32(ROWS)}
    if plan is None:
        return PaddedBatch(**{k: jnp.asarray(v) for k, v in leaves.items()})
    by, every = plan.data_sharding(), plan.replicated_sharding()
    return PaddedBatch(**{k: jax.device_put(
        v, every if k in ("row_ptr", "num_rows") else by)
        for k, v in leaves.items()})


def follow(m, batches, params=None, seed=3):
    params = m.init(seed) if params is None else params
    losses = []
    for b in batches:
        params, loss = m.train_step(params, staged(b, m.mesh))
        losses.append(float(loss))
    m.flush_step_counters()
    return params, losses


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


def drawn_rows(seed=3):
    rows = np.asarray(machine().init(seed)["v"], np.float64)
    return lambda ids: rows[ids]


def sgd_counters(before: dict) -> dict:
    return {k: v for k, v in telemetry.counters_delta(
        before, telemetry.snapshot()).items()
        if k.startswith(("sgd.", "mesh."))}


# (a) the step -----------------------------------------------------------------
@pytest.mark.parametrize("devices,hosts", PLANS)
@pytest.mark.parametrize("seed", (0, 1))
def test_sharded_step_follows_the_reference_on_the_global_minibatch(
        reference, devices, hosts, seed):
    """Five global steps in which keys cross the count threshold on counts
    that only all workers' rows together reach, keys stay under it, and
    weights leave zero: counts, the zero and active sets and the untouched
    ids exact, the state within the one-chip step's tolerances."""
    batches = drawn(seed)
    m = machine(plan_of(devices, hosts))
    params, losses = follow(m, batches)
    ref = reference.difacto_steps(batches, SIZES, drawn_rows())
    want = reference.sampled(ref, np.arange(FEATURES), drawn_rows())
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
    over = got["c"][1:] > SIZES["threshold"]
    assert moved[1:].sum() > 3 and ((got["c"][1:] > 0) & ~over).sum() > 0
    # an id no row names holds zero state and its drawn row bit for bit
    never = got["c"][1:] == 0
    assert never.sum() > FEATURES // 2
    first = state_of(m.init(3))
    for name in ("w", "z", "n", "v", "nv"):
        assert np.array_equal(got[name][1:][never], first[name][1:][never])


@pytest.mark.parametrize("devices,hosts", PLANS)
def test_sharded_step_is_one_step_on_the_concatenated_batch(devices, hosts):
    """Against ONE ``_touched_rows_step`` on the workers' rows together:
    the counts bit for bit, everything else to a key's gradient summed in
    another order."""
    batches = drawn(7)
    whole, losses = follow(machine(), batches)
    parts, again = follow(machine(plan_of(devices, hosts)), batches)
    np.testing.assert_allclose(again, losses, rtol=1e-6)
    assert np.array_equal(np.asarray(parts["count"]),
                          np.asarray(whole["count"]))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(whole),
                            jax.tree.leaves(parts)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-5,
                                   atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
        assert np.array_equal(np.asarray(a) == 0, np.asarray(b) == 0)


def test_a_plan_of_one_device_gives_the_unsharded_steps_tables():
    """The exchanges are the identity there, and nothing stands in for the
    absent chips: every table and the losses bit for bit."""
    batches = drawn(11)
    whole, losses = follow(machine(), batches)
    one, again = follow(machine(plan_of(1)), batches)
    assert again == losses
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(whole),
                            jax.tree.leaves(one)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            jax.tree_util.keystr(path))


def test_init_makes_each_shard_on_its_own_chip_and_draws_the_same_rows():
    plan = plan_of(4)
    params = machine(plan).init(3)
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        want = plan.data_sharding() if leaf.ndim else (
            plan.replicated_sharding())
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
            jax.tree_util.keystr(path))
    assert {s.data.shape for s in params["v"].addressable_shards} == {
        (FEATURES // 4, FACTORS)}
    assert np.array_equal(np.asarray(params["v"]),
                          np.asarray(machine().init(3)["v"]))


@pytest.mark.parametrize("model", ("fm", "linear"))
def test_under_a_plan_one_program_makes_every_table(model, monkeypatch):
    """So that every chip lays its shards out alike (where a table lies sets
    what its reads cost, and the chips wait for each other): nothing is put
    on a chip between one table and the next."""
    plan = plan_of(4)
    m = machine(plan) if model == "fm" else SparseLinearModel(
        FEATURES, optimizer=rules()["w"], mesh=plan)
    programs, real = [], common._tables_on_plan

    def counted(*args):
        programs.append(args)
        return real(*args)
    monkeypatch.setattr(common, "_tables_on_plan", counted)
    params = m.init(2 ** 32 - 1)
    # the seed is the program's one argument, a host scalar: jit's to place
    assert len(programs) == 1 and type(programs[0][2]) is np.uint32
    assert "count" in params or model == "linear"
    # without a plan: table by table, as before (the one-chip cells' programs)
    (machine() if model == "fm" else SparseLinearModel(
        FEATURES, optimizer=rules()["w"])).init(3)
    assert len(programs) == 1


@pytest.mark.parametrize("seed", (-1, 2 ** 32))
def test_under_a_plan_a_seed_is_32_bits(seed):
    with pytest.raises(ValueError, match="seed"):
        machine(plan_of(4)).init(seed)
    machine().init(seed)        # no plan: whatever PRNGKey takes


# (b) the exchange's capacity --------------------------------------------------
@pytest.mark.parametrize("lanes", ((8, 32), (16,), (40, 64)))
def test_keys_past_the_capacity_take_a_wider_candidate_and_none_is_dropped(
        monkeypatch, lanes):
    """Steps whose keys all fall to ONE owner (ids 256 to 511 are chip 1's of
    four) and pass the first capacity, between steps that spread: the tables
    are what the unsharded step gives, key for key, and
    ``sgd.exchange_overflow`` counts the steps that took a wider candidate."""
    spans = ((0, 1024), (256, 512), (0, 24), (300, 340), (0, 1024))
    batches = [drawn(20 + i, 1, lo, hi, 1)[0]
               for i, (lo, hi) in enumerate(spans)]
    whole, losses = follow(machine(), batches)
    monkeypatch.setattr(common, "EXCHANGE_LANES", lanes)
    m = machine(plan_of(4))
    before = telemetry.snapshot()
    parts, again = follow(m, batches)
    counted = sgd_counters(before)
    np.testing.assert_allclose(again, losses, rtol=1e-6)
    assert np.array_equal(np.asarray(parts["count"]),
                          np.asarray(whole["count"]))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parts)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-5,
                                   atol=1e-6)
    # the fullest (worker, owner) pair of each step, counted on the host
    entries = ROWS * COLUMNS // 4
    most = []
    for b in batches:
        live = b["value"] != 0
        most.append(max(
            len(np.unique(b["index"][w * entries:(w + 1) * entries][
                live[w * entries:(w + 1) * entries]
                & (b["index"][w * entries:(w + 1) * entries] // 256 == o)]))
            for w in range(4) for o in range(4)))
    sizes = [c for c in lanes if c < entries] + [entries]
    ran = [min(c for c in sizes if c >= n) for n in most]
    assert counted["sgd.exchange_overflow"] == sum(
        c != sizes[0] for c in ran) > 0
    assert counted["sgd.exchange_lanes"] == sum(ran)
    assert counted["sgd.steps"] == len(batches)
    assert counted["sgd.touched_rows"] == sum(
        len(np.unique(b["index"][b["value"] != 0])) for b in batches)
    assert counted["mesh.alltoall_calls"] == 3 * len(batches)
    # keys and counts, then (w, gate, v) back, then (g_w, g_v) out
    assert counted["mesh.alltoall_bytes"] == sum(
        4 * 4 * c * (2 + (2 + FACTORS) + (1 + FACTORS)) for c in ran)


def test_step_counters_are_the_global_steps(reference):
    batches = drawn(2)
    m = machine(plan_of(4))
    before = telemetry.snapshot()
    follow(m, batches)
    counted = sgd_counters(before)
    ref = reference.difacto_steps(batches, SIZES, drawn_rows())
    assert counted["sgd.steps"] == len(batches)
    assert counted["sgd.active_rows"] == sum(ref["opened"])
    assert counted["sgd.touched_rows"] == sum(
        len(np.unique(b["index"][b["value"] != 0])) for b in batches)
    assert counted["sgd.spread_entries"] == sum(
        int((b["value"] != 0).sum()) for b in batches)
    # the fullest owner holds at least its share and no more than all
    assert (counted["sgd.touched_rows"] / 4 <= counted["sgd.owner_rows"]
            <= counted["sgd.touched_rows"])
    assert counted["sgd.exchange_overflow"] == 0
    # the fullest pair's pmax, the bias and the loss, the counts, the
    # fullest owner: four reductions a step through the plan
    assert counted["mesh.allreduce_calls"] == 4 * len(batches)


def test_an_entry_on_another_chips_lanes_poisons_the_loss():
    """Rows of uneven length put a row's entries on its neighbour's lanes:
    the step says so (NaN), it does not drop them."""
    b = drawn(4, 1)[0]
    batch = staged(b, plan_of(4))
    counts = np.full(ROWS, COLUMNS)
    counts[0], counts[ROWS - 1] = COLUMNS + 3, COLUMNS - 3
    uneven = jax.device_put(
        np.concatenate([[0], np.cumsum(counts)]).astype(np.int32),
        batch.row_ptr.sharding)
    m = machine(plan_of(4))
    _, loss = m.train_step(m.init(3), PaddedBatch(
        label=batch.label, weight=batch.weight, row_ptr=uneven,
        index=batch.index, value=batch.value, num_rows=batch.num_rows))
    assert np.isnan(float(loss))
    _, loss = m.train_step(m.init(3), batch)
    assert np.isfinite(float(loss))


# (c) the linear model, scoring, arguments -------------------------------------
@pytest.mark.parametrize("devices", (4, 8))
def test_the_linear_model_takes_the_same_step_under_ftrl(devices):
    rule = rules()["w"]
    batches = drawn(13)
    whole = SparseLinearModel(FEATURES, optimizer=rule)
    parts = SparseLinearModel(FEATURES, optimizer=rule,
                              mesh=plan_of(devices))
    a, losses = follow(whole, batches)
    b, again = follow(parts, batches)
    assert "count" not in b and b["w"].sharding.is_equivalent_to(
        parts.mesh.data_sharding(), 1)
    np.testing.assert_allclose(again, losses, rtol=1e-6)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=2e-5,
                                   atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    batch = staged(batches[0])
    np.testing.assert_allclose(
        np.asarray(parts.predict(b, staged(batches[0], parts.mesh))),
        np.asarray(whole.predict(a, batch)), atol=1e-6)


@pytest.mark.parametrize("devices,hosts", ((4, None), (8, 2)))
def test_predict_reads_sharded_tables_under_the_gate(devices, hosts):
    """Scoring over tables sharded by key is scoring over the same tables
    on one chip, gate and all: bit for bit."""
    batches = drawn(5, 6)
    m = machine(plan_of(devices, hosts))
    params, _ = follow(m, batches[:5])
    one = machine()
    gathered = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), params)
    batch = staged(batches[5])
    on = (batch.value != 0) & one.active(
        gathered["count"][batch.index], gathered["w"][batch.index])
    assert 0 < int(on.sum()) < int((batch.value != 0).sum())
    want = np.asarray(one.predict(gathered, batch))
    # a batch every chip holds, and one laid over the chips as the feed
    # lays it, through the one jitted scorer
    assert np.array_equal(
        np.asarray(m.predict_bucketed(params, batch)), want)
    assert np.array_equal(np.asarray(m.predict_bucketed(
        params, staged(batches[5], m.mesh))), want)


def test_a_plan_wants_a_rule_that_has_a_sharded_step():
    plan = plan_of(4)
    with pytest.raises(ValueError, match="sharded step"):
        SparseLinearModel(FEATURES, mesh=plan)
    with pytest.raises(ValueError, match="sharded step"):
        SparseLinearModel(FEATURES, optimizer=SGD(), mesh=plan)
    with pytest.raises(TypeError, match="MeshPlan"):
        FactorizationMachine(FEATURES, FACTORS, optimizer=rules(),
                             threshold=2, mesh=plan.mesh)
    with pytest.raises(ValueError, match="does not split"):
        SparseLinearModel(FEATURES + 2, optimizer=rules()["w"], mesh=plan)


# (d) the plan's exchange and key layout ----------------------------------------
@pytest.mark.parametrize("devices,hosts", PLANS)
def test_alltoall_hands_every_shard_what_each_had_for_it(devices, hosts):
    plan = plan_of(devices, hosts)
    n = plan.num_shards
    # sent[s, d]: what shard s has for shard d
    sent = (np.arange(n)[:, None, None] * 1000 + np.arange(n)[None, :, None]
            * 10 + np.arange(3)[None, None, :]).astype(np.int32)

    def body(x):
        got = plan.alltoall(x[0])
        return got[None], plan.alltoall(got)[None], plan.shard_index()[None]

    got, back, place = jax.jit(plan.shard_map(
        body, plan.row_spec, (plan.row_spec,) * 3))(
            jax.device_put(sent, plan.data_sharding()))
    assert np.array_equal(np.asarray(place), np.arange(n))
    assert np.array_equal(np.asarray(got), sent.transpose(1, 0, 2))
    assert np.array_equal(np.asarray(back), sent)


def test_counting_counts_an_exchange_once_a_call_of_its_program():
    plan = plan_of(4)

    @jax.jit
    def program(x):
        return plan.shard_map(
            lambda x: plan.allreduce(jnp.sum(plan.alltoall(
                plan.alltoall(x.reshape(4, -1)).astype(jnp.float32)))),
            plan.row_spec, P())(x)

    x = jax.device_put(np.arange(4 * 4 * 6, dtype=np.int32),
                       plan.data_sharding())
    before = telemetry.snapshot()
    for _ in range(3):      # traced by the first call, counted by each
        with plan.counting("test.exchange"):
            program(x)
    with plan.counting("test.exchange", executions=5):
        program(x)
    counted = sgd_counters(before)
    assert counted["mesh.alltoall_calls"] == 2 * 8
    assert counted["mesh.alltoall_bytes"] == 2 * 4 * 6 * 4 * 8
    assert counted["mesh.allreduce_calls"] == 8
    with pytest.raises(ValueError, match="one row a shard"):
        plan.shard_map(lambda x: plan.alltoall(x), plan.row_spec,
                       plan.row_spec)(x)


@pytest.mark.parametrize("devices,hosts", PLANS)
def test_key_layout_is_the_range_partition_and_take_rows_reads_it(devices,
                                                                  hosts):
    plan = plan_of(devices, hosts)
    n = plan.num_shards
    assert plan.rows_per_shard(FEATURES) == FEATURES // n
    keys = np.array([0, FEATURES // n - 1, FEATURES // n, FEATURES - 1])
    assert np.array_equal(np.asarray(plan.owner_of(jnp.asarray(keys),
                                                   FEATURES)),
                          [0, 0, 1, n - 1])
    with pytest.raises(ValueError, match="does not split"):
        plan.rows_per_shard(FEATURES + 1)
    rng = np.random.default_rng(0)
    table = rng.standard_normal((FEATURES, 3)).astype(np.float32)
    counts = rng.integers(0, 99, FEATURES).astype(np.int32)
    ids = np.concatenate([rng.integers(0, FEATURES, 50), [FEATURES]])
    by = plan.data_sharding()
    got = jax.jit(plan.take_rows)(jax.device_put(table, by),
                                  jnp.asarray(ids))
    assert np.array_equal(np.asarray(got)[:-1], table[ids[:-1]])
    assert not np.asarray(got)[-1].any()        # an id past the table: 0
    got = jax.jit(plan.take_rows)(jax.device_put(counts, by),
                                  jnp.asarray(ids[:-1]))
    assert np.array_equal(np.asarray(got), counts[ids[:-1]])


# (e) the feed -------------------------------------------------------------------
@pytest.mark.parametrize("num_workers", (1, 3))
def test_staging_deals_a_global_batch_to_the_chips_by_rows(tmp_path,
                                                           num_workers):
    """``DeviceStagingIter(sharding=plan.data_sharding())`` with the entry
    lanes bucketed at a batch's own entries: chip ``c`` holds rows ``[c B/S,
    (c + 1) B/S)`` of every global batch and their entries; over an epoch
    whose last batch is short every row is delivered exactly once, to
    exactly one chip, and the padding rows weigh nothing."""
    plan = plan_of(4)
    rows, batch, per = 150, ROWS, ROWS // 4
    rng = np.random.default_rng(1)
    index = rng.integers(0, FEATURES, (rows, COLUMNS))
    label = rng.integers(0, 2, rows)
    path = tmp_path / "rows.libsvm"
    path.write_text("".join(
        f"{label[r]} " + " ".join(f"{i}:1" for i in index[r]) + "\n"
        for r in range(rows)))
    it = DeviceStagingIter(str(path), format="libsvm", batch_size=batch,
                           nnz_bucket=batch * COLUMNS,
                           num_workers=num_workers, reorder=True,
                           sharding=plan.data_sharding())
    seen, steps = [], 0
    m = machine(plan)
    params = m.init(3)
    for b in it:
        assert b.index.shape == (batch * COLUMNS,)
        assert b.row_ptr.sharding.is_fully_replicated
        first = steps * batch
        for shard in b.index.addressable_shards:
            c = shard.index[0].start // (per * COLUMNS)
            lo, hi = first + c * per, min(first + (c + 1) * per, rows)
            want = index[lo:max(hi, lo)].reshape(-1)
            got = np.asarray(shard.data)
            assert np.array_equal(got[:len(want)], want)
        for shard in b.weight.addressable_shards:
            c = shard.index[0].start // per
            live = np.clip(rows - first - c * per, 0, per)
            assert np.array_equal(np.asarray(shard.data),
                                  np.arange(per) < live)
        live = np.asarray(b.value) != 0
        seen.append(np.asarray(b.index)[live])
        params, loss = m.train_step(params, b)
        assert np.isfinite(float(loss))
        steps += 1
    it.close()
    assert steps == 3 and int(np.asarray(b.num_rows)) == rows - 2 * batch
    assert np.array_equal(np.concatenate(seen), index.reshape(-1))
    # the step counted every delivered entry once, the short batch's too
    assert np.array_equal(
        np.asarray(params["count"]),
        np.bincount(index.reshape(-1), minlength=FEATURES))
