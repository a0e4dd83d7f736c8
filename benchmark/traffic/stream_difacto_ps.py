"""Traffic kind ``stream_difacto_ps``: ``stream_difacto``'s traffic into a
factorization machine whose tables are sharded by key over the cell's chips,
each chip a worker and a server (DiFacto over ps-lite, mapped onto one host):
``stream_ftrl``'s libsvm file, replayed epoch after epoch through
``DeviceStagingIter(format="libsvm", sharding=plan.data_sharding())`` — a
GLOBAL batch of ``batch_size x workers`` rows laid over the chips, chip ``c``
holding rows ``[c batch_size, (c + 1) batch_size)`` and their entries — into
``FactorizationMachine(optimizer={"w": FTRL, "v": AdaGrad}, threshold=...,
mesh=plan).train_step``.

The file, its draw, the delivery tally and ``teardown`` are ``stream_ftrl``'s
and the count of distinct keys ``stream_difacto``'s (imported, not copied),
over a view of the cell whose ``batch_size`` is the global one.  ``step`` and
``window`` are ``stream_ftrl``'s written again for four chips: nothing the
timed loop hands a program lies on the first chip alone (``step``), and the
tables are the first arrays the run puts on the chips (``setup``).  A program
whose model takes no plan fails at once, before a byte of the file is drawn.

What is read is ``stream_difacto``'s — the compared first steps at the
sampled ids, one live step after the window at EVERY distinct id of the
global minibatch, the ids no row names — through ``MeshPlan.take_rows`` (each
chip reads the rows it owns).  ``check`` adds two numbers of its own, both
held to 0:

- ``exchange_dropped``  distinct keys of the compared steps that the program
  did not update: a sampled id some compared row names whose count is still 0,
  or a key of the live minibatch whose count the live step did not move (every
  named key gains at least one occurrence);
- ``owner_mismatch``  the live step's keys whose state changed anywhere but in
  their owner's shard: with the table range-partitioned, key ``k`` lives at
  row ``k mod F/S`` of shard ``k div F/S``, so a write of ``k`` on another
  chip lands on the id ``k mod F/S + c F/S``; those ids (the ones the
  minibatch names itself left out) are read before and after the step and
  must not have changed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory
from benchmark.traffic import stream_ftrl as base
from benchmark.traffic.stream_difacto import distinct_keys, with_bias
from benchmark.traffic.stream_ftrl import teardown  # noqa: F401

#: ids a read is padded to a multiple of, so that reads of about as many ids
#: share a program
READ_BUCKET = 1 << 16


def global_cell(cell):
    """The cell as ``stream_ftrl``'s file and tally see it: one step's rows
    are every worker's minibatch together."""
    sizes = dict(cell.sizes, batch_size=int(cell.sizes["batch_size"])
                 * int(cell.sizes["workers"]))
    return dataclasses.replace(cell, config=dict(cell.config, sizes=sizes))


def make_model(cell):
    import jax
    try:
        from dmlc_core_tpu.models.common import FTRL, AdaGrad
        from dmlc_core_tpu.models.fm import FactorizationMachine
        from dmlc_core_tpu.parallel import MeshPlan
    except ImportError as exc:
        raise BenchFailure("this program has no AdaGrad rule for embedding "
                           f"rows or no MeshPlan: {exc}") from exc
    s = cell.sizes
    if int(s["workers"]) != cell.chips or int(s["servers"]) != cell.chips:
        raise BenchFailure("every chip is one worker and one server: the "
                           f"cell has {cell.chips} chips, the configuration "
                           f"{s['workers']} workers and {s['servers']} servers")
    plan = MeshPlan.build(jax.devices()[:cell.chips])
    try:
        model = FactorizationMachine(
            num_features=s["num_features"], num_factors=s["num_factors"],
            objective=s["objective"], init_scale=s["init_scale"],
            optimizer={"w": FTRL(alpha=s["alpha"], beta=s["beta"],
                                 l1=s["l1"], l2=s["l2"]),
                       "v": AdaGrad(alpha=s["alpha_v"], beta=s["beta_v"],
                                    l2=s["l2_v"])},
            threshold=s["threshold"], mesh=plan)
    except TypeError as exc:
        raise BenchFailure(
            "this program's FactorizationMachine takes no plan (mesh=): its "
            f"tables cannot be sharded by key over {cell.chips} chips, and "
            f"one chip cannot hold them ({exc})") from exc
    return model, plan


def padded_ids(ids: np.ndarray, features: int):
    """``ids`` as a device array padded to a whole ``READ_BUCKET`` with an id
    past the table (which reads 0)."""
    import jax.numpy as jnp
    lanes = -(-max(len(ids), 1) // READ_BUCKET) * READ_BUCKET
    return jnp.asarray(np.concatenate(
        [ids, np.full(lanes - len(ids), features)]).astype(np.int32))


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu import DeviceStagingIter
    from benchmark.harness import seed31
    # first of all: a program that cannot shard this model fails here, before
    # a byte of the file is drawn
    model, plan = make_model(cell)
    whole = global_cell(cell)
    s, p = whole.sizes, cell.params
    batch, columns = int(s["batch_size"]), int(s["entries_per_row"])
    features, want = int(s["num_features"]), int(p["sample_features"])
    steps = int(p["compare_steps"])
    if cell.cache_dir.parent.name == cell.name:     # .cache/<cell>/<seed>
        for other in cell.cache_dir.parent.glob("*/train.libsvm"):
            if other.parent != cell.cache_dir:
                other.unlink()
    t0 = time.perf_counter()
    path = cell.cache_dir / "train.libsvm"
    candidates = np.unique(np.random.default_rng(cell.seed).integers(
        0, features, 4 * want))
    made = base.make_file(whole, path, candidates, steps * batch)
    log(f"{path.name}: {p['file_rows']} rows, {made['bytes'] / 1e6:.0f} MB, "
        f"{'written' if made['written'] else 'of this seed was there; drawn'}"
        f" in {time.perf_counter() - t0:.1f}s")
    untouched = candidates[~made["named"]]
    if len(untouched) < want:
        raise ValueError("too few sampled ids that no row names")
    untouched = untouched[:want]
    sample_ids = np.resize(np.unique(
        made["index"][:int(p["sample_rows"])]), want)

    # every row holds ``columns`` entries, so with the entry lanes bucketed
    # at a batch's own entries chip ``c``'s rows' entries lie on its lanes
    it = DeviceStagingIter(str(path), format="libsvm", batch_size=batch,
                           nnz_bucket=batch * columns,
                           num_workers=int(p["num_workers"]), reorder=True,
                           sharding=plan.data_sharding())

    def replay():
        while True:
            yield from it

    @jax.jit
    def tally_add(acc, place, b):
        live = b.value != 0
        ids = jnp.sum(jnp.where(live, b.index, 0).astype(jnp.uint32))
        return {"rows": acc["rows"] + b.num_rows.astype(jnp.uint32),
                "entries": acc["entries"] + jnp.sum(live).astype(jnp.uint32),
                "positives": acc["positives"] + jnp.sum(
                    (b.label > 0.5) & (b.weight > 0)).astype(jnp.uint32),
                "ids": acc["ids"] + ids,
                "ids_by_place": acc["ids_by_place"] + ids * place}

    @jax.jit
    def read_state(params, ids):
        """Everything a key holds, at ``ids`` (every chip holds them; each
        reads the rows it owns); the bias's triple."""
        f, a = params["ftrl"], params["adagrad"]
        at = {"w": params["w"], "z": f["z"]["w"], "n": f["n"]["w"],
              "c": params["count"], "v": params["v"], "nv": a["n"]["v"]}
        return ({k: plan.take_rows(t, ids) for k, t in at.items()},
                jnp.stack([params["b"], f["z"]["b"], f["n"]["b"]]))

    @jax.jit
    def untouched_changed(params, ids, drawn):
        """Elements at ``ids`` that are not as the tables were made: zero
        state, zero counts, the drawn rows bit for bit."""
        at, _ = read_state(params, ids)
        return (sum(jnp.sum(at[k] != 0) for k in ("w", "z", "n", "c", "nv"))
                + jnp.sum(at["v"] != drawn))

    def read(ids: np.ndarray) -> tuple:
        """``read_state`` at host ids, on the host."""
        at, bias = jax.device_get(read_state(
            state["params"], padded_ids(ids, features)))
        return {k: v[:len(ids)] for k, v in at.items()}, bias

    # The tables are the first arrays of the run on any chip, and one program
    # makes them: every chip's heap lays them out alike.  An id array or a
    # tally made before them lies on the first chip alone and moves its
    # tables; where a table lies sets what its reads a distinct key cost
    # (count and ``w`` 0.8 ms more, ``v`` 0.75 less on that chip), and the
    # other chips wait at each exchange (PERF.md section 6, PR 45).
    params = model.init(seed31(cell.seed))
    jax.block_until_ready(params)
    for d in plan.mesh.devices.ravel():
        log_memory(f"tables made, no step yet, chip {d.id}", d)
    state = {"cell": whole, "sizes": cell.sizes, "model": model, "plan": plan,
             "it": it, "params": params,
             "batches": replay(), "spans": spans, "tally_add": tally_add,
             "read": read, "untouched_changed": untouched_changed,
             "made": made, "path": path, "batch": batch, "columns": columns,
             "steps": 0, "losses": [],
             "per_epoch": int(p["file_rows"]) // batch,
             "sample_ids": sample_ids,
             "untouched_ids": jnp.asarray(untouched),
             "tally": {k: np.zeros((), np.uint32) for k in
                       ("rows", "entries", "positives", "ids",
                        "ids_by_place")}}
    drawn_ids = np.unique(made["index"])
    state["drawn_ids"] = drawn_ids
    state["drawn_rows"] = read(drawn_ids)[0]["v"]
    state["untouched_rows"] = read_state(
        state["params"], state["untouched_ids"])[0]["v"]
    # The first steps go through the window's own iterator and train_step
    # and are what the reference follows; they also compile everything.
    for _ in range(steps):
        state["losses"].append(float(step(state, spans)))
    state["compared"] = read(sample_ids)
    # compile the window's last reads before the window
    jax.block_until_ready(untouched_changed(
        state["params"], state["untouched_ids"], state["untouched_rows"]))
    model.flush_step_counters()
    return state


def step(state: dict, spans):
    """One timed step, as ``stream_ftrl.step``: next batch from the staging
    iterator, ``train_step``, the delivery tally.  The step's place in its
    epoch goes to the tally as a host scalar, which ``jit`` hands every chip
    itself: made as a device scalar it lies on the first chip alone, and the
    tally waits on each other chip for its copy, behind the step (1.2 ms of
    every 58 with the chips idle, my chip run, PR 45)."""
    with spans.span("next"):
        batch = next(state["batches"])
    state["params"], loss = state["model"].train_step(state["params"], batch)
    place = np.uint32(state["steps"] % state["per_epoch"] + 1)
    state["tally"] = state["tally_add"](state["tally"], place, batch)
    state["steps"] += 1
    return loss


def window(state: dict, seconds: float, spans) -> dict:
    """``stream_ftrl.window`` over ``step`` above: closed loop, the loss
    fetched every ``loss_every`` steps the one of as many steps back.  Its
    counts gain ``stream_difacto``'s (``num_factors``; ``distinct_keys`` once
    ``check`` has drawn the window's rows again) and the chips, so that a
    chip's share of the work can be taken."""
    import jax
    every = int(state["cell"].params["loss_every"])
    first = state["steps"]
    behind = None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        loss = step(state, spans)
        if (state["steps"] - first) % every == 0:
            if behind is not None:
                with spans.span("loss_fetch"):
                    state["last_loss"] = float(behind)
            behind = loss
    jax.block_until_ready(state["params"])
    elapsed = time.perf_counter() - t0
    state["model"].flush_step_counters()
    steps = state["steps"] - first
    rows = steps * state["batch"]
    counts = {"rows": rows, "steps": steps, "entries": rows * state["columns"],
              "num_factors": int(state["cell"].sizes["num_factors"]),
              "chips": state["plan"].num_shards}
    state["window"] = (first, counts)
    return {"metrics": {"train_rows_per_s": rows / elapsed},
            "attempted": steps, "failed": 0, "counts": counts}


def aliases(keys: np.ndarray, features: int, shards: int) -> np.ndarray:
    """Where a write of ``keys`` would land on a chip that does not own them:
    each key's row of every OTHER shard, the ids ``keys`` names itself left
    out; sorted."""
    owned = features // shards
    every = (keys % owned)[:, None] + owned * np.arange(shards)[None, :]
    return np.setdiff1d(every.reshape(-1), keys)


def live_step(state: dict) -> dict:
    """One more step, from the state the window left: the global minibatch
    the file holds at the step's place, drawn again; its distinct ids; what
    each holds (the bias first) before and after the step; its loss; and what
    the ids a misplaced write would land on hold, before and after."""
    cell, s = state["cell"], state["cell"].sizes
    batch, rows = state["batch"], int(cell.params["file_rows"])
    chunk_rows = min(base.CHUNK_ROWS, rows)
    chunk, first = divmod(state["steps"] % state["per_epoch"] * batch,
                          chunk_rows)
    label, index = base.draw_rows(cell.seed, chunk, chunk_rows,
                                  int(s["num_features"]), state["columns"],
                                  cell.config["assumed"]["label_rate"])
    label, index = label[first:first + batch], index[first:first + batch]
    keys = np.unique(index)
    elsewhere = aliases(keys, int(s["num_features"]),
                        state["plan"].num_shards)
    both = np.concatenate([keys, elsewhere])

    def read():
        at, bias = state["read"](both)
        return (with_bias({k: v[:len(keys)] for k, v in at.items()}, bias),
                {k: v[len(keys):] for k, v in at.items()})
    before, away_before = read()
    loss = float(step(state, state["spans"]))
    after, away_after = read()
    moved = np.zeros(len(elsewhere), bool)
    for k in away_before:
        differs = away_before[k] != away_after[k]
        moved |= differs.any(axis=tuple(range(1, differs.ndim)))
    return {"label": label, "index": index, "keys": keys, "loss": loss,
            "before": before, "after": after,
            "misplaced": int(moved.sum()), "elsewhere": len(elsewhere)}


def check(state: dict, reference, control: int = 0) -> list:
    import jax
    t0 = time.perf_counter()
    cell, made = state["cell"], state["made"]
    if "window" in state:
        # the dict the window returned: the run's record reads it after this
        first, counts = state.pop("window")
        counts["distinct_keys"] = distinct_keys(state, first, counts["steps"])
        log(f"the window's global minibatches named "
            f"{counts['distinct_keys']} distinct keys in "
            f"{time.perf_counter() - t0:.1f}s")
    live = live_step(state)
    got_tally = {k: int(v) for k, v in jax.device_get(state["tally"]).items()}
    want_tally = base.expected_tally(made["ids"], made["positives"],
                                     state["batch"], state["columns"],
                                     state["steps"])
    mismatch = sum(got_tally[k] != want_tally[k] for k in want_tally)
    if mismatch:
        log(f"delivered {got_tally}, the file holds {want_tally}")
    changed = int(state["untouched_changed"](
        state["params"], state["untouched_ids"], state["untouched_rows"]))
    state["model"].flush_step_counters()
    state["params"] = None          # the program's tables leave the devices
    compared = with_bias(*state["compared"])
    got = dict(compared, losses=state["losses"],
               drawn_ids=state["drawn_ids"], drawn_rows=state["drawn_rows"],
               untouched_changed=changed)
    out = reference.compare(got, made["label"], made["index"],
                            state["sample_ids"], state["sizes"],
                            control=bool(control), live=live)
    out.append({"name": "delivery_mismatch", "value": mismatch})
    # every key a compared step names has gained an occurrence
    named = np.isin(state["sample_ids"], made["index"])
    dropped = int(np.sum(named & (compared["c"][1:] == 0))) + int(np.sum(
        live["after"]["c"][1:] == live["before"]["c"][1:]))
    out.append({"name": "exchange_dropped", "value": dropped})
    out.append({"name": "owner_mismatch", "value": live["misplaced"]})
    moved = int(np.sum(np.any(live["after"]["nv"] != live["before"]["nv"],
                              axis=1)))
    log(f"last loss {state.get('last_loss')}; the live step named "
        f"{len(live['keys'])} ids and moved the embedding rows of {moved}; "
        f"{live['elsewhere']} ids on the other chips read for misplaced "
        f"writes; reference took {time.perf_counter() - t0:.1f}s")
    for c in out:
        if c["name"].endswith("loss_rel_err"):
            log(f"read {c['name']}: {c['value']!r} (held to no limit)")
    return [c for c in out if not c["name"].endswith("loss_rel_err")]
