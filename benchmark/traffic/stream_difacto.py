"""Traffic kind ``stream_difacto``: ``stream_ftrl``'s libsvm file, replayed
epoch after epoch through ``DeviceStagingIter(format="libsvm")`` into the
touched-rows ``train_step`` of a factorization machine trained as DiFacto
trains it: FTRL-Proximal on ``w`` and the bias, AdaGrad on embedding rows
``v`` that switch on by a key's count.

The file, its draw, the delivery tally, ``step``, ``window`` and ``teardown``
are ``stream_ftrl``'s (imported, not copied; its docstring describes them and
the cell's ``params``).  What differs is what is read and compared:

- set-up reads the program's drawn embedding rows at every id of the compared
  steps' rows (the reference starts from them) and at the ids no row names,
  before the first step;
- after the compared steps, ``(w, z, n)``, the count, ``v`` and the
  embedding's ``N`` at the sampled ids and the bias;
- when the window has closed, one more step (``live_step``): all of those at
  EVERY distinct id of its minibatch, before and after it; and the ids no row
  names must hold ``(0, 0, 0)``, count 0, ``N = 0`` and their drawn rows bit
  for bit.
"""
from __future__ import annotations

import concurrent.futures
import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory
from benchmark.traffic import stream_ftrl as base
from benchmark.traffic.stream_ftrl import step, teardown  # noqa: F401


def make_model(cell):
    try:
        from dmlc_core_tpu.models.common import FTRL, AdaGrad
    except ImportError as exc:
        raise BenchFailure("this program has no AdaGrad rule for embedding "
                           f"rows (models/common.py): {exc}") from exc
    from dmlc_core_tpu.models.fm import FactorizationMachine
    s = cell.sizes
    return FactorizationMachine(
        num_features=s["num_features"], num_factors=s["num_factors"],
        objective=s["objective"], init_scale=s["init_scale"],
        optimizer={"w": FTRL(alpha=s["alpha"], beta=s["beta"], l1=s["l1"],
                             l2=s["l2"]),
                   "v": AdaGrad(alpha=s["alpha_v"], beta=s["beta_v"],
                                l2=s["l2_v"])},
        threshold=s["threshold"])


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu import DeviceStagingIter
    from benchmark.harness import seed31
    # first of all: a program that cannot run this model fails here, before
    # a byte of the file is drawn
    model = make_model(cell)
    s, p = cell.sizes, cell.params
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
    made = base.make_file(cell, path, candidates, steps * batch)
    log(f"{path.name}: {p['file_rows']} rows, {made['bytes'] / 1e6:.0f} MB, "
        f"{'written' if made['written'] else 'of this seed was there; drawn'}"
        f" in {time.perf_counter() - t0:.1f}s")
    untouched = candidates[~made["named"]]
    if len(untouched) < want:
        raise ValueError("too few sampled ids that no row names")
    untouched = untouched[:want]
    sample_ids = np.resize(np.unique(
        made["index"][:int(p["sample_rows"])]), want)

    it = DeviceStagingIter(str(path), format="libsvm", batch_size=batch,
                           num_workers=int(p["num_workers"]), reorder=True)

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
        """Everything a key holds, at ``ids``; the bias's triple."""
        f, a = params["ftrl"], params["adagrad"]
        return ({"w": params["w"][ids], "z": f["z"]["w"][ids],
                 "n": f["n"]["w"][ids], "c": params["count"][ids],
                 "v": params["v"][ids], "nv": a["n"]["v"][ids]},
                jnp.stack([params["b"], f["z"]["b"], f["n"]["b"]]))

    @jax.jit
    def untouched_changed(params, ids, drawn):
        """Elements at ``ids`` that are not as the tables were made: zero
        state, zero counts, the drawn rows bit for bit."""
        at, _ = read_state(params, ids)
        return (sum(jnp.sum(at[k] != 0) for k in ("w", "z", "n", "c", "nv"))
                + jnp.sum(at["v"] != drawn))

    state = {"cell": cell, "model": model, "it": it, "batches": replay(),
             "spans": spans, "tally_add": tally_add, "read_state": read_state,
             "untouched_changed": untouched_changed, "made": made,
             "path": path, "batch": batch, "columns": columns, "steps": 0,
             "losses": [], "per_epoch": int(p["file_rows"]) // batch,
             "sample_ids": sample_ids,
             "untouched_ids": jnp.asarray(untouched),
             "tally": {k: jnp.zeros((), jnp.uint32) for k in
                       ("rows", "entries", "positives", "ids",
                        "ids_by_place")}}
    state["params"] = model.init(seed31(cell.seed))
    log_memory("tables made, no step yet")
    drawn_ids = np.unique(made["index"])
    state["drawn_ids"] = drawn_ids
    state["drawn_rows"] = jax.device_get(
        state["params"]["v"][jnp.asarray(drawn_ids)])
    state["untouched_rows"] = state["params"]["v"][state["untouched_ids"]]
    # The first steps go through the window's own iterator and train_step
    # and are what the reference follows; they also compile everything.
    for _ in range(steps):
        state["losses"].append(float(step(state, spans)))
    state["compared"] = jax.device_get(
        read_state(state["params"], jnp.asarray(sample_ids)))
    # compile the window's last reads before the window
    jax.block_until_ready(untouched_changed(
        state["params"], state["untouched_ids"], state["untouched_rows"]))
    model.flush_step_counters()
    return state


def window(state: dict, seconds: float, spans) -> dict:
    """``stream_ftrl``'s window.  Its counts gain the embedding's width and,
    once ``check`` has drawn the window's rows again, ``distinct_keys``."""
    first = state["steps"]
    out = base.window(state, seconds, spans)
    out["counts"]["num_factors"] = int(state["cell"].sizes["num_factors"])
    state["window"] = (first, out["counts"])
    return out


def distinct_keys(state: dict, first: int, steps: int) -> int:
    """The distinct ids of each minibatch of steps ``first`` to ``first +
    steps``, summed: counted on the host from the seed's rows drawn again,
    not taken from the program and not on the device's time.  What the rows
    kernel's roofline share is taken against."""
    cell, s, batch = state["cell"], state["cell"].sizes, state["batch"]
    chunk_rows = min(base.CHUNK_ROWS, int(cell.params["file_rows"]))
    per_chunk = chunk_rows // batch
    times = np.bincount(np.arange(first, first + steps) % state["per_epoch"],
                        minlength=state["per_epoch"])

    def one(chunk: int) -> int:
        mine = times[chunk * per_chunk:(chunk + 1) * per_chunk]
        if not mine.any():
            return 0
        _label, index = base.draw_rows(
            cell.seed, chunk, chunk_rows, int(s["num_features"]),
            state["columns"], cell.config["assumed"]["label_rate"])
        return sum(int(t) * len(np.unique(index[i * batch:(i + 1) * batch]))
                   for i, t in enumerate(mine) if t)

    with concurrent.futures.ThreadPoolExecutor(base.WRITERS) as pool:
        return sum(pool.map(one, range(state["per_epoch"] // per_chunk)))


def with_bias(at: dict, bias) -> dict:
    """A read of ``read_state`` as the reference takes it: the bias first
    (its count 0, its embedding row zeros)."""
    w, z, n = (float(x) for x in bias)
    return {k: np.concatenate([np.full((1,) + at[k].shape[1:], first,
                                       at[k].dtype), at[k]])
            for k, first in (("w", w), ("z", z), ("n", n), ("c", 0),
                             ("v", 0), ("nv", 0))}


def live_step(state: dict) -> dict:
    """One more step, from the state the window left: the rows the file holds
    at the step's place, drawn again; their distinct ids; what each holds
    (the bias first) before and after the step; its loss."""
    import jax
    import jax.numpy as jnp
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

    def read():
        return with_bias(*jax.device_get(state["read_state"](
            state["params"], jnp.asarray(keys))))
    before = read()
    loss = float(step(state, state["spans"]))
    return {"label": label, "index": index, "keys": keys, "loss": loss,
            "before": before, "after": read()}


def check(state: dict, reference, control: int = 0) -> list:
    import jax
    t0 = time.perf_counter()
    cell, made = state["cell"], state["made"]
    if "window" in state:
        # the dict the window returned: the run's record reads it after this
        first, counts = state.pop("window")
        counts["distinct_keys"] = distinct_keys(state, first, counts["steps"])
        log(f"the window's minibatches named {counts['distinct_keys']} "
            f"distinct keys in {time.perf_counter() - t0:.1f}s")
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
    state["params"] = None          # the program's tables leave the device
    got = dict(with_bias(*state["compared"]), losses=state["losses"],
               drawn_ids=state["drawn_ids"], drawn_rows=state["drawn_rows"],
               untouched_changed=changed)
    out = reference.compare(got, made["label"], made["index"],
                            state["sample_ids"], cell.sizes,
                            control=bool(control), live=live)
    out.append({"name": "delivery_mismatch", "value": mismatch})
    moved = int(np.sum(np.any(live["after"]["nv"] != live["before"]["nv"],
                              axis=1)))
    log(f"last loss {state.get('last_loss')}; the live step named "
        f"{len(live['keys'])} ids and moved the embedding rows of {moved}; "
        f"reference took {time.perf_counter() - t0:.1f}s")
    for c in out:
        if c["name"].endswith("loss_rel_err"):
            log(f"read {c['name']}: {c['value']!r} (held to no limit)")
    return [c for c in out if not c["name"].endswith("loss_rel_err")]
