"""Traffic kind ``stream_ftrl``: a libsvm text file on disk, replayed epoch
after epoch through ``DeviceStagingIter(format="libsvm")`` into the
touched-rows ``train_step`` of a hashed linear model under FTRL-Proximal.

Parameters (the cell's ``params``): ``file_rows``, ``num_workers`` (native
parse workers), ``loss_every`` (steps between loss fetches, the only points
where the host waits for the device; the loss fetched is the one of as many
steps back, see ``window``), ``compare_steps`` (first steps the
reference follows; they run through the window's own iterator and
``train_step`` during set-up), ``sample_rows`` and ``sample_features`` (the
first so many distinct ids of the first so many rows: their ``(w, z, n)`` is
compared element by element; as many ids again that no row of the file names
are read when the window has closed).

When the window has closed, one more step is taken through the same iterator
and ``train_step`` from the state the window left (``live_step``): the
``(w, z, n)`` of every distinct id of its minibatch, read before and after
it, go to the reference, which takes the same step from the same ``(z, n)``
over the rows the file holds at that place.  A compared loss is logged and
held to no limit.

The file is made in chunks of rows on threads (numpy integer arithmetic into
fixed-width digit bytes, no per-row Python), each chunk drawn from ``(seed,
chunk)``, under the cell's cache directory, and stays there: a later run of
the same seed in the same checkout finds it, checks its size, and only draws
the rows again for what it compares with.  It is 470 B a row (3.94 GB at
the cell's size), so the files of other seeds of this cell are removed
first: a run with another seed writes its own again, 24 s of set-up.  Every
row has exactly ``entries_per_row`` entries, one a column, every value 1.
Ids are Zipf(1.1)-popular inside each column's share of the hashed space
(13 numeric columns of 64 values; 26 categorical ones sharing the rest by the
Criteo cardinalities), drawn by the analytic inverse of the bounded continuous Zipf
CDF, so that no column needs a table of its vocabulary, and scattered over
the table by a fixed odd multiplier, as the hashing trick scatters them.  A
label is a fixed rule of four columns' ids plus noise.
"""
from __future__ import annotations

import concurrent.futures
import os
import time

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory
from benchmark.traffic.stream_epochs import (CRITEO_CARDINALITY, NUMERIC_BINS,
                                             NUMERIC_FIELDS, SCATTER, ZIPF_S)

CHUNK_ROWS = 262144
WRITERS = 8
LABEL_COLUMNS = (0, 5, 13 + 1, 13 + 8)      # two numeric, C2 and C9
LABEL_SCALE = 4.0
THREE_DIGITS = np.array([[ord(c) for c in f"{i:03d}"] for i in range(1000)],
                        np.uint8)


def column_vocabulary(num_features: int, columns: int) -> np.ndarray:
    """Vocabulary size of each column; they sum to ``num_features``."""
    numeric = min(NUMERIC_FIELDS, columns)
    sizes = [min(NUMERIC_BINS, max(num_features // (4 * columns), 2))
             ] * numeric
    rest = num_features - sum(sizes)
    cards = np.array((CRITEO_CARDINALITY * 2)[:columns - numeric], np.float64)
    share = np.maximum((cards / cards.sum() * rest).astype(np.int64), 2)
    if len(share):
        share[np.argmax(share)] += rest - share.sum()
    return np.array(sizes + share.tolist(), np.int64)


def zipf_rank(u: np.ndarray, vocabulary: int) -> np.ndarray:
    """Ranks ``0 .. vocabulary - 1`` from uniforms ``u``: the inverse of the
    CDF of the density ``x ** -ZIPF_S`` on ``[0.5, vocabulary + 0.5)``."""
    e = 1.0 - ZIPF_S
    low, high = 0.5 ** e, (vocabulary + 0.5) ** e
    x = (low - u * (low - high)) ** (1.0 / e)
    return np.clip(np.floor(x + 0.5).astype(np.int64) - 1, 0, vocabulary - 1)


def draw_rows(seed: int, chunk: int, rows: int, num_features: int,
              columns: int, label_rate: float):
    """``(label [rows] u8, index [rows, columns] i32)`` of one chunk."""
    rng = np.random.default_rng([seed, chunk])
    vocab = column_vocabulary(num_features, columns)
    offset = np.concatenate([[0], np.cumsum(vocab)[:-1]])
    index = np.empty((rows, columns), np.int64)
    for c in range(columns):
        index[:, c] = offset[c] + zipf_rank(rng.random(rows), int(vocab[c]))
    index = (index * SCATTER) % num_features
    # a fixed pseudo-random number in [0, 1) an id, summed over four columns
    mark = sum(((index[:, c] * 0x85EBCA6B) >> 7) % 1024 for c in
               LABEL_COLUMNS if c < columns) / (1024.0 * len(LABEL_COLUMNS))
    logit = np.log(label_rate / (1 - label_rate)) + LABEL_SCALE * (mark - 0.5)
    label = rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))
    return label.astype(np.uint8), index.astype(np.int32)


def id_width(num_features: int) -> int:
    return -(-len(str(num_features - 1)) // 3) * 3


def libsvm_bytes(rows: int, columns: int, num_features: int) -> int:
    """Size of the file these shapes make: ``label id:1 ... id:1\\n``."""
    return rows * (1 + columns * (1 + id_width(num_features) + 2) + 1)


def libsvm_text(label: np.ndarray, index: np.ndarray,
                num_features: int) -> np.ndarray:
    """The rows as one uint8 matrix: ids zero-padded to a fixed width, three
    digits at a time from a table."""
    rows, columns = index.shape
    iw = id_width(num_features)
    entry = 1 + iw + 2                      # " iiiiiiiii:1"
    text = np.full((rows, 1 + columns * entry + 1), ord(" "), np.uint8)
    text[:, 0] = label + ord("0")
    text[:, -1] = ord("\n")
    for c in range(columns):
        at = 1 + c * entry + 1
        col = index[:, c].astype(np.int64)
        for k in range(iw // 3):
            group = (col // 1000 ** (iw // 3 - 1 - k)) % 1000
            text[:, at + 3 * k:at + 3 * k + 3] = THREE_DIGITS[group]
        text[:, at + iw] = ord(":")
        text[:, at + iw + 1] = ord("1")
    return text


def make_file(cell, path, candidates: np.ndarray, keep_rows: int) -> dict:
    """Draw every chunk of the seed's file (and write it unless it is
    there).  Returns what the run compares with: each batch's sum of ids and
    of positive labels, the first ``keep_rows`` rows, and which of the
    sorted ``candidates`` some row names."""
    s, rows = cell.sizes, int(cell.params["file_rows"])
    batch, columns = int(s["batch_size"]), int(s["entries_per_row"])
    features = int(s["num_features"])
    chunk_rows = min(CHUNK_ROWS, rows)
    if rows % chunk_rows or chunk_rows % batch:
        raise ValueError("file_rows must be whole chunks of whole batches")
    size = libsvm_bytes(rows, columns, features)
    there = path.is_file() and path.stat().st_size == size
    part = f"{path}.part"
    row_bytes = size // rows
    fd = None if there else os.open(part, os.O_WRONLY | os.O_CREAT | os.O_TRUNC)

    def one(chunk: int):
        label, index = draw_rows(cell.seed, chunk, chunk_rows, features,
                                 columns, cell.config["assumed"]["label_rate"])
        if fd is not None:
            text = libsvm_text(label, index, features)
            os.pwrite(fd, text.tobytes(), chunk * chunk_rows * row_bytes)
        flat = index.reshape(-1)
        at = np.minimum(np.searchsorted(candidates, flat), len(candidates) - 1)
        named = np.zeros(len(candidates), bool)
        named[at[candidates[at] == flat]] = True
        return (index.astype(np.uint64).reshape(-1, batch * columns).sum(1),
                label.astype(np.uint64).reshape(-1, batch).sum(1),
                (label[:keep_rows], index[:keep_rows]) if chunk == 0 else None,
                named)

    try:
        with concurrent.futures.ThreadPoolExecutor(WRITERS) as pool:
            parts = list(pool.map(one, range(rows // chunk_rows)))
    finally:
        if fd is not None:
            os.close(fd)
    if fd is not None:
        os.replace(part, path)
    if keep_rows > chunk_rows:
        raise ValueError("the compared rows must lie in the first chunk")
    return {"ids": np.concatenate([p[0] for p in parts]),
            "positives": np.concatenate([p[1] for p in parts]),
            "label": parts[0][2][0], "index": parts[0][2][1],
            "named": np.any([p[3] for p in parts], axis=0), "written": not there,
            "bytes": size}


def expected_tally(ids, positives, batch: int, columns: int,
                   steps: int) -> dict:
    """What ``steps`` batches of the replayed file must add up to, from each
    batch's sums: rows, entries, positive labels, and two 32-bit checksums
    of the ids — plain, and weighted by the batch's place in its epoch,
    which a batch out of order changes."""
    per_epoch = len(ids)
    full, part = divmod(steps, per_epoch)
    times = np.full(per_epoch, full, np.uint64)
    times[:part] += 1
    place = np.arange(1, per_epoch + 1, dtype=np.uint64)
    return {"rows": steps * batch % 2 ** 32,
            "entries": steps * batch * columns % 2 ** 32,
            "positives": int((positives * times).sum() % 2 ** 32),
            "ids": int((ids * times).sum() % 2 ** 32),
            "ids_by_place": int((ids % 2 ** 32 * place * times).sum()
                                % 2 ** 32)}


def make_model(cell):
    try:
        from dmlc_core_tpu.models.common import FTRL
    except ImportError as exc:
        raise BenchFailure("this program has no FTRL optimizer "
                           f"(models/common.py): {exc}") from exc
    from dmlc_core_tpu.models.linear import SparseLinearModel
    s = cell.sizes
    return SparseLinearModel(
        num_features=s["num_features"], objective=s["objective"],
        optimizer=FTRL(alpha=s["alpha"], beta=s["beta"], l1=s["l1"],
                       l2=s["l2"]))


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu import DeviceStagingIter
    # first of all: a program that cannot run this optimizer fails here,
    # before a byte of the file is drawn
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
    made = make_file(cell, path, candidates, steps * batch)
    log(f"{path.name}: {p['file_rows']} rows, {made['bytes'] / 1e6:.0f} MB, "
        f"{'written' if made['written'] else 'of this seed was there; drawn'}"
        f" in {time.perf_counter() - t0:.1f}s")
    untouched = candidates[~made["named"]]
    if len(untouched) < want:
        raise ValueError("too few sampled ids that no row names")
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
        """``[ids, 3]``: ``(w, z, n)`` at ``ids``; the bias's triple."""
        at = lambda table: table[ids]       # noqa: E731
        f = params["ftrl"]
        return (jnp.stack([at(params["w"]), at(f["z"]["w"]),
                           at(f["n"]["w"])], axis=1),
                jnp.stack([params["b"], f["z"]["b"], f["n"]["b"]]))

    state = {"cell": cell, "model": model, "it": it, "batches": replay(),
             "spans": spans,
             "tally_add": tally_add, "read_state": read_state, "made": made,
             "path": path, "batch": batch, "columns": columns, "steps": 0,
             "losses": [], "per_epoch": int(p["file_rows"]) // batch,
             "sample_ids": sample_ids, "untouched_ids": untouched[:want],
             "tally": {k: jnp.zeros((), jnp.uint32) for k in
                       ("rows", "entries", "positives", "ids",
                        "ids_by_place")}}
    state["params"] = model.init()
    log_memory("tables made, no step yet")
    # The first steps go through the window's own iterator and train_step
    # and are what the reference follows; they also compile everything.
    for _ in range(steps):
        state["losses"].append(float(step(state, spans)))
    state["compared"] = jax.device_get(
        read_state(state["params"], jnp.asarray(sample_ids)))
    # compile the window's last read before the window
    jax.block_until_ready(
        read_state(state["params"], jnp.asarray(state["untouched_ids"])))
    model.flush_step_counters()
    return state


def step(state: dict, spans):
    """One timed step: next batch from the staging iterator, ``train_step``,
    the delivery tally.  The tests break it here."""
    import jax.numpy as jnp
    with spans.span("next"):
        batch = next(state["batches"])
    state["params"], loss = state["model"].train_step(state["params"], batch)
    place = jnp.uint32(state["steps"] % state["per_epoch"] + 1)
    state["tally"] = state["tally_add"](state["tally"], place, batch)
    state["steps"] += 1
    return loss


def window(state: dict, seconds: float, spans) -> dict:
    import jax
    every = int(state["cell"].params["loss_every"])
    first = state["steps"]
    # The loss fetched every ``loss_every`` steps is the one of ``loss_every``
    # steps back: the host still waits for the device there and so never
    # leads it by more than twice as many steps, but the device has steps
    # queued while the host wakes.  Fetching the step just dispatched leaves
    # the device idle for as long as the host takes to notice (3.6 ms a fetch
    # on a quiet host, 50 ms on a busy one: windows read 10-14% low).
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
    return {"metrics": {"train_rows_per_s": rows / elapsed},
            "attempted": steps, "failed": 0,
            "counts": {"rows": rows, "steps": steps,
                       "entries": rows * state["columns"]}}


def live_step(state: dict) -> dict:
    """One more step, from the state the window left: the rows the file holds
    at the step's place, drawn again; their distinct ids; the ``(w, z, n)``
    there and at the bias (first) before and after the step; its loss."""
    import jax
    import jax.numpy as jnp
    cell, s = state["cell"], state["cell"].sizes
    batch, rows = state["batch"], int(cell.params["file_rows"])
    chunk_rows = min(CHUNK_ROWS, rows)
    chunk, first = divmod(state["steps"] % state["per_epoch"] * batch,
                          chunk_rows)
    label, index = draw_rows(cell.seed, chunk, chunk_rows,
                             int(s["num_features"]), state["columns"],
                             cell.config["assumed"]["label_rate"])
    label, index = label[first:first + batch], index[first:first + batch]
    keys = np.unique(index)

    def read():
        at, bias = jax.device_get(state["read_state"](state["params"],
                                                      jnp.asarray(keys)))
        return np.concatenate([bias[None, :], at])
    before = read()
    loss = float(step(state, state["spans"]))
    return {"label": label, "index": index, "keys": keys, "loss": loss,
            "before": before, "after": read()}


def check(state: dict, reference, control: int = 0) -> list:
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    cell, made = state["cell"], state["made"]
    live = live_step(state)
    got_tally = {k: int(v) for k, v in jax.device_get(state["tally"]).items()}
    want_tally = expected_tally(made["ids"], made["positives"],
                                state["batch"], state["columns"],
                                state["steps"])
    mismatch = sum(got_tally[k] != want_tally[k] for k in want_tally)
    if mismatch:
        log(f"delivered {got_tally}, the file holds {want_tally}")
    untouched, _ = jax.device_get(state["read_state"](
        state["params"], jnp.asarray(state["untouched_ids"])))
    state["params"] = None          # the program's tables leave the device
    rows, bias = state["compared"]
    both = np.concatenate([bias[None, :], rows])    # the bias first
    got = {"losses": state["losses"], "w": both[:, 0], "z": both[:, 1],
           "n": both[:, 2], "untouched": untouched}
    out = reference.compare(got, made["label"], made["index"],
                            state["sample_ids"], cell.sizes,
                            control=bool(control), live=live)
    out.append({"name": "delivery_mismatch", "value": mismatch})
    log(f"last loss {state.get('last_loss')}; the live step named "
        f"{len(live['keys'])} ids; reference took "
        f"{time.perf_counter() - t0:.1f}s")
    for c in out:
        if c["name"].endswith("loss_rel_err"):
            log(f"read {c['name']}: {c['value']!r} (held to no limit)")
    return [c for c in out if not c["name"].endswith("loss_rel_err")]


def teardown(state: dict) -> None:
    # the epoch in flight ends first, so that its producer threads let go
    # of the native cursor and ``close`` does not wait for them
    state["batches"].close()
    state["it"].close()         # the file stays for the seed's next run
    state.clear()
