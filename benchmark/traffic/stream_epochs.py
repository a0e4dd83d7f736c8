"""Traffic kind ``stream_epochs``: a libfm text file on disk, replayed epoch
after epoch through ``DeviceStagingIter`` into a model's ``train_step``.

Parameters (the cell's ``params``): ``file_rows``, ``num_workers`` (native
parse workers), ``loss_every`` (steps between loss fetches, the only points
where the host waits for the device), ``compare_steps`` (first steps the
reference follows), ``sample_rows`` and ``sample_features`` (the first so
many distinct features of the first so many rows: their parameters' change is
compared element by element; a fixed count, so that no program's shape
depends on the seed).

The file is written once a seed by ``write_libfm`` (numpy integer arithmetic
into fixed-width digit bytes, no per-row Python) under the cell's cache
directory and stays there: a later run of the same seed in the same checkout
finds it, checks its size, and only draws the rows again to compare with.
Every row has exactly ``entries_per_row`` entries, one a field, every value 1;
feature ids are Zipf(1.1)-popular inside each field's share of the hashed
space and scattered over the table by a fixed odd multiplier, as the hashing
trick scatters them.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmark.harness import log, log_memory, seed31

# Criteo Kaggle categorical cardinalities (fields C1..C26), the proportions
# in which the categorical fields share the hashed space
CRITEO_CARDINALITY = (1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3,
                      93145, 5683, 8351593, 3194, 27, 14992, 5461306, 10,
                      5652, 2173, 4, 7046547, 18, 15, 286181, 105, 142572)
NUMERIC_FIELDS, NUMERIC_BINS = 13, 64
ZIPF_S = 1.1
SCATTER = 0x9E3779B1        # odd: a bijection modulo a power of two


def field_vocabulary(num_features: int, num_fields: int) -> np.ndarray:
    """Vocabulary size of each field; they sum to ``num_features``."""
    numeric = min(NUMERIC_FIELDS, num_fields)
    sizes = [min(NUMERIC_BINS, max(num_features // (4 * num_fields), 2))
             ] * numeric
    rest = num_features - sum(sizes)
    cards = np.minimum(np.array(
        (CRITEO_CARDINALITY * 2)[:num_fields - numeric], np.float64), 3e5)
    share = np.maximum((cards / cards.sum() * rest).astype(np.int64), 2)
    if len(share):
        share[np.argmax(share)] += rest - share.sum()
    return np.array(sizes + share.tolist(), np.int64)


def draw_rows(seed: int, rows: int, num_features: int, num_fields: int,
              label_rate: float):
    """``(label [rows] u8, index [rows, fields] i32)`` from the seed."""
    rng = np.random.default_rng(seed)
    vocab = field_vocabulary(num_features, num_fields)
    offset = np.concatenate([[0], np.cumsum(vocab)[:-1]])
    index = np.empty((rows, num_fields), np.int64)
    for f in range(num_fields):
        cdf = np.cumsum(np.arange(1, vocab[f] + 1, dtype=np.float64)
                        ** -ZIPF_S)
        rank = np.searchsorted(cdf, rng.random(rows) * cdf[-1], side="right")
        index[:, f] = offset[f] + np.minimum(rank, vocab[f] - 1)
    index = (index * SCATTER) % num_features
    label = (rng.random(rows) < label_rate).astype(np.uint8)
    return label, index.astype(np.int32)


def libfm_bytes(rows: int, fields: int, num_features: int) -> int:
    """Size of the file ``write_libfm`` writes for these shapes."""
    entry = 1 + len(str(fields - 1)) + 1 + len(str(num_features - 1)) + 2
    return rows * (1 + fields * entry + 1)


def write_libfm(path, label: np.ndarray, index: np.ndarray,
                num_features: int) -> int:
    """``label f:idx:1 ...`` a row, fields and ids zero-padded to a fixed
    width so that the whole file is one uint8 matrix.  Written beside
    ``path`` and moved into place whole.  Returns its bytes."""
    rows, fields = index.shape
    fw, iw = len(str(fields - 1)), len(str(num_features - 1))
    entry = 1 + fw + 1 + iw + 2          # " ff:iiiiiii:1"
    text = np.full((rows, 1 + fields * entry + 1), ord(" "), np.uint8)
    text[:, 0] = label + ord("0")
    text[:, -1] = ord("\n")
    for f in range(fields):
        at = 1 + f * entry
        for k in range(fw):
            text[:, at + 1 + k] = ord("0") + (f // 10 ** (fw - 1 - k)) % 10
        text[:, at + 1 + fw] = ord(":")
        col = index[:, f].astype(np.int64)
        for k in range(iw):
            text[:, at + 2 + fw + k] = ord("0") + (col // 10 ** (iw - 1 - k)) % 10
        text[:, at + 2 + fw + iw] = ord(":")
        text[:, at + 3 + fw + iw] = ord("1")
    part = f"{path}.part"
    with open(part, "wb") as out:
        out.write(text.tobytes())
    os.replace(part, path)
    return text.size


def expected_tally(label, index, batch: int, steps: int) -> dict:
    """What ``steps`` batches of the replayed file must add up to: rows,
    entries, positive labels, and two 32-bit checksums of the feature ids —
    plain, and weighted by the batch's place in its epoch, which a batch out
    of order changes."""
    per_epoch = len(label) // batch
    ids = index.astype(np.uint64).reshape(per_epoch, -1).sum(axis=1)
    pos = label.astype(np.uint64).reshape(per_epoch, batch).sum(axis=1)
    full, part = divmod(steps, per_epoch)
    times = np.full(per_epoch, full, np.uint64)
    times[:part] += 1
    place = np.arange(1, per_epoch + 1, dtype=np.uint64)
    return {"rows": steps * batch,
            "entries": steps * batch * index.shape[1],
            "positives": int((pos * times).sum()),
            "ids": int((ids * times).sum() % 2 ** 32),
            "ids_by_place": int((ids % 2 ** 32 * place * times).sum()
                                % 2 ** 32)}


def make_model(cell):
    from dmlc_core_tpu.models.ffm import FieldAwareFactorizationMachine
    s = cell.sizes
    return FieldAwareFactorizationMachine(
        num_features=s["num_features"], num_fields=s["num_fields"],
        num_factors=s["num_factors"], objective=s["objective"], l2=s["l2"],
        learning_rate=s["learning_rate"], init_scale=s["init_scale"])


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu import DeviceStagingIter
    s, p = cell.sizes, cell.params
    batch, fields = int(s["batch_size"]), int(s["num_fields"])
    rows = int(p["file_rows"])
    if rows % batch or s["entries_per_row"] != fields:
        raise ValueError("file_rows must be whole batches, one entry a field")
    t0 = time.perf_counter()
    label, index = draw_rows(cell.seed, rows, s["num_features"], fields,
                             cell.config["assumed"]["label_rate"])
    path = cell.cache_dir / "train.libfm"
    size = libfm_bytes(rows, fields, s["num_features"])
    if path.is_file() and path.stat().st_size == size:
        log(f"{path.name} of this seed is there ({size / 1e6:.0f} MB); rows "
            f"drawn again in {time.perf_counter() - t0:.1f}s")
    else:
        write_libfm(path, label, index, s["num_features"])
        log(f"wrote {path.name}: {rows} rows, {size / 1e6:.0f} MB in "
            f"{time.perf_counter() - t0:.1f}s")

    model = make_model(cell)
    it = DeviceStagingIter(str(path), format="libfm", with_field=True,
                           batch_size=batch, num_workers=int(p["num_workers"]))

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
    def moved(a, b):
        return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum((y - x) ** 2)), a, b)

    sample_ids = np.resize(np.unique(index[:int(p["sample_rows"])]),
                           int(p["sample_features"]))

    @jax.jit
    def sample_change(a, b, ids):
        return {k: b[k][ids] - a[k][ids] for k in ("w", "v")}

    state = {"cell": cell, "model": model, "it": it, "batches": replay(),
             "tally_add": tally_add, "label": label, "index": index,
             "path": path, "batch": batch, "steps": 0, "losses": [],
             "per_epoch": rows // batch,
             "tally": {k: jnp.zeros((), jnp.uint32) for k in
                       ("rows", "entries", "positives", "ids",
                        "ids_by_place")}}
    # The first steps go through the window's own iterator and train_step
    # and are what the reference follows; they also compile everything.
    state["params"] = model.init(seed31(cell.seed))
    log_memory("table made, no step yet")
    start = jax.tree.map(jnp.copy, state["params"])
    for t in range(int(p["compare_steps"])):
        state["losses"].append(float(step(state, spans)))
        if t == 0:
            state["first_grad"] = jax.device_get(
                moved(start, state["params"]))
    state["change"] = jax.device_get(moved(start, state["params"]))
    state["sample_change"] = jax.device_get(
        sample_change(start, state["params"], jnp.asarray(sample_ids)))
    state["sample_ids"] = sample_ids
    del start
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
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        loss = step(state, spans)
        if (state["steps"] - first) % every == 0:
            with spans.span("loss_fetch"):
                state["last_loss"] = float(loss)
    jax.block_until_ready(state["params"])
    elapsed = time.perf_counter() - t0
    steps = state["steps"] - first
    rows = steps * state["batch"]
    return {"metrics": {"train_rows_per_s": rows / elapsed},
            "attempted": steps, "failed": 0,
            "counts": {"rows": rows, "steps": steps,
                       "entries": rows * state["index"].shape[1]}}


def check(state: dict, reference, control: int = 0) -> list:
    import jax
    t0 = time.perf_counter()
    cell = state["cell"]
    got_tally = {k: int(v) for k, v in jax.device_get(state["tally"]).items()}
    want_tally = expected_tally(state["label"], state["index"],
                                state["batch"], state["steps"])
    for k in want_tally:
        want_tally[k] %= 2 ** 32
    mismatch = sum(got_tally[k] != want_tally[k] for k in want_tally)
    if mismatch:
        log(f"delivered {got_tally}, the file holds {want_tally}")
    state["params"] = None          # the program's table leaves the device
    n = int(cell.params["compare_steps"]) * state["batch"]
    got = {"losses": state["losses"], "first_grad": state["first_grad"],
           "change": state["change"], "sample_change": state["sample_change"]}
    out = reference.compare(
        got, state["label"][:n], state["index"][:n], state["sample_ids"],
        cell.sizes, seed31(cell.seed), control=bool(control))
    out.append({"name": "delivery_mismatch", "value": mismatch})
    log(f"reference took {time.perf_counter() - t0:.1f}s")
    return out


def teardown(state: dict) -> None:
    state["it"].close()         # the file stays for the seed's next run
    state.clear()
