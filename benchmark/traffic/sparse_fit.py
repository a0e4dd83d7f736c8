"""Traffic kind ``sparse_fit``: a wide, mostly-absent libsvm file staged whole
into one resident ``PaddedBatch`` and one GBDT instance whose ``fit_batch``
is called back to back until the window closes.

Parameters (the cell's ``params``): ``rows``, ``num_trees`` a fit,
``num_workers`` (native parse workers of the one staging pass),
``nnz_bucket`` (the batch's entry lanes are padded to a multiple of it: wide
enough that every seed's draw of the rows gives the same shapes, so that the
programs one seed compiled serve the next), ``histogram`` (the model's route; the run fails unless every level resolves
to the sparse Pallas kernel), ``regret_levels`` (the ``[tree, depth]``
levels whose chosen splits and default directions the reference holds
against its own full histogram).

The rows are drawn from the seed on the host (``draw_rows``): the
configuration's ``stations`` groups of neighbouring features, each visited
by a row with that station's probability (fixed by ``station_plan``, the same
for every seed, so that every seed gives the same amount of work to a
rounding), every feature of a visited station present, values with three
decimals and never zero, the label the top ``label_rate`` of a fixed
nonlinear score of a few values and of which stations were visited.  They
are written once a seed as libsvm text (``write_libsvm``: fixed-width
tokens, ``" 123:-1.234"``, so that the file is one uint8 matrix a chunk of
rows; no per-row Python) under the cell's cache directory, where a later run
of the same seed in the same checkout finds the file, checks its size and
only draws the rows again to compare with.  Set-up reads it back through
the native libsvm parser and ``DeviceStagingIter`` in one batch, which must
hold exactly the drawn entries; fits the binner's cuts on a COO sample; and
runs one whole fit, so that nothing compiles in the window.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import BenchFailure, log, log_memory

THREADS = 12            # numpy releases the interpreter lock
CHUNK_ROWS = 16384
TOKEN = 11              # bytes a token: " fff:-d.ddd" or " fff:d.ddd "
KEY_TILE, NNZ_TILE = 512, 1024      # the kernel's tiles, for the counts


def host_memory() -> str:
    """This process's resident and peak resident memory, for the log: the
    one-chip machine has 40 GiB and this cell's set-up holds several."""
    with open("/proc/self/status") as f:
        got = dict(line.split(":", 1) for line in f if line[:5] in
                   ("VmRSS", "VmHWM"))
    return ", ".join(f"{k} {int(v.split()[0]) / 1e6:.1f} GB"
                     for k, v in sorted(got.items()))


def station_plan(features: int, stations: int, present_share: float):
    """``(sizes, visit probability)`` of the stations, both ``[stations]``.
    Sizes are uneven and sum to ``features``; probabilities fall
    geometrically from 0.95, floored at 0.01, at the one rate that makes
    the mean over cells ``present_share``; which station gets which
    probability is scattered by a fixed stride."""
    base = 8.0 + (np.arange(stations) * 7) % 23
    sizes = np.maximum((base / base.sum() * features).astype(np.int64), 1)
    sizes[np.argmax(sizes)] += features - sizes.sum()
    stride = next(s for s in (31, 29, 23, 19, 17, 13, 11, 7, 5, 3, 1)
                  if np.gcd(s, stations) == 1)
    rank = (np.arange(stations) * stride) % stations

    def share(k):
        p = np.maximum(0.95 * np.exp(-k * rank / max(stations - 1, 1)), 0.01)
        return p, float((p * sizes).sum() / features)

    lo, hi = 0.0, 64.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if share(mid)[1] > present_share else (lo, mid)
    return sizes, share(0.5 * (lo + hi))[0]


def score_terms(sizes: np.ndarray, prob: np.ndarray):
    """The features and stations the label's rule reads: the first feature
    of each of the five most visited stations, and two stations visited by
    about a third and a tenth of the rows."""
    first = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    by_prob = np.argsort(-prob, kind="stable")
    third = int(np.argmin(np.abs(prob - 0.33)))
    tenth = int(np.argmin(np.abs(prob - 0.10)))
    return first[by_prob[:5]], third, tenth


def draw_chunk(seed: int, chunk: int, rows: int, sizes, prob, terms):
    """One chunk of rows: ``counts [rows]`` (entries a row), ``fi`` and ``q``
    (feature and value in thousandths of every entry, row-major, features
    ascending in a row), ``score [rows]``."""
    rng = np.random.default_rng([seed, chunk])
    features = int(sizes.sum())
    visit = rng.random((rows, len(sizes)), dtype=np.float32) < prob[None, :]
    present = np.repeat(visit, sizes, axis=1)
    rid, fi = np.nonzero(present)
    counts = present.sum(axis=1)
    del present
    # a feature's values: its own centre and spread, three decimals, not 0
    centre = ((np.arange(features) * 37) % 11 - 5) * 0.3
    spread = 0.5 + ((np.arange(features) * 13) % 7) * 0.25
    v = rng.standard_normal(len(fi), dtype=np.float32)
    q = np.rint((v * spread[fi] + centre[fi]) * 1000.0)
    q = np.clip(q, -9999, 9999).astype(np.int16)
    q[q == 0] = 1
    feats, third, tenth = terms

    def column(f):      # the feature's value a row, 0 where absent
        out = np.zeros(rows, np.float32)
        at = fi == f
        out[rid[at]] = q[at] / 1000.0
        return out

    a, b, c, d, e = (column(f) for f in feats)
    score = (a * b + np.sin(2.0 * c) + 0.5 * (d * d - 1.0)
             + 1.2 * visit[:, third] - 0.9 * visit[:, tenth] * e
             + 0.7 * rng.standard_normal(rows, dtype=np.float32))
    return (counts.astype(np.int32), fi.astype(np.int16), q,
            score.astype(np.float32))


def draw_rows(seed: int, rows: int, features: int, stations: int,
              present_share: float, label_rate: float) -> dict:
    """The seed's rows: ``row_ptr [rows + 1]``, ``fi``/``q`` ``[entries]``,
    ``label [rows]`` u8 (the top ``label_rate`` of the scores)."""
    sizes, prob = station_plan(features, stations, present_share)
    terms = score_terms(sizes, prob)
    bounds = [(c, min(CHUNK_ROWS, rows - c * CHUNK_ROWS))
              for c in range(-(-rows // CHUNK_ROWS))]
    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(
            lambda cn: draw_chunk(seed, cn[0], cn[1], sizes, prob, terms),
            bounds))
    counts = np.concatenate([p[0] for p in parts])
    score = np.concatenate([p[3] for p in parts])
    positives = max(int(round(label_rate * rows)), 1)
    cut = np.partition(score, rows - positives)[rows - positives]
    row_ptr = np.zeros(rows + 1, np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return {"row_ptr": row_ptr,
            "fi": np.concatenate([p[1] for p in parts]),
            "q": np.concatenate([p[2] for p in parts]),
            "label": (score >= cut).astype(np.uint8),
            "rows": rows, "features": features}


def libsvm_bytes(rows: int, entries: int) -> int:
    """Size of the file ``write_libsvm`` writes for these counts."""
    return (rows + entries) * TOKEN


def chunk_text(label, row_ptr, fi, q) -> np.ndarray:
    """The chunk's rows as a ``[rows + entries, TOKEN]`` uint8 matrix: a row
    is its label token (newline, label, blanks) and its entries' tokens
    (blank, index right-aligned in three, colon, the value in thousandths
    with its sign before and a blank after if it has none)."""
    rows, entries = len(label), len(fi)
    text = np.full((rows + entries, TOKEN), ord(" "), np.uint8)
    at_label = row_ptr[:-1] + np.arange(rows)
    is_entry = np.ones(rows + entries, bool)
    is_entry[at_label] = False
    head = np.full((rows, TOKEN), ord(" "), np.uint8)
    head[:, 0] = ord("\n")
    head[:, 1] = ord("0") + label
    text[at_label] = head
    tok = np.full((entries, TOKEN), ord(" "), np.uint8)
    idx = fi.astype(np.int32)
    tok[:, 1] = np.where(idx >= 100, ord("0") + idx // 100, ord(" "))
    tok[:, 2] = np.where(idx >= 10, ord("0") + idx // 10 % 10, ord(" "))
    tok[:, 3] = ord("0") + idx % 10
    tok[:, 4] = ord(":")
    neg = q < 0
    mag = np.abs(q.astype(np.int32))
    digits = [ord("0") + mag // 10 ** k % 10 for k in (3, 2, 1, 0)]
    point = np.full(entries, ord("."), np.int32)
    plain = [digits[0], point, digits[1], digits[2], digits[3],
             np.full(entries, ord(" "), np.int32)]
    minus = [np.full(entries, ord("-"), np.int32), digits[0], point,
             digits[1], digits[2], digits[3]]
    for k in range(6):
        tok[:, 5 + k] = np.where(neg, minus[k], plain[k])
    text[is_entry] = tok
    return text


def write_libsvm(path, data: dict) -> int:
    """``label idx:val ...`` a row, written beside ``path`` chunk by chunk
    and moved into place whole.  The first row's newline is left out and
    the last row gets one.  Returns the file's bytes."""
    rows, row_ptr = data["rows"], data["row_ptr"]
    starts = list(range(0, rows, CHUNK_ROWS))

    def one(r0):
        r1 = min(r0 + CHUNK_ROWS, rows)
        e0, e1 = int(row_ptr[r0]), int(row_ptr[r1])
        return chunk_text(data["label"][r0:r1], row_ptr[r0:r1 + 1] - e0,
                          data["fi"][e0:e1], data["q"][e0:e1])

    part = f"{path}.part"
    size = 0
    with open(part, "wb") as out, ThreadPoolExecutor(THREADS) as pool:
        # a few chunks ahead of the writer, not the whole file in memory
        for k in range(0, len(starts), THREADS):
            for text in pool.map(one, starts[k:k + THREADS]):
                flat = memoryview(text.reshape(-1))
                out.write(flat[1:] if size == 0 else flat)
                size += len(flat)
        out.write(b"\n")
    os.replace(part, path)
    return size


def tile_counts(fi: np.ndarray, features: int, num_bins: int) -> dict:
    """What the entries mean for the sparse kernel's grid, worked out here
    from the drawn entries alone: key tiles, the fullest tile's entry
    blocks (the grid's inner extent) and the blocks a level executes."""
    nb = 1 << max(num_bins - 1, 1).bit_length()
    key_tiles = -(-features * nb // KEY_TILE)
    starts = np.zeros(features + 1, np.int64)
    np.cumsum(np.bincount(fi, minlength=features), out=starts[1:])
    kt = np.arange(key_tiles, dtype=np.int64)
    flo = np.minimum(kt * KEY_TILE // nb, features)
    fhi = np.minimum(-(-(kt + 1) * KEY_TILE // nb), features)
    s, e = starts[flo], starts[fhi]
    blocks = np.where(e > s, -(-e // NNZ_TILE) - s // NNZ_TILE, 0)
    return {"key_tiles": int(key_tiles), "max_tiles": int(blocks.max()),
            "executed_tiles": int(blocks.sum())}


def stage(path, rows: int, num_workers: int, nnz_bucket: int):
    """The file through the native libsvm parser and ``DeviceStagingIter``,
    whole, as one resident batch, its entry lanes padded to a multiple of
    ``nnz_bucket``."""
    from dmlc_core_tpu import DeviceStagingIter
    it = DeviceStagingIter(str(path), format="libsvm", batch_size=rows,
                           num_workers=num_workers, nnz_bucket=nnz_bucket)
    batches = list(it)
    it.close()
    if len(batches) != 1:
        raise BenchFailure(f"staging gave {len(batches)} batches of "
                           f"{rows} rows, want the file whole in one")
    return batches[0]


def setup(cell, spans) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.models import GBDT, QuantileBinner
    from dmlc_core_tpu.ops.pallas_segment import SparseHistLayout
    if "fstart" not in getattr(SparseHistLayout, "__dataclass_fields__", ()):
        # the program before PR 27: row ids by a binary search an entry
        # (105 s a fit at this size), the layout sorted on the host in
        # int64 copies that do not fit the one-chip machine's memory
        raise BenchFailure("this program cannot run the cell: it has no "
                           "device-built sparse layout (PR 27)")
    sizes, p, assumed = cell.sizes, cell.params, cell.config["assumed"]
    rows, features = int(p["rows"]), int(sizes["num_features"])
    t0 = time.perf_counter()
    data = draw_rows(cell.seed, rows, features, int(assumed["stations"]),
                     float(sizes["present_share"]),
                     float(assumed["label_rate"]))
    entries = int(data["row_ptr"][-1])
    path = cell.cache_dir / "train.libsvm"
    size = libsvm_bytes(rows, entries)
    if path.is_file() and path.stat().st_size == size:
        log(f"{path.name} of this seed is there ({size / 1e6:.0f} MB); rows "
            f"drawn again in {time.perf_counter() - t0:.1f}s")
    else:
        t1 = time.perf_counter()
        write_libsvm(path, data)
        log(f"drew {rows} rows, {entries} entries in {t1 - t0:.1f}s; wrote "
            f"{path.name}, {size / 1e6:.0f} MB, in "
            f"{time.perf_counter() - t1:.1f}s")
    t1 = time.perf_counter()
    batch = stage(path, rows, int(p["num_workers"]), int(p["nnz_bucket"]))
    want = (data["q"] / 1000.0).astype(np.float32)
    lanes = int(batch.index.shape[0])

    @jax.jit
    def same(b, fi, value, row_ptr, label):
        # on the device: a host copy of a device array stays with the array
        live = jnp.arange(lanes) < entries
        return (jnp.all(jnp.where(live, b.index, 0) == fi)
                & jnp.all(jnp.where(live, b.value, 0.0) == value)
                & ~jnp.any(jnp.where(live, 0.0, b.value) != 0)
                & jnp.all(b.row_ptr == row_ptr)
                & jnp.all((b.label > 0.5) == (label > 0)))

    pad = lanes - entries
    if (int(batch.num_rows) != rows or pad < 0
            or int(batch.label.shape[0]) != rows or not bool(same(
                batch, np.pad(data["fi"].astype(np.int32), (0, pad)),
                np.pad(want, (0, pad)), data["row_ptr"].astype(np.int32),
                data["label"]))):
        raise BenchFailure("the staged batch is not the rows that were "
                           "written: parser or staging lost or changed some")
    log(f"staged {rows} rows, {entries} entries ({lanes} lanes) in "
        f"{time.perf_counter() - t1:.1f}s; they are the rows drawn; host "
        f"memory {host_memory()}")
    data["value"] = want

    binner = QuantileBinner(num_bins=sizes["num_bins"],
                            missing_aware=sizes["missing_aware"])
    sample = int(data["row_ptr"][min(int(assumed["binner_sample_rows"]),
                                     rows)])
    t1 = time.perf_counter()
    binner.fit_sparse(data["fi"][:sample], want[:sample], features)
    log(f"cuts from {sample} entries in {time.perf_counter() - t1:.1f}s")
    model = GBDT(num_features=features, num_trees=int(p["num_trees"]),
                 max_depth=sizes["max_depth"], num_bins=sizes["num_bins"],
                 learning_rate=sizes["learning_rate"],
                 lambda_=sizes["lambda"],
                 min_child_weight=sizes["min_child_weight"],
                 objective=sizes["objective"],
                 missing_aware=sizes["missing_aware"],
                 histogram=p["histogram"])
    levels = model.level_backends(sparse=True)
    if levels != ["pallas"] * int(sizes["max_depth"]):
        raise BenchFailure(f"sparse histogram levels resolved to {levels}: "
                           "this cell times the sparse Pallas kernel at "
                           "every level and nothing else")
    log_memory("batch resident, no fit yet")
    log(f"host memory before the warm-up fit: {host_memory()}")
    state = {"cell": cell, "model": model, "binner": binner, "batch": batch,
             "data": data, "rows": rows, "entries": entries, "forest": None,
             "tiles": tile_counts(data["fi"], features, sizes["num_bins"])}
    t1 = time.perf_counter()
    fit_once(state)     # compiles the sort, the tree and the boosting ops
    log(f"warm-up fit {time.perf_counter() - t1:.1f}s; host memory "
        f"{host_memory()}")
    return state


def fit_once(state: dict) -> None:
    """The timed call: one ``fit_batch`` to its end.  The tests break it
    here."""
    import jax
    state["forest"] = jax.block_until_ready(
        state["model"].fit_batch(state["batch"], state["binner"]))


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
    depth = state["model"].max_depth
    counts = {"rows": rows * rounds, "rounds": rounds,
              "levels": rounds * depth, "entries": state["entries"],
              "data_rows": rows, "features": state["model"].num_features,
              "bins": state["model"].num_bins, "max_depth": depth}
    counts.update(state["tiles"])
    return {"metrics": {"train_rows_per_s": rows * rounds / elapsed},
            "attempted": rounds // trees, "failed": 0, "counts": counts}


def check(state: dict, reference, control: int = 0) -> list:
    """Hold the forest the window's last fit returned, at the timed size,
    against the float64 reference, which bins the drawn entries itself
    under the program's cuts."""
    t0 = time.perf_counter()
    log(f"host memory before the reference: {host_memory()}")
    forest = {k: np.asarray(v) for k, v in state["forest"].items()}
    data, cell = state["data"], state["cell"]
    out = reference.compare(
        data["row_ptr"], data["fi"], data["value"],
        np.asarray(state["binner"].cuts), data["label"], forest, cell.sizes,
        int(cell.params["num_trees"]), cell.params["regret_levels"],
        int(cell.config["assumed"]["binner_sample_rows"]),
        control=bool(control))
    log(f"reference took {time.perf_counter() - t0:.1f}s; host memory "
        f"{host_memory()}")
    return out


def teardown(state: dict) -> None:
    state.clear()
