"""Sparse CSR/COO ops on static padded shapes — the TPU analogue of the
reference's Row::SDot loop (include/dmlc/data.h:146-161).

All ops take flattened COO arrays (index/value/row_id from a PaddedBatch) so
they jit to gathers + segment-sums with fully static shapes.  The dense-side
operands (weight vectors / embedding tables) are where the MXU work lives for
FM-style models.  The reduction backend is selectable per call (``force``,
threaded to ops.segment_sum): None/"xla" keeps XLA's scatter-add, "pallas"
runs the tiled one-hot-contraction kernel — the same scatter-free trade the
GBDT histogram uses, for the Row::SDot reductions of the linear/FM models.
Padding convention: value == 0 ⇒ the entry contributes nothing.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pallas_segment import segment_sum


def csr_matvec(weights: jax.Array, index: jax.Array, value: jax.Array,
               row_id: jax.Array, num_rows: int,
               force: str | None = None) -> jax.Array:
    """Per-row sparse dot product: out[r] = Σ_{k: row_id[k]=r} w[index[k]]·value[k].

    The vectorized Row::SDot: one gather + one segment-sum.
    """
    contrib = weights[index] * value
    return segment_sum(contrib, row_id, num_rows, force=force)


def csr_matmul(table: jax.Array, index: jax.Array, value: jax.Array,
               row_id: jax.Array, num_rows: int,
               force: str | None = None) -> jax.Array:
    """Sparse×dense: out[r, :] = Σ_k value[k] · table[index[k], :].

    `table` is [num_features, K] (an embedding / factor matrix); output
    [num_rows, K].  Gather rows, scale, segment-sum (K lanes share one
    kernel pass under force="pallas").
    """
    gathered = table[index] * value[:, None]
    return segment_sum(gathered, row_id, num_rows, force=force)


def csr_row_sumsq_matmul(table: jax.Array, index: jax.Array, value: jax.Array,
                         row_id: jax.Array, num_rows: int,
                         force: str | None = None) -> jax.Array:
    """out[r, :] = Σ_k value[k]² · table[index[k], :]² (FM second-order term)."""
    gathered = (table[index] ** 2) * (value[:, None] ** 2)
    return segment_sum(gathered, row_id, num_rows, force=force)


def padded_row_mean(per_row: jax.Array, weight: jax.Array) -> jax.Array:
    """Weighted mean over rows that treats padding rows (weight 0) as absent."""
    total = jnp.sum(weight)
    return jnp.sum(per_row * weight) / jnp.maximum(total, 1.0)


def csr_to_dense(index: jax.Array, value: jax.Array, row_id: jax.Array,
                 num_rows: int, num_features: int) -> jax.Array:
    """Densify a COO batch: out[r, f] = Σ_{k: row_id[k]=r, index[k]=f} value[k].

    The bridge from the staged sparse pipeline to dense consumers (the
    binned GBDT path); a single scatter-add with static output shape.
    Padding lanes (value 0) contribute nothing; entries with out-of-range
    feature or row ids are dropped (not aliased into a real column).
    """
    out = jnp.zeros((num_rows, num_features), value.dtype)
    return out.at[row_id, index].add(value, mode="drop")


def csr_to_dense_missing(index: jax.Array, value: jax.Array,
                         row_id: jax.Array, num_rows: int,
                         num_features: int) -> jax.Array:
    """Densify with NaN for ABSENT cells instead of 0 — the sparse-data
    semantics XGBoost uses (absent feature != zero-valued feature).  Feed
    the result to a ``missing_aware`` QuantileBinner/GBDT pair.

    Note the staging pad convention (value == 0 lanes) cannot mark
    presence, so a real stored 0 at a padding lane's (row, col) target is
    indistinguishable from padding; stage with nnz-exact buckets or accept
    that explicit zeros in the data behave as missing.
    """
    # one fused two-lane scatter (value, presence): the (row_id, index)
    # key arrays are read once, matching the histogram-build pattern
    lanes = jnp.stack([value.astype(jnp.float32),
                       (value != 0).astype(jnp.float32)], axis=-1)
    acc = jnp.zeros((num_rows, num_features, 2), jnp.float32
                    ).at[row_id, index].add(lanes, mode="drop")
    return jnp.where(acc[..., 1] > 0, acc[..., 0], jnp.nan)


# ---- reduction onto the distinct keys of a batch ----------------------------


def _over(mask: jax.Array, like: jax.Array) -> jax.Array:
    """A mask a lane, broadcast over ``like``'s trailing dimensions."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def run_sums(keys: jax.Array, *columns: jax.Array, below=None,
             looped: bool = False) -> tuple:
    """Inclusive running sum of each of ``columns`` (``[lanes]`` or ``[lanes,
    K]``: every trailing element along its own run) inside every run of
    equal ``keys`` (runs contiguous, as after a sort): the last lane of a run
    holds the run's sum.  ``ceil(log2(n))`` passes of one shifted add — no
    scatter, and a pairwise order of summation.  ``below`` (a power of two):
    only the passes at a distance under it, after which a lane holds the sum
    of the last ``below`` lanes of its run, itself included.  ``looped``: the
    passes as ONE loop body (:func:`_looped_passes`), for a path that hardly
    ever runs."""
    if looped:
        return _looped_passes(keys, columns, fill=False)
    n, d = keys.shape[0], 1
    while d < min(n, below or n):
        same = jnp.concatenate([jnp.zeros(d, bool), keys[d:] == keys[:-d]])
        columns = tuple(
            c + jnp.where(_over(same, c), jnp.concatenate(
                [jnp.zeros((d,) + c.shape[1:], c.dtype), c[:-d]]), 0)
            for c in columns)
        d *= 2
    return columns


def run_fill(keys: jax.Array, *columns: jax.Array,
             looped: bool = False) -> tuple:
    """The value on the LAST lane of every run of equal ``keys`` (runs
    contiguous) set on all the run's lanes: :func:`run_sums`'s passes the
    other way round, by select and not by adding zeros, so every bit
    (``-0.0``'s sign, an int32) arrives as it was.  After the pass at
    distance ``d`` a lane holds lane ``min(i + 2d - 1, its run's end)``.
    ``looped``: as :func:`run_sums`'s."""
    if looped:
        return _looped_passes(keys, columns, fill=True)
    n, d = keys.shape[0], 1
    while d < n:
        same = jnp.concatenate([keys[:-d] == keys[d:], jnp.zeros(d, bool)])
        columns = tuple(
            jnp.where(same, jnp.concatenate([c[d:], jnp.zeros(d, c.dtype)]), c)
            for c in columns)
        d *= 2
    return columns


def _looped_passes(keys: jax.Array, columns: tuple, fill: bool) -> tuple:
    """:func:`run_sums` (or with ``fill`` :func:`run_fill`) of ``columns``
    with the passes in a loop over the distances, the shift a dynamic slice:
    the same values pass for pass, in one body whatever the lanes (written
    out, a pass over ``[lanes, K]`` is 0.2-0.4 MB of the TPU's program)."""
    n = keys.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    keys2 = jnp.concatenate([keys, keys])

    def one(i, columns):
        d = jnp.left_shift(1, i)
        # a fill looks ``d`` lanes ahead, a sum ``d`` lanes back
        at = d if fill else n - d
        same = (jax.lax.dynamic_slice_in_dim(keys2, at, n) == keys) & (
            lane + d < n if fill else lane >= d)
        moved = tuple(jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([c, c]), at, n) for c in columns)
        if fill:
            return tuple(jnp.where(_over(same, c), m, c)
                         for c, m in zip(columns, moved))
        return tuple(c + jnp.where(_over(same, c), m, 0)
                     for c, m in zip(columns, moved))

    if not columns:
        return ()
    return jax.lax.fori_loop(0, max(n - 1, 0).bit_length(), one,
                             tuple(columns))


class KeyRuns(NamedTuple):
    """How a batch's entries lie on its distinct keys, as the two sorts of
    :func:`reduce_by_key` found it; what :func:`spread_by_key` takes back
    the other way.  All on the entry lanes."""
    #: the compact lanes: :func:`reduce_by_key`'s ``keys`` and ``count``
    keys: jax.Array
    count: jax.Array
    #: the live entries' keys ascending, ``bound`` on the dead ones after
    sorted_keys: jax.Array
    #: the entry lane that each sorted lane came from
    order: jax.Array
    #: the sorted lane where compact lane ``j``'s run ends; after the
    #: distinct keys, the sorted lanes that end no run (a permutation)
    ends: jax.Array


def reduce_by_key(index: jax.Array, live: jax.Array, columns: tuple,
                  bound: int, rows: tuple = (), runs: bool = False,
                  looped: bool = False) -> tuple:
    """Per-entry ``columns`` summed onto the distinct ``index`` values of
    the ``live`` entries.  Sorts are this chip's cheap primitive (0.8 ms for
    655,360 lanes of key and payload on a v5e, where a gather or a scatter
    of as many elements takes 10), so both halves are one: a sort by key
    that carries the columns, :func:`run_sums`, and a second sort that
    brings the lanes where a run ends to the front.  Returns ``(keys, sums,
    count)`` on the entry lanes: the first ``count`` lanes hold the distinct
    keys, ascending, and their sums; every later lane holds a distinct id
    ``>= bound``, ascending too, so that a gather with ``mode="fill"`` or a
    scatter with ``mode="drop"`` may be told ``unique_indices`` and
    ``indices_are_sorted`` and passes those lanes over.  Needs
    ``bound + len(index) <= 2**31``.

    ``rows``: payloads of K floats an entry (``[lanes, K]``).  A sort that
    carried K columns more would pay for each, so with them the first sort
    also carries the lane it took an entry from, each of ``rows`` is brought
    into key order by ONE gather of rows and summed along its runs, and the
    second sort carries the lane where each run ends: a fourth element
    ``(running, ends)`` is returned, and the rows' sums at the first
    ``lanes`` distinct keys are ``r[ends[:lanes]]`` for each ``r`` of
    ``running`` — a gather of as many rows as a visit takes, not of every
    entry.

    ``runs``: the sorts carry the lanes as they do for ``rows`` and a last
    element, the :class:`KeyRuns`, is returned for :func:`spread_by_key`.

    ``looped``: the run sums' passes in a loop (:func:`run_sums`)."""
    n = index.shape[0]
    key = jnp.where(live, index, bound)
    lane = (jnp.arange(n, dtype=jnp.int32),) if rows or runs else ()
    sk, *sc = jax.lax.sort((key, *lane, *columns), num_keys=1, is_stable=False)
    order, sc = sc[:len(lane)], sc[len(lane):]
    sums = run_sums(sk, *sc, looped=looped)
    ends = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones(1, bool)]) & (
        sk < bound)
    spare = bound + jnp.arange(n, dtype=sk.dtype)
    keys, *sums = jax.lax.sort((jnp.where(ends, sk, spare), *lane, *sums),
                               num_keys=1, is_stable=False)
    at, sums = sums[:len(lane)], tuple(sums[len(lane):])
    count = jnp.sum(ends, dtype=jnp.int32)
    out = (keys, sums, count)
    if rows:
        out += ((run_sums(sk, *(r[order[0]] for r in rows), looped=looped),
                 at[0]),)
    if runs:
        out += (KeyRuns(keys, count, sk, order[0], at[0]),)
    return out


def spread_by_key(runs: KeyRuns, columns: tuple,
                  looped: bool = False) -> tuple:
    """The transpose of :func:`reduce_by_key`: ``columns`` hold one value a
    DISTINCT key on the compact lanes (``c[j]`` belongs to ``runs.keys[j]``;
    ``[m]`` for any ``m`` that holds the distinct keys, any 32-bit dtype),
    and every live entry gets its own key's value, bit for bit what a gather
    of the column at the entry's rank would give; a dead entry gets 0.  No
    gather an entry: a sort that sets each compact lane on the lane where
    its run ends, :func:`run_fill` along the runs, and a sort by the lane
    each entry came from.  A key's rank rides as a column too
    (``jnp.arange``), and rows of K floats then take ONE gather by it out
    of their compact array (a sort would pay for each of the K columns).
    ``looped``: the fill's passes in a loop (:func:`run_fill`)."""
    n = runs.order.shape[0]
    held = jnp.arange(n, dtype=jnp.int32) < runs.count
    columns = tuple(
        jnp.where(held, jnp.concatenate(
            [c, jnp.zeros(n - c.shape[0], c.dtype)]), 0) for c in columns)
    _, *at_ends = jax.lax.sort((runs.ends, *columns), num_keys=1,
                               is_stable=False)
    filled = run_fill(runs.sorted_keys, *at_ends, looped=looped)
    _, *spread = jax.lax.sort((runs.order, *filled), num_keys=1,
                              is_stable=False)
    return tuple(spread)


# ---- sums over the rows of a CSR batch, without a scatter an entry ----------


def csr_row_spread(per_row: jax.Array, row_id: jax.Array,
                   row_ptr: jax.Array) -> jax.Array:
    """``per_row[row_id]`` an entry lane (the transpose of
    :func:`csr_row_sums`; ``per_row`` of ``[rows]`` or ``[rows, K]``): each
    row's value set on its first lane — one scatter a ROW — and carried
    along the row's run by :func:`run_sums`, which adds zeros to it and so
    changes no bit."""
    lanes = row_id.shape[0]
    first = jnp.where(row_ptr[1:] > row_ptr[:-1], row_ptr[:-1], lanes)
    marks = jnp.zeros((lanes,) + per_row.shape[1:], per_row.dtype).at[
        first].set(per_row, mode="drop", unique_indices=True)
    return run_sums(row_id, marks)[0]


@jax.custom_vjp
def csr_row_sums(contrib: jax.Array, row_id: jax.Array,
                 row_ptr: jax.Array) -> jax.Array:
    """``out[r] = sum of contrib over row r's lanes`` (``contrib`` of
    ``[lanes]`` or ``[lanes, K]``) for a CSR batch whose lanes lie in row
    order (``row_id`` from ``PaddedBatch.row_ids()``): :func:`run_sums`
    along the rows and one gather a ROW at its last lane.
    What ``segment_sum(contrib, row_id, rows)`` gives, where that is a
    scatter-add an entry forward and a gather an entry backward (5.8 and 5.7
    ms for 655,360 entries of 16,384 rows on a v5e, against 0.3 and 0.5)."""
    (running,) = run_sums(row_id, contrib)
    held = row_ptr[1:] > row_ptr[:-1]
    return jnp.where(_over(held, running),
                     running[jnp.maximum(row_ptr[1:] - 1, 0)], 0.0)


def _csr_row_sums_fwd(contrib, row_id, row_ptr):
    return csr_row_sums(contrib, row_id, row_ptr), (row_id, row_ptr)


def _csr_row_sums_bwd(res, ct):
    row_id, row_ptr = res
    return csr_row_spread(ct, row_id, row_ptr), None, None


csr_row_sums.defvjp(_csr_row_sums_fwd, _csr_row_sums_bwd)


# ---- sums along runs at two levels ------------------------------------------
# Rows so wide that a pass of ``run_sums`` over every entry lane goes through
# HBM (156 floats a row: 5 ms a pass on a v5e, 20 passes) are summed along
# WINDOWS inside their keys' runs first, ``run_sums(..., below=window)``, and
# then the windows a run is made of are summed: those are a ``window``-th of
# the entries and one more a run.  (Nothing here is gathered a lane where a
# sort or a running maximum does: a gather of one float an entry lane takes
# 5 ms of a v5e, a sort of the lanes 0.8.)


def run_windows(sorted_keys: jax.Array, live: jax.Array,
                window: int) -> tuple:
    """``(ids, anchors)`` of the windows that tile the runs of
    ``sorted_keys``, ``window`` lanes each from a run's start (its last one
    what is left): ``ids`` the lane a sorted lane's window starts on, a key
    for :func:`run_sums` (``below=window``: the passes a window needs, after
    which a window's last lane holds its sum); ``anchors`` those last lanes,
    ascending, ids past the lanes after them.  A running maximum and one
    sort of the lanes."""
    n = live.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    start = jnp.concatenate(
        [jnp.ones(1, bool), sorted_keys[1:] != sorted_keys[:-1]])
    run_start = jax.lax.cummax(jnp.where(start, lane, 0))
    ids = lane - (lane - run_start) % window
    last = live & jnp.concatenate([ids[1:] != ids[:-1], jnp.ones(1, bool)])
    return ids, jax.lax.sort(jnp.where(last, lane, n + lane),
                             is_stable=False)


def window_totals(sums: tuple, sorted_keys: jax.Array, anchors: jax.Array,
                  lo: jax.Array, hi: jax.Array, held: int, keys: int,
                  bound: int) -> tuple:
    """Each of ``sums`` (``run_sums(ids, ..., below=window)`` over
    :func:`run_windows`' ``ids``) summed
    over the windows of every run that lies on the sorted lanes ``[lo, hi)``
    (whole runs, at most ``keys`` of them, tiled by at most ``held`` windows):
    ``[keys, ...]`` in key order, what the whole runs' sums hold at those
    runs' ends; rows past the runs hold nothing a caller keeps.  The windows'
    lanes are gathered, summed along their keys' runs, and a sort brings each
    key's last window to the front."""
    n = sorted_keys.shape[0]
    sorted_distinct = dict(mode="fill", unique_indices=True,
                           indices_are_sorted=True)
    spare = 2 * n + jnp.arange(held, dtype=jnp.int32)
    at = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([anchors, spare]),
        jnp.searchsorted(anchors, lo).astype(jnp.int32), held)
    at = jnp.where(at < hi, at, spare)
    of_key = sorted_keys.at[at].get(fill_value=bound, **sorted_distinct)
    totals = run_sums(of_key, *(s.at[at].get(fill_value=0, **sorted_distinct)
                                for s in sums))
    last = jnp.concatenate([of_key[1:] != of_key[:-1], jnp.ones(1, bool)]) & (
        of_key < bound)
    place = jnp.arange(held, dtype=jnp.int32)
    ends = jax.lax.sort(jnp.where(last, place, held + place),
                        is_stable=False)[:keys]
    return tuple(t.at[ends].get(fill_value=0, **sorted_distinct)
                 for t in totals)


# ---- sums along runs as ONE loop body ---------------------------------------
# ``run_sums`` writes its passes out, a fusion or two each, and the TPU's
# compiler makes 0.2-0.4 MB of program of each over ``[entries, K]``: forty of
# them (a sum and its backward) are a second of every warm start's fetch.  A
# path that hardly ever runs takes its passes in a loop instead: the shift a
# dynamic slice, one body whatever the lanes.


def _run_passes(keys: jax.Array, column: jax.Array, back: bool) -> jax.Array:
    """``run_sums(keys, column)[0]``, or with ``back`` the same sums taken
    from each run's END (a lane holds its own and every later lane's of its
    run), as a loop over the distances."""
    n = keys.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    keys2 = jnp.concatenate([keys, keys])

    def one(i, c):
        d = jnp.left_shift(1, i)
        at = d if back else n - d
        other = jax.lax.dynamic_slice_in_dim(keys2, at, n)
        inside = lane + d < n if back else lane >= d
        moved = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([c, c]), at, n)
        return c + jnp.where(_over((other == keys) & inside, c), moved, 0)

    return jax.lax.fori_loop(0, max(n - 1, 0).bit_length(), one, column)


@jax.custom_vjp
def slot_sums(contrib: jax.Array, slot: jax.Array,
              ptr: jax.Array) -> jax.Array:
    """:func:`csr_row_sums` (``contrib`` ``[lanes, K]`` summed over the runs
    of equal ``slot``, read at ``ptr[1:] - 1``) with its passes in a loop,
    forward and backward: for the field-aware product's general branch."""
    running = _run_passes(slot, contrib, False)
    held = ptr[1:] > ptr[:-1]
    return jnp.where(_over(held, running),
                     running[jnp.maximum(ptr[1:] - 1, 0)], 0.0)


def _slot_sums_fwd(contrib, slot, ptr):
    return slot_sums(contrib, slot, ptr), (slot, ptr)


def _slot_sums_bwd(res, ct):
    slot, ptr = res
    lanes = slot.shape[0]
    last = jnp.where(ptr[1:] > ptr[:-1], ptr[1:] - 1, lanes)
    marks = jnp.zeros((lanes,) + ct.shape[1:], ct.dtype).at[last].set(
        ct, mode="drop", unique_indices=True)
    return _run_passes(slot, marks, True), None, None


slot_sums.defvjp(_slot_sums_fwd, _slot_sums_bwd)
