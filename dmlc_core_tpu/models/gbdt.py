"""Histogram gradient-boosted decision trees, TPU-native.

The reference library exists to feed XGBoost: its data layer produces the
RowBlocks XGBoost's hist algorithm consumes, and its tracker brokers the
rabit allreduce XGBoost uses to combine per-worker **gradient histograms**
(reference tracker/dmlc_tracker/tracker.py:185-252 builds that tree+ring
topology; BASELINE target 5 is "XGBoost-hist Higgs-11M").  This module is
the TPU-native closure of that loop: the same hist algorithm, designed for
XLA —

* features are quantile-binned once into uint8 (``QuantileBinner``), so a
  dataset is a dense ``[rows, features]`` byte matrix — static shapes,
  VPU-friendly gathers, 4-32x smaller than f32 in HBM;
* each tree level is ONE jitted pass: a fused segment-sum builds the
  ``[nodes, features, bins]`` (grad, hess) histograms, split finding is a
  dense cumsum + argmax over that array, and row→child routing is a gather
  — no per-node recursion, no data-dependent control flow;
* under a mesh with rows sharded over ``data`` and tree state replicated,
  XLA lowers the histogram reduction to a psum over ICI — the rabit
  histogram-allreduce, as a compiler-inserted collective (SURVEY §5's
  "distributed communication backend" mapping);
* trees are fixed-depth complete binary heaps in flat arrays
  (``feature/threshold`` per internal node, ``leaf`` per leaf), so
  prediction is ``max_depth`` vectorized gathers — XLA-friendly and
  checkpointable as a plain pytree via dmlc_core_tpu.checkpoint.

Below the root a level builds ONE child of every parent, the one with the
smaller hessian mass, and derives its sibling as parent - built (XGBoost
hist's subtraction): the kernels' time grows with the node columns on the
MXU's M axis, so half the columns is a level's time of the level above.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .common import count_predict_retrace
from ..ops import pallas_segment
from ..ops.pallas_segment import (HIST_NODE_LIMIT, SPARSE_HIST_NODE_LIMIT,
                                  entry_values, hist_dead_key_tiles,
                                  histogram_gh,
                                  histogram_gh_sparse_kernel,
                                  layout_bin_engages, push_to_rows,
                                  route_push_engages, run_spans, segment_sum,
                                  sparse_hist_layout)

# Most nodes a level for which `GBDT._route_level` finds a row's split by
# comparing its node id with every node of the level; beyond it, by one
# gather a row from the level's packed table.  On a v5e the compares cost
# 10.5M rows 36 ms at 4,096 nodes and 96 ms at 8,192, the gather 76 ms
# whatever the table's size (PERF.md, PR 26).
_ROUTE_SELECT_NODES = 4096


def counter_add(name: str, n: int) -> None:
    """Add to a native telemetry counter (without the native runtime the
    models stay pure-JAX usable)."""
    try:
        telemetry.counter_add(name, n)
    except Exception:
        pass


def _first_lane(lo: jax.Array, hi: jax.Array, rounds: int, before
                ) -> jax.Array:
    """For every span ``[lo, hi)`` of lanes the first lane at which
    ``before(lane)`` no longer holds (``hi`` if it holds throughout), by
    ``rounds`` halvings — enough for the longest span.  ``before`` must hold
    on a prefix of each span and is only asked about lanes inside it."""
    def halve(_, span):
        lo, hi = span
        mid = (lo + hi) // 2
        go = (lo < hi) & before(jnp.where(lo < hi, mid, lo))
        return jnp.where(go, mid + 1, lo), jnp.where(go | (lo >= hi), hi, mid)

    return jax.lax.fori_loop(0, rounds, halve, (lo, hi))[0]


# Entries from which `_bin_entries` bins by sorting them, not by a bisection
# an entry.  At 2.18e8 entries the sorts win by eight times on a v5e (2.5 s
# against 20.2 s), but a sort costs the TPU's compiler 9.8 s a shape at 2^14
# lanes, 39.7 s at 2^17 and about 54 s at 2^20, where the bisection compiles
# in 1.6 s (PERF.md, PR 27).  A scoring server's first request of each
# bucket compiles what it runs inside the server's 30 s wait, so the
# threshold lies above a request's bucket (1,024 rows of up to 16,384
# entries each): 2^24 entries, which the bisection bins in about 1.5 s.
# Who still bins in entry order: scoring and eval sets, `fit_streamed`, and
# a `fit_batch` whose layout cannot bin for it (`GBDT._layout_bins`) or
# whose tree keeps the entries beside the layout; where the layout is the
# only copy of a value-carrying batch's entries, its own sort carries the
# values and `ops.pallas_segment._bin_runs_pallas` bins them after it.
_BIN_BY_SORT_ENTRIES = 1 << 24


def _bin_entries(cuts: jax.Array, index: jax.Array, value: jax.Array
                 ) -> jax.Array:
    """``1 + #{c: cuts[index[k], c] <= value[k]}`` for every entry k, 0 for
    a NaN value; ``cuts``: [features, C], non-decreasing along C.  Two
    forms with one result, chosen by the number of entries
    (`_BIN_BY_SORT_ENTRIES`).  A feature id outside ``[0, features)`` is
    clamped, as a gather's index is."""
    if value.shape[0] < _BIN_BY_SORT_ENTRIES:
        return _bin_by_bisection(cuts, index, value)
    return _bin_by_sort(cuts, index, value)


@jax.jit
def _bin_by_bisection(cuts, index, value):
    """A vectorized binary search — ``ceil(log2(C+1))`` rounds of one
    gather an entry each, instead of materializing the [nnz, C] per-entry
    cut matrix."""
    C = cuts.shape[1]
    fi = jnp.clip(index.astype(jnp.int32), 0, cuts.shape[0] - 1)
    v = value.astype(jnp.float32)
    lo = jnp.zeros(v.shape, jnp.int32)
    hi = jnp.full(v.shape, C, jnp.int32)
    for _ in range(max(1, int(np.ceil(np.log2(C + 1))))):
        mid = (lo + hi) // 2
        cut = cuts[fi, jnp.minimum(mid, C - 1)]
        go = (cut <= v) & (mid < hi)  # searchsorted side="right"
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    # NaN entries read as missing (code 0), matching the dense transform
    return jnp.where(jnp.isnan(v), 0, lo + 1).astype(jnp.int32)


@jax.jit
def _bin_by_sort(cuts, index, value):
    """The entries are sorted by (feature, value), each of the
    ``features * C`` cuts is placed among its feature's values by bisection
    (a gather a *cut* a round), a running count of the cuts placed at or
    before each lane is the code, and a second sort puts the codes back in
    entry order."""
    features, num_cuts = cuts.shape
    n = value.shape[0]
    v = value.astype(jnp.float32)
    key = jnp.clip(index.astype(jnp.int32), 0, features - 1)
    key_s, v_s, lane = jax.lax.sort(
        (key, v, jnp.arange(n, dtype=jnp.int32)), num_keys=2)
    cut_f = jnp.repeat(jnp.arange(features, dtype=jnp.int32), num_cuts)
    cut_v = cuts.astype(jnp.float32).reshape(-1)
    # the first lane that does not sort before the cut
    place = _first_lane(
        jnp.zeros(cut_f.shape, jnp.int32), jnp.full(cut_f.shape, n),
        n.bit_length(), lambda lane: (key_s[lane] < cut_f) | (
            (key_s[lane] == cut_f) & (v_s[lane] < cut_v)))
    # every cut of a lower feature lies at or before a lane, none of a
    # higher one does, and a cut of the lane's own feature does when it is
    # at or below the lane's value
    placed = jnp.cumsum(jnp.zeros(n, jnp.int32).at[place].add(1, mode="drop"))
    _, code = jax.lax.sort((lane, 1 + placed - key_s * num_cuts), num_keys=1)
    # NaN entries read as missing (code 0), matching the dense transform
    return jnp.where(jnp.isnan(v), 0, code).astype(jnp.int32)


class QuantileBinner:
    """Per-feature quantile binning to uint8 codes (XGBoost-hist's sketch).

    ``fit`` computes per-feature quantile cut points on a host sample
    (numpy; the sketch is a once-per-dataset preprocessing step);
    ``transform`` is jittable and maps values to bin codes in
    ``[0, num_bins)`` via searchsorted over the cuts.

    With ``missing_aware=True`` bin 0 is RESERVED for missing values
    (NaN); present values map to ``[1, num_bins)``.  Pair with
    ``GBDT(missing_aware=True)``, which then learns a per-node default
    direction for the missing bin (XGBoost's sparsity-aware splits,
    the semantics sparse libsvm data wants: absent feature != 0).
    """

    def __init__(self, num_bins: int = 256, missing_aware: bool = False,
                 sketch_size: int = 4096, sketch_seed: int = 0):
        if not 2 <= num_bins <= 256:
            raise ValueError("num_bins must be in [2, 256] (uint8 codes)")
        if missing_aware and num_bins < 3:
            raise ValueError("missing_aware needs >= 3 bins")
        if sketch_size < num_bins:
            raise ValueError("sketch_size must be >= num_bins")
        self.num_bins = num_bins
        self.missing_aware = missing_aware
        # streaming-sketch knobs (partial_fit*/finalize): per-feature
        # reservoir capacity (memory = features x sketch_size x 4 B;
        # nearest-rank quantile error ~ 1/sqrt(sketch_size)) and the seed
        # making a streamed fit deterministic
        self.sketch_size = sketch_size
        self.sketch_seed = sketch_seed
        # f32 [features, value_bins - 1] where value_bins excludes bin 0
        # in missing_aware mode
        self.cuts: Optional[jax.Array] = None

    @telemetry.span("binner.fit", total="binner.fit_us")
    def fit(self, sample: np.ndarray) -> "QuantileBinner":
        sample = np.asarray(sample, np.float32)
        if sample.ndim != 2:
            raise ValueError("fit expects [rows, features]")
        if not self.missing_aware and np.isnan(sample).any():
            # without the reserved bin, searchsorted would silently map NaN
            # to the TOP bin — a plausible-looking but wrong model
            raise ValueError(
                "sample contains NaN but missing_aware=False; construct "
                "QuantileBinner(..., missing_aware=True) (and pair it with "
                "GBDT(missing_aware=True)) to model missing values")
        value_bins = self.num_bins - 1 if self.missing_aware else self.num_bins
        qs = np.linspace(0.0, 1.0, value_bins + 1)[1:-1]
        import warnings
        with warnings.catch_warnings():
            # an all-NaN column (fully-missing feature) is legal input;
            # nanquantile warns through the warnings module, not errstate
            warnings.simplefilter("ignore", RuntimeWarning)
            cuts = np.nanquantile(sample, qs, axis=0).T
        cuts = np.nan_to_num(cuts)  # all-missing feature: degenerate cuts
        # non-decreasing cuts keep searchsorted stable on ties
        cuts = np.maximum.accumulate(cuts, axis=1)
        self.cuts = jnp.asarray(cuts)
        return self

    def transform(self, x: jax.Array) -> jax.Array:
        """[rows, features] float -> [rows, features] uint8 bin codes."""
        if self.cuts is None:
            raise RuntimeError("QuantileBinner.transform before fit")
        codes = jax.vmap(
            lambda col, cut: jnp.searchsorted(cut, col, side="right"),
            in_axes=(1, 0), out_axes=1)(x, self.cuts)
        if self.missing_aware:
            codes = jnp.where(jnp.isnan(x), 0, codes + 1)
        return codes.astype(jnp.uint8)

    def fit_transform(self, x: np.ndarray) -> jax.Array:
        return self.fit(x).transform(jnp.asarray(x, jnp.float32))

    # ---- sparse (COO-entry) surface -----------------------------------------

    @telemetry.span("binner.fit", total="binner.fit_us")
    def fit_sparse(self, index: np.ndarray, value: np.ndarray,
                   num_features: int) -> "QuantileBinner":
        """Per-feature quantile cuts from a COO sample (host sketch), the
        sparse analogue of ``fit`` — entries of feature f are that
        feature's PRESENT values.  Requires ``missing_aware=True`` (absent
        cells are missing by construction in sparse data)."""
        if not self.missing_aware:
            raise ValueError("fit_sparse requires missing_aware=True "
                             "(absent cells are missing, not 0)")
        index = np.asarray(index, np.int64)
        value = np.asarray(value, np.float32)
        # NaN entries are malformed COO (missing = absent entry); excluding
        # them from the sketch mirrors the dense path's nanquantile
        keep = ~np.isnan(value)
        index, value = index[keep], value[keep]
        order = np.lexsort((value, index))
        idx_s, val_s = index[order], value[order]
        feats = np.arange(num_features)
        starts = np.searchsorted(idx_s, feats)
        ends = np.searchsorted(idx_s, feats + 1)
        lens = ends - starts
        value_bins = self.num_bins - 1
        qs = np.linspace(0.0, 1.0, value_bins + 1)[1:-1]
        # nearest-rank quantiles per feature, fully vectorized over (F, q)
        pos = starts[:, None] + np.round(
            qs[None, :] * np.maximum(lens[:, None] - 1, 0)).astype(np.int64)
        pos = np.minimum(pos, np.maximum(ends[:, None] - 1, starts[:, None]))
        # empty trailing features have starts == ends == len(val_s); keep
        # the gather in bounds (their cuts are overwritten below anyway)
        pos = np.clip(pos, 0, max(val_s.size - 1, 0))
        cuts = (val_s[pos] if val_s.size
                else np.zeros((num_features, qs.size), np.float32))
        cuts[lens == 0] = 0.0  # feature never present: degenerate cuts
        self.cuts = jnp.asarray(np.maximum.accumulate(cuts, axis=1))
        return self

    def cuts_digest(self) -> str:
        """Short content digest of the fitted cuts — the identity the
        binned epoch cache (data/binned_cache.py) keys its pre-computed
        bin codes on."""
        if self.cuts is None:
            raise RuntimeError("cuts_digest before fit")
        a = np.ascontiguousarray(np.asarray(self.cuts, np.float32))
        h = hashlib.sha256(a.tobytes())
        h.update(repr(a.shape).encode())
        return h.hexdigest()[:16]

    def transform_entries(self, index: jax.Array, value: jax.Array
                          ) -> jax.Array:
        """Bin COO entries: code of ``value[k]`` under feature
        ``index[k]``'s cuts, in ``[1, num_bins)`` (0 stays reserved for
        missing = absent).  Jittable; see `_bin_entries`."""
        if not self.missing_aware:
            raise ValueError("transform_entries requires missing_aware=True")
        if self.cuts is None:
            raise RuntimeError("transform_entries before fit")
        return _bin_entries(self.cuts, index, value)

    # ---- streaming (bounded-memory, mergeable) sketch -----------------------
    #
    # The one-shot fit/fit_sparse need the whole sample in memory at once;
    # at the Higgs-11M scale (BASELINE target 5) the dataset only ever
    # exists as a stream of staged batches.  partial_fit/partial_fit_sparse
    # accumulate a UNIFORM k-reservoir per feature across any number of
    # chunks — the merge draws a hypergeometric split of the union, so the
    # combined reservoir is an exact uniform subsample of everything seen —
    # and finalize() turns the reservoirs into cut points.  Memory is
    # features x sketch_size x 4 bytes, independent of stream length.
    # While a feature's stream still fits its reservoir the sketch is
    # lossless: finalize() cuts equal the one-shot fit_sparse cuts.
    # (This is the role XGBoost's streaming quantile sketch plays for
    # hist boosters; same nearest-rank cut rule as fit_sparse.)

    def partial_fit(self, x: np.ndarray) -> "QuantileBinner":
        """Accumulate a dense ``[rows, features]`` chunk into the sketch."""
        x = np.asarray(x, np.float32)
        if x.ndim != 2:
            raise ValueError("partial_fit expects [rows, features]")
        if not self.missing_aware and np.isnan(x).any():
            raise ValueError(
                "chunk contains NaN but missing_aware=False; construct "
                "QuantileBinner(..., missing_aware=True)")
        self._sketch_ensure(x.shape[1])
        for f in range(x.shape[1]):
            col = x[:, f]
            self._sketch_absorb(f, col[~np.isnan(col)])
        return self

    def partial_fit_sparse(self, index: np.ndarray, value: np.ndarray,
                           num_features: int) -> "QuantileBinner":
        """Accumulate a COO entry chunk (e.g. one staged batch's
        ``index``/``value`` with padding masked off) into the sketch."""
        if not self.missing_aware:
            raise ValueError("partial_fit_sparse requires missing_aware=True "
                             "(absent cells are missing, not 0)")
        index = np.asarray(index, np.int64)
        value = np.asarray(value, np.float32)
        # malformed COO entries: NaN values and indices outside
        # [0, num_features) are quietly dropped, matching fit_sparse
        # (whose arange(num_features) never visits a stray index)
        keep = (~np.isnan(value)) & (index >= 0) & (index < num_features)
        index, value = index[keep], value[keep]
        self._sketch_ensure(num_features)
        order = np.argsort(index, kind="stable")
        idx_s, val_s = index[order], value[order]
        feats = np.unique(idx_s)
        starts = np.searchsorted(idx_s, feats)
        ends = np.searchsorted(idx_s, feats + 1)
        for f, lo, hi in zip(feats, starts, ends):
            self._sketch_absorb(int(f), val_s[lo:hi])
        return self

    def finalize(self) -> "QuantileBinner":
        """Compute cuts from the accumulated reservoirs (nearest-rank, the
        fit_sparse rule) and drop the sketch state."""
        if getattr(self, "_sketch_values", None) is None:
            raise RuntimeError("finalize before partial_fit/partial_fit_sparse")
        res, fill = self._sketch_values, self._sketch_fill
        k = res.shape[1]
        value_bins = self.num_bins - 1 if self.missing_aware else self.num_bins
        qs = np.linspace(0.0, 1.0, value_bins + 1)[1:-1]
        # sort with +inf padding so every row's live prefix is its sample
        padded = np.where(np.arange(k)[None, :] < fill[:, None], res, np.inf)
        srt = np.sort(padded, axis=1)
        pos = np.round(qs[None, :] * np.maximum(fill[:, None] - 1, 0)
                       ).astype(np.int64)
        cuts = np.take_along_axis(srt, pos, axis=1).astype(np.float32)
        cuts[fill == 0] = 0.0  # feature never present: degenerate cuts
        self.cuts = jnp.asarray(np.maximum.accumulate(cuts, axis=1))
        self._sketch_values = None
        self._sketch_fill = None
        self._sketch_seen = None
        return self

    def _sketch_ensure(self, num_features: int) -> None:
        """Create (or grow, for sparse streams that discover new feature
        indices) the per-feature reservoir state."""
        if getattr(self, "_sketch_values", None) is None:
            self._sketch_rng = np.random.default_rng(self.sketch_seed)
            self._sketch_values = np.zeros((num_features, self.sketch_size),
                                           np.float32)
            self._sketch_fill = np.zeros(num_features, np.int64)
            self._sketch_seen = np.zeros(num_features, np.int64)
            return
        have = self._sketch_values.shape[0]
        if num_features > have:
            grow = num_features - have
            self._sketch_values = np.concatenate(
                [self._sketch_values,
                 np.zeros((grow, self.sketch_size), np.float32)])
            self._sketch_fill = np.concatenate(
                [self._sketch_fill, np.zeros(grow, np.int64)])
            self._sketch_seen = np.concatenate(
                [self._sketch_seen, np.zeros(grow, np.int64)])

    def _sketch_absorb(self, f: int, chunk: np.ndarray) -> None:
        """Merge one feature's chunk into its reservoir, keeping the
        reservoir a uniform sample of everything seen for that feature."""
        m = chunk.size
        if m == 0:
            return
        k = self.sketch_size
        fill = int(self._sketch_fill[f])
        seen = int(self._sketch_seen[f])
        rng = self._sketch_rng
        if seen + m <= k:
            # everything still fits: the reservoir is the complete stream
            self._sketch_values[f, fill:fill + m] = chunk
            self._sketch_fill[f] = fill + m
        else:
            # union sample: t slots from the old side (a uniform sub-sample
            # of a uniform sample is uniform), k - t from the new chunk
            t = int(rng.hypergeometric(seen, m, k))
            t = min(t, fill)  # guard the degenerate fill < seen edge
            old = self._sketch_values[f, rng.choice(fill, t, replace=False)] \
                if t else np.empty(0, np.float32)
            new = chunk[rng.choice(m, k - t, replace=False)]
            self._sketch_values[f, :t] = old
            self._sketch_values[f, t:k] = new
            self._sketch_fill[f] = k
        self._sketch_seen[f] = seen + m


from .common import logistic_nll


def _logistic_grad_hess(margin: jax.Array, label: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    p = jax.nn.sigmoid(margin)
    y = jnp.where(label > 0.5, 1.0, 0.0)
    return p - y, jnp.maximum(p * (1.0 - p), 1e-16)


def _squared_grad_hess(margin: jax.Array, label: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    return margin - label, jnp.ones_like(margin)


# (grad, hess) as ONE program a round, for `GBDT.fit_paged`
_GRAD_HESS_PROGRAM = {f: jax.jit(f)
                      for f in (_logistic_grad_hess, _squared_grad_hess)}


@functools.partial(jax.jit, static_argnames=("max_shift",))
def _pairwise_terms(margin: jax.Array, label: jax.Array, qid: jax.Array,
                    weight: jax.Array, max_shift: int):
    """Pairwise logistic (RankNet) terms over qid-contiguous rows.

    Instead of materializing O(n^2) pairs, scan ``s = 1..max_shift`` and
    pair each row i with row i+s when both sit in the same query group —
    every within-group pair appears for exactly one shift, so the scan is
    O(rows * max_group) with only rolls/masks (XLA-friendly, ragged groups
    included).  Returns (grad, hess, loss_sum, pair_count); grad/hess
    follow XGBoost's rank:pairwise (winner pushed up, loser down).
    """
    rows = margin.shape[0]
    pos = jnp.arange(rows)

    def body(s, carry):
        g, h, loss, npairs = carry
        mj = jnp.roll(margin, -s)
        yj = jnp.roll(label, -s)
        qj = jnp.roll(qid, -s)
        wj = jnp.roll(weight, -s)
        mask = ((qid == qj) & (pos < rows - s)   # same group, no wraparound
                & (weight > 0) & (wj > 0))
        dy = label - yj
        winner_i = dy > 0
        pair = mask & (dy != 0)
        d = jnp.where(winner_i, margin - mj, mj - margin)  # winner - loser
        p = jax.nn.sigmoid(-d)
        lam = jnp.where(pair, p, 0.0)
        hh = jnp.where(pair, jnp.maximum(p * (1.0 - p), 1e-16), 0.0)
        gi = jnp.where(winner_i, -lam, lam)   # row i's share of the pair
        g = g + gi + jnp.roll(-gi, s)         # row i+s gets the other sign
        h = h + hh + jnp.roll(hh, s)
        # stable log(1 + e^-d)
        loss = loss + jnp.sum(jnp.where(
            pair, jnp.maximum(-d, 0) + jnp.log1p(jnp.exp(-jnp.abs(d))), 0.0))
        npairs = npairs + jnp.sum(pair)
        return g, h, loss, npairs

    zero = jnp.zeros(rows, jnp.float32)
    return jax.lax.fori_loop(1, max_shift + 1, body,
                             (zero, zero, jnp.float32(0.0), jnp.int32(0)))


def _validate_rank_qid(qid, weight=None) -> int:
    """Host-side qid checks for the pairwise scan.

    Real (weight>0) rows of each query must form one contiguous block
    (the libsvm ranking layout; padding rows are ignored).  Returns the
    scan depth: the max POSITIONAL span of a group's real rows plus one —
    spans, not counts, so interior weight-0 gaps (multi-host pad gaps)
    cannot hide valid pairs from the shifted scan."""
    q = np.asarray(qid)
    pos = (np.flatnonzero(np.asarray(weight) > 0) if weight is not None
           else np.arange(q.size))
    qf = q[pos]
    if qf.size == 0:
        raise ValueError("rank:pairwise needs a non-empty qid array")
    boundaries = np.flatnonzero(np.diff(qf) != 0)
    starts = np.concatenate([[0], boundaries + 1])
    ends = np.concatenate([boundaries + 1, [qf.size]])
    if len(starts) != len(np.unique(qf)):
        raise ValueError(
            "rank:pairwise requires qid groups to be contiguous runs "
            "(sort rows by qid; libsvm ranking files already are)")
    spans = pos[ends - 1] - pos[starts]
    return int(spans.max()) + 1


def _softmax_ce(margin: jax.Array, label: jax.Array) -> jax.Array:
    """Per-row cross-entropy from [rows, K] margins and integer labels."""
    logz = jax.scipy.special.logsumexp(margin, axis=1)
    picked = jnp.take_along_axis(
        margin, label.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return logz - picked


# ---- sibling subtraction: what a level builds, and what it derives ----------
# A level's histograms are determined by its parent's and ONE child's of each
# parent: hist(sibling) = hist(parent) - hist(built), bucket by bucket
# (XGBoost hist's subtraction).  Both kernels' time grows with the node
# columns on the MXU's M axis, so every tree builder (`_build_tree`,
# `_build_tree_sparse`, `fit_streamed`) asks its backend for half a level's
# columns through these functions, and for the root whole.

# The column of a row that stands in no built node.  Every histogram backend
# drops it: the kernels compare ids with their node columns, all >= 0, and
# `jax.ops.segment_sum` drops a negative key (tests/test_gbdt_siblings.py).
_NO_SLOT = -1


def _built_columns(depth: int) -> int:
    """Node histograms the level at ``depth`` asks its backend for: the root,
    then one child of each of the ``2 ** (depth - 1)`` parents."""
    return 2 ** max(depth - 1, 0)


def _smaller_child(hl_dirs, h_tot, split_f, split_b, split_d) -> jax.Array:
    """Which child of each node the next level builds: bool [nodes], True
    for the right one.  The child with the smaller hessian mass at the
    node's chosen split, so that the derived sibling, which inherits the
    parent's absolute rounding error, is the larger: its relative error is
    at most twice the parent's plus the built child's (deriving a child of
    a thousandth of the mass would multiply it by a thousand).  A null
    split sends every row left, so its empty right child is built.

    hl_dirs: the left hessian mass ``[nodes, F, B]`` of every cut, one array
    a default direction; h_tot: [nodes]; split_*: the chosen [nodes]
    tables, nulls as `_pick_splits` encodes them."""
    n_nodes, _, B = hl_dirs[0].shape
    at = (split_f * B + jnp.minimum(split_b, B - 1))[:, None]
    hl = [jnp.take_along_axis(a.reshape(n_nodes, -1), at, 1)[:, 0]
          for a in hl_dirs]
    left = hl[0] if len(hl) == 1 else jnp.where(split_d == 1, hl[1], hl[0])
    left = jnp.where(split_b >= B, h_tot, left)
    return h_tot - left < left


def _child_slot(rel: jax.Array, go_right: jax.Array,
                right_built: jax.Array) -> jax.Array:
    """A routed row's column among the histograms the next level builds: its
    parent's index ``rel`` if it went to the child that is built
    (``right_built``, its parent's word of `_smaller_child`), else
    ``_NO_SLOT``."""
    return jnp.where(go_right == right_built, rel, _NO_SLOT)


def _entry_values(layout, table: jax.Array) -> jax.Array:
    """The rows' ``table`` on the sorted entries' lanes, for the sparse
    kernel: a level's slots ``[rows]`` -> ``[nnz]``, a tree's (grad, hess)
    ``[rows, 2]`` -> ``[2, nnz]``.  `ops.entry_values` holds the rule and both
    routes: the lookup kernel where the layout's rows ascend, on a chip, at
    a table that fits it (110.7 ms a level and 410.7 ms a tree's pair at the
    Bosch cell's 2.18e8 entries on a v5e; PERF.md, PR 46), else XLA's gather,
    8.6 ns an element (1,876 ms a level, 3,616 the pair; PR 27)."""
    with jax.named_scope("gbdt.entry_gather"):
        return entry_values(layout.rid, layout.cspan, table,
                            layout.rows_ascend)


def _entry_slots(layout, slot: jax.Array, depth: int) -> jax.Array:
    """The rows' ``slot`` on the layout's entry lanes (`_entry_values`).  At
    the root every slot is 0 and nothing is looked up."""
    if depth == 0:
        with jax.named_scope("gbdt.entry_gather"):
            return jnp.zeros(layout.rid.shape, jnp.int32)
    return _entry_values(layout, slot)


def _routes_by_push(entries, layout, rows: int) -> bool:
    """Whether `GBDT._build_tree_sparse` routes its levels by the entries'
    push: the layout is the only copy of the entries (`fit_batch` has seen
    to its ``rows_ascend`` and to every level's running the kernel on one
    device) and `route_push_engages` says so."""
    return entries is None and route_push_engages(layout.rows_ascend, rows)


def _entry_rels(layout, rel: jax.Array, right_built, depth: int):
    """For a level that routes by the push (`GBDT._route_layout`): the rows'
    node ``rel`` on the layout's entry lanes, and the level's slots derived
    from it there, so that one lookup a level serves the histogram and the
    routing both.  ``rel = 2 * parent + went_right``, so an entry's slot is
    `_child_slot` of its parent: ``rel >> 1`` if ``rel & 1`` is the child that
    was built (``right_built``: the level above's [nodes / 2] word, taken by
    `_select_by_bits`, 0.0003 ns a lane a node where a gather is 8), else
    ``_NO_SLOT``: the int32 values `_entry_slots` lays there.  A kernel
    level holds at most 256 nodes, so ``rel`` is a bfloat16 as it stands.
    At the root both are 0 and nothing is looked up."""
    if depth == 0:
        rel_e = _entry_slots(layout, rel, depth)
        return rel_e, rel_e
    rel_e = _entry_values(layout, rel)
    with jax.named_scope("gbdt.entry_gather"):
        parent = rel_e >> 1
        built = _select_by_bits(list(right_built), parent)
        return rel_e, jnp.where(((rel_e & 1) == 1) == built, parent, _NO_SLOT)


def _with_siblings(parent, built: jax.Array, right_built) -> jax.Array:
    """A level's ``[2 * parents, F, B, 2]`` histograms from the level
    above's ``parent`` ``[parents, F, B, 2]`` and the ``built`` child of
    each (``right_built``: which), the other derived as parent - built in
    float32, interleaved in heap order.  The root has no parent and is built
    whole."""
    if parent is None:
        return built
    derived = parent - built
    right = right_built[:, None, None, None]
    pair = jnp.stack([jnp.where(right, derived, built),
                      jnp.where(right, built, derived)], axis=1)
    return pair.reshape((-1,) + built.shape[1:])


def _split_child_sums(dirs, split_f, split_b, split_d) -> jax.Array:
    """(G, H) of both children of every node at its chosen split, float32
    ``[2 * nodes, 2]`` in heap order (left, right): read off the cumulative
    histograms the split search already holds, so a dense tree's leaves cost
    no pass over the rows (the scatter-add of a ``[rows, 2]`` pair was 6.8 ns
    and 512 B of scratch a row on a v5e: PERF.md, PR 38) and, under a mesh
    plan, they come from the reduced histograms: global with no collective.
    The left child's sums are ``dirs[split_d]`` at ``(split_f, split_b)``,
    missing mass on its side included; the right child's the node's totals
    in that feature's histogram (its last cumulative sum) less the left's,
    the one subtraction the gain was computed with (XGBoost's hist takes a
    leaf's weight from the same statistics).  A null split's cut lies past
    the last bin and defaults left: the read is clamped to the last bin,
    where the cumulative sum IS the total, so the left child is the node and
    the right child exactly (0, 0), weight 0.

    dirs: ``(gl, hl)`` ``[nodes, F, B]`` a default direction, left-to-right
    cumulative sums, ``dirs[0]`` with the missing bin on the left; split_*:
    the chosen [nodes] tables, nulls as `_pick_splits` encodes them."""
    n_nodes, _, B = dirs[0][0].shape
    last = (split_f * B + B - 1)[:, None]
    at = jnp.minimum(last, (split_f * B + split_b)[:, None])

    def pick(a, i):
        return jnp.take_along_axis(a.reshape(n_nodes, -1), i, 1)[:, 0]

    left = [jnp.stack([pick(gl, at), pick(hl, at)], axis=-1)
            for gl, hl in dirs]
    left = (left[0] if len(left) == 1
            else jnp.where((split_d == 1)[:, None], left[1], left[0]))
    total = jnp.stack([pick(a, last) for a in dirs[0]], axis=-1)
    return jnp.stack([left, total - left], axis=1).reshape(-1, 2)


# ---- the margin update: a finished tree's leaf value onto every row ---------

# Most leaf values (``leaf.shape[0]``) for which a row takes its own by
# selects; past it, by XLA's gather.  The gather costs a v5e 6.7-8.2 ns a row
# whatever its operand's size; the selects cost 0.0003 ns a row a leaf value:
# at 28,750,000 rows 2.8 ms against 222 at 256 leaves, 139 against 192 at
# 16,384, 208 against 192 at 24,576; at 10,500,000 rows 49.6 against 75.3 at
# 16,384 and 74.3 against 75.3 at 24,576.  They cross between 22,600 and
# 24,900 leaves; at 64, where XLA itself expands a jitted gather into
# compares and selects, 0.69 ms against 0.88, so there is no floor (PERF.md,
# PR 43: one chip, `_leaf_values` on both sides of this constant).
_MARGIN_SELECT_LEAVES = 16384
# Leaf values one pass over the rows selects among, a power of two.  At 64 a
# pass is near what its bytes take (0.6 ms of 0.42 at 28,750,000 rows); 128
# and 256 read 5% and 17% slower at 1,024 leaves, and an unrolled pass of 256
# compiles in 4.2 s and 1.3 MB where this one takes 0.7 s and 0.3 MB
# whatever the leaves (same runs).
_MARGIN_SELECT_CHUNK = 64


def _select_by_bits(vals: list, index: jax.Array) -> jax.Array:
    """``vals[index]`` a row, ``vals`` a list of scalars, by halving:
    bit 0 of ``index`` picks within pairs of values, bit 1 within pairs of
    those pairs, and so on.  One select a value a row, nothing compared
    with an id, and what comes out is a value that went in, bit for bit (a
    ``-0.0`` too); an odd value out at a level rides up as it is; bits past
    the values' are not looked at.  Scalars against ``[rows]`` arrays: XLA
    fuses the lot into one pass over the rows, which stay 1-D."""
    bit = 1
    while len(vals) > 1:
        odd = (index & bit) != 0
        pairs = [jnp.where(odd, vals[j + 1], vals[j])
                 for j in range(0, len(vals) - 1, 2)]
        vals = pairs + vals[len(vals) - len(vals) % 2:]
        bit *= 2
    return vals[0]


def _selected_leaf_values(leaf: jax.Array, leaf_rel: jax.Array,
                          margin: Optional[jax.Array]) -> jax.Array:
    """``leaf[leaf_rel]`` ([rows]), added to ``margin`` if there is one, with
    no gather: `_select_by_bits` among ``_MARGIN_SELECT_CHUNK`` values a
    pass, the low bits of ``leaf_rel`` choosing within a chunk and the high
    ones the pass in which a row takes its value.  The margins are what the
    passes hand on, so each is added to once and nothing else the size of
    the rows is kept; no ``[leaves, rows]`` array exists, and the program's
    size does not grow with the leaves.  leaf_rel in [0, leaves)."""
    chunk = _MARGIN_SELECT_CHUNK          # a power of two
    n = leaf.shape[0]
    chunks = -(-n // chunk)
    if chunks > 1:
        leaf = jnp.pad(leaf, (0, chunks * chunk - n))

    def taken(c, value):
        part = jax.lax.dynamic_slice(leaf, (c * chunk,), (min(chunk, n),))
        part = _select_by_bits(list(part), leaf_rel)
        return part if margin is None else value + part
    if chunks == 1:
        return jnp.broadcast_to(taken(0, margin), leaf_rel.shape)
    mine = leaf_rel >> (chunk.bit_length() - 1)
    return jax.lax.fori_loop(
        0, chunks,
        lambda c, value: jnp.where(mine == c, taken(c, value), value),
        jnp.zeros(leaf_rel.shape, leaf.dtype) if margin is None else margin)


def _margin_selects(leaf) -> bool:
    return leaf.shape[0] <= _MARGIN_SELECT_LEAVES


@functools.partial(jax.jit, donate_argnames="margin")
def _leaf_values(leaf: jax.Array, leaf_rel: jax.Array,
                 margin: Optional[jax.Array] = None) -> jax.Array:
    """Every row's value of a finished tree, ``leaf[leaf_rel]`` ([rows]), or
    with ``margin`` (donated) the margins it brings them to, in one program.
    leaf: f32 [leaves]; leaf_rel: i32 [rows] in [0, leaves).  Which way a
    row finds its value is read off ``leaf.shape[0]`` (`_margin_selects`).
    Elementwise over the rows: under a mesh plan, rows sharded and ``leaf``
    replicated, no collective, and the margins stay where they lay."""
    with jax.named_scope("gbdt.boost"), jax.named_scope("gbdt.margin"):
        if _margin_selects(leaf):
            return _selected_leaf_values(leaf, leaf_rel, margin)
        value = leaf[leaf_rel]
        return value if margin is None else margin + value


def _add_leaf_values(margin: Optional[jax.Array], leaf: jax.Array,
                     leaf_rel: jax.Array) -> jax.Array:
    """``margin + leaf[leaf_rel]`` (the values alone for ``margin=None``) by
    `_leaf_values`; counts ``gbdt.margin_select`` on the host, once a call
    that takes the select path: a boosting round's one update, or each of
    a softmax round's ``num_class``."""
    if _margin_selects(leaf):
        counter_add("gbdt.margin_select", 1)
    return _leaf_values(leaf, leaf_rel, margin)


class GBDT:
    """Gradient-boosted complete binary trees over binned features.

    Parameters mirror the XGBoost-hist essentials: ``num_trees``,
    ``max_depth`` (trees are complete; a node that finds no positive-gain
    split stores a null split routing every row left, so its whole subtree
    degenerates to the leftmost leaf and unreachable nodes stay zero),
    ``learning_rate`` (shrinkage), ``lambda_`` (L2
    on leaf weights), ``min_child_weight`` (minimum hessian mass per
    child), ``gamma`` (min split loss: splits below it become null —
    XGBoost's complexity pruning), ``objective`` ("logistic", "squared", or "softmax" with
    ``num_class`` — K trees per round against the shared softmax
    distribution, XGBoost's multi:softprob), ``monotone_constraints``
    (per-feature -1/0/+1: violating splits are gain-masked, per-node
    output bounds propagate down the tree, and leaves clamp into them —
    the forest is guaranteed monotone in constrained features'
    present values), ``interaction_constraints`` (feature groups; every
    root-to-leaf path's splits stay within one group, via per-node
    allowed-feature masks propagated down the levels),
    ``colsample_bylevel`` (a fresh feature draw per depth, composing with
    colsample_bytree), ``base_score`` (initial prediction — a probability
    for the logistic objective per XGBoost semantics, a raw margin for
    squared/softmax; None derives the weighted prior from the data),
    ``scale_pos_weight`` (positive-class weight multiplier, logistic
    only — weight rows directly for other objectives), ``subsample`` /
    ``colsample_bytree`` in (0, 1] (stochastic boosting: a per-tree
    Bernoulli row mask folded into the sample weights, and a per-tree
    feature subset masking the split gains — both derived from ``seed``
    and the tree index only, so sharded and multi-host runs sample
    identically and fits are deterministic per seed).

    The forest is a pytree of flat arrays::

        feature       i32 [num_trees, 2**max_depth - 1]  per internal node
        threshold     i32 [num_trees, 2**max_depth - 1]  go right if bin > thr
        default_right i32 [num_trees, 2**max_depth - 1]  missing-bin routing
        leaf          f32 [num_trees, 2**max_depth]      shrunken leaf weights
        base          f32 []                             initial margin

    Null splits use ``threshold == num_bins`` (no uint8 code exceeds it).

    With ``missing_aware=True`` (pair with a missing-aware binner), bin 0
    is the missing bin: split finding evaluates every cut with the missing
    mass routed left AND right — from the same histograms, no extra pass —
    and stores the winning direction per node (XGBoost's sparsity-aware
    split enumeration).  Otherwise bin 0 is an ordinary ordered bin and
    ``default_right`` stays 0.
    """

    # ``GBDT(..., grow_policy="lossguide", max_leaves=L)`` constructs
    # `gbdt_leafwise.LeafwiseGBDT`, the best-first builder and its pointer
    # forest (XGBoost's names; "depthwise" is this file's level-by-level
    # builder and the default).  ``__init__`` takes the two names and does
    # nothing with them: they are checked here, where the class is chosen.
    grow_policy = "depthwise"
    max_leaves = 0

    def __new__(cls, *args, grow_policy: str = "depthwise",
                max_leaves: int = 0, **kwargs):
        if grow_policy not in ("depthwise", "lossguide"):
            raise ValueError("grow_policy must be 'depthwise' or 'lossguide'")
        if grow_policy == "depthwise" and max_leaves:
            raise ValueError("max_leaves belongs to grow_policy='lossguide': "
                             "a depth-wise tree holds 2 ** max_depth leaves")
        if cls is GBDT and grow_policy == "lossguide":
            from .gbdt_leafwise import LeafwiseGBDT
            cls = LeafwiseGBDT
        return object.__new__(cls)

    def __init__(self, num_features: int, num_trees: int = 20,
                 max_depth: int = 6, num_bins: int = 256,
                 learning_rate: float = 0.3, lambda_: float = 1.0,
                 min_child_weight: float = 1e-3,
                 gamma: float = 0.0,
                 objective: str = "logistic",
                 missing_aware: bool = False,
                 subsample: float = 1.0,
                 colsample_bytree: float = 1.0,
                 seed: int = 0,
                 num_class: int = 0,
                 monotone_constraints=None,
                 colsample_bylevel: float = 1.0,
                 interaction_constraints=None,
                 base_score=None,
                 scale_pos_weight: float = 1.0,
                 histogram: str = "auto",
                 histogram_mesh=None, grow_policy="depthwise", max_leaves=0):
        if objective not in ("logistic", "squared", "softmax",
                             "rank:pairwise"):
            raise ValueError(f"unknown objective '{objective}'")
        if objective == "softmax" and num_class < 2:
            raise ValueError("objective='softmax' needs num_class >= 2")
        if objective != "softmax" and num_class:
            raise ValueError("num_class is only valid with "
                             "objective='softmax'")
        if not 0.0 < subsample <= 1.0:
            raise ValueError("subsample must be in (0, 1]")
        if not 0.0 < colsample_bytree <= 1.0:
            raise ValueError("colsample_bytree must be in (0, 1]")
        self.num_features = num_features
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.num_bins = num_bins
        self.learning_rate = learning_rate
        self.lambda_ = lambda_
        self.min_child_weight = min_child_weight
        if gamma < 0:
            raise ValueError("gamma must be >= 0")
        self.gamma = gamma
        self.objective = objective
        self.missing_aware = missing_aware
        self.subsample = subsample
        self.colsample_bytree = colsample_bytree
        self.seed = seed
        self.num_class = num_class
        if monotone_constraints is not None:
            raw = np.asarray(monotone_constraints)
            # validate before casting: int32 truncation would silently
            # accept (and neuter) values like 0.5
            if (raw.shape != (num_features,)
                    or not np.isin(raw, (-1, 0, 1)).all()):
                raise ValueError("monotone_constraints must be a length-"
                                 "num_features sequence of -1/0/+1")
            mc = raw.astype(np.int32)
            if not mc.any():
                monotone_constraints = None  # all-zero = unconstrained
            else:
                monotone_constraints = jnp.asarray(mc)
        self.monotone_constraints = monotone_constraints
        if not 0.0 < colsample_bylevel <= 1.0:
            raise ValueError("colsample_bylevel must be in (0, 1]")
        self.colsample_bylevel = colsample_bylevel
        self._interaction_groups = None
        if interaction_constraints is not None:
            # membership[g, f]: feature f belongs to group g.  XGBoost
            # semantics need group IDENTITY (a pairwise co-occurrence
            # union over-permits with overlapping groups): each node
            # tracks which groups remain active, a split on f keeps only
            # the active groups containing f, and the node's allowed
            # features are the union of its active groups.  Features in
            # no group become singletons.
            rows = []
            grouped = np.zeros(num_features, dtype=bool)
            for group in interaction_constraints:
                g = np.asarray(group, np.int64)
                if g.size and ((g < 0) | (g >= num_features)).any():
                    raise ValueError(
                        "interaction_constraints feature ids must be in "
                        f"[0, {num_features})")
                row = np.zeros(num_features, dtype=bool)
                row[g] = True
                rows.append(row)
                grouped[g] = True
            for f in np.flatnonzero(~grouped):
                row = np.zeros(num_features, dtype=bool)
                row[f] = True
                rows.append(row)
            self._interaction_groups = jnp.asarray(np.stack(rows))  # [G, F]
        self.base_score = base_score  # None = weighted prior from data
        if scale_pos_weight <= 0:
            raise ValueError("scale_pos_weight must be > 0")
        if scale_pos_weight != 1.0 and objective != "logistic":
            raise ValueError("scale_pos_weight applies to the logistic "
                             "objective (weight rows directly otherwise)")
        self.scale_pos_weight = scale_pos_weight
        if histogram not in ("auto", "xla", "pallas"):
            raise ValueError("histogram must be 'auto', 'xla' or 'pallas'")
        self.histogram = histogram
        # The explicit multi-device route: under a ``parallel.MeshPlan``
        # every level builds its histogram by shard_map(local histogram) +
        # the plan's allreduce instead of relying on GSPMD to partition
        # segment_sum — pallas_call has no auto-partitioning rule, so this
        # is the ONLY way the kernel can serve a row-sharded fit.  fit()
        # lays its inputs over the plan axes (`_shard_inputs`), and
        # shard_map's even-sharding rule applies: rows must divide by the
        # shard count (without a plan GSPMD tolerates uneven rows).
        if histogram_mesh is not None:
            from ..parallel.meshplan import MeshPlan
            if not isinstance(histogram_mesh, MeshPlan):
                raise TypeError(
                    "histogram_mesh takes a parallel.MeshPlan, not "
                    f"{type(histogram_mesh).__name__}: pass "
                    "MeshPlan(mesh, axes)")
        self.mesh_plan = histogram_mesh
        self._grad_hess = (_logistic_grad_hess if objective == "logistic"
                           else _squared_grad_hess)

    def _hist_impl(self, n_nodes: int,
                   node_limit: int = HIST_NODE_LIMIT) -> str:
        """Histogram backend for a level with ``n_nodes`` nodes.  Resolved
        lazily (never in __init__: touching jax.default_backend() there
        would initialize the backend as a constructor side effect, breaking
        construct-before-jax.distributed.initialize programs).  Explicit
        "xla"/"pallas" always wins; "auto" = the Pallas kernel on a TPU
        while the level fits the kernel's node cap
        (``ops.pallas_segment.HIST_NODE_LIMIT``), XLA scatter-add elsewhere.
        Without ``histogram_mesh`` that holds on a SINGLE device only: the
        sharded fit path relies on ``segment_sum`` being GSPMD-partitionable
        so the compiler inserts the histogram psum (the rabit-allreduce
        analogue), and ``pallas_call`` has no partitioning rule.  The
        multi-device kernel route is the explicit one: construct with
        ``histogram_mesh=`` and ``_level_histogram`` runs the kernel
        per-device under shard_map with the plan's allreduce."""
        if self.histogram != "auto":
            return self.histogram
        if (jax.default_backend() == "tpu" and n_nodes <= node_limit
                and (self.mesh_plan is not None
                     or jax.device_count() == 1)):
            return "pallas"
        return "xla"

    def _dead_key_tiles(self, depth: int) -> int:
        """Key tiles of padding that ONE dense kernel call for the level at
        ``depth`` is planned with and leaves out (``hist_dead_key_tiles`` at
        the level's built columns); 0 where the level's backend is not the
        kernel.  What the counter ``gbdt.hist_dead_key_tiles`` sums."""
        if self._hist_impl(2 ** depth) != "pallas":
            return 0
        return hist_dead_key_tiles(self.num_features, self.num_bins,
                                   _built_columns(depth))

    def level_backends(self, sparse: bool = False) -> list:
        """The histogram backend ("pallas" or "xla") each level of a fit
        resolves to on this process's devices, root level first —
        ``sparse=True`` for the COO builders (``fit_batch`` /
        ``fit_streamed``), else the dense ``fit``."""
        impl = self._hist_impl_sparse if sparse else self._hist_impl
        return [impl(2 ** d) for d in range(self.max_depth)]

    def _level_histogram(self, bins_i: jax.Array, rel: jax.Array,
                         gh: jax.Array, n_nodes: int, impl: str) -> jax.Array:
        """[n_nodes, F, bins, 2] histogram of the rows whose ``rel`` is in
        [0, n_nodes) — a level's built columns; ``_NO_SLOT`` adds nothing
        on either backend — through ``impl``, the level's `_hist_impl`.
        Plain ``histogram_gh`` call normally (GSPMD partitions the XLA
        path and inserts the psum on sharded fits).  With a mesh plan the
        histogram is built per-device on local row shards under
        ``jax.shard_map`` and the shards combine with the plan's allreduce
        (`test_histogram_gh_shardmap_psum_matches_global`), which carries
        the built columns only: their siblings are derived after it
        (`_with_siblings`)."""
        B, plan = self.num_bins, self.mesh_plan
        if plan is None:
            return histogram_gh(bins_i, rel, gh, n_nodes, B, force=impl)
        from jax.sharding import PartitionSpec as P

        def local(b, r, g):
            return plan.allreduce(
                histogram_gh(b, r, g, n_nodes, B, force=impl))

        # replication check off: pallas_call's out_shape carries no
        # varying-axes annotation, so the static check cannot see through
        # it; the allreduce replicates the output regardless.
        spec = plan.row_spec
        return plan.shard_map(local, in_specs=(spec, spec, spec),
                              out_specs=P(), check_replication=False)(
                                  bins_i, rel, gh)

    def _hist_impl_sparse(self, n_nodes: int) -> str:
        """Sparse-histogram backend for a level: `_hist_impl`'s rule
        against the sparse kernel's node cap."""
        return self._hist_impl(n_nodes, SPARSE_HIST_NODE_LIMIT)

    def _sparse_layout_enabled(self, streamed: bool = False) -> bool:
        """Whether this fit's configuration can route any level through the
        sparse Pallas kernel — i.e. whether `_sparse_fit_layout` would
        build a layout.  Checked *before* entry arrays exist (streamed
        fits use it to decide whether pass 0 should accumulate the global
        entry arrays the sort needs)."""
        if streamed and self.mesh_plan is not None:
            return False
        return "pallas" in self.level_backends(sparse=True)

    def _sparse_fit_layout(self, row_id, findex, ebin, emask, rows: int,
                           streamed: bool = False, value=None, cuts=None):
        """The once-per-fit feature-sorted entry layout, or None when no
        level of this fit can resolve to the sparse Pallas kernel.  One
        device sort serves every tree's levels (``ops.sparse_hist_layout``;
        span ``gbdt.entry_sort``, counter ``gbdt.entry_sort_us``); the entry
        sub-tiles a kernel level runs and the grid steps it launches go to
        ``gbdt.sparse_hist_blocks`` and ``gbdt.sparse_hist_grid_steps``.
        Sharded over ``histogram_mesh``'s axis, never in a streamed fit.
        With ``value`` and ``cuts`` in ``ebin``'s place the layout bins the
        entries itself, after its sort (counter ``gbdt.layout_bin``, one a
        fit)."""
        if not self._sparse_layout_enabled(streamed):
            return None
        num_shards = (1 if self.mesh_plan is None
                      else self.mesh_plan.num_shards)
        with telemetry.span("gbdt.entry_sort", total="gbdt.entry_sort_us"):
            layout = jax.block_until_ready(sparse_hist_layout(
                row_id, findex, ebin, emask, self.num_features,
                self.num_bins, num_shards=num_shards, rows=rows,
                value=value, cuts=cuts))
        counter_add("gbdt.layout_bin", int(value is not None))
        counter_add("gbdt.sparse_hist_blocks",
                    int(np.asarray(layout.tcount).sum()))
        counter_add("gbdt.sparse_hist_grid_steps", layout.grid_steps)
        return layout

    def _entry_lookups(self, layout, rows: int) -> int:
        """Calls of the lookup kernel that one `_build_tree_sparse` program
        holds (counter ``gbdt.entry_lookup``; 0 where `_entry_values` takes
        XLA's gather): on one device the slots of every kernel level below
        the root (the nodes they are derived from, where the level routes by
        the push: `_entry_rels`) and the tree's (grad, hess); under
        ``histogram_mesh`` both at every kernel level, of a shard's rows."""
        if layout is None:
            return 0
        levels = self.level_backends(sparse=True).count("pallas")
        local = rows // layout.num_shards
        slots, pair = (pallas_segment.entry_lookup_engages(
            layout.rows_ascend, planes * local) for planes in (1, 6))
        if self.mesh_plan is not None:
            return levels * (slots + pair)
        return max(levels - 1, 0) * slots + (levels > 0) * pair

    def _level_histogram_sparse(self, layout, rel: jax.Array,
                                gh_row: jax.Array, gh_e, rel_e, n_nodes: int):
        """Sparse [n_nodes, F, bins, 2] via the Pallas kernel, of the rows
        with ``rel`` in [0, n_nodes): a level's built columns, no ``_NO_SLOT``.
        Single-device: the rows' values laid onto the feature-sorted layout
        (``gh_e`` once a tree, ``rel_e`` once a level, both by the caller
        through `_entry_values`, scope ``gbdt.entry_gather``) feed one kernel
        call.  With
        ``histogram_mesh`` the packed per-shard layout slices ride
        ``shard_map`` ``P(axis)`` in_specs, each device runs the kernel on
        its local rows' entries, and the plan's allreduce combines the
        shards — the same rabit-histogram-allreduce shape as the dense
        `_level_histogram` route (both lookups move inside the shard_map
        body there, since rows are only device-local under the mesh)."""
        F, B = self.num_features, self.num_bins
        if self.mesh_plan is not None:
            from jax.sharding import PartitionSpec as P

            plan = self.mesh_plan
            mt = layout.max_tiles

            def local(gk, rid_l, cs_l, ts, tc, rel_l, gh_l):
                with jax.named_scope("gbdt.entry_gather"):
                    rel_e, gh_e = (
                        entry_values(rid_l, cs_l, t, layout.rows_ascend)
                        for t in (rel_l, gh_l.astype(jnp.float32)))
                h = histogram_gh_sparse_kernel(
                    gk, rel_e, gh_e, ts, tc, n_nodes, F, B, mt)
                return plan.allreduce(h)

            spec = plan.row_spec
            return plan.shard_map(local,
                                  in_specs=(spec,) * 7, out_specs=P(),
                                  check_replication=False)(
                layout.gkey, layout.rid, layout.cspan, layout.tstart,
                layout.tcount, rel, gh_row)
        return histogram_gh_sparse_kernel(
            layout.gkey, rel_e, gh_e, layout.tstart, layout.tcount,
            n_nodes, F, B, layout.max_tiles)

    # ---- forest construction ------------------------------------------------

    def init(self) -> dict:
        n_internal = 2 ** self.max_depth - 1
        # softmax grows K trees per round (round-major: tree i -> class i%K)
        total = self.num_trees * max(self.num_class, 1)
        return {
            "feature": jnp.zeros((total, n_internal), jnp.int32),
            "threshold": jnp.full((total, n_internal),
                                  self.num_bins, jnp.int32),
            "default_right": jnp.zeros((total, n_internal), jnp.int32),
            "split_gain": jnp.zeros((total, n_internal), jnp.float32),
            "split_cover": jnp.zeros((total, n_internal), jnp.float32),
            "leaf": jnp.zeros((total, 2 ** self.max_depth), jnp.float32),
            "base": (jnp.zeros(self.num_class, jnp.float32)
                     if self.objective == "softmax"
                     else jnp.zeros((), jnp.float32)),
            # NOTE: forests checkpointed before trees_used / split_gain /
            # split_cover existed have fewer leaves; load those with a
            # template that pops the newer keys (margins()/predict() only
            # require feature/threshold/leaf/base)
            "trees_used": jnp.zeros((), jnp.int32),
        }

    @staticmethod
    def _collapse_dir_ties(gain: jax.Array) -> jax.Array:
        """Deterministic default-direction tie-break on a
        [nodes, F, B, n_dir] gain array.  When a (feature, bin) has no
        missing mass the two directions' gains are mathematically equal,
        but different accumulation orders (resident vs streamed histogram
        sums, dense vs sparse builders) can leave them an ulp apart and
        flip the argmax.  Where dir 0's gain sits within eps of the pair
        max, lift dir 0 TO the pair max: the flat argmax (direction is the
        fastest axis) then lands on dir 0 — missing-left, the XGBoost
        default — identically on every path, while each (feature, bin)'s
        best value, which cross-candidate selection sees, is unchanged."""
        if gain.shape[3] < 2:
            return gain
        g0, g1 = gain[..., 0], gain[..., 1]
        best = jnp.maximum(g0, g1)
        prefer0 = g0 >= best - 1e-6 * (jnp.abs(best) + 1.0)
        return jnp.stack([jnp.where(prefer0, best, g0), g1], axis=3)

    def _pick_splits(self, gain: jax.Array, col_mask: jax.Array):
        """Flat argmax over a [nodes, F, B, n_dir] gain array plus
        null-split encoding; shared by the dense and sparse builders.
        ``col_mask`` disables features: [F] (colsample_bytree / bylevel)
        or [nodes, F] (per-node interaction constraints).
        Returns (split_f, split_b, split_d, split_gain) with nulls encoded
        as (0, num_bins, 0, 0.0)."""
        n_nodes = gain.shape[0]
        B = self.num_bins
        n_dir = gain.shape[3]
        mask = (col_mask[None, :, None, None] if col_mask.ndim == 1
                else col_mask[:, :, None, None])
        gain = jnp.where(mask, gain, -jnp.inf)
        flat = gain.reshape(n_nodes, -1)
        best_flat = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best_flat[:, None], 1)[:, 0]
        split_d = (best_flat % n_dir).astype(jnp.int32)
        best = best_flat // n_dir
        split_f = (best // B).astype(jnp.int32)
        split_b = (best % B).astype(jnp.int32)
        # gamma = min_split_loss on XGBoost's scale: its objective carries
        # a 0.5 factor this formulation omits, so its "0.5*gain <= gamma"
        # pruning rule is raw gain <= 2*gamma here (default 0 keeps the
        # positive-gain requirement; configs port over unchanged)
        null = best_gain <= 2.0 * self.gamma
        return (jnp.where(null, 0, split_f),
                jnp.where(null, B, split_b),   # everything routes left
                jnp.where(null, 0, split_d),
                jnp.where(null, 0.0, best_gain))  # importance bookkeeping

    def _objective_loss(self, margin: jax.Array, label: jax.Array,
                        weight: Optional[jax.Array]) -> jax.Array:
        """Weighted mean objective from margins (shared by loss() and the
        early-stopping eval).  softmax: margin is [rows, K], label integer
        class ids."""
        if self.objective == "logistic":
            per = logistic_nll(margin, label)
        elif self.objective == "softmax":
            per = _softmax_ce(margin, label)
        else:
            per = 0.5 * (margin - label) ** 2
        if weight is None:
            return jnp.mean(per)
        w = weight.astype(jnp.float32)
        return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-12)

    def _rank_fns(self, qid, w, eval_qid=None, eval_w=None,
                  have_eval: bool = False):
        """(grad_hess, eval_loss_fn) closures for rank:pairwise.  qid must
        be contiguous per group; weight-0 padding rows never pair."""
        if qid is None:
            raise ValueError("objective='rank:pairwise' needs qid= "
                             "(per-row query ids; stage with with_qid=True)")
        if have_eval and eval_qid is None:
            # falling back to _objective_loss would silently monitor
            # squared error for a ranking model
            raise ValueError(
                "rank:pairwise eval_set needs the eval qids: pass "
                "(eval_bins, eval_label, eval_weight_or_None, eval_qid), "
                "or an eval PaddedBatch staged with with_qid=True")
        qid = jnp.asarray(qid).astype(jnp.int32)
        max_group = _validate_rank_qid(qid, w)

        def grad_hess(margin, label):
            g, h, _, _ = _pairwise_terms(margin, label, qid, w,
                                         max_group - 1)
            return g, h

        eval_loss_fn = None
        if eval_qid is not None:
            eval_qid_arr = jnp.asarray(eval_qid).astype(jnp.int32)
            ev_group = _validate_rank_qid(eval_qid_arr, eval_w)

            def eval_loss_fn(margin, label, weight):  # noqa: F811
                ew = (jnp.ones_like(label) if weight is None
                      else weight.astype(jnp.float32))
                _, _, loss, npairs = _pairwise_terms(
                    margin, label, eval_qid_arr, ew, ev_group - 1)
                return loss / jnp.maximum(npairs, 1)

        return grad_hess, eval_loss_fn

    def rank_scores(self, params: dict, bins: jax.Array) -> jax.Array:
        """Ranking scores (higher = ranked above) — just the margins."""
        return self.margins(params, bins)

    def pairwise_loss(self, params: dict, bins: jax.Array,
                      label: jax.Array, qid: jax.Array,
                      weight: Optional[jax.Array] = None) -> jax.Array:
        """Mean pairwise logistic loss over same-query pairs."""
        w = (jnp.ones_like(label) if weight is None
             else weight.astype(jnp.float32))
        qid = jnp.asarray(qid).astype(jnp.int32)
        max_group = _validate_rank_qid(qid, w)
        m = self.margins(params, bins)
        _, _, loss, npairs = _pairwise_terms(
            m, label.astype(jnp.float32), qid, w, max_group - 1)
        return loss / jnp.maximum(npairs, 1)

    def _on(self, label):
        """Where ``_boost`` makes its margins: under a mesh plan beside a
        label that lies over the plan's axes (``jnp.full(label.shape, ...)``
        made them whole on the first chip, 460 MB at 115M rows, and every
        round's first op resharded them); else where ``jnp.full`` puts
        them, as ever."""
        plan = self.mesh_plan
        if (plan is None or not getattr(label, "committed", False)
                or label.shape[0] % plan.num_shards):
            return None
        return label.sharding

    def _tree_span(self, build_tree, trees: int = 1):
        """The span ``gbdt.tree`` around one boosting round's ``trees`` calls
        of ``build_tree``, which also counts the node histograms those trees
        build and derive (``gbdt.hist_nodes_built``, ``gbdt.hist_nodes_derived``:
        `_built_columns`) and, for the resident dense builder (``GBDT.fit``:
        one kernel call a level and shard), the padding key tiles those
        calls leave out (``gbdt.hist_dead_key_tiles``: `_dead_key_tiles`;
        `fit_paged` counts its own, a page visit at a time); under a mesh
        plan it also counts the reductions those calls run
        (``MeshPlan.counting``), under the name of the fit that made
        ``build_tree`` (``GBDT.fit``, ``GBDT.fit_batch``, ...: one tree
        program each)."""
        span = telemetry.span("gbdt.tree")
        built = trees * sum(map(_built_columns, range(self.max_depth)))
        counter_add("gbdt.hist_nodes_built", built)
        counter_add("gbdt.hist_nodes_derived",
                    trees * (2 ** self.max_depth - 1) - built)
        plan = self.mesh_plan
        kind = getattr(build_tree, "__qualname__", "").split(".<locals>")[0]
        if kind == "GBDT.fit":
            counter_add("gbdt.hist_dead_key_tiles",
                        trees * (1 if plan is None else plan.num_shards)
                        * sum(map(self._dead_key_tiles,
                                  range(self.max_depth))))
        if plan is None:
            return span
        return plan.counting(kind, trees, around=span)

    def _boost(self, label: jax.Array, w: jax.Array, build_tree,
               eval_margin=None, eval_label=None, eval_weight=None,
               early_stopping_rounds: int = 0,
               grad_hess=None, eval_loss_fn=None) -> dict:
        """Shared boosting driver (base prior, tree loop, stochastic
        row/column sampling, stacking) for the dense (`fit`) and
        sparse-native (`fit_batch`) input paths.
        ``build_tree(grad, hess, col_mask, col_key)`` returns `_build_tree`'s
        7-tuple.

        Early stopping: ``eval_margin(tree_params) -> per-row margins`` on
        a held-out set; when its loss fails to improve for
        ``early_stopping_rounds`` consecutive trees, boosting stops and
        the forest is truncated at the best round (XGBoost's
        ``early_stopping_rounds`` semantics).  Unused leading capacity is
        null-padded so the pytree keeps its static [num_trees, ...]
        shapes (null trees route everything to leaf 0 with weight 0)."""
        params = self.init()
        if self.scale_pos_weight != 1.0:
            # XGBoost's positive-class reweighting, as weight sugar
            w = w * jnp.where(label > 0.5, self.scale_pos_weight, 1.0)
        if self.base_score is not None:
            bs = jnp.asarray(self.base_score, jnp.float32)
            if self.objective == "logistic":
                # XGBoost semantics: base_score is a PROBABILITY for the
                # logistic objective (its default 0.5 means margin 0)
                bs = jnp.clip(bs, 1e-6, 1 - 1e-6)
                base = jnp.log(bs / (1 - bs))
            else:
                base = bs
        else:
            sum_w = jnp.maximum(jnp.sum(w), 1e-12)  # div-by-zero guard only
            if self.objective == "logistic":
                # base margin from the weighted prior, clamped away from 0/1
                p = jnp.clip(jnp.sum(jnp.where(label > 0.5, w, 0.0)) / sum_w,
                             1e-6, 1 - 1e-6)
                base = jnp.log(p / (1 - p))
            else:
                base = jnp.sum(label * w) / sum_w
        params["base"] = base.astype(jnp.float32)

        margin = jnp.full(label.shape, params["base"], device=self._on(label))
        root_key = jax.random.PRNGKey(self.seed)
        have_eval = eval_margin is not None
        ev_m = (jnp.full(eval_label.shape, params["base"]) if have_eval
                else None)
        best_loss, best_t, since_best = float("inf"), 0, 0
        feats, thrs, dirs, sgains, scovers, leaves = [], [], [], [], [], []
        grad_hess = grad_hess or self._grad_hess
        eval_loss_fn = eval_loss_fn or self._objective_loss
        for t_idx in range(self.num_trees):
            with self._tree_span(build_tree):
                # these run op by op, each its own program: a profile finds
                # them as the programs that are not jit(_build_tree); the
                # scope names them once the round is one program
                with jax.named_scope("gbdt.boost"):
                    g, h = grad_hess(margin, label)
                    w_t, col_mask = self._tree_sampling(root_key, t_idx, w)
                    ck = jax.random.fold_in(root_key, 1_000_000 + t_idx)
                    g, h = g * w_t, h * w_t
                f, t, d, sg, sc, leaf, leaf_rel = build_tree(g, h,
                                                             col_mask, ck)
                with jax.named_scope("gbdt.boost"):
                    margin = _add_leaf_values(margin, leaf, leaf_rel)
                feats.append(f)
                thrs.append(t)
                dirs.append(d)
                sgains.append(sg)
                scovers.append(sc)
                leaves.append(leaf)
                if have_eval:
                    ev_m = ev_m + eval_margin(f, t, d, leaf)
                    loss = float(eval_loss_fn(ev_m, eval_label,
                                              eval_weight))
                    if loss < best_loss:
                        best_loss, best_t, since_best = loss, t_idx + 1, 0
                    elif early_stopping_rounds > 0:
                        since_best += 1
                        if since_best >= early_stopping_rounds:
                            break
        # truncation at the best round only when stopping was requested:
        # an eval_set alone is monitoring, not a pruning instruction
        stop_on = have_eval and early_stopping_rounds > 0
        trees_used = best_t if stop_on else len(feats)
        return self._stack_forest(params, feats, thrs, dirs, sgains,
                                  scovers, leaves, trees_used,
                                  self.num_trees)

    def _dir_child_weights(self, dirs, g_tot, h_tot):
        """Child weights -GL/(HL+λ), -GR/(HR+λ) per direction, stacked to
        the gain array's [nodes, F, B, n_dir] layout (one formula shared
        by the dense and sparse builders)."""
        lam = self.lambda_
        ws = [(-a / (b + lam), -(g_tot - a) / (h_tot - b + lam))
              for a, b in dirs]
        wl = jnp.stack([wp[0] for wp in ws], axis=3)
        wr = jnp.stack([wp[1] for wp in ws], axis=3)
        return wl, wr

    def _apply_monotone(self, gain, wl, wr, lo, hi):
        """Mask monotonicity-violating splits (XGBoost monotone_constraints).

        gain/wl/wr: [nodes, F, B, n_dir]; lo/hi: [nodes] output bounds.
        For constraint +1 on feature f the left child's weight must not
        exceed the right child's (and both must admit a value inside the
        node's bounds after clipping); -1 mirrors.  Unconstrained features
        pass through."""
        c = self.monotone_constraints  # [F] in {-1, 0, +1}
        wl_c = jnp.clip(wl, lo[:, None, None, None], hi[:, None, None, None])
        wr_c = jnp.clip(wr, lo[:, None, None, None], hi[:, None, None, None])
        ok_pos = wl_c <= wr_c
        ok_neg = wl_c >= wr_c
        cb = c[None, :, None, None]
        ok = jnp.where(cb > 0, ok_pos, jnp.where(cb < 0, ok_neg, True))
        return jnp.where(ok, gain, -jnp.inf)

    def _child_bounds(self, split_f, split_b, split_d, wl, wr, lo, hi):
        """Bounds for the next level's nodes after splitting.

        Gathers the chosen split's (clipped) child weights, takes their
        midpoint, and narrows the children of constrained features:
        +1: left.hi = min(hi, mid), right.lo = max(lo, mid); -1 mirrored.
        Null splits (threshold == num_bins) pass bounds through.  Returns
        (lo2, hi2) of length 2 * nodes in heap child order."""
        n_nodes = wl.shape[0]
        B = self.num_bins
        # null splits encode threshold == B: clamp the gather (mid is
        # unused for them — the where below passes bounds through)
        flat_idx = jnp.minimum((split_f * B + split_b) * wl.shape[3]
                               + split_d,
                               wl.shape[1] * B * wl.shape[3] - 1)
        pick = lambda a: jnp.take_along_axis(  # noqa: E731
            a.reshape(n_nodes, -1), flat_idx[:, None], 1)[:, 0]
        wl_c = jnp.clip(pick(wl), lo, hi)
        wr_c = jnp.clip(pick(wr), lo, hi)
        mid = 0.5 * (wl_c + wr_c)
        c = self.monotone_constraints[split_f]
        null = split_b >= B
        hi_l = jnp.where(~null & (c > 0), jnp.minimum(hi, mid), hi)
        lo_l = jnp.where(~null & (c < 0), jnp.maximum(lo, mid), lo)
        lo_r = jnp.where(~null & (c > 0), jnp.maximum(lo, mid), lo)
        hi_r = jnp.where(~null & (c < 0), jnp.minimum(hi, mid), hi)
        # heap order: children of node n are 2n+1, 2n+2 -> interleave
        lo2 = jnp.stack([lo_l, lo_r], axis=1).reshape(-1)
        hi2 = jnp.stack([hi_l, hi_r], axis=1).reshape(-1)
        return lo2, hi2

    def _level_feature_mask(self, col_mask, col_key, depth: int, active):
        """Effective feature mask for one level: the per-tree mask, an
        optional fresh colsample_bylevel draw (sampled WITHIN the tree
        subset, so the intersection can never go empty), and the per-node
        interaction allowed sets.  Returns [F] or [nodes, F]."""
        eff = col_mask
        if self.colsample_bylevel < 1.0:
            k_tree = (max(1, int(round(self.colsample_bytree
                                       * self.num_features)))
                      if self.colsample_bytree < 1.0 else self.num_features)
            k_level = max(1, int(round(self.colsample_bylevel * k_tree)))
            kd = jax.random.fold_in(col_key, depth)
            scores = jnp.where(col_mask,
                               jax.random.uniform(kd, (self.num_features,)),
                               jnp.inf)
            thresh = jnp.sort(scores)[k_level - 1]
            eff = scores <= thresh
        if active is not None:
            # allowed features per node = union of its active groups
            allowed = jnp.einsum("ng,gf->nf", active,
                                 self._interaction_groups) > 0
            return allowed & eff[None, :]
        return eff

    def _next_active(self, active, split_f, split_b):
        """Propagate interaction-constraint group sets to the children: a
        real split on f keeps only the active groups CONTAINING f (group
        identity, not pairwise co-occurrence — overlapping groups stay
        correct); null splits pass through.  [n, G] -> [2n, G] in heap
        child order."""
        null = (split_b >= self.num_bins)[:, None]
        in_group = self._interaction_groups[:, split_f].T  # [n, G]
        nxt = jnp.where(null, active, active & in_group)
        return jnp.repeat(nxt, 2, axis=0)

    def _tree_sampling(self, root_key, t_idx: int, w: jax.Array):
        """Per-tree stochastic-GBM masks, shared by every boosting driver:
        a Bernoulli row mask folded into the weights (routing still sees
        all rows) and a feature subset for the gains.  Derived from
        (seed, tree index) only, so sharded / multi-host runs sample
        identically."""
        w_t = w
        if self.subsample < 1.0:
            kr = jax.random.fold_in(root_key, 2 * t_idx)
            w_t = w * jax.random.bernoulli(
                kr, self.subsample, w.shape).astype(jnp.float32)
        if self.colsample_bytree < 1.0:
            kc = jax.random.fold_in(root_key, 2 * t_idx + 1)
            k_cols = max(1, int(round(self.colsample_bytree
                                      * self.num_features)))
            sel = jax.random.permutation(kc, self.num_features)[:k_cols]
            col_mask = jnp.zeros(self.num_features, bool).at[sel].set(True)
        else:
            col_mask = jnp.ones(self.num_features, bool)
        return w_t, col_mask

    @telemetry.span("gbdt.stack")
    def _stack_forest(self, params, feats, thrs, dirs, sgains, scovers,
                      leaves, trees_used: int, total: int) -> dict:
        """Null-pad the per-tree lists to ``total`` static slots (trees past
        trees_used — stopped early or worse-than-best — route every row
        left to leaf 0 whose weight is 0) and stack into the pytree."""
        n_internal = 2 ** self.max_depth - 1
        null_f = jnp.zeros(n_internal, jnp.int32)
        null_t = jnp.full(n_internal, self.num_bins, jnp.int32)
        null_g = jnp.zeros(n_internal, jnp.float32)
        null_leaf = jnp.zeros(2 ** self.max_depth, jnp.float32)
        for i in range(total):
            if i < trees_used:
                continue
            if i < len(feats):
                feats[i], thrs[i], dirs[i] = null_f, null_t, null_f
                sgains[i], scovers[i], leaves[i] = null_g, null_g, null_leaf
            else:
                feats.append(null_f)
                thrs.append(null_t)
                dirs.append(null_f)
                sgains.append(null_g)
                scovers.append(null_g)
                leaves.append(null_leaf)
        params["feature"] = jnp.stack(feats)
        params["threshold"] = jnp.stack(thrs)
        params["default_right"] = jnp.stack(dirs)
        params["split_gain"] = jnp.stack(sgains)
        params["split_cover"] = jnp.stack(scovers)
        params["leaf"] = jnp.stack(leaves)
        params["trees_used"] = jnp.asarray(np.int32(trees_used))
        return params

    def _boost_multi(self, label: jax.Array, w: jax.Array, build_tree,
                     eval_margin=None, eval_label=None, eval_weight=None,
                     early_stopping_rounds: int = 0) -> dict:
        """Softmax boosting: K one-vs-rest trees per round against the
        shared softmax distribution (XGBoost multi:softprob).  Tree i
        belongs to class ``i % K`` (round-major); early stopping operates
        on whole rounds against the held-out cross-entropy."""
        K = self.num_class
        params = self.init()
        label = label.astype(jnp.int32)
        if bool(jnp.any((label < 0) | (label >= K))):
            # out-of-range classes would silently train a corrupted forest
            # (zero one-hot rows, clamped CE indices)
            raise ValueError(
                f"softmax labels must be integers in [0, {K}); got range "
                f"[{int(jnp.min(label))}, {int(jnp.max(label))}]")
        sum_w = jnp.maximum(jnp.sum(w), 1e-12)
        onehot = jax.nn.one_hot(label, K, dtype=jnp.float32)
        if self.base_score is not None:
            base = jnp.broadcast_to(
                jnp.asarray(self.base_score, jnp.float32), (K,))
            params["base"] = base
        else:
            prior = jnp.clip(jnp.sum(onehot * w[:, None], axis=0) / sum_w,
                             1e-6, 1.0)
            params["base"] = jnp.log(prior)

        margin = jnp.broadcast_to(params["base"], (label.shape[0], K))
        have_eval = eval_margin is not None
        ev_m = (jnp.broadcast_to(params["base"],
                                 (eval_label.shape[0], K)) if have_eval
                else None)
        best_loss, best_round, since_best = float("inf"), 0, 0
        root_key = jax.random.PRNGKey(self.seed)
        feats, thrs, dirs, sgains, scovers, leaves = [], [], [], [], [], []
        for r in range(self.num_trees):
            with self._tree_span(build_tree, K):   # one round: K trees
                with jax.named_scope("gbdt.boost"):
                    p = jax.nn.softmax(margin, axis=1)
                if have_eval:
                    ev_round = []
                for k in range(K):
                    t_idx = r * K + k
                    with jax.named_scope("gbdt.boost"):
                        g = (p[:, k] - onehot[:, k])
                        h = jnp.maximum(p[:, k] * (1.0 - p[:, k]), 1e-16)
                        w_t, col_mask = self._tree_sampling(root_key, t_idx,
                                                            w)
                        ck = jax.random.fold_in(root_key, 1_000_000 + t_idx)
                        g, h = g * w_t, h * w_t
                    f, t, d, sg, sc, leaf, leaf_rel = build_tree(
                        g, h, col_mask, ck)
                    value = _add_leaf_values(None, leaf, leaf_rel)
                    margin = margin.at[:, k].add(value)
                    feats.append(f)
                    thrs.append(t)
                    dirs.append(d)
                    sgains.append(sg)
                    scovers.append(sc)
                    leaves.append(leaf)
                    if have_eval:
                        ev_round.append(eval_margin(f, t, d, leaf))
                if have_eval:
                    ev_m = ev_m + jnp.stack(ev_round, axis=1)
                    loss = float(self._objective_loss(ev_m, eval_label,
                                                      eval_weight))
                    if loss < best_loss:
                        best_loss, best_round, since_best = loss, r + 1, 0
                    elif early_stopping_rounds > 0:
                        since_best += 1
                        if since_best >= early_stopping_rounds:
                            break
        stop_on = have_eval and early_stopping_rounds > 0
        trees_used = (best_round * K if stop_on else len(feats))
        return self._stack_forest(params, feats, thrs, dirs, sgains,
                                  scovers, leaves, trees_used,
                                  self.num_trees * K)

    @functools.partial(jax.jit, static_argnums=0)
    def _build_tree(self, bins: jax.Array, grad: jax.Array, hess: jax.Array,
                    col_mask: jax.Array, col_key: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                               jax.Array, jax.Array, jax.Array]:
        """One tree from per-row (grad, hess); levels unrolled under jit.
        The root's histogram is built whole; a level below it builds one
        child of every parent (`_smaller_child`) from the rows that stand in
        it (`_child_slot`; the bit travels in `_route_level`'s word, no
        gather a row) and derives the other (`_with_siblings`): the backend
        sums ``2 ** (depth - 1)`` node columns of the level's ``2 ** depth``,
        reduced under a mesh plan before the subtraction.  A leaf's (G, H)
        are the sums its parent's split was chosen by (`_split_child_sums`).

        bins: u8 [rows, features]; grad/hess: f32 [rows] (weight-scaled,
        padding rows carry 0 mass).  Returns (feature, threshold,
        default_right, split_gain, split_cover, leaf, leaf_rel) where
        leaf_rel is each row's final leaf index.
        """
        F, B = self.num_features, self.num_bins
        rows = bins.shape[0]
        # every phase of the level loop carries a scope (doc/observability.md,
        # "Device scope contract"): a profile and the benchmark's per-layer
        # metrics find device time by these names, not by %fusion.N
        with jax.named_scope("gbdt.cast"):
            bins_i = bins.astype(jnp.int32)

        node = jnp.zeros(rows, jnp.int32)  # heap id of each row's node
        lo = jnp.full(1, -jnp.inf)
        hi = jnp.full(1, jnp.inf)
        active = (jnp.ones((1, self._interaction_groups.shape[0]), bool)
                  if self._interaction_groups is not None else None)
        features, thresholds, defaults, gains, covers = [], [], [], [], []
        # a row's column among the level's built histograms: at the root its
        # node, below it `_child_slot`; the level above, and which child of
        hist, slot, right_built = None, node, None  # each parent was built
        for depth in range(self.max_depth):
            first = 2 ** depth - 1          # heap id of the level's first node
            n_nodes = 2 ** depth
            # fused histogram build: ONE reduction over rows x features
            # carrying (grad, hess) lanes together — the key array (the
            # bandwidth bottleneck) is read once, not once per statistic —
            # of the built columns only (`_built_columns`), their siblings
            # by subtraction.  Backend per level via _hist_impl: the Pallas
            # kernel on TPU while the level is shallow (scatter-free; see
            # ops.histogram_gh), XLA scatter-add otherwise.
            with jax.named_scope("gbdt.hist"):
                rel = node - first          # [rows] in [0, n_nodes)
                gh = jnp.stack([grad, hess], axis=-1)  # [rows, 2]
                hist = _with_siblings(hist, self._level_histogram(
                    bins_i, slot, gh, _built_columns(depth),
                    self._hist_impl(n_nodes)), right_built)
            with jax.named_scope("gbdt.split"):
                (split_f, split_b, split_d, split_g, lo, hi, active,
                 right_built, cover, dirs) = self._dense_level_splits(
                    hist, depth, col_mask, col_key, lo, hi, active)
                covers.append(cover)
            features.append(split_f)
            thresholds.append(split_b)
            defaults.append(split_d)
            gains.append(split_g)
            # route rows: children of heap node n are 2n+1 (left), 2n+2
            with jax.named_scope("gbdt.route"):
                # the same expression as ops.histogram_gh's kernel layout:
                # XLA keeps one transpose of the bins for the whole tree
                go_right, built_bit = self._route_level(
                    bins_i.T, rel, split_f, split_b, split_d, right_built)
                node = 2 * node + 1 + go_right.astype(jnp.int32)
                slot = _child_slot(rel, go_right, built_bit)

        # leaf weights: -G/(H + lambda) per leaf, shrunken (clamped into the
        # node's propagated bounds first under monotone constraints).  Leaves
        # 2k, 2k + 1 are the children of the last level's node k: their
        # (G, H) are the sums its split was chosen by, no pass over the rows
        # (a null split's right leaf holds none: exactly (0, 0), weight 0)
        n_leaves = 2 ** self.max_depth
        with jax.named_scope("gbdt.leaf"):
            leaf_rel = node - (n_leaves - 1)  # each row's leaf, for `_boost`
            leaf = self._leaves_from_split_sums(dirs, split_f, split_b,
                                                split_d, lo, hi)
        return (jnp.concatenate(features), jnp.concatenate(thresholds),
                jnp.concatenate(defaults), jnp.concatenate(gains),
                jnp.concatenate(covers), leaf, leaf_rel)

    def _dense_level_splits(self, hist, depth: int, col_mask, col_key,
                            lo, hi, active):
        """Split finding for one level of a DENSE fit from its whole
        ``[n_nodes, F, B, 2]`` (grad, hess) histogram, in which the missing
        values' mass lies in bin 0 (a missing-aware binner's code for them):
        cumulative sums, both default directions' gains from the same
        histograms, monotone bounds, feature masks, interaction groups, the
        arg-max, and which child of each node the next level builds.  The
        resident `_build_tree` and the paged `fit_paged` both call it, so
        the two grow one forest from one histogram; only how the histogram
        was summed differs.  Returns ``(split_f, split_b, split_d, split_g,
        lo, hi, active, right_built, cover, dirs)``: ``cover`` the nodes'
        hessian mass, ``dirs`` the cumulative (G, H) a default direction,
        which `_split_child_sums` reads the leaves from."""
        mono = self.monotone_constraints is not None
        hist_g = hist[..., 0]
        hist_h = hist[..., 1]
        # left cumulative mass for "go right if bin > b" at each cut
        gl = jnp.cumsum(hist_g, axis=2)
        hl = jnp.cumsum(hist_h, axis=2)
        g_tot = gl[:, :, -1:]
        h_tot = hl[:, :, -1:]
        lam = self.lambda_

        def split_gain(gl_, hl_):
            gr_ = g_tot - gl_
            hr_ = h_tot - hl_
            g = (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
                 - g_tot ** 2 / (h_tot + lam))      # [nodes, F, B]
            ok = ((hl_ >= self.min_child_weight) &
                  (hr_ >= self.min_child_weight))
            return jnp.where(ok, g, -jnp.inf)

        if self.missing_aware:
            # evaluate every cut twice from the same histograms:
            # missing (bin 0) mass on the left (its natural cumsum
            # side) vs on the right.  dir axis: 0 = left, 1 = right
            # (argmax ties resolve to left, the XGBoost default).
            dirs = [(gl, hl),
                    (gl - hist_g[:, :, 0:1], hl - hist_h[:, :, 0:1])]
        else:
            dirs = [(gl, hl)]
        gain = jnp.stack([split_gain(a, b) for a, b in dirs], axis=3)
        if mono:
            wl, wr = self._dir_child_weights(dirs, g_tot, h_tot)
            gain = self._apply_monotone(gain, wl, wr, lo, hi)
        gain = self._collapse_dir_ties(gain)
        node_mask = self._level_feature_mask(col_mask, col_key, depth,
                                             active)
        split_f, split_b, split_d, split_g = self._pick_splits(
            gain, node_mask)
        if mono:
            lo, hi = self._child_bounds(split_f, split_b, split_d,
                                        wl, wr, lo, hi)
        if active is not None:
            active = self._next_active(active, split_f, split_b)
        cover = h_tot[:, 0, 0]          # node hessian mass (any f)
        right_built = _smaller_child([hl_ for _, hl_ in dirs], cover,
                                     split_f, split_b, split_d)
        return (split_f, split_b, split_d, split_g, lo, hi, active,
                right_built, cover, dirs)

    def _leaves_from_split_sums(self, dirs, split_f, split_b, split_d,
                                lo, hi) -> jax.Array:
        """The shrunken leaf weights of a dense tree off its last level's
        cumulative sums (`_split_child_sums`: no pass over the rows),
        clamped into the nodes' bounds under monotone constraints."""
        gh_leaf = _split_child_sums(dirs, split_f, split_b, split_d)
        leaf_w = -gh_leaf[:, 0] / (gh_leaf[:, 1] + self.lambda_)
        if self.monotone_constraints is not None:
            leaf_w = jnp.clip(leaf_w, lo, hi)
        return self.learning_rate * leaf_w

    def _route_level(self, bins_t: jax.Array, rel: jax.Array,
                     split_f: jax.Array, split_b: jax.Array,
                     split_d: jax.Array, right_built: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
        """Which rows of a level go to their node's right child, and whether
        their node's right child is the one the next level builds
        (``right_built[rel]``, for `_child_slot`): two bool [rows].

        bins_t: i32 [F, rows], feature-major; rel: [rows] in [0, n_nodes);
        split_f / split_b / split_d / right_built: the level's [n_nodes]
        split tables.
        Dense compare-and-select with rows on the lanes, integers only: a
        per-row gather (``bins[arange(rows), split_f[rel]]``) took 22 ns a
        row a level on a v5e whatever the table's size, a quarter of a
        round at 10.5M rows (PERF.md, PR 26).  The node's four entries
        travel as one word, so the pass over the nodes selects once (past
        ``_ROUTE_SELECT_NODES`` nodes one gather of that word is cheaper);
        the row's bin is then the one term of a sum over the features that
        its node's feature leaves standing.
        """
        F = bins_t.shape[0]
        n_nodes = split_f.shape[0]
        # split_b reaches num_bins, the null split's sentinel
        bbits = self.num_bins.bit_length()
        if F.bit_length() + bbits + 2 > 31:
            raise ValueError(f"{F} features do not pack beside "
                             f"{self.num_bins} bins into an int32 word")
        word = ((split_f << (bbits + 2))
                | (right_built.astype(jnp.int32) << (bbits + 1))
                | (split_d << bbits) | split_b)
        if n_nodes > _ROUTE_SELECT_NODES:
            word = word[rel]
        elif n_nodes > 1:
            ids = jnp.arange(n_nodes, dtype=jnp.int32)[:, None]
            word = jnp.sum(jnp.where(rel[None, :] == ids, word[:, None], 0),
                           axis=0)                          # [rows]
        feat = jnp.arange(F, dtype=jnp.int32)[:, None]
        row_bin = jnp.sum(jnp.where((word >> (bbits + 2))[None, :] == feat,
                                    bins_t, 0), axis=0)
        go_right = row_bin > (word & ((1 << bbits) - 1))
        if self.missing_aware:
            go_right = jnp.where(row_bin == 0, ((word >> bbits) & 1) == 1,
                                 go_right)
        return go_right, ((word >> (bbits + 1)) & 1) == 1

    @functools.partial(jax.jit, static_argnums=0)
    def _tree_margins(self, feature: jax.Array, threshold: jax.Array,
                      default_right: jax.Array, leaf: jax.Array,
                      bins: jax.Array) -> jax.Array:
        """Route every row down one tree; returns its leaf weight per row."""
        rows = bins.shape[0]
        bins_i = bins.astype(jnp.int32)
        node = jnp.zeros(rows, jnp.int32)
        for _ in range(self.max_depth):
            f = feature[node]
            t = threshold[node]
            b = bins_i[jnp.arange(rows), f]
            go_right = b > t
            if self.missing_aware:
                go_right = jnp.where(b == 0, default_right[node] == 1,
                                     go_right)
            node = 2 * node + 1 + go_right.astype(jnp.int32)
        return leaf[node - (2 ** self.max_depth - 1)]

    @functools.partial(jax.jit, static_argnums=0)
    def _level_splits_from_hist(self, hist, gh_node, depth, col_mask,
                                col_key, lo, hi, active):
        """Split finding for one level given its accumulated
        ``[n_nodes, F, B, 2]`` (grad, hess) histogram and ``[n_nodes, 2]``
        node totals: missing-mass derivation, dual-direction gains,
        monotone bounds, per-level feature masks, interaction-group
        propagation.  Shared verbatim by the resident sparse tree builder
        and the out-of-core streamed builder, so the two produce identical
        forests from identical data — only how the histogram was
        accumulated differs.  Returns ``(split_f, split_b, split_d,
        split_g, lo, hi, active, right_built)``, the last `_smaller_child`'s
        word on which child of each node the next level builds."""
        with jax.named_scope("gbdt.split"):
            lam = self.lambda_
            mono = self.monotone_constraints is not None
            miss = gh_node[:, None, :] - jnp.sum(hist, axis=2)   # [n, F, 2]
            gl = jnp.cumsum(hist, axis=2)                   # present mass
            g_tot = gh_node[:, 0][:, None, None]            # [n, 1, 1]
            h_tot = gh_node[:, 1][:, None, None]

            def split_gain(gl_, hl_):
                gr_ = g_tot - gl_
                hr_ = h_tot - hl_
                g = (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
                     - g_tot ** 2 / (h_tot + lam))
                ok = ((hl_ >= self.min_child_weight) &
                      (hr_ >= self.min_child_weight))
                return jnp.where(ok, g, -jnp.inf)

            # dir 0: missing left (GL gains the missing mass); dir 1: right
            dirs = [(gl[..., 0] + miss[:, :, None, 0],
                     gl[..., 1] + miss[:, :, None, 1]),
                    (gl[..., 0], gl[..., 1])]
            gain = jnp.stack([split_gain(a, b) for a, b in dirs], axis=3)
            if mono:
                wl, wr = self._dir_child_weights(dirs, g_tot, h_tot)
                gain = self._apply_monotone(gain, wl, wr, lo, hi)
            gain = self._collapse_dir_ties(gain)
            node_mask = self._level_feature_mask(col_mask, col_key, depth,
                                                 active)
            split_f, split_b, split_d, split_g = self._pick_splits(gain,
                                                                   node_mask)
            if mono:
                lo, hi = self._child_bounds(split_f, split_b, split_d,
                                            wl, wr, lo, hi)
            if active is not None:
                active = self._next_active(active, split_f, split_b)
            right_built = _smaller_child([hl_ for _, hl_ in dirs],
                                         gh_node[:, 1], split_f, split_b,
                                         split_d)
        return split_f, split_b, split_d, split_g, lo, hi, active, right_built

    @functools.partial(jax.jit, static_argnums=0)
    def _build_tree_sparse(self, entries, layout, grad: jax.Array,
                           hess: jax.Array, col_mask: jax.Array,
                           col_key: jax.Array):
        """One tree from COO entries — O(nnz) histogram work per level, one
        jitted program a tree as `_build_tree` is.

        The sparse formulation of `_build_tree`: present entries
        accumulate their row's (grad, hess) into [nodes, features, bins]
        keyed by (node(row), feature, bin); each (node, feature)'s missing
        mass is the node total minus its present sum, and the
        dual-direction gain machinery is shared with the dense
        missing-aware path.  Requires ``missing_aware=True`` bins from
        ``transform_entries`` (all codes >= 1; bin 0 stays empty).
        As in `_build_tree`, a level below the root accumulates one child
        of every parent — an entry is keyed by its row's `_child_slot`, and
        ``_NO_SLOT`` drops it on either backend — and derives the sibling
        (`_with_siblings`); the node totals are still every node's.  The
        root's slots are all 0 and are not gathered (`_entry_slots`).

        Histogram accumulation routes through the ``histogram=`` backend
        knob per level (`_hist_impl_sparse`): XLA keeps the flattened-key
        scatter-add over ``entries``; "pallas" runs the feature-sorted
        one-hot-contraction kernel against ``layout`` (the once-per-fit
        sorted entry layout from `_sparse_fit_layout`: ``findex`` never
        changes across levels or trees, so one sort serves the whole fit).
        On kernel levels the node totals and leaf sums ride the multi-lane
        pallas ``segment_sum``; under ``histogram_mesh`` they stay on XLA
        scatter so GSPMD inserts their psum.

        entries: ``(row_id, findex, ebin, emask)`` int32/bool entry arrays
        (emask 0 marks padding lanes), or None when every level runs the
        kernel on one device and the layout's ``rows_ascend`` holds: the
        layout's live entries are then the only copy, and rows are routed
        by them (`_route_layout`; a (row, feature) pair then has one entry
        at most, so the bin found is `_route_sparse`'s maximum over it).
        grad/hess: [rows] weight-scaled.  Returns the same 7-tuple as
        `_build_tree`.
        """
        F, B = self.num_features, self.num_bins
        rows = grad.shape[0]
        mono = self.monotone_constraints is not None
        mesh = self.mesh_plan is not None
        gh_row = jnp.stack([grad, hess], axis=-1)          # [rows, 2]
        impls = [self._hist_impl_sparse(2 ** d) if layout is not None
                 else "xla" for d in range(self.max_depth)]
        # entry-level (grad, hess) lanes, gathered once per TREE (the
        # values change with the margins, so this is the hoist floor):
        # scatter levels want unsorted gh_k, kernel levels the sorted gh_e
        gh_k = gh_e = None
        if "pallas" in impls and not mesh:
            # lanes first for the kernel; where it is gathered, one gather of
            # a row's pair: two gathers of one lane each took 7.6 s against
            # 3.0 s at 2.18e8 entries on a v5e (PERF.md, PR 27)
            gh_e = _entry_values(layout, gh_row)
        if entries is not None:
            rid, fi, ebin, emask = entries
        push = _routes_by_push(entries, layout, rows)
        rel_e = None

        node = jnp.zeros(rows, jnp.int32)
        lo = jnp.full(1, -jnp.inf)
        hi = jnp.full(1, jnp.inf)
        active = (jnp.ones((1, self._interaction_groups.shape[0]), bool)
                  if self._interaction_groups is not None else None)
        features, thresholds, defaults, gains, covers = [], [], [], [], []
        # as in `_build_tree`: a row's column among the built histograms,
        # the level above, and which child of each of its nodes was built
        hist, slot, right_built = None, node, None
        for depth, impl in enumerate(impls):
            first = 2 ** depth - 1
            n_nodes = 2 ** depth
            cols = _built_columns(depth)
            rel = node - first
            if impl == "pallas":
                if push:
                    rel_e, slot_e = _entry_rels(layout, rel, right_built,
                                                depth)
                else:
                    slot_e = (None if mesh
                              else _entry_slots(layout, slot, depth))
                with jax.named_scope("gbdt.hist"):
                    built = self._level_histogram_sparse(
                        layout, slot, gh_row, gh_e, slot_e, cols)
            else:
                with jax.named_scope("gbdt.hist"):
                    if gh_k is None:    # padding lanes carry 0 mass
                        gh_k = (gh_row[rid]
                                * emask.astype(jnp.float32)[:, None])
                    keys = (slot[rid] * F + fi) * B + ebin
                    built = jax.ops.segment_sum(
                        gh_k, keys, num_segments=cols * F * B
                    ).reshape(cols, F, B, 2)                # bin 0 is empty
            with jax.named_scope("gbdt.hist"):
                hist = _with_siblings(hist, built, right_built)
            with jax.named_scope("gbdt.node_totals"):
                gh_node = segment_sum(
                    gh_row, rel, num_segments=n_nodes,
                    force="pallas" if impl == "pallas" and not mesh
                    else None)
            (split_f, split_b, split_d, split_g, lo, hi, active,
             right_built) = self._level_splits_from_hist(
                hist, gh_node, depth, col_mask, col_key, lo, hi, active)
            features.append(split_f)
            thresholds.append(split_b)
            defaults.append(split_d)
            gains.append(split_g)
            covers.append(gh_node[:, 1])
            with jax.named_scope("gbdt.route"):
                if entries is None:
                    go_right = self._route_layout(layout, rel, rel_e,
                                                  split_f, split_b, split_d)
                else:
                    go_right = self._route_sparse(fi, ebin, emask, rid,
                                                  split_f[rel], split_b[rel],
                                                  split_d[rel], rows)
                node = 2 * node + 1 + go_right.astype(jnp.int32)
                if not push:    # (the push's levels derive it on the lanes)
                    slot = _child_slot(rel, go_right, right_built[rel])

        n_leaves = 2 ** self.max_depth
        with jax.named_scope("gbdt.leaf"):
            leaf_rel = node - (n_leaves - 1)
            leaf_force = ("pallas" if layout is not None and not mesh
                          and self._hist_impl_sparse(n_leaves) == "pallas"
                          else None)
            gh_leaf = segment_sum(gh_row, leaf_rel, num_segments=n_leaves,
                                  force=leaf_force)
            leaf_w = -gh_leaf[:, 0] / (gh_leaf[:, 1] + self.lambda_)
            if mono:
                leaf_w = jnp.clip(leaf_w, lo, hi)
            leaf = self.learning_rate * leaf_w
        return (jnp.concatenate(features), jnp.concatenate(thresholds),
                jnp.concatenate(defaults), jnp.concatenate(gains),
                jnp.concatenate(covers), leaf, leaf_rel)

    @staticmethod
    def _route_layout(layout, rel, rel_e, split_f, split_b, split_d):
        """One level of routing by the feature-sorted layout alone: bool
        [rows], which rows go to their node's right child.  A row's bin on
        its node's split feature lies in that feature's run of entries, in
        strictly ascending row order (the caller has checked the layout's
        ``rows_ascend``: a row holds the feature once at most, so the bin is
        `_route_sparse`'s maximum over it).  No entry of the row in the run:
        the cell is absent, and the row follows the node's default
        direction, as it does on a NaN's bin 0.

        Two routes to the same bool a row; which one is the caller's word,
        by `_routes_by_push`: it hands ``rel_e``, the rows' ``rel`` on the
        entry lanes (`_entry_rels`), or None.

        *The entries push* (``rel_e``): every lane compares its own feature
        and bin with its row's node's split — one key a node, taken by
        `_select_by_bits` — and carries 1 (left) or 2 (right) if the
        feature is the node's, else 0; `push_to_rows` adds the lanes up a
        row, visiting only the sub-tiles that hold a run of a feature some
        node splits on (`run_spans`); a row left at 0 has no entry there.

        *The rows pull* (None): a row is found in the run by bisection — a
        gather a *row* a round, 21 rounds 0.64 s a level at 1.18M rows on a
        v5e where the push takes 8 to 37 ms (PERF.md, PR 47), and where the
        maximum over every entry (`_route_sparse`) took 5.8 s for 2.18e8 of
        them (PR 27)."""
        rows = rel.shape[0]
        if rel_e is not None:
            # a node's split as the key of its last bin that goes left (a
            # null split's num_bins: no bin lies past nb - 1 either)
            nb = layout.nb
            key = _select_by_bits(
                list(split_f * nb + jnp.minimum(split_b, nb - 1)), rel_e)
            shift = nb.bit_length() - 1
            mine = ((layout.gkey >> shift == key >> shift)  # padding lanes: -1
                    & (layout.gkey & (nb - 1) > 0))
            val = jnp.where(mine, 1 + (layout.gkey > key), 0)
            side = push_to_rows(
                layout.rid, run_spans(layout.cspan, layout.fstart, split_f),
                val, rows)
            return jnp.where(side == 0,
                             _select_by_bits(list(split_d), rel) == 1,
                             side == 2)
        feat, thr, right = split_f[rel], split_b[rel], split_d[rel] == 1
        lo, end = layout.fstart[feat], layout.fstart[feat + 1]
        row = jnp.arange(rows, dtype=jnp.int32)
        # the first lane of the run at or past the row
        at = _first_lane(lo, end, layout.run_bits,
                         lambda lane: layout.rid[lane] < row)
        lane = jnp.minimum(at, layout.rid.shape[0] - 1)
        found = (at < end) & (layout.rid[lane] == row)
        row_bin = jnp.where(found, layout.gkey[lane] & (layout.nb - 1), 0)
        return jnp.where(row_bin == 0, right, row_bin > thr)

    @staticmethod
    def _route_sparse(fi, ebin, emask, rid, row_feat, row_thr, row_dir,
                      rows: int):
        """One level of sparse routing, shared by training and inference:
        recover each row's bin for its per-row split feature (segment-max
        over matching entries; 0 = no matching entry = missing) and apply
        the threshold / default-direction rule.  The max with 0 clamps
        segment_max's empty-segment identity (INT_MIN) for rows with no
        entries at all."""
        match = (fi == row_feat[rid]) & emask
        row_bin = jnp.maximum(jax.ops.segment_max(
            jnp.where(match, ebin, 0), rid, num_segments=rows), 0)
        return jnp.where(row_bin == 0, row_dir == 1, row_bin > row_thr)

    @functools.partial(jax.jit, static_argnums=0)
    def _tree_margins_sparse_one(self, feature, threshold, default_right,
                                 leaf, row_id, findex, ebin, emask,
                                 rows_template):
        """One tree's sparse routing (the eval-set incremental path)."""
        rows = rows_template.shape[0]
        rid = row_id.astype(jnp.int32)
        fi = findex.astype(jnp.int32)
        node = jnp.zeros(rows, jnp.int32)
        for _ in range(self.max_depth):
            go_right = self._route_sparse(
                fi, ebin, emask, rid, feature[node], threshold[node],
                default_right[node], rows)
            node = 2 * node + 1 + go_right.astype(jnp.int32)
        return leaf[node - (2 ** self.max_depth - 1)]

    @functools.partial(jax.jit, static_argnums=0)
    def _margins_sparse(self, feature, threshold, default_right, leaf,
                        base, row_id, findex, ebin, emask):
        """All-trees sparse margins in ONE jitted fori_loop (the sparse
        mirror of `margins`; one dispatch, XLA-fusable)."""
        count_predict_retrace()
        rows = base.shape[0]
        rid = row_id.astype(jnp.int32)
        fi = findex.astype(jnp.int32)

        def one_tree(i, m):
            node = jnp.zeros(rows, jnp.int32)
            for _ in range(self.max_depth):
                go_right = self._route_sparse(
                    fi, ebin, emask, rid, feature[i][node],
                    threshold[i][node], default_right[i][node], rows)
                node = 2 * node + 1 + go_right.astype(jnp.int32)
            return m + leaf[i][node - (2 ** self.max_depth - 1)]

        return jax.lax.fori_loop(0, self.num_trees, one_tree, base)

    def _shard_inputs(self, bins, label, weight):
        """Under a mesh plan, ``fit``'s row arrays laid out over the plan's
        axes: ``shard_map``'s even-rows rule checked here, by name, and
        each array placed with ``plan.data_sharding()`` — one that already
        lies so is handed back as it is, a host array or one on a single
        chip is divided once a fit instead of once a tree."""
        plan = self.mesh_plan
        if plan is None:
            return bins, label, weight
        rows, shards = int(bins.shape[0]), plan.num_shards
        if rows % shards:
            raise ValueError(
                f"{rows} rows do not divide over the mesh plan's "
                f"{shards} shards: the kernel runs under shard_map, "
                "which wants as many rows on every shard")
        sharding = plan.data_sharding()
        return tuple(a if a is None else jax.device_put(a, sharding)
                     for a in (bins, label, weight))

    # ---- public API ---------------------------------------------------------

    @telemetry.span("gbdt.fit", total="gbdt.fit_us")
    def fit(self, bins: jax.Array, label: jax.Array,
            weight: Optional[jax.Array] = None,
            eval_set: Optional[tuple] = None,
            early_stopping_rounds: int = 0,
            qid: Optional[jax.Array] = None) -> dict:
        """Train the forest on binned features.

        bins: u8 [rows, features] (``QuantileBinner.transform`` output; may
        be sharded over a mesh's data axis — tree state stays replicated
        and XLA inserts the histogram psum).  ``eval_set``: optional
        ``(eval_bins, eval_label[, eval_weight])`` held-out set; with
        ``early_stopping_rounds > 0``, boosting stops after that many
        rounds without eval-loss improvement and the forest is truncated
        at the best round (``trees_used``).

        ``qid``: per-row query ids, required for
        ``objective='rank:pairwise'`` (contiguous groups; stage with
        ``with_qid=True``); its eval_set form is the 4-tuple
        ``(eval_bins, eval_label, eval_weight_or_None, eval_qid)``.
        Returns the forest pytree.
        """
        bins, label, weight = self._shard_inputs(bins, label, weight)
        label = label.astype(jnp.float32)
        w = (jnp.ones_like(label) if weight is None
             else weight.astype(jnp.float32))
        eval_margin = eval_label = eval_weight = None
        if eval_set is not None:
            eval_bins, eval_label = eval_set[0], eval_set[1].astype(jnp.float32)
            eval_weight = eval_set[2] if len(eval_set) > 2 else None
            eval_margin = (lambda f, t, d, leaf:
                           self._tree_margins(f, t, d, leaf, eval_bins))
        if self.objective == "rank:pairwise":
            grad_hess, eval_loss_fn = self._rank_fns(
                qid, w,
                eval_qid=(eval_set[3] if eval_set is not None and
                          len(eval_set) > 3 else None),
                eval_w=eval_weight, have_eval=eval_set is not None)
            return self._boost(label, w,
                               lambda g, h, cm, ck: self._build_tree(
                                   bins, g, h, cm, ck),
                               eval_margin=eval_margin,
                               eval_label=eval_label,
                               eval_weight=eval_weight,
                               early_stopping_rounds=early_stopping_rounds,
                               grad_hess=grad_hess,
                               eval_loss_fn=eval_loss_fn)
        driver = (self._boost_multi if self.objective == "softmax"
                  else self._boost)
        return driver(label, w,
                      lambda g, h, cm, ck: self._build_tree(bins, g, h,
                                                            cm, ck),
                      eval_margin=eval_margin, eval_label=eval_label,
                      eval_weight=eval_weight,
                      early_stopping_rounds=early_stopping_rounds)

    @staticmethod
    def _entry_arrays(batch):
        """(row_id, findex, emask) for a PaddedBatch.

        Entries with ``value == 0`` are masked as missing — this covers
        trailing padding lanes AND the mid-array pad gaps of multi-host
        global batches (staging.py's PaddedBatch docstring), and matches
        ``csr_to_dense_missing``'s documented semantics: under the
        value-0 padding convention a stored explicit zero is
        indistinguishable from padding, so both input paths treat it as
        missing.  NaN entries are likewise masked (the dense route
        densifies them to NaN = missing; leaving them live would scatter
        their mass into the reserved bin 0)."""
        v = batch.value
        emask = (v != 0) & ~jnp.isnan(v)
        return batch.row_ids(), batch.index, emask

    @staticmethod
    def _entry_bins(batch, binner: QuantileBinner):
        """(row_id, findex, ebin, emask) for a staged batch of either kind.

        A pre-binned ``BinnedBatch`` (data/binned_cache.py) ships its
        ``ebin``/``emask`` straight from the epoch cache — the trainer
        skips its own per-entry binning pass — after checking the batch's
        ``cuts_digest`` against the binner's (mixing bin vocabularies
        would silently train a wrong forest).  A value-carrying
        ``PaddedBatch`` goes through ``transform_entries`` as before.
        """
        if hasattr(batch, "ebin"):
            digest = getattr(batch, "cuts_digest", "")
            if digest and binner.cuts is not None \
                    and digest != binner.cuts_digest():
                raise ValueError(
                    f"pre-binned batch was built under cuts {digest} but "
                    f"the binner holds {binner.cuts_digest()}; rebuild the "
                    "cache or pass the matching binner")
            return (batch.row_ids(), batch.index,
                    batch.ebin.astype(jnp.int32), batch.emask)
        rid, fi, emask = GBDT._entry_arrays(batch)
        return (rid.astype(jnp.int32), fi.astype(jnp.int32),
                binner.transform_entries(fi, batch.value), emask)

    def _layout_bins(self, batch, binner: QuantileBinner) -> bool:
        """Whether `fit_batch` hands the layout the batch's values to bin
        after its sort (`layout_bin_engages`), and does not bin them in
        entry order first: the batch carries values, the binner its cuts,
        and a layout will be built."""
        if (hasattr(batch, "ebin") or binner.cuts is None
                or not self._sparse_layout_enabled()):
            return False
        shards = 1 if self.mesh_plan is None else self.mesh_plan.num_shards
        return layout_bin_engages(binner.cuts.shape, self.num_features,
                                  batch.value.shape[0], shards)

    @telemetry.span("gbdt.fit", total="gbdt.fit_us")
    def fit_batch(self, batch, binner: QuantileBinner,
                  weight: Optional[jax.Array] = None,
                  eval_set=None, early_stopping_rounds: int = 0) -> dict:
        """Train directly on a staged CSR ``PaddedBatch`` — no densify.

        The sparse-native XGBoost-hist path: per-entry bins
        (``binner.transform_entries``), O(nnz) histogram scatters per tree
        level, and absent cells handled as missing via the learned default
        directions.  Requires ``missing_aware=True`` on both this model
        and the binner.  ``weight`` defaults to ``batch.weight`` (padding
        rows already carry 0 there).  Entries with an explicit stored 0
        are treated as missing — the value-0 padding convention makes them
        indistinguishable from pad lanes, and ``csr_to_dense_missing``
        (the dense route) documents the same semantics, so the two paths
        build identical forests on any input.
        """
        if not (self.missing_aware and binner.missing_aware):
            raise ValueError("fit_batch requires missing_aware=True on "
                             "both the GBDT and the QuantileBinner")
        label = batch.label.astype(jnp.float32)
        w = (batch.weight if weight is None else weight).astype(jnp.float32)
        # invariant across every tree: the entry arrays (one row_ids() a
        # fit) and, for the pallas backend, the feature-sorted layout,
        # which bins a value-carrying batch's entries itself where it can
        rows = int(label.shape[0])
        if self._layout_bins(batch, binner):
            rid, fi, emask = self._entry_arrays(batch)
            layout = self._sparse_fit_layout(
                rid, fi, None, emask, rows=rows, value=batch.value,
                cuts=binner.cuts)
            entries = None
        else:
            entries = self._entry_bins(batch, binner)
            layout = self._sparse_fit_layout(*entries, rows=rows)
        kernel_levels = self.level_backends(sparse=True).count("pallas")
        if (layout is not None and self.mesh_plan is None
                and kernel_levels == self.max_depth and layout.rows_ascend):
            entries = None      # the layout holds every live entry
        elif entries is None:   # the tree wants them in entry order too
            entries = (rid.astype(jnp.int32), fi.astype(jnp.int32),
                       binner.transform_entries(fi, batch.value), emask)

        lookups = self._entry_lookups(layout, rows)
        # levels a tree's program routes by the entries' push
        pushes = self.max_depth * _routes_by_push(entries, layout, rows)

        def build_tree(g, h, col_mask, col_key):
            if kernel_levels:
                counter_add("gbdt.hist_sparse_pallas", kernel_levels)
                counter_add("gbdt.entry_lookup", lookups)
                counter_add("gbdt.route_push", pushes)
            return self._build_tree_sparse(entries, layout, g, h, col_mask,
                                           col_key)

        eval_margin = eval_label = eval_weight = None
        if eval_set is not None:
            # eval_set: a held-out PaddedBatch (weight-0 rows excluded
            # from the eval loss via its own weight vector)
            ev = eval_set
            ev_rid, ev_fi, ev_bin, ev_mask = self._entry_bins(ev, binner)
            eval_label = ev.label.astype(jnp.float32)
            eval_weight = ev.weight
            eval_margin = (lambda f, t, d, leaf:
                           self._tree_margins_sparse_one(
                               f, t, d, leaf, ev_rid, ev_fi, ev_bin,
                               ev_mask, ev.label))
        if self.objective == "rank:pairwise":
            grad_hess, eval_loss_fn = self._rank_fns(
                batch.qid, w,
                eval_qid=(eval_set.qid if eval_set is not None else None),
                eval_w=(eval_set.weight if eval_set is not None else None),
                have_eval=eval_set is not None)
            return self._boost(
                label, w, build_tree,
                eval_margin=eval_margin, eval_label=eval_label,
                eval_weight=eval_weight,
                early_stopping_rounds=early_stopping_rounds,
                grad_hess=grad_hess, eval_loss_fn=eval_loss_fn)
        driver = (self._boost_multi if self.objective == "softmax"
                  else self._boost)
        return driver(
            label, w, build_tree,
            eval_margin=eval_margin, eval_label=eval_label,
            eval_weight=eval_weight,
            early_stopping_rounds=early_stopping_rounds)

    @telemetry.span("gbdt.fit", total="gbdt.fit_us")
    def fit_streamed(self, batches, binner: QuantileBinner,
                     eval_set=None, early_stopping_rounds: int = 0,
                     staging_options: Optional[dict] = None) -> dict:
        """Out-of-core training — XGBoost's external-memory mode, the
        workload the reference's disk-cache layer exists to feed
        (`/root/reference/src/data/disk_row_iter.h:94-141` replays 64MB
        pages per epoch so hist boosters can train past RAM).

        ``batches``: a replayable source of staged ``PaddedBatch``es —
        a dataset URI string (a fresh ``DeviceStagingIter`` is built per
        replay from ``staging_options``, e.g.
        ``staging_options=dict(batch_size=8192, num_workers=4)``; the
        parallel sharded parse keeps replays bit-identical for any worker
        count), a zero-arg callable returning a fresh iterator (e.g.
        ``lambda: DeviceStagingIter("data.libsvm#cache", ...)``, where the
        chunk-level cache makes every replay a sequential local read), or
        a materialized sequence.  Every replay must yield the same batches
        in the same order; the staging layer's determinism guarantees
        this for a fixed URI/config.

        Residency contract: row-level state (label, weight, margins, node
        positions, grad/hess — a few words per row, ~50 MB at Higgs-11M)
        stays in memory; entry-level data (indices/values, the dominant
        term) is re-streamed ``max_depth + 1`` passes per tree — routing
        for the previous level rides the same pass as the next level's
        histogram accumulation, and per-batch entry bins are recomputed
        per pass (compute is cheap next to the IO it avoids holding).
        When the ``histogram=`` knob resolves levels to the sparse Pallas
        kernel, the contract relaxes by exactly one resident structure:
        the once-per-fit feature-sorted entry layout (~13 bytes/entry —
        int32 key, int32 row, f32 weight), built in pass 0 and reused for
        every ``num_trees * max_depth`` kernel level; routing still
        re-streams, so the pass count is unchanged.
        Builds the same forest as ``fit_batch`` on the concatenated data:
        split finding is shared (`_level_splits_from_hist`) and histogram
        accumulation is mathematically associative, though per-batch
        accumulation reorders the float sums — gains can differ by an ulp
        between the two paths.  The shared default-direction tie-break
        absorbs the one place an ulp can change the FOREST (the dual-
        direction argmax on a feature with no missing mass); a near-tie
        between two different (feature, bin) candidates can still, in
        principle, resolve differently.

        All objectives and training controls of ``fit_batch`` work here
        (rank:pairwise needs ``with_qid=True`` batches); ``eval_set`` is a
        resident held-out PaddedBatch, as in ``fit_batch``.
        """
        if not (self.missing_aware and binner.missing_aware):
            raise ValueError("fit_streamed requires missing_aware=True on "
                             "both the GBDT and the QuantileBinner")
        if isinstance(batches, str):
            from ..data.staging import DeviceStagingIter
            uri, opts = batches, dict(staging_options or {})
            replay = lambda: iter(DeviceStagingIter(uri, **opts))  # noqa: E731
        elif staging_options is not None:
            raise ValueError("staging_options only applies when `batches` "
                             "is a dataset URI string")
        else:
            replay = batches if callable(batches) else (lambda: iter(batches))

        # pass 0: resident row-level state + per-batch row offsets (plus,
        # when a level can resolve to the sparse Pallas kernel, the
        # globalized entry arrays the once-per-fit feature sort needs)
        want_layout = self._sparse_layout_enabled(streamed=True)
        labels, weights, qids, offsets = [], [], [], [0]
        ent = ([], [], [], []) if want_layout else None
        with telemetry.span("gbdt.stream_pass"):
            for b in replay():
                if want_layout:
                    rid_b, fi_b, eb_b, em_b = self._entry_bins(b, binner)
                    ent[0].append(np.asarray(rid_b, np.int64) + offsets[-1])
                    ent[1].append(np.asarray(fi_b))
                    ent[2].append(np.asarray(eb_b))
                    ent[3].append(np.asarray(em_b))
                labels.append(np.asarray(b.label, np.float32))
                weights.append(np.asarray(b.weight, np.float32))
                if b.qid is not None:
                    qids.append(np.asarray(b.qid))
                offsets.append(offsets[-1] + int(b.label.shape[0]))
        if not labels:
            raise ValueError("fit_streamed: the batch source is empty")
        label = jnp.asarray(np.concatenate(labels))
        w = jnp.asarray(np.concatenate(weights))
        qid = (jnp.asarray(np.concatenate(qids))
               if len(qids) == len(labels) else None)
        rows = int(label.shape[0])
        F, B = self.num_features, self.num_bins
        layout = None
        if want_layout:
            layout = self._sparse_fit_layout(
                np.concatenate(ent[0]), np.concatenate(ent[1]),
                np.concatenate(ent[2]), np.concatenate(ent[3]),
                rows=rows, streamed=True)
            ent = None  # only the sorted layout stays resident

        def stream():
            # one pass over the batches: a re-parse unless the source is
            # resident; max_depth + 1 of these a tree
            with telemetry.span("gbdt.stream_pass"):
                for i, b in enumerate(replay()):
                    yield offsets[i], b

        def batch_entries(b):
            return self._entry_bins(b, binner)

        def route_batch(node_b, prev, first_prev, rid, fi, ebin, emask):
            # a batch's rows through `prev`'s splits: their nodes, and their
            # columns among the histograms the next level builds
            pf, pb, pd, pr = prev
            rel_p = node_b - first_prev
            go_right = self._route_sparse(fi, ebin, emask, rid, pf[rel_p],
                                          pb[rel_p], pd[rel_p],
                                          node_b.shape[0])
            return (2 * node_b + 1 + go_right.astype(jnp.int32),
                    _child_slot(rel_p, go_right, pr[rel_p]))

        def route_pass(node, prev, first_prev):
            # one streamed pass routing every row through `prev`'s splits
            # (per-batch entry bins recomputed, per the residency contract)
            routed = []
            for off, b in stream():
                nb = int(b.label.shape[0])
                routed.append(route_batch(node[off:off + nb], prev,
                                          first_prev, *batch_entries(b)))
            return tuple(jnp.concatenate(a) for a in zip(*routed))

        def build_tree(grad, hess, col_mask, ck):
            gh_row = jnp.stack([grad, hess], axis=-1)      # [rows, 2]
            # per-TREE hoist for kernel levels: the sorted entry gather of
            # this tree's (grad, hess); only rel changes across levels
            gh_e = (_entry_values(layout, gh_row) if layout is not None
                    else None)
            node = jnp.zeros(rows, jnp.int32)
            lo = jnp.full(1, -jnp.inf)
            hi = jnp.full(1, jnp.inf)
            active = (jnp.ones((1, self._interaction_groups.shape[0]), bool)
                      if self._interaction_groups is not None else None)
            features, thresholds, defaults, gains, covers = [], [], [], [], []
            # previous level's (split_f, split_b, split_d, right_built) while
            # its rows are still to be routed; `hist4` its histograms, and
            # `slot` (as in `_build_tree`) every row's built column
            prev, hist4, slot, right_built = None, None, node, None
            for depth in range(self.max_depth):
                first = 2 ** depth - 1
                n_nodes = 2 ** depth
                cols = _built_columns(depth)
                impl = (self._hist_impl_sparse(n_nodes)
                        if layout is not None else "xla")
                if impl == "pallas":
                    # kernel level: routing takes its own streamed pass
                    # (same total pass count — the scatter branch fuses
                    # routing into its accumulation pass), then ONE kernel
                    # call over the resident sorted layout
                    if prev is not None:
                        node, slot = route_pass(node, prev,
                                                2 ** (depth - 1) - 1)
                        prev = None
                    counter_add("gbdt.hist_sparse_pallas", 1)
                    built = self._level_histogram_sparse(
                        layout, slot, gh_row, gh_e,
                        _entry_slots(layout, slot, depth), cols)
                    gh_node = segment_sum(gh_row, node - first,
                                          num_segments=n_nodes,
                                          force="pallas")
                else:
                    hist = jnp.zeros((cols * F * B, 2), jnp.float32)
                    routed = []
                    for off, b in stream():
                        nb = int(b.label.shape[0])
                        rid, fi, ebin, emask = batch_entries(b)
                        slot_b = slot[off:off + nb]
                        if prev is not None:
                            # route through the previous level's splits in
                            # the same pass that accumulates this level's
                            # histogram, which wants the routed rows' slots
                            # and not the ones they came with
                            node_b, slot_b = route_batch(
                                node[off:off + nb], prev,
                                2 ** (depth - 1) - 1, rid, fi, ebin, emask)
                            routed.append((node_b, slot_b))
                        gh_k = (gh_row[off:off + nb][rid]
                                * emask.astype(jnp.float32)[:, None])
                        keys = (slot_b[rid] * F + fi) * B + ebin
                        hist = hist + jax.ops.segment_sum(
                            gh_k, keys, num_segments=cols * F * B)
                    if prev is not None:
                        node, slot = (jnp.concatenate(a)
                                      for a in zip(*routed))
                    built = hist.reshape(cols, F, B, 2)
                    gh_node = jax.ops.segment_sum(gh_row, node - first,
                                                  num_segments=n_nodes)
                hist4 = _with_siblings(hist4, built, right_built)
                (split_f, split_b, split_d, split_g, lo, hi, active,
                 right_built) = self._level_splits_from_hist(
                    hist4, gh_node, depth,
                    col_mask, col_key=ck, lo=lo, hi=hi, active=active)
                features.append(split_f)
                thresholds.append(split_b)
                defaults.append(split_d)
                gains.append(split_g)
                covers.append(gh_node[:, 1])
                prev = (split_f, split_b, split_d, right_built)

            # final pass: route through the deepest splits to the leaves
            node, _ = route_pass(node, prev, 2 ** (self.max_depth - 1) - 1)

            n_leaves = 2 ** self.max_depth
            leaf_rel = node - (n_leaves - 1)
            leaf_force = ("pallas" if layout is not None
                          and self._hist_impl_sparse(n_leaves) == "pallas"
                          else None)
            gh_leaf = segment_sum(gh_row, leaf_rel, num_segments=n_leaves,
                                  force=leaf_force)
            leaf_w = -gh_leaf[:, 0] / (gh_leaf[:, 1] + self.lambda_)
            if self.monotone_constraints is not None:
                leaf_w = jnp.clip(leaf_w, lo, hi)
            leaf = self.learning_rate * leaf_w
            return (jnp.concatenate(features), jnp.concatenate(thresholds),
                    jnp.concatenate(defaults), jnp.concatenate(gains),
                    jnp.concatenate(covers), leaf, leaf_rel)

        eval_margin = eval_label = eval_weight = None
        if eval_set is not None:
            ev = eval_set
            ev_rid, ev_fi, ev_bin, ev_mask = self._entry_bins(ev, binner)
            eval_label = ev.label.astype(jnp.float32)
            eval_weight = ev.weight
            eval_margin = (lambda f, t, d, leaf:
                           self._tree_margins_sparse_one(
                               f, t, d, leaf, ev_rid, ev_fi, ev_bin,
                               ev_mask, ev.label))
        if self.objective == "rank:pairwise":
            if qid is None:
                raise ValueError("rank:pairwise fit_streamed needs batches "
                                 "staged with_qid=True")
            grad_hess, eval_loss_fn = self._rank_fns(
                qid, w,
                eval_qid=(eval_set.qid if eval_set is not None else None),
                eval_w=(eval_set.weight if eval_set is not None else None),
                have_eval=eval_set is not None)
            return self._boost(
                label, w, build_tree,
                eval_margin=eval_margin, eval_label=eval_label,
                eval_weight=eval_weight,
                early_stopping_rounds=early_stopping_rounds,
                grad_hess=grad_hess, eval_loss_fn=eval_loss_fn)
        driver = (self._boost_multi if self.objective == "softmax"
                  else self._boost)
        return driver(
            label, w, build_tree,
            eval_margin=eval_margin, eval_label=eval_label,
            eval_weight=eval_weight,
            early_stopping_rounds=early_stopping_rounds)

    # ---- the paged dense fit: XGBoost's external-memory `hist` --------------

    @functools.partial(jax.jit, static_argnums=(0, 1),
                       donate_argnames=("hist", "node"))
    def _page_visit(self, depth: int, hist, node: jax.Array, page: jax.Array,
                    grad: jax.Array, hess: jax.Array, offset, prev):
        """One page's visit of the pass at ``depth``, ONE program whatever the
        page: the page's slice of ``node`` (``page.shape[0]`` rows from the
        traced ``offset``) routed through the level above's splits ``prev``
        (`_route_level`, as the resident tree routes), the routed slice
        written back in place, and the built columns' histogram of the page
        (`_built_columns`, `_child_slot`: the dense backend, the Pallas kernel
        on a chip) added into the level's ``hist``.  ``depth == 0`` routes
        nothing (every row stands in the root); ``depth == max_depth`` builds
        nothing (``hist`` is None) and leaves each row's LEAF in ``node``.

        hist: f32 [built columns, F, B, 2], donated; node: i32 [rows], heap
        ids, donated; page: the row slices of a u8 [page_rows, F] page, as
        `PagePrefetcher` puts them; grad / hess: f32 [rows];
        prev: (split_f, split_b, split_d, right_built) of level
        ``depth - 1``.  Returns (hist, node, tick): ``tick`` a fresh scalar,
        the token by which the prefetcher learns that the visit has run.
        The scopes nest in the resident tree's (`gbdt.route`, `gbdt.hist`),
        so that what reads those finds the paged fit too."""
        with jax.named_scope("gbdt.hist"), jax.named_scope("gbdt.page.hist"):
            bins_i = jnp.concatenate(page).astype(jnp.int32)
        rows = bins_i.shape[0]
        node_p = jax.lax.dynamic_slice(node, (offset,), (rows,))
        slot = node_p               # the root's rows: node 0, column 0
        if depth:
            with jax.named_scope("gbdt.route"), \
                    jax.named_scope("gbdt.page.route"):
                rel = node_p - (2 ** (depth - 1) - 1)
                go_right, built_bit = self._route_level(bins_i.T, rel, *prev)
                node_p = 2 * node_p + 1 + go_right.astype(jnp.int32)
                slot = _child_slot(rel, go_right, built_bit)
                if depth == self.max_depth:
                    node_p = node_p - (2 ** depth - 1)
                node = jax.lax.dynamic_update_slice(node, node_p, (offset,))
        if depth < self.max_depth:
            with jax.named_scope("gbdt.hist"):
                with jax.named_scope("gbdt.page.hist"):
                    gh = jnp.stack(
                        [jax.lax.dynamic_slice(a, (offset,), (rows,))
                         for a in (grad, hess)], axis=-1)
                    built = histogram_gh(bins_i, slot, gh,
                                         _built_columns(depth), self.num_bins,
                                         force=self._hist_impl(2 ** depth))
                with jax.named_scope("gbdt.page.accumulate"):
                    hist = hist + built
        return hist, node, jnp.asarray(offset, jnp.int32) + 1

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _paged_level_splits(self, depth: int, parent, built, right_built,
                            col_mask, col_key, lo, hi, active):
        """A level's splits from the histograms its pass summed: the built
        columns' siblings derived from the level above (`_with_siblings`),
        then the resident tree's own split finding (`_dense_level_splits`);
        at the last level also the leaves, read off that level's cumulative
        sums (`_split_child_sums`), as `_build_tree` reads them.  Returns
        (hist, splits, leaf): ``hist`` the level's whole histograms, the
        next level's ``parent``; ``splits`` as `_dense_level_splits` gives
        them less ``dirs``; ``leaf`` None above the last level."""
        with jax.named_scope("gbdt.hist"):
            hist = _with_siblings(parent, built, right_built)
        with jax.named_scope("gbdt.split"):
            *splits, dirs = self._dense_level_splits(
                hist, depth, col_mask, col_key, lo, hi, active)
        leaf = None
        if depth == self.max_depth - 1:
            with jax.named_scope("gbdt.leaf"):
                leaf = self._leaves_from_split_sums(dirs, *splits[:3],
                                                    *splits[4:6])
        return hist, tuple(splits), leaf

    @telemetry.span("gbdt.fit", total="gbdt.fit_us")
    def fit_paged(self, pages, label: jax.Array,
                  weight: Optional[jax.Array] = None, *, page_rows: int,
                  prefetch_pages: int = 2) -> dict:
        """Train on dense binned rows that no one chip holds — XGBoost's
        external-memory ``hist`` (``ExtMemQuantileDMatrix``, ``on_host``):
        the quantised pages lie in host memory, are cached once and visited
        every level.

        ``pages``: a replayable source of host ``uint8`` arrays
        ``[<= page_rows, num_features]`` of ``QuantileBinner`` codes, all but
        the last ``page_rows`` long: a sequence, or a zero-argument callable
        returning a fresh iterator; every replay gives the same pages in the
        same order (``data.PagePrefetcher``).  ``label`` (and ``weight``):
        ``[rows]``, the pages' rows in order.

        Residency contract: the ROW STATE — label, weight, margin, grad,
        hess, node: six words a row — lies on the device from the fit's
        start to its end and never visits the host; the PAGES never all do:
        at most ``prefetch_pages + 1`` are on the device at once, the one the
        kernel holds and ``prefetch_pages`` staged ahead of it on the
        prefetcher's thread.  A tree is ``max_depth + 1`` passes over the
        pages, each page's visit one jitted program (`_page_visit`, the same
        for every page of a pass): the pass at depth d routes the page's
        rows through level d - 1's splits and adds the page to level d's
        histogram in the same visit, the last pass routes to the leaves.
        No host concatenation, no fetch inside a tree: splits stay device
        arrays from `_paged_level_splits` into the next pass's visits.

        Guarantees: every row of every page enters every level's histogram
        exactly once (no sampling of pages, no skipping by gradient, no
        approximate histogram); a seed gives one forest; split finding,
        sibling subtraction, the leaves and the boosting driver are the
        resident `fit`'s own code, so the forest's splits are `fit`'s on the
        same rows — float32 sums reorder by page, nothing else (a near-tie
        between two candidates can in principle resolve differently).
        Rows that do not fill the last page are padded with weight 0.

        Not here: an ``eval_set`` and early stopping, softmax and
        ``rank:pairwise``, a mesh plan, the leaf-wise builder."""
        from ..data.staging import PagePrefetcher
        if self.objective not in ("logistic", "squared"):
            raise ValueError("fit_paged trains objective='logistic' or "
                             f"'squared', not {self.objective!r}")
        if self.mesh_plan is not None:
            raise ValueError("fit_paged runs on one device: no histogram_mesh")
        if page_rows < 1 or prefetch_pages < 1:
            raise ValueError("fit_paged: page_rows and prefetch_pages are "
                             "at least 1")
        label = jnp.asarray(label, jnp.float32)
        rows = int(label.shape[0])
        n_pages = -(-rows // page_rows)
        pad = n_pages * page_rows - rows
        w = (jnp.ones_like(label) if weight is None
             else jnp.asarray(weight, jnp.float32))
        if pad:                     # whole pages: the tail weighs nothing
            label, w = jnp.pad(label, (0, pad)), jnp.pad(w, (0, pad))
        F, B = self.num_features, self.num_bins

        def build_tree(grad, hess, col_mask, ck):
            node = jnp.zeros(label.shape, jnp.int32)
            lo, hi = jnp.full(1, -jnp.inf), jnp.full(1, jnp.inf)
            active = (jnp.ones((1, self._interaction_groups.shape[0]), bool)
                      if self._interaction_groups is not None else None)
            features, thresholds, defaults, gains, covers = [], [], [], [], []
            parent, right_built, prev, leaf = None, None, None, None
            for depth in range(self.max_depth + 1):
                hist = (None if depth == self.max_depth else jnp.zeros(
                    (_built_columns(depth), F, B, 2), jnp.float32))
                visited = streamed = 0
                with telemetry.span("gbdt.page_pass"):
                    for index, held, page in feed.pages():
                        hist, node, tick = self._page_visit(
                            depth, hist, node, page, grad, hess,
                            np.int32(index * page_rows), prev)
                        feed.release(page, tick)
                        visited, streamed = visited + 1, streamed + held
                counter_add("gbdt.page_passes", 1)
                counter_add("gbdt.rows_streamed", streamed)
                if depth < self.max_depth:
                    counter_add("gbdt.hist_dead_key_tiles",
                                visited * self._dead_key_tiles(depth))
                if (visited, streamed) != (n_pages, rows):
                    raise ValueError(
                        f"fit_paged: a pass gave {visited} pages of "
                        f"{streamed} rows for {rows} labels in pages of "
                        f"{page_rows}")
                if depth == self.max_depth:
                    break
                parent, splits, leaf = self._paged_level_splits(
                    depth, parent, hist, right_built, col_mask, ck, lo, hi,
                    active)
                (split_f, split_b, split_d, split_g, lo, hi, active,
                 right_built, cover) = splits
                features.append(split_f)
                thresholds.append(split_b)
                defaults.append(split_d)
                gains.append(split_g)
                covers.append(cover)
                prev = (split_f, split_b, split_d, right_built)
            return (jnp.concatenate(features), jnp.concatenate(thresholds),
                    jnp.concatenate(defaults), jnp.concatenate(gains),
                    jnp.concatenate(covers), leaf, node)

        # one program a round for (grad, hess): op by op, as the resident
        # fit takes them, the host queues every op before the first has run,
        # and each op's result is a row array that is freed only when the
        # device has passed it: 15.1 GB in use at 2^28 rows against 10.6 so
        # (PERF.md, PR 48); the arithmetic is the same
        with PagePrefetcher(pages, self.num_trees * (self.max_depth + 1),
                            page_rows, F, depth=prefetch_pages) as feed:
            return self._boost(label, w, build_tree,
                               grad_hess=_GRAD_HESS_PROGRAM[self._grad_hess])

    def margins_batch(self, params: dict, batch,
                      binner: QuantileBinner) -> jax.Array:
        """Margins over a staged CSR batch (sparse-native routing)."""
        if not (self.missing_aware and binner.missing_aware):
            # a dense missing_aware=False forest has every bin code shifted
            # -1 relative to transform_entries; routing it here would be
            # silently wrong, so mirror fit_batch's guard
            raise ValueError("margins_batch requires missing_aware=True on "
                             "both the GBDT and the QuantileBinner")
        row_id, findex, ebin, emask = self._entry_bins(batch, binner)
        default_right = params.get("default_right")
        if default_right is None:
            default_right = jnp.zeros_like(params["feature"])
        base = jnp.full(batch.label.shape, params["base"])
        return self._margins_sparse(params["feature"], params["threshold"],
                                    default_right, params["leaf"], base,
                                    row_id, findex, ebin, emask)

    def margins_multi_batch(self, params: dict, batch,
                            binner: QuantileBinner) -> jax.Array:
        """[rows, K] softmax margins over a staged CSR batch."""
        if not (self.missing_aware and binner.missing_aware):
            raise ValueError("margins_multi_batch requires "
                             "missing_aware=True on both sides")
        row_id, findex, ebin, emask = self._entry_bins(batch, binner)
        default_right = params.get("default_right")
        if default_right is None:
            default_right = jnp.zeros_like(params["feature"])
        return self._margins_multi_sparse_impl(
            params["feature"], params["threshold"], default_right,
            params["leaf"], params["base"], row_id, findex, ebin, emask,
            batch.label)

    @functools.partial(jax.jit, static_argnums=0)
    def _margins_multi_sparse_impl(self, feature, threshold, default_right,
                                   leaf, base, row_id, findex, ebin, emask,
                                   rows_template) -> jax.Array:
        count_predict_retrace()
        K = self.num_class
        rows = rows_template.shape[0]

        def body(i, m):
            tm = self._tree_margins_sparse_one(
                feature[i], threshold[i], default_right[i], leaf[i],
                row_id, findex, ebin, emask, rows_template)
            return m + tm[:, None] * jax.nn.one_hot(i % K, K,
                                                    dtype=jnp.float32)

        init = jnp.broadcast_to(base, (rows, K))
        return jax.lax.fori_loop(0, feature.shape[0], body, init)

    def predict_batch(self, params: dict, batch,
                      binner: QuantileBinner) -> jax.Array:
        if self.objective == "softmax":
            return jax.nn.softmax(
                self.margins_multi_batch(params, batch, binner), axis=1)
        m = self.margins_batch(params, batch, binner)
        return jax.nn.sigmoid(m) if self.objective == "logistic" else m

    def margins_batch_bucketed(self, params: dict, batch,
                               binner: QuantileBinner,
                               row_bucket=None, nnz_bucket=None) -> jax.Array:
        """Geometry-stable ``margins_batch``: pad the staged batch up to
        its pow-2 (rows, nnz) bucket before routing, so an ad-hoc request
        stream reuses one compiled sparse-routing executable per bucket
        instead of retracing per geometry (``models.predict_retrace``
        counts the traces).  Real-row margins are bit-identical — padding
        lanes are value-0 / emask-False and padding rows route to leaves
        that are sliced away."""
        from ..data.staging import pad_batch_to_bucket
        padded = pad_batch_to_bucket(batch, row_bucket, nnz_bucket)
        return self.margins_batch(params, padded, binner)[:batch.batch_size]

    def predict_batch_bucketed(self, params: dict, batch,
                               binner: QuantileBinner,
                               row_bucket=None, nnz_bucket=None) -> jax.Array:
        """Bucketed-geometry ``predict_batch`` (see
        :meth:`margins_batch_bucketed`); the serving engine's route."""
        from ..data.staging import pad_batch_to_bucket
        padded = pad_batch_to_bucket(batch, row_bucket, nnz_bucket)
        return self.predict_batch(params, padded, binner)[:batch.batch_size]

    def predict_staged(self, params: dict, uri: str,
                       binner: QuantileBinner, batch_size: int = 65536,
                       **staging_kwargs) -> np.ndarray:
        """Streaming inference over a whole dataset URI: stage sparse
        batches (`DeviceStagingIter`), score each with the sparse-native
        routing, and return the real rows' predictions in file order
        (padding rows dropped).  Any staging kwarg (part/num_parts,
        format, nnz_bucket, ...) passes through — except ``sharding``:
        this surface slices ``pred[:num_rows]`` on the assumption that
        padding is tail-only, which sharded (and multi-host) batches break
        (padding interleaves per shard), so those are rejected rather than
        silently misaligned."""
        from ..data import DeviceStagingIter

        if staging_kwargs.get("sharding") is not None:
            raise ValueError(
                "predict_staged is a single-host, unsharded surface "
                "(tail-only padding assumption); for sharded or "
                "multi-host data, stage with DeviceStagingIter(sharding="
                "...) and score with predict_batch, keeping rows where "
                "batch.weight > 0")
        if jax.process_count() > 1:
            raise ValueError(
                "predict_staged under multi-host jax.distributed would "
                "interleave padding across processes; use "
                "DeviceStagingIter + predict_batch per batch instead")
        it = DeviceStagingIter(uri, batch_size=batch_size, **staging_kwargs)
        outs = []
        try:
            for batch in it:
                pred = np.asarray(self.predict_batch(params, batch, binner))
                # padding is tail-only on single-host batches: slice by the
                # real-row count (a weight>0 filter would silently drop
                # legitimately zero-weighted file rows and misalign output)
                outs.append(pred[:int(batch.num_rows)])
        finally:
            it.close()
        if not outs:
            shape = (0, self.num_class) if self.objective == "softmax" else (0,)
            return np.zeros(shape, np.float32)
        return np.concatenate(outs)

    @functools.partial(jax.jit, static_argnums=0)
    def margins(self, params: dict, bins: jax.Array) -> jax.Array:
        count_predict_retrace()
        # forests checkpointed before default_right existed predict as
        # missing-left everywhere (the exact pre-feature behavior)
        default_right = params.get("default_right")
        if default_right is None:
            default_right = jnp.zeros_like(params["feature"])

        def body(i, m):
            return m + self._tree_margins(params["feature"][i],
                                          params["threshold"][i],
                                          default_right[i],
                                          params["leaf"][i], bins)
        init = jnp.full(bins.shape[:1], params["base"])
        return jax.lax.fori_loop(0, self.num_trees, body, init)

    @functools.partial(jax.jit, static_argnums=0)
    def _margins_multi_impl(self, feature, threshold, default_right, leaf,
                            base, bins) -> jax.Array:
        """All softmax trees in ONE jitted fori_loop: tree i accumulates
        into class column i % K via a one-hot outer product (dynamic
        column updates are not fori-friendly)."""
        count_predict_retrace()
        K = self.num_class
        rows = bins.shape[0]

        def body(i, m):
            tm = self._tree_margins(feature[i], threshold[i],
                                    default_right[i], leaf[i], bins)
            return m + tm[:, None] * jax.nn.one_hot(i % K, K,
                                                    dtype=jnp.float32)

        init = jnp.broadcast_to(base, (rows, K))
        return jax.lax.fori_loop(0, feature.shape[0], body, init)

    def margins_multi(self, params: dict, bins: jax.Array) -> jax.Array:
        """[rows, K] softmax margins (tree i contributes to class i % K)."""
        default_right = params.get("default_right")
        if default_right is None:
            default_right = jnp.zeros_like(params["feature"])
        return self._margins_multi_impl(params["feature"],
                                        params["threshold"], default_right,
                                        params["leaf"], params["base"], bins)

    def predict(self, params: dict, bins: jax.Array) -> jax.Array:
        if self.objective == "softmax":
            return jax.nn.softmax(self.margins_multi(params, bins), axis=1)
        m = self.margins(params, bins)
        return jax.nn.sigmoid(m) if self.objective == "logistic" else m

    def predict_bucketed(self, params: dict, bins: jax.Array,
                         row_bucket=None) -> jax.Array:
        """Dense ``predict`` padded up to a pow-2 row bucket — one
        compiled forest executable per bucket rather than one per distinct
        row count (padding rows densify to bin 0 and are sliced away)."""
        from ..data.staging import bucket_pow2
        rows = bins.shape[0]
        rb = (bucket_pow2(rows) if row_bucket is None
              else max(int(row_bucket), rows))
        if rb != rows:
            bins = jnp.pad(bins, ((0, rb - rows), (0, 0)))
        return self.predict(params, bins)[:rows]

    def feature_importance(self, params: dict,
                           kind: str = "gain") -> jax.Array:
        """Per-feature importance over real splits (the get_score surface).

        kind follows XGBoost's ``importance_type`` semantics: "weight"
        (split count), "gain"/"cover" (PER-SPLIT AVERAGE gain / hessian
        mass, XGBoost's default meaning), "total_gain"/"total_cover"
        (sums).  Returns f32 [num_features]; null splits are excluded.
        """
        feat = np.asarray(params["feature"]).reshape(-1)
        thr = np.asarray(params["threshold"]).reshape(-1)
        real = thr < self.num_bins
        counts = np.zeros(self.num_features, np.float64)
        np.add.at(counts, feat[real], 1.0)
        if kind == "weight":
            return jnp.asarray(counts.astype(np.float32))
        base = kind[len("total_"):] if kind.startswith("total_") else kind
        if base not in ("gain", "cover"):
            raise ValueError(f"unknown importance kind '{kind}'")
        key = f"split_{base}"
        if key not in params:
            raise KeyError(
                f"forest has no '{key}' (checkpointed before importance "
                "bookkeeping existed); kind='weight' still works")
        vals = np.asarray(params[key], np.float64).reshape(-1)
        out = np.zeros(self.num_features, np.float64)
        np.add.at(out, feat[real], vals[real])
        if not kind.startswith("total_"):
            out = np.divide(out, counts, out=np.zeros_like(out),
                            where=counts > 0)
        return jnp.asarray(out.astype(np.float32))

    def loss(self, params: dict, bins: jax.Array, label: jax.Array,
             weight: Optional[jax.Array] = None) -> jax.Array:
        """Mean objective over rows; ``weight`` masks padding rows (weight
        0) exactly as in ``fit`` and the other model families."""
        if self.objective == "rank:pairwise":
            raise ValueError("ranking loss needs qids: use "
                             "pairwise_loss(params, bins, label, qid)")
        m = (self.margins_multi(params, bins)
             if self.objective == "softmax"
             else self.margins(params, bins))
        return self._objective_loss(m, label, weight)
