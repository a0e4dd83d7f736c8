"""Leaf-wise (best-first) tree growth: ``GBDT(grow_policy="lossguide")``.

`GBDT` grows a tree level by level to a fixed depth: a complete heap, every
level one pass of the histogram backend over all rows.  LightGBM (its only
policy) and XGBoost ``hist`` with ``grow_policy=lossguide`` grow leaf by
leaf instead: the leaf whose best split gains most is split next, until the
tree holds ``max_leaves`` leaves, whatever its depth.  `LeafwiseGBDT` is that
builder on the same surface (``fit``, ``_boost``, ``predict``, snapshots and
checkpoints); ``GBDT(..., grow_policy="lossguide", max_leaves=L)`` constructs
one.

Semantics (``benchmark/references/epsilon-lgbm.py`` is the same rule in
numpy float64):

* objective as `GBDT`'s: logistic ``p = sigmoid(m)``, ``g = p - y``,
  ``h = p (1 - p)``, first margin ``log(ybar / (1 - ybar))`` (`_boost`);
* a leaf with sums ``(G, H)`` over its rows and a cut ``(f, b)`` that gives
  ``(G_L, H_L)``, ``(G_R, H_R)``: gain ``G_L^2 / (H_L + lambda) + G_R^2 /
  (H_R + lambda) - G^2 / (H + lambda)``, the scale `GBDT` stores; the cut is
  valid only if ``H_L >= min_child_weight``, ``H_R >= min_child_weight`` and
  gain > 0 (``gamma``: > 2 gamma).  ``min_child_weight`` must be > 0, so a
  valid cut leaves a row on each side;
* the frontier is every leaf with a valid best split; the leaf of largest
  gain is expanded next, ties to the lower node id; its children take the
  next two node ids (left, then right); growth stops at ``max_leaves`` leaves
  or on an empty frontier; ``max_depth > 0`` also keeps a leaf at that depth
  from splitting (0: no limit); a leaf's value is ``-eta G / (H + lambda)``;
* an expansion builds the histogram of the child with FEWER ROWS from that
  child's rows only and derives the other from the parent's stored
  histogram (float32 ``parent - built``).  No pass over rows outside the
  expanded leaf.

How it runs on the device — one jitted program a tree (`_grow_tree`), a
``while`` over expansions:

* the rows are kept as a permutation ``order`` partitioned by leaf: a leaf
  is the segment ``order[start : start + count]``.  An expansion partitions
  its leaf's segment in place, stably (`_partition`: a gather of the split
  feature's bin a row of the segment, two running counts, one scatter);
* a segment's length is data: every piece of work over a segment exists at a
  ladder of static sizes (`_segment_sizes`) and ``lax.switch`` takes the
  least that holds it, so the work is within two times the segment's;
* the smaller child's rows are gathered whole out of a packed copy of the
  bins (`_pack_rows`: four features a 32-bit word, so a row's gather moves
  ``F`` bytes) and handed to the dense histogram backend as one node column
  (``ops.histogram_gh``: the Pallas kernel on a TPU); a segment's whole
  chunks of `_SEGMENT_CHUNK` rows go through it one at a time, then the
  rest of it at the ladder's size;
* every leaf's histogram stays in a pool ``[max_leaves, F, bins, 2]`` until
  the leaf is expanded (LightGBM's ``histogram_pool_size = -1``); the left
  child takes its parent's place in it;
* the split search of the two children is `GBDT._build_tree`'s arithmetic
  (cumulative sums, both default directions when ``missing_aware``,
  `_pick_splits`, `_split_child_sums` for the children's ``(G, H)``).

The forest is a pytree of per-node arrays, ``2 * max_leaves - 1`` nodes a
tree in creation order, node 0 the root::

    feature, threshold, default_right  i32 [trees, nodes]  as `GBDT`'s
    left, right    i32 [trees, nodes]  child ids; a leaf points at itself
    node_rows      i32 [trees, nodes]  rows that reached the node
    split_gain     f32 [trees, nodes]  0 at a leaf
    split_cover    f32 [trees, nodes]  hessian mass of every node
    leaf           f32 [trees, nodes]  shrunken value of every node
    base, trees_used                    as `GBDT`'s

A leaf (and every unused node) carries ``threshold == num_bins``, the null
split, so ``feature_importance`` reads it as `GBDT`'s; a row is scored by
following ``left`` / ``right`` until it stands still.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..ops.pallas_segment import histogram_gh
from .common import count_predict_retrace
from .gbdt import (GBDT, QuantileBinner, _NO_SLOT, _split_child_sums,
                   counter_add)

# Rows a histogram call takes at most: a longer segment goes through the
# backend a chunk at a time (a chunk's gathered rows are F * 4 B each as the
# kernel reads them: 0.5 GB at 2,000 features).
_SEGMENT_CHUNK = 65536
# The shortest static size of a segment's work (the dense kernel's row tile).
_SEGMENT_MIN = 1024

# A tree's tables, the columns of the builder's per-node arrays (int32 and
# float32), in this order.
_TREE_KEYS_I = ("feature", "threshold", "default_right", "left", "right",
                "node_rows")
_TREE_KEYS_F = ("split_gain", "split_cover", "leaf")
_N_ROWS = _TREE_KEYS_I.index("node_rows")
_V_GAIN = _TREE_KEYS_F.index("split_gain")
# The columns of the per-leaf array the builder reads by name: a frontier
# leaf's node id and its segment of the row order (then its depth and its
# best split's feature, bin and direction).
_S_NODE, _S_START, _S_COUNT = range(3)


def _segment_sizes(rows: int, cap: int) -> list:
    """The static sizes at which a segment's work exists, ascending: powers
    of two from `_SEGMENT_MIN`, then ``min(rows, cap)`` itself."""
    top, sizes, s = min(rows, cap), [], _SEGMENT_MIN
    while s < top:
        sizes.append(s)
        s *= 2
    return sizes + [top]


def _size_index(sizes: list, n: jax.Array) -> jax.Array:
    """Index of the least of ``sizes`` that holds ``n`` rows (``len(sizes)``
    when none does)."""
    return jnp.sum(n > jnp.asarray(sizes, jnp.int32)).astype(jnp.int32)


@jax.jit
def _pack_rows(bins: jax.Array) -> jax.Array:
    """``[rows, F]`` uint8 bin codes as ``[rows, ceil(F / 4)]`` int32 words:
    word ``j`` holds features ``j``, ``W + j``, ``2 W + j``, ``3 W + j`` in
    its bytes, low first (``W`` words a row), so that `_unpack_rows` is four
    shifts and a concatenation in feature order.  One fusion over four
    column slices: no ``[rows, 4, W]`` array is laid out (padded to the
    device's tiles that one is eight times the bins)."""
    F = bins.shape[1]
    words = -(-F // 4)
    b = jnp.pad(bins.astype(jnp.uint8), ((0, 0), (0, 4 * words - F)))
    word = functools.reduce(jnp.bitwise_or, (
        b[:, k * words:(k + 1) * words].astype(jnp.uint32) << (8 * k)
        for k in range(4)))
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def _unpack_rows(words: jax.Array, num_features: int) -> jax.Array:
    """`_pack_rows` undone on gathered rows: ``[n, W]`` -> ``[n, F]`` int32."""
    w = jax.lax.bitcast_convert_type(words, jnp.uint32)
    parts = [(w >> (8 * k)) & 255 for k in range(4)]
    return jnp.concatenate(parts, axis=1)[:, :num_features].astype(jnp.int32)


def _packed_column(packed: jax.Array, f: jax.Array) -> jax.Array:
    """Feature ``f``'s bin of every row, ``[rows]`` int32."""
    words = packed.shape[1]
    word = jax.lax.dynamic_slice_in_dim(packed, f % words, 1, axis=1)[:, 0]
    return (word >> (8 * (f // words))) & 255


def _row(*values, dtype=jnp.int32) -> jax.Array:
    """Scalars as one table row."""
    return jnp.stack([jnp.asarray(v, dtype) for v in values])


def _window(order: jax.Array, start: jax.Array, n: jax.Array, size: int):
    """``size`` lanes of ``order`` that hold the segment ``[start, start +
    n)`` (``n <= size``): the slice's first lane (``dynamic_slice`` keeps it
    inside the array), its rows, and which of its lanes are the segment's."""
    lo = jnp.minimum(start, order.shape[0] - size)
    lane = lo + jnp.arange(size, dtype=jnp.int32)
    return (lo, jax.lax.dynamic_slice(order, (lo,), (size,)),
            (lane >= start) & (lane < start + n))


def _running_count(mask: jax.Array) -> jax.Array:
    """Inclusive running count of a bool vector, int32: by rows of 128 lanes
    and then over the rows' totals.  One ``cumsum`` over 400,000 lanes costs
    the TPU's compiler 7.5 s an instance, 65,536 lanes 1.9 s (compiled for a
    described v5e, PR 41); the two short ones compile in a fraction."""
    n = mask.shape[0]
    tile = jnp.pad(mask.astype(jnp.int32), (0, -n % 128)).reshape(-1, 128)
    within = jnp.cumsum(tile, axis=1)
    before = jnp.cumsum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)[:n]


class LeafwiseGBDT(GBDT):
    """`GBDT` grown leaf by leaf to ``max_leaves`` leaves (module docstring).
    Dense ``fit`` on one device; ``logistic`` and ``squared`` objectives,
    ``subsample`` and ``colsample_bytree``, ``missing_aware`` bins."""

    def __init__(self, num_features: int, *args,
                 grow_policy: str = "lossguide", max_leaves: int = 0,
                 max_depth: int = 0, **kwargs):
        super().__init__(num_features, *args, max_depth=max_depth, **kwargs)
        if grow_policy != "lossguide":
            raise ValueError("LeafwiseGBDT is grow_policy='lossguide'")
        if max_leaves < 2:
            raise ValueError("grow_policy='lossguide' needs max_leaves >= 2")
        if max_depth < 0:
            raise ValueError("max_depth must be >= 0 (0: no limit)")
        if self.min_child_weight <= 0:
            raise ValueError("grow_policy='lossguide' needs min_child_weight "
                             "> 0: it is what keeps a child from being empty")
        unsupported = {
            "objective": self.objective not in ("logistic", "squared"),
            "monotone_constraints": self.monotone_constraints is not None,
            "interaction_constraints": self._interaction_groups is not None,
            "colsample_bylevel": self.colsample_bylevel < 1.0,
            "histogram_mesh": self.mesh_plan is not None}
        if any(unsupported.values()):
            raise ValueError("grow_policy='lossguide' does not take "
                             + ", ".join(k for k, v in unsupported.items()
                                         if v))
        self.grow_policy = grow_policy
        self.max_leaves = max_leaves

    @property
    def num_nodes(self) -> int:
        """Node slots a tree: ``max_leaves`` leaves and their parents."""
        return 2 * self.max_leaves - 1

    def level_backends(self, sparse: bool = False) -> list:
        """The histogram backend of the root and of every expansion: each is
        one node column."""
        if sparse:
            raise NotImplementedError("no leaf-wise fit on sparse entries")
        return [self._hist_impl(1)]

    # ---- the forest ---------------------------------------------------------

    def _null_tree(self) -> dict:
        """A tree of one leaf of value 0: what a slot past ``trees_used``
        holds, and what a tree's tables start from."""
        n = self.num_nodes
        ids = jnp.arange(n, dtype=jnp.int32)
        tree = {k: jnp.zeros(n, jnp.int32) for k in _TREE_KEYS_I}
        tree.update({k: jnp.zeros(n, jnp.float32) for k in _TREE_KEYS_F})
        tree.update(threshold=jnp.full(n, self.num_bins, jnp.int32),
                    left=ids, right=ids)
        return tree

    def init(self) -> dict:
        null = self._null_tree()
        params = {k: jnp.broadcast_to(v, (self.num_trees,) + v.shape)
                  for k, v in null.items()}
        params["base"] = jnp.zeros((), jnp.float32)
        params["trees_used"] = jnp.zeros((), jnp.int32)
        return params

    @telemetry.span("gbdt.stack")
    def _stack_forest(self, params, trees, thrs, dirs, sgains, scovers,
                      leaves, trees_used: int, total: int) -> dict:
        """`_boost` hands back what the tree builder gave it: ``trees`` is
        the list of per-tree dicts, the other lists are not used."""
        null = self._null_tree()
        kept = [t if i < trees_used else null
                for i, t in enumerate(trees)] + [null] * (total - len(trees))
        for key in null:
            params[key] = jnp.stack([t[key] for t in kept])
        params["trees_used"] = jnp.asarray(np.int32(trees_used))
        return params

    def _tree_span(self, build_tree, trees: int = 1):
        """The host span around one boosting round (`_boost` opens it); the
        depth-wise builder's counters of node columns do not apply."""
        return telemetry.span("gbdt.leafwise.tree")

    # ---- training -----------------------------------------------------------

    @telemetry.span("gbdt.fit", total="gbdt.fit_us")
    def fit(self, bins: jax.Array, label: jax.Array,
            weight: Optional[jax.Array] = None,
            eval_set: Optional[tuple] = None,
            early_stopping_rounds: int = 0) -> dict:
        """`GBDT.fit` for the pointer forest; bins: u8 ``[rows, features]``
        on one device.  The counters ``gbdt.expansions``,
        ``gbdt.hist_rows_visited``, ``gbdt.leaves`` and ``gbdt.depth_max``
        are device scalars a tree, added once the last tree is built: the
        call returns when the fit has finished.  With them, from the plan
        and the expansions, ``gbdt.hist_dead_key_tiles`` (`GBDT._tree_span`
        tells it for the depth-wise builder)."""
        label = label.astype(jnp.float32)
        w = (jnp.ones_like(label) if weight is None
             else weight.astype(jnp.float32))
        packed = _pack_rows(bins)
        stats = []

        def build_tree(g, h, col_mask, col_key):
            tree, leaf_rel, grown = self._grow_tree(packed, g, h, col_mask)
            stats.append(grown)
            return tree, None, None, None, None, tree["leaf"], leaf_rel

        eval_margin = eval_label = eval_weight = None
        if eval_set is not None:
            eval_bins, eval_label = eval_set[0], eval_set[1].astype(jnp.float32)
            eval_weight = eval_set[2] if len(eval_set) > 2 else None
            eval_margin = (lambda tree, t, d, leaf:
                           self._tree_margins(tree, eval_bins))
        params = self._boost(label, w, build_tree, eval_margin=eval_margin,
                             eval_label=eval_label, eval_weight=eval_weight,
                             early_stopping_rounds=early_stopping_rounds)
        grown = np.asarray(jnp.stack(stats)).sum(axis=0)
        counter_add("gbdt.expansions", int(grown[0]))
        counter_add("gbdt.hist_rows_visited", int(grown[1]))
        counter_add("gbdt.leaves", int(grown[2]))
        counter_add("gbdt.depth_max", int(grown[3]))
        # a segment's histogram (the root's, then one an expansion) is at
        # least one kernel call of one node column, as the root level's is;
        # the whole chunks of a segment past `_SEGMENT_CHUNK` rows are calls
        # the host cannot count
        counter_add("gbdt.hist_dead_key_tiles",
                    (len(stats) + int(grown[0])) * self._dead_key_tiles(0))
        return params

    def fit_batch(self, *args, **kwargs):
        raise NotImplementedError("no leaf-wise fit on sparse entries")

    def fit_streamed(self, *args, **kwargs):
        raise NotImplementedError("no leaf-wise streamed fit")

    def _partition(self, order, start, n, col, thr, dflt):
        """The segment ``[start, start + n)`` of ``order`` partitioned in
        place, stably: the rows that stay left of the cut first.  ``col``:
        the split feature's bin of every row.  Returns the new order and how
        many rows went left.  At the least static size that holds the
        segment; past `_SEGMENT_CHUNK` lanes in two passes of chunks (the
        count of the left rows, then each chunk's rows sent to their
        places)."""
        rows = order.shape[0]
        sizes = _segment_sizes(rows, _SEGMENT_CHUNK)

        def sides(size, start, n):
            lo, seg, inside = _window(order, start, n, size)
            b = col[seg]
            right = b > thr
            if self.missing_aware:
                right = jnp.where(b == 0, dflt == 1, right)
            return lo, seg, inside & ~right, inside & right

        def places(goes_l, goes_r, first_l, first_r, stay):
            return jnp.where(goes_l, first_l + _running_count(goes_l) - 1,
                             jnp.where(goes_r, first_r
                                       + _running_count(goes_r) - 1, stay))

        def whole(size, _):
            lo, seg, goes_l, goes_r = sides(size, start, n)
            n_left = jnp.sum(goes_l, dtype=jnp.int32)
            lane = jnp.arange(size, dtype=jnp.int32)
            first = start - lo
            moved = jnp.zeros(size, jnp.int32).at[
                places(goes_l, goes_r, first, first + n_left, lane)].set(
                    seg, unique_indices=True)
            return jax.lax.dynamic_update_slice(order, moved, (lo,)), n_left

        def chunked(_):
            C = _SEGMENT_CHUNK
            trips = (n + C - 1) // C

            def piece(i):
                return sides(C, start + i * C, jnp.minimum(n - i * C, C))

            n_left = jax.lax.fori_loop(
                0, trips, lambda i, total: total + jnp.sum(
                    piece(i)[2], dtype=jnp.int32), jnp.int32(0))

            def send(i, carry):
                moved, done_l, done_r = carry
                _, seg, goes_l, goes_r = piece(i)
                at = places(goes_l, goes_r, start + done_l,
                            start + n_left + done_r, rows)   # rows: dropped
                return (moved.at[at].set(seg, unique_indices=True,
                                         mode="drop"),
                        done_l + jnp.sum(goes_l, dtype=jnp.int32),
                        done_r + jnp.sum(goes_r, dtype=jnp.int32))

            moved, _, _ = jax.lax.fori_loop(
                0, trips, send, (order, jnp.int32(0), jnp.int32(0)))
            return moved, n_left

        branches = [functools.partial(whole, s) for s in sizes]
        if rows > _SEGMENT_CHUNK:
            branches.append(chunked)
        return jax.lax.switch(_size_index(sizes, n), branches, None)

    def _segment_histogram(self, packed, order, grad, hess, start, n):
        """``[F, bins, 2]`` (grad, hess) histogram of the rows ``order[start :
        start + n]`` and of no other: the rows gathered out of ``packed`` as
        one node column of the dense backend (`_hist_impl`), whole chunks of
        `_SEGMENT_CHUNK` rows first and what is left at the least static size
        that holds it, so the backend's work follows ``n`` and takes no step
        of a whole chunk where a segment's length crosses one."""
        F, B = self.num_features, self.num_bins
        impl = self._hist_impl(1)
        sizes = _segment_sizes(order.shape[0], _SEGMENT_CHUNK)
        chunk = sizes[-1]

        def piece(size, start, n):
            _, seg, inside = _window(order, start, n, size)
            gh = jnp.where(inside[:, None],
                           jnp.stack([grad[seg], hess[seg]], axis=-1), 0.0)
            return histogram_gh(
                _unpack_rows(packed[seg], F), jnp.where(inside, 0, _NO_SLOT),
                gh, 1, B, force=impl)[0]

        whole = n // chunk
        rest_at, rest = start + whole * chunk, n - whole * chunk
        hist = jax.lax.switch(
            _size_index(sizes, rest),
            [functools.partial(lambda size, _: piece(size, rest_at, rest), s)
             for s in sizes], None)
        return jax.lax.fori_loop(
            0, whole, lambda i, h: h + piece(chunk, start + i * chunk, chunk),
            hist)

    def _node_splits(self, hist, col_mask):
        """Best split of every node of ``hist`` ``[k, F, bins, 2]``, by
        `GBDT._build_tree`'s arithmetic: ``(f, b, d, gain)`` ``[k]`` each,
        nulls as `_pick_splits` encodes them (gain 0), the children's ``(G,
        H)`` at that split ``[k, 4]`` (left, then right) and the node's own
        ``[k, 2]``."""
        with jax.named_scope("gbdt.leafwise.split"):
            hist_g, hist_h = hist[..., 0], hist[..., 1]
            gl = jnp.cumsum(hist_g, axis=2)
            hl = jnp.cumsum(hist_h, axis=2)
            g_tot, h_tot = gl[:, :, -1:], hl[:, :, -1:]
            lam = self.lambda_

            def split_gain(gl_, hl_):
                gr_, hr_ = g_tot - gl_, h_tot - hl_
                g = (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
                     - g_tot ** 2 / (h_tot + lam))
                ok = ((hl_ >= self.min_child_weight)
                      & (hr_ >= self.min_child_weight))
                return jnp.where(ok, g, -jnp.inf)

            dirs = [(gl, hl)]
            if self.missing_aware:  # the missing bin's mass sent right too
                dirs.append((gl - hist_g[:, :, 0:1], hl - hist_h[:, :, 0:1]))
            gain = self._collapse_dir_ties(
                jnp.stack([split_gain(a, b) for a, b in dirs], axis=3))
            f, b, d, g = self._pick_splits(gain, col_mask)
            child = _split_child_sums(dirs, f, b, d).reshape(-1, 4)
            own = jnp.stack([g_tot[:, 0, 0], h_tot[:, 0, 0]], axis=-1)
        return f, b, d, g, child, own

    def _leaf_value(self, gh):
        return -self.learning_rate * gh[..., 0] / (gh[..., 1] + self.lambda_)

    @functools.partial(jax.jit, static_argnums=0)
    def _grow_tree(self, packed: jax.Array, grad: jax.Array, hess: jax.Array,
                   col_mask: jax.Array):
        """One tree, best-first (module docstring).  packed: `_pack_rows` of
        the bins; grad/hess: f32 [rows], weight-scaled.  Returns the tree's
        tables (a dict, `_null_tree`'s keys), every row's node id, and
        ``[expansions, rows visited by histograms, leaves, depth]`` i32."""
        L = self.max_leaves
        rows = packed.shape[0]
        order = jnp.arange(rows, dtype=jnp.int32)
        with jax.named_scope("gbdt.leafwise.hist"):
            root = self._segment_histogram(packed, order, grad, hess,
                                           jnp.int32(0), jnp.int32(rows))
        f, b, d, gain, child, own = self._node_splits(root[None], col_mask)
        with jax.named_scope("gbdt.leafwise.pick"):
            null = self._null_tree()
            node_i = jnp.stack([null[k] for k in _TREE_KEYS_I], axis=1)
            node_f = jnp.stack([null[k] for k in _TREE_KEYS_F], axis=1)
            node_i = node_i.at[0, _N_ROWS].set(rows)
            node_f = node_f.at[0].set(_row(
                0.0, own[0, 1], self._leaf_value(own[0]), dtype=jnp.float32))
            slot_i = jnp.zeros((L, 7), jnp.int32).at[0].set(
                _row(0, 0, rows, 0, f[0], b[0], d[0]))
            slot_gain = jnp.zeros(L, jnp.float32).at[0].set(
                self._frontier_gain(gain, jnp.zeros(1, jnp.int32))[0])
            slot_child = jnp.zeros((L, 4), jnp.float32).at[0].set(child[0])
            pool = jnp.zeros((L,) + root.shape, jnp.float32).at[0].set(root)
        state = (order, pool, slot_i, slot_gain, slot_child, node_i, node_f,
                 jnp.int32(1), jnp.asarray([0, rows, 1, 0], jnp.int32))

        def more(state):
            slot_gain, leaves = state[3], state[7]
            return (leaves < L) & (jnp.max(slot_gain) > 0)

        def expand(state):
            (order, pool, slot_i, slot_gain, slot_child, node_i, node_f,
             leaves, stats) = state
            with jax.named_scope("gbdt.leafwise.pick"):
                # the frontier's largest gain, ties to the lower node id
                best = jnp.max(slot_gain)
                s = jnp.argmin(jnp.where(slot_gain == best,
                                         slot_i[:, _S_NODE], 2 * L))
                parent, start, n, depth, pf, pb, pd = (
                    slot_i[s, k] for k in range(7))
                left_id = 2 * leaves - 1        # nodes so far: 2 leaves - 1
            with jax.named_scope("gbdt.leafwise.partition"):
                order, n_left = self._partition(
                    order, start, n, _packed_column(packed, pf), pb, pd)
            n_right = n - n_left
            right_built = n_right < n_left
            with jax.named_scope("gbdt.leafwise.hist"):
                built = self._segment_histogram(
                    packed, order, grad, hess,
                    jnp.where(right_built, start + n_left, start),
                    jnp.minimum(n_left, n_right))
            with jax.named_scope("gbdt.leafwise.subtract"):
                # the parent's histogram leaves the pool before the children's
                # go in (the barrier): else the compiler reads it out of the
                # carried pool after the first write, and copies the pool, a
                # gigabyte an expansion, to keep that read sound
                parent_hist, pool = jax.lax.optimization_barrier(
                    (jax.lax.dynamic_index_in_dim(pool, s, 0, False), pool))
                derived = parent_hist - built
                pair = jnp.stack([jnp.where(right_built, derived, built),
                                  jnp.where(right_built, built, derived)])
                pool = jax.lax.dynamic_update_slice(
                    pool, pair[:1], (s, 0, 0, 0))
                pool = jax.lax.dynamic_update_slice(
                    pool, pair[1:], (leaves, 0, 0, 0))
            f, b, d, gain, child, _ = self._node_splits(pair, col_mask)
            with jax.named_scope("gbdt.leafwise.pick"):
                kids = left_id + jnp.arange(2, dtype=jnp.int32)
                kid_gh = slot_child[s].reshape(2, 2)
                node_i = node_i.at[parent].set(
                    _row(pf, pb, pd, kids[0], kids[1], n))
                node_f = node_f.at[parent, _V_GAIN].set(best)
                node_i = node_i.at[kids, _N_ROWS].set(_row(n_left, n_right))
                node_f = node_f.at[kids].set(jnp.stack(
                    [jnp.zeros(2), kid_gh[:, 1], self._leaf_value(kid_gh)],
                    axis=1))
                where = _row(s, leaves)
                slot_i = slot_i.at[where].set(jnp.stack(
                    [kids, _row(start, start + n_left), _row(n_left, n_right),
                     jnp.full(2, depth + 1), f, b, d], axis=1))
                slot_gain = slot_gain.at[where].set(self._frontier_gain(
                    gain, jnp.full(2, depth + 1)))
                slot_child = slot_child.at[where].set(child)
                stats = stats + _row(1, jnp.minimum(n_left, n_right), 1, 0)
                stats = stats.at[3].max(depth + 1)
            return (order, pool, slot_i, slot_gain, slot_child, node_i,
                    node_f, leaves + 1, stats)

        (order, _, slot_i, _, _, node_i, node_f, leaves, stats
         ) = jax.lax.while_loop(more, expand, state)
        with jax.named_scope("gbdt.leafwise.pick"):
            # every row's leaf: its lane's segment, then back to row order
            lane = jnp.arange(rows, dtype=jnp.int32)[None, :]
            first = slot_i[:, _S_START, None]
            live = (jnp.arange(L) < leaves)[:, None]
            holds = live & (lane >= first) & (
                lane < first + slot_i[:, _S_COUNT, None])
            lane_node = jnp.sum(
                jnp.where(holds, slot_i[:, _S_NODE, None], 0), axis=0)
            leaf_rel = jnp.zeros(rows, jnp.int32).at[order].set(
                lane_node, unique_indices=True)
            tree = {k: node_i[:, i] for i, k in enumerate(_TREE_KEYS_I)}
            tree.update({k: node_f[:, i] for i, k in enumerate(_TREE_KEYS_F)})
        return tree, leaf_rel, stats

    def _frontier_gain(self, gain, depth):
        """A new leaf's place in the frontier: its best split's gain, 0 (none:
        `_pick_splits`' null) at the depth limit."""
        if self.max_depth > 0:
            return jnp.where(depth >= self.max_depth, 0.0, gain)
        return gain

    # ---- scoring ------------------------------------------------------------

    @functools.partial(jax.jit, static_argnums=0)
    def _tree_margins(self, tree: dict, bins: jax.Array) -> jax.Array:
        """One tree's value for every row of ``bins`` (u8 [rows, F])."""
        rows = bins.shape[0]
        bins_i = bins.astype(jnp.int32)

        def goes_right(node):
            b = bins_i[jnp.arange(rows), tree["feature"][node]]
            right = b > tree["threshold"][node]
            if self.missing_aware:
                right = jnp.where(b == 0, tree["default_right"][node] == 1,
                                  right)
            return right

        return tree["leaf"][_follow(tree, goes_right, rows)]

    @functools.partial(jax.jit, static_argnums=0)
    def margins(self, params: dict, bins: jax.Array) -> jax.Array:
        count_predict_retrace()
        trees = {k: params[k] for k in _TREE_KEYS_I + _TREE_KEYS_F}

        def body(i, m):
            return m + self._tree_margins(
                {k: v[i] for k, v in trees.items()}, bins)

        init = jnp.full(bins.shape[:1], params["base"])
        return jax.lax.fori_loop(0, self.num_trees, body, init)

    def margins_batch(self, params: dict, batch,
                      binner: QuantileBinner) -> jax.Array:
        """Margins over a staged CSR batch (`GBDT.margins_batch`'s rule for
        a row's bin: its entry on the node's feature, else missing)."""
        if not (self.missing_aware and binner.missing_aware):
            raise ValueError("margins_batch requires missing_aware=True on "
                             "both the GBDT and the QuantileBinner")
        row_id, findex, ebin, emask = self._entry_bins(batch, binner)
        trees = {k: params[k] for k in _TREE_KEYS_I + _TREE_KEYS_F}
        base = jnp.full(batch.label.shape, params["base"])
        return self._margins_entries(trees, base, row_id, findex, ebin, emask)

    @functools.partial(jax.jit, static_argnums=0)
    def _margins_entries(self, trees, base, row_id, findex, ebin, emask):
        count_predict_retrace()
        rows = base.shape[0]
        rid = row_id.astype(jnp.int32)
        fi = findex.astype(jnp.int32)

        def one_tree(i, m):
            tree = {k: v[i] for k, v in trees.items()}

            def goes_right(node):
                return self._route_sparse(
                    fi, ebin, emask, rid, tree["feature"][node],
                    tree["threshold"][node], tree["default_right"][node],
                    rows)

            return m + tree["leaf"][_follow(tree, goes_right, rows)]

        return jax.lax.fori_loop(0, self.num_trees, one_tree, base)


def _follow(tree: dict, goes_right, rows: int) -> jax.Array:
    """Every row's leaf of one pointer tree: ``left`` / ``right`` followed
    from the root until no row moves (a leaf points at itself).
    ``goes_right(node)``: bool [rows] for the rows' nodes ``node``."""
    def step(node):
        return jnp.where(goes_right(node), tree["right"][node],
                         tree["left"][node])

    def moving(node):
        return jnp.any(tree["left"][node] != node)

    return jax.lax.while_loop(moving, step, jnp.zeros(rows, jnp.int32))
