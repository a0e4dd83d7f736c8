"""Pallas TPU kernel: segment-sum over flattened COO batches.

The hot op of every model here is ``out[r] = sum contrib[k] where
row_id[k] == r`` (the vectorized Row::SDot, reference
include/dmlc/data.h:146-161).  ``jax.ops.segment_sum`` lowers to an XLA
scatter-add; this kernel instead computes the same reduction as a *tiled
one-hot contraction*:

    out[rt] += (row_id[nt] == rows[rt]) . contrib[nt]

over a (row-tile, nnz-tile) grid — no scatter, no dynamic shapes, pure
VPU/MXU work with sequential accumulation over the nnz axis.  That trades
O(R * NNZ / tile) redundant compare-work for a scatter-free schedule; it
wins when rows-per-shard is modest (the sharded-DP layout this library
stages) and scatter serialization dominates, and it exists as the template
for fusing more per-entry math into the reduction.

``segment_sum(..., force=...)`` picks the implementation; the default
keeps XLA's scatter.  On non-TPU backends the kernels run in interpret
mode (``pallas_interpret``; tests exercise them on the CPU mesh).

Sparse histogram
----------------

``histogram_gh_sparse`` extends the histogram-as-matmul idea to COO
entries — the O(nnz) GBDT formulation, where each present entry owns a
static ``(feature, bin)`` key and only its row's node assignment changes
per tree level.  The naive one-hot contraction over unsorted entries
would compare every entry tile against every key tile (full
``nnz x (F * bins)`` compare cost, which is why the scatter path used to
be the only sparse backend).  The fix is that ``findex`` never changes:
:func:`sparse_hist_layout` sorts the entries by feature ONCE per staged
batch (a device sort, amortized over ``num_trees x max_depth`` level passes)
and records, per key tile, the contiguous block span of entries whose
keys can land in that tile.  The kernel grid is then ``(key tiles, blocks
of 32 entry sub-tiles in the fullest tile's span)`` with the span table
scalar-prefetched: each grid step DMAs only its own features' entries, so
compare work is O(nnz * KEY_TILE / NNZ_TILE) per entry tile — no
``n_nodes`` factor (nodes ride the MXU M axis like ``_hist_kernel``) and
no full-F factor (a tile only ever sees its own features' entries).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_TILE = 512    # rows per out tile (lane-friendly multiple of 128)
_NNZ_TILE = 1024   # entries per inner step

# the one authoritative list of reduction backends; every force=/
# sdot_backend= surface validates through check_force so adding a
# backend is a one-place change
VALID_FORCE = (None, "xla", "pallas")


# The kernels' names in a device trace: the TPU compiler names a Pallas
# call's instruction ``%<name>.<n>``, and without ``name=`` takes the jitted
# wrapper's, so renaming a wrapper would rename the kernel.  The dense
# histogram's is matched by ``^%_histogram_gh_pallas`` in the benchmark's
# ``layer_metrics/hist_*.json`` (tests/test_chip_names.py compiles it and
# checks): change it only together with those files.
SEGMENT_SUM_KERNEL = "_segment_sum_pallas"
DENSE_HIST_KERNEL = "_histogram_gh_pallas"
SPARSE_HIST_KERNEL = "_histogram_gh_sparse_pallas"
ENTRY_LOOKUP_KERNEL = "_entry_lookup_pallas"
ENTRY_PUSH_KERNEL = "_entry_push_pallas"
BIN_RUNS_KERNEL = "_bin_runs_pallas"


def check_force(force, what: str = "backend") -> None:
    if force not in VALID_FORCE:
        raise ValueError(f"unknown {what} force={force!r} "
                         f"(want one of {VALID_FORCE})")


def pallas_interpret() -> bool:
    """Whether the three kernels here run through the Pallas interpreter
    (a correctness tool: plain XLA ops, no Mosaic) instead of being
    compiled for the chip.  The one place that decides it: interpreted
    exactly when the default backend is not a TPU."""
    return jax.default_backend() != "tpu"


def _seg_kernel(row_id_ref, contrib_ref, out_ref):
    rt = pl.program_id(0)
    nt = pl.program_id(1)

    @pl.when(nt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # All shapes stay 2-D: squeezing basic indexing like rid[0, :, None]
    # lowers to a gather Mosaic rejects on real TPU ("Shape mismatch in
    # input, indices and output"); reshape+broadcast lowers cleanly.
    # rows[n, r] = absolute row id of out-tile column r
    rows = rt * _ROW_TILE + jax.lax.broadcasted_iota(
        jnp.int32, (_NNZ_TILE, _ROW_TILE), 1)
    rid = row_id_ref[...]          # [1, NNZ_TILE] int32
    contrib = contrib_ref[...]     # [L, NNZ_TILE] f32 (L lanes)
    rid_col = jnp.broadcast_to(rid.reshape(_NNZ_TILE, 1),
                               (_NNZ_TILE, _ROW_TILE))
    onehot = (rid_col == rows).astype(jnp.float32)
    # [L, NNZ] @ [NNZ, ROWS] -> [L, ROWS]; accumulate across nnz steps.
    # HIGHEST keeps contrib in f32 on the MXU — DEFAULT rounds the operand
    # through bf16 (~1e-2 abs error on N(0,1) data), breaking the
    # documented f32-accumulation contract and the gradients that flow
    # through the custom VJP below.
    out_ref[...] += jnp.dot(contrib, onehot,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _segment_sum_pallas(contrib: jax.Array, row_id: jax.Array,
                        num_segments: int, interpret: bool) -> jax.Array:
    """contrib: [nnz] or [nnz, L] (multi-lane — e.g. (grad, hess) carried
    through one kernel, the shape the GBDT histogram build uses)."""
    if contrib.ndim > 2:
        raise ValueError("pallas segment_sum supports [nnz] or [nnz, L] "
                         f"contrib, got shape {contrib.shape}")
    lanes = 1 if contrib.ndim == 1 else contrib.shape[1]
    if contrib.shape[0] == 0:  # empty shard: zero histogram, like XLA
        shape = ((num_segments,) if contrib.ndim == 1
                 else (num_segments, lanes))
        # honor contrib's dtype like the non-empty path does after its
        # f32 accumulation — a float32 zero here would make the two
        # backends stop being drop-in interchangeable exactly on the
        # empty-shard edge (seen by zero-row shards of uneven splits)
        return jnp.zeros(shape, contrib.dtype)
    contrib2 = contrib.reshape(contrib.shape[0], lanes).T  # [L, nnz]
    nnz = contrib2.shape[1]
    nnz_pad = pl.cdiv(nnz, _NNZ_TILE) * _NNZ_TILE
    rows_pad = pl.cdiv(num_segments, _ROW_TILE) * _ROW_TILE
    # pad entries land in an out-of-range row with contribution 0
    contrib_p = jnp.zeros((lanes, nnz_pad), jnp.float32).at[:, :nnz].set(
        contrib2.astype(jnp.float32))
    row_id_p = jnp.full((1, nnz_pad), rows_pad, jnp.int32).at[0, :nnz].set(
        row_id.astype(jnp.int32))
    out = pl.pallas_call(
        _seg_kernel,
        grid=(rows_pad // _ROW_TILE, nnz_pad // _NNZ_TILE),
        in_specs=[
            pl.BlockSpec((1, _NNZ_TILE), lambda rt, nt: (0, nt)),
            pl.BlockSpec((lanes, _NNZ_TILE), lambda rt, nt: (0, nt)),
        ],
        out_specs=pl.BlockSpec((lanes, _ROW_TILE), lambda rt, nt: (0, rt)),
        out_shape=jax.ShapeDtypeStruct((lanes, rows_pad), jnp.float32),
        interpret=interpret,
        name=SEGMENT_SUM_KERNEL,
    )(row_id_p, contrib_p)
    res = out[:, :num_segments]
    return res[0] if contrib.ndim == 1 else res.T


# pallas_call has no autodiff rule, but segment-sum's VJP is exact and
# trivial — d_contrib[k] = g_out[row_id[k]], a gather — so the kernel
# stays usable under jax.grad (the FM/linear train steps differentiate
# through their Row::SDot reductions; GBDT alone wouldn't need this, its
# grad/hess are analytic).
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _segment_sum_pallas_diff(contrib, row_id, num_segments, interpret):
    return _segment_sum_pallas(contrib, row_id, num_segments, interpret)


def _segment_sum_fwd(contrib, row_id, num_segments, interpret):
    out = _segment_sum_pallas(contrib, row_id, num_segments, interpret)
    # zero-size dtype token: residuals must be JAX types, not dtypes
    return out, (row_id, jnp.zeros((0,), contrib.dtype))


def _segment_sum_bwd(num_segments, interpret, res, g):
    row_id, dtype_token = res
    import numpy as _np
    d_contrib = g[row_id].astype(dtype_token.dtype)
    # integer primal: cotangent is float0 by JAX convention
    d_row_id = _np.zeros(row_id.shape, jax.dtypes.float0)
    return d_contrib, d_row_id


_segment_sum_pallas_diff.defvjp(_segment_sum_fwd, _segment_sum_bwd)


_KEY_TILE = 512    # (feature, bin) key lanes per out tile (sparse kernel)

# Most nodes per level the two histogram kernels are routed to by
# ``GBDT(histogram="auto")``.  Their compare work does not depend on
# n_nodes; what grows with it is the MXU M axis and the VMEM tiles (the A
# tile, its mask and one-hot temporaries, the out tile).  Each cap is the
# most a chip run has held its kernel to.  PR 21 set the sparse one where
# the float32 step of that time stopped fitting the 16 MiB of scoped VMEM on
# a v5e; since PR 30 its bfloat16 step compiles for a described v5e at 512
# nodes too and runs out of VMEM at 1024 (libtpu 0.0.34).  The dense kernel
# compiles for a described v5e at 1024 nodes
# too (its step is sized by ``_hist_plan``, not by the node count alone),
# and nothing has run it there.  chip_smoke.py compiles and checks both
# kernels at exactly these values, so a cap that stops compiling fails
# there and not in somebody's depth-10 fit.
HIST_NODE_LIMIT = 512
SPARSE_HIST_NODE_LIMIT = 256

# The dense kernel's own tile sizes (the other kernels read none of them);
# how a call chooses among them: ``_hist_plan``, at the end of this file.
_HIST_ROW_TILE = 1024      # rows a grid step
_HIST_MIN_STRIDE = 64      # least key lanes a feature: <= 4 features a tile
_HIST_STACK_NODES = 32     # node columns up to which ONE dot holds 3 parts
_HIST_STEP_BYTES = 6 << 20     # a step's out block and A^T may take this ...
_HIST_STEP_TILES = 32          # ... and a step unrolls at most this many tiles


def _hist_kernel(plan, bins_ref, rel_ref, gh_ref, out_ref):
    """One (key-group, row-tile) step of the histogram-as-matmul:

        out[(part, lane, node), (feature, bin)] += A^T B
        A^T[(part, lane, node), row] = gh[part, lane, row] * [rel[row] == node]
        B^T[(feature, bin), row]     = [bins[feature, row] == bin]

    Rows stay on the lane axis, as ``bins``, ``rel`` and ``gh`` arrive, so
    no operand is relaid: A^T and B^T are sublane broadcasts compared with
    an iota, and the contraction is over the last axis of both.  Both are
    bfloat16.  B^T is 0/1; ``gh_ref`` holds (grad, hess) as three parts
    that are each exactly a bfloat16 and sum to the float32
    (``_split_bf16x3``); so every product is exact and the MXU's float32
    accumulation gives what ``Precision.HIGHEST`` gave, in one pass,
    without the passes that multiplied by the zero low parts of B.  The M
    axis is (3 parts x 2 lanes x n_pad nodes).  A^T is built once a step
    and serves each of the step's ``tiles`` key tiles of ``w`` lanes
    (``fpt`` whole features each, or a ``w``-lane slice of one feature
    when ``q`` > 1); B's one-hot is the only work that grows with the
    keys, O(rows * F * num_bins) compares whatever n_nodes.  Everything
    stays 2-D and feature rows are read via dynamic *ref* loads (squeezing
    indexing and lax.dynamic_slice on a loaded array do not lower in
    Mosaic)."""
    w, nb, fpt, q, n_pad = plan.w, plan.nb, plan.fpt, plan.q, plan.n_pad
    first_tile = pl.program_id(0) * plan.tiles

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def step(tiles):
        """A^T once, then the step's first ``tiles`` key tiles."""
        rt = rel_ref.shape[1]
        # Padding rows carry rel == n_pad (matches no node column) AND gh ==
        # 0: whatever bins the ragged last block holds there is inert.
        node_ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, rt), 0)
        mask = jnp.broadcast_to(rel_ref[...], (n_pad, rt)) == node_ids
        a_t = jnp.concatenate(
            [jnp.where(mask,
                       jnp.broadcast_to(gh_ref[i:i + 1, :], (n_pad, rt)), 0.0)
             for i in range(6)], axis=0).astype(jnp.bfloat16)
        lanes = min(nb, w)
        loc = jax.lax.broadcasted_iota(jnp.int32, (lanes, rt), 0)

        def one_hot(feature, offset):
            # bins_ref is every feature or an 8-feature block (see
            # in_specs); a padding feature past the last, in a tile that
            # also holds live ones, reads some row: its keys are cut
            row = bins_ref[pl.ds(feature % plan.fb, 1), :]      # [1, rt]
            hit = loc == jnp.broadcast_to(row, (lanes, rt)) - offset
            return jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)

        dot = functools.partial(jax.lax.dot_general,
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m = 2 * n_pad
        for j in range(tiles):
            kt = first_tile + j
            if q == 1:  # tile kt: features kt * fpt + fl, one after another
                b_t = jnp.concatenate(
                    [one_hot(kt * fpt + fl, 0) for fl in range(fpt)], axis=0)
            else:       # tile kt: slice kt % q of feature kt // q
                b_t = one_hot(kt // q, (kt % q) * w)
            if plan.parts == 3:
                acc = dot(a_t, b_t)
            else:
                acc = sum(dot(a_t[p * m:(p + 1) * m], b_t) for p in range(3))
            out_ref[:, j * w:(j + 1) * w] += acc

    # The last group may be short: its tiles from ``live_kt`` on hold no
    # feature, and its steps do nothing for them (no one-hot, no dot, no
    # add: their out lanes keep `_init`'s zeros and are cut by the caller).
    # The WHOLE step is under one of two branches, not the tail of its tile
    # loop: a branch inside the loop cost every step of the full groups more
    # than the last group's saved (PERF.md, PR 51).  A plan without such
    # tiles has no branch.
    dead = plan.num_kt - plan.live_kt
    if not dead:
        step(plan.tiles)
    else:
        last = pl.program_id(0) == pl.num_programs(0) - 1
        pl.when(jnp.logical_not(last))(lambda: step(plan.tiles))
        pl.when(last)(lambda: step(plan.tiles - dead))


def _hist_key_lanes(p: "_HistPlan", bins_t: jax.Array, rel: jax.Array,
                    gh: jax.Array, interpret: bool) -> jax.Array:
    """`_hist_kernel` over the grid of plan ``p``: float32
    ``[parts * 2 * n_pad, num_kt * w]``, every key lane the plan has, those
    of its padding tiles (zeros) too."""
    rows = bins_t.shape[1]
    k_pad = p.num_kt * p.w
    m_pad = 2 * p.parts * p.n_pad
    rows_pad = pl.cdiv(max(rows, 1), _HIST_ROW_TILE) * _HIST_ROW_TILE
    with jax.named_scope("ops.hist_layout"):
        rel_p = jnp.full((1, rows_pad), p.n_pad, jnp.int32
                         ).at[0, :rows].set(rel)
        # (part, lane) rows: g_hi, h_hi, g_mid, h_mid, g_lo, h_lo, 0, 0
        gh_p = jnp.zeros((8, rows_pad), jnp.float32).at[:6, :rows].set(
            jnp.concatenate(_split_bf16x3(gh.astype(jnp.float32).T)))
    def bins_index(g, rt):
        # the block that holds step g's features: its first tile's first
        return ((g * p.tiles * p.fpt // p.q) // p.fb, rt)

    return pl.pallas_call(
        functools.partial(_hist_kernel, p),
        grid=(p.num_kt // p.tiles, rows_pad // _HIST_ROW_TILE),
        in_specs=[
            pl.BlockSpec((p.fb, _HIST_ROW_TILE), bins_index),
            pl.BlockSpec((1, _HIST_ROW_TILE), lambda g, rt: (0, rt)),
            pl.BlockSpec((8, _HIST_ROW_TILE), lambda g, rt: (0, rt)),
        ],
        out_specs=pl.BlockSpec((m_pad, p.tiles * p.w), lambda g, rt: (0, g)),
        out_shape=jax.ShapeDtypeStruct((m_pad, k_pad), jnp.float32),
        interpret=interpret,
        name=DENSE_HIST_KERNEL,
    )(bins_t, rel_p, gh_p)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "num_bins", "interpret"))
def _histogram_gh_pallas(bins_t: jax.Array, rel: jax.Array, gh: jax.Array,
                         n_nodes: int, num_bins: int,
                         interpret: bool) -> jax.Array:
    """bins_t: [F, rows] int32; rel: [rows] int32 node ids; gh: [rows, 2].
    Returns [n_nodes, F, num_bins, 2]."""
    F = bins_t.shape[0]
    p = _hist_plan(F, num_bins, n_nodes)
    out = _hist_key_lanes(p, bins_t, rel, gh, interpret)
    with jax.named_scope("ops.hist_layout"):
        return (out.reshape(p.parts, 2, p.n_pad, -1, p.nb).sum(0)
                [:, :n_nodes, :F, :num_bins]
                .transpose(1, 2, 3, 0))                 # [n, F, B, 2]


def histogram_gh(bins: jax.Array, rel: jax.Array, gh: jax.Array,
                 n_nodes: int, num_bins: int,
                 force: str | None = None) -> jax.Array:
    """Per-level GBDT gradient histogram: ``out[n, f, b, :] = sum of
    gh[row] where rel[row] == n and bins[row, f] == b``.

    bins: [rows, F] int bin codes; rel: [rows] node ids in [0, n_nodes);
    gh: [rows, 2] (grad, hess) lanes.  Returns [n_nodes, F, num_bins, 2].

    force: None/"xla" -> flattened-key ``jax.ops.segment_sum`` (XLA
    scatter-add).  NOTE this path materializes a [rows, F] int32 key
    array and a [rows, F, 2] f32 broadcast per call — ~12*rows*F bytes
    of HBM traffic (Higgs-11M x 28 features: ~3.7 GB per level); it is
    the right trade on CPU.

    "pallas" -> the histogram-as-matmul kernel above: per row tile it
    builds A^T = node-masked (grad, hess) [3*2*nodes, ROW] and, key tile by
    key tile, B^T = bin one-hot [KEYS, ROW], both bfloat16, and contracts
    over rows on the MXU in ONE pass with float32 accumulation —
    scatter-free, nothing materialized at [rows, F] granularity, compare
    work O(rows*F*bins) independent of n_nodes.  Float32 exactness comes
    from the operands, not from ``Precision.HIGHEST``: (grad, hess) go in
    as three bfloat16 parts that sum to the float32 bit for bit
    (``_split_bf16x3``: every finite float32 of magnitude >= 9.9e-32, up to
    the largest; below that a value loses at most 1.2e-38 where the chip
    flushes denormals), B is 0/1, and the parts' partial histograms are
    added in float32 (on the M axis of one dot up to 32 node columns, three
    dots above).  A non-finite grad or hess makes every bucket of its
    row's node non-finite (inf * 0 on the MXU), as it always did.  The
    tiling is chosen by the static shapes alone (``_hist_plan``).
    Against the XLA path on a v5e (rows=65,536, F=28, 256 bins,
    chip_smoke.py): max error <= 7e-7 of the largest bucket at 1, 32 and
    512 nodes (accumulation order only), so the backends stay drop-in
    interchangeable.  Its speed is in PERF.md, from the benchmark's
    ledger; against XLA scatter it has no measurement on the current
    installation.  Interpret mode off-TPU is a correctness tool, not an
    execution path.
    """
    check_force(force, "histogram backend")
    if force == "pallas":
        with jax.named_scope("ops.hist_layout"):
            bins_t = jnp.asarray(bins, jnp.int32).T
        return _histogram_gh_pallas(
            bins_t, jnp.asarray(rel, jnp.int32),
            gh, n_nodes, num_bins, pallas_interpret()).astype(gh.dtype)
    rows, F = bins.shape
    feat_cols = jnp.arange(F, dtype=jnp.int32)
    keys = ((rel[:, None] * F + feat_cols[None, :]) * num_bins
            + jnp.asarray(bins, jnp.int32)).reshape(-1)
    return jax.ops.segment_sum(
        jnp.broadcast_to(gh[:, None, :], (rows, F, 2)).reshape(-1, 2),
        keys, num_segments=n_nodes * F * num_bins
    ).reshape(n_nodes, F, num_bins, 2)


# ---- sparse (COO) histogram -------------------------------------------------


def _sparse_geometry(num_features: int, num_bins: int) -> tuple[int, int]:
    """(nb, num_kt): per-feature key stride (pow2 >= num_bins) and key-tile
    count.  Unlike the dense kernel there is no ``_KEY_TILE // 8`` floor —
    that clamp bounds the dense kernel's unrolled per-feature compare loop,
    and the sparse kernel has no such loop (each entry carries its own
    key)."""
    nb = 1 << max(num_bins - 1, 1).bit_length()
    if num_features * nb >= 2 ** 31:
        raise ValueError(f"feature x bin key space overflows int32 "
                         f"({num_features} features x {nb} bin stride)")
    num_kt = pl.cdiv(num_features * nb, _KEY_TILE)
    return nb, num_kt


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["gkey", "rid", "tstart", "tcount", "fstart", "nnz_live",
                 "cspan"],
    meta_fields=["num_features", "num_bins", "num_shards", "nb", "num_kt",
                 "max_tiles", "nnz_pad", "run_bits", "rows_ascend"])
@dataclasses.dataclass(frozen=True)
class SparseHistLayout:
    """Feature-sorted COO layout for :func:`histogram_gh_sparse`.

    ``findex``/``ebin`` are static across every level of every tree, so
    the expensive part of the sparse kernel — sorting the entries by
    feature and computing, per key tile, which contiguous span of
    ``_NNZ_TILE`` entry blocks can contribute to it — happens ONCE per
    staged batch (:func:`sparse_hist_layout`, a device sort) and is reused
    for the whole fit.  Masked (``emask == 0``) entries sort past the live
    ones and are dropped; the padding lanes that fill the last block carry
    ``gkey == -1``, which matches no key of any tile, so whatever row their
    ``rid`` (0) points at adds nothing.

    The live entries carry everything the tree builder needs of them —
    feature ``gkey // nb``, bin ``gkey % nb``, row ``rid`` — so a fit whose
    levels all run the kernel keeps no other copy of the entries.

    With ``num_shards > 1`` the layout is built per row-shard (entries
    bucketed to the shard owning their row, row ids localized) and packed
    into flat arrays whose equal per-shard slices are exactly what
    ``shard_map`` with ``P(axis)`` in_specs hands each device — the
    multi-chip psum route (`gbdt._level_histogram` mirror).

    A pytree: ``gkey``/``rid`` are ``[num_shards * nnz_pad]`` packed
    per-entry arrays (global key ``fi * nb + ebin`` and row id, shard-local
    when sharded); ``tstart``/``tcount`` are ``[num_shards * num_kt]``
    per-key-tile entry-block spans; ``fstart`` is ``[num_shards * (F + 1)]``,
    the lane at which each feature's run of entries begins in its shard's
    slice (and, last, where the live entries end).  Within a run the
    entries keep their input order; ``rows_ascend`` says whether row ids
    strictly ascend in every run, as they do when the input is row-major (a
    CSR batch's is) and no row holds a feature twice: only then is a row's
    entry on a feature the one lane that a bisection of the run finds.
    ``nnz_live`` counts the live entries.  ``cspan`` is
    ``[num_shards * nnz_pad / _NNZ_TILE]``: of each sub-tile of 1,024 entry
    lanes, the first and the last chunk of 16,384 rows that its row ids
    touch (``_chunk_spans``), which is all `entry_values`' lookup kernel
    visits for it.  The rest
    is static, and what depends on the data is rounded up (`_round_up_some`)
    so that nearly equal data sets share their compiled programs — a
    program that takes a layout is compiled for its static fields:
    ``nnz_pad`` (lanes a shard, from the fullest shard's entries),
    ``max_tiles`` (the grid's inner extent, from the largest span over all
    tiles and shards) and ``run_bits`` (bisection rounds that find a lane
    in the longest run)."""

    num_features: int
    num_bins: int
    num_shards: int
    nb: int
    num_kt: int
    max_tiles: int
    nnz_pad: int
    run_bits: int
    rows_ascend: bool
    gkey: jax.Array
    rid: jax.Array
    tstart: jax.Array
    tcount: jax.Array
    fstart: jax.Array
    nnz_live: jax.Array
    cspan: jax.Array

    @property
    def grid_steps(self) -> int:
        """Grid steps that one level's kernel calls launch, over all shards
        (``tcount.sum()`` is the entry sub-tiles they run)."""
        return self.num_shards * self.num_kt * _sparse_steps(self.max_tiles)


def _round_up_some(n: int, granule: int, parts: int) -> int:
    """``n`` rounded up to a multiple of ``granule`` and of about one
    ``parts``-th of its own magnitude: two counts that differ by a little
    (two seeds' draws of one data set) round to the same number, at the
    price of at most ``1 / parts`` more."""
    step = max(granule, 1 << max(max(n, 1).bit_length() - parts.bit_length(),
                                 0))
    return max(-(-max(n, 1) // step) * step, granule)


@functools.partial(jax.jit, static_argnames=("num_features", "num_bins",
                                             "nb", "num_shards", "local",
                                             "binned"))
def _layout_sort(row_id, findex, carried, emask, num_features: int,
                 num_bins: int, nb: int, num_shards: int, local: int,
                 binned: bool = True):
    """Live entries sorted by (owning shard, feature), dead ones last.

    The sort is stable, so within a feature the entries keep their input
    order and the layout — and the kernel's accumulation order — is a pure
    function of the entry stream (feature-sort determinism test).
    ``carried`` is what the sort takes along beside the row id: the entries'
    bin codes (``binned``), which go as the key ``fi * nb + code``, or their
    float32 values, for `_layout_pack` to bin where they then lie; the
    lanes' order is the same either way.  Returns the sorted ``gkey`` (or
    values) and shard-local ``rid``, ``starts`` (the sorted position at
    which each (shard, feature) run begins, ``F + 1`` a shard: the last is
    where the shard's live entries end), whether a live entry is out of
    range, and whether row ids strictly ascend within every run."""
    rid = row_id.astype(jnp.int32)
    fi = findex.astype(jnp.int32)
    live = emask.astype(bool)
    off = (fi < 0) | (fi >= num_features)
    if binned:
        eb = jnp.asarray(carried, jnp.int32)
        off |= (eb < 0) | (eb >= num_bins)
        carried = fi * nb + eb
    else:
        carried = carried.astype(jnp.float32)
    bad = jnp.any(live & off)
    f1 = num_features + 1
    owner = rid // local if num_shards > 1 else 0
    key = jnp.where(live, owner * f1 + fi, num_shards * f1)
    key, carried, rid = jax.lax.sort(
        (key, carried, rid - owner * local), num_keys=1, is_stable=True)
    starts = jnp.searchsorted(
        key, jnp.arange(num_shards * f1, dtype=jnp.int32), side="left")
    same_run = (key[1:] == key[:-1]) & (key[1:] < num_shards * f1)
    ascend = jnp.all(~same_run | (rid[1:] > rid[:-1]))
    return carried, rid, starts.astype(jnp.int32), bad, ascend


@functools.partial(jax.jit, static_argnames=("num_shards", "nnz_pad", "nb",
                                             "interpret"))
def _layout_pack(carried, rid, offset, count, num_shards: int, nnz_pad: int,
                 rstart=None, cuts=None, nb: int = 0,
                 interpret: bool = False):
    """The sorted entries as ``num_shards`` slices of ``nnz_pad`` lanes:
    shard ``s`` is ``[offset[s], offset[s] + count[s])`` of the sorted
    arrays, then ``gkey == -1`` / ``rid == 0`` filler; and the packed
    row ids' `_chunk_spans`.  ``carried`` is the sorted ``gkey`` or, with
    ``cuts``, the sorted values, whose keys are made here on the packed
    lanes (`_bin_runs_pallas`; ``rstart``: the packed lane at which each
    (shard, feature) run begins, and last the lanes in all)."""
    lane = jnp.arange(nnz_pad, dtype=jnp.int32)
    if num_shards == 1:     # a slice or a pad of the sorted arrays, no gather
        def fit(a):
            n = a.shape[0]
            return a[:nnz_pad] if n >= nnz_pad else jnp.pad(a, (0, nnz_pad - n))
        ok = lane < count[0]
        carried_p, rid_p = fit(carried), jnp.where(ok, fit(rid), 0)
    else:
        src = jnp.minimum(offset[:, None] + lane[None, :],
                          carried.shape[0] - 1)
        ok = (lane[None, :] < count[:, None]).reshape(-1)
        carried_p = carried[src].reshape(-1)
        rid_p = jnp.where(ok, rid[src].reshape(-1), 0)
    if cuts is None:
        gkey_p = jnp.where(ok, carried_p, -1)
    else:
        with jax.named_scope("gbdt.layout_bin"):
            gkey_p = _bin_runs_pallas(carried_p, rstart, cuts, nb,
                                      num_shards, interpret)
    return gkey_p, rid_p, _chunk_spans(rid_p)


def sparse_hist_layout(row_id, findex, ebin, emask,
                       num_features: int, num_bins: int,
                       num_shards: int = 1,
                       rows: int | None = None,
                       value=None, cuts=None) -> SparseHistLayout:
    """Build the feature-sorted layout (see :class:`SparseHistLayout`).

    row_id/findex/ebin/emask: [nnz] COO entry arrays (any int/bool dtypes;
    device or host).  ``num_shards > 1`` buckets entries by the row shard
    that owns them (``rows`` must then divide evenly — shard_map's
    even-sharding rule) and localizes row ids to the shard.

    In place of the codes ``ebin`` the entries may come as they were staged:
    ``value`` ([nnz] float) with ``cuts`` ([F, C] float32, non-decreasing
    along C, ``C + 2 <= num_bins``).  A live entry's code is then ``1 + #{c:
    cuts[f, c] <= value}``, what `QuantileBinner.transform_entries` gives,
    worked out after the sort, where the entries of a feature lie together
    (`_bin_runs_pallas`); the layout is the one that those codes would have
    given, field for field.

    The entries are sorted on the device (one stable ``lax.sort`` by
    feature that carries key, or value, and row id); the host reads back
    only the ``num_shards * (F + 1)`` run starts, from which it sizes the
    packed arrays and works out the per-key-tile block spans."""
    nb, num_kt = _sparse_geometry(num_features, num_bins)
    local = 0
    if num_shards > 1:
        if rows is None or rows % num_shards:
            raise ValueError("sharded layout needs rows divisible by "
                             f"num_shards (rows={rows}, "
                             f"num_shards={num_shards})")
        local = rows // num_shards
    binned = value is None
    if not binned:
        cuts = jnp.asarray(cuts, jnp.float32)
        if cuts.shape[0] != num_features or cuts.shape[1] + 2 > num_bins:
            raise ValueError(f"cuts {cuts.shape} do not bin {num_features} "
                             f"features into {num_bins} codes")
    carried, rid, starts, bad, ascend = _layout_sort(
        jnp.asarray(row_id), jnp.asarray(findex),
        jnp.asarray(ebin if binned else value), jnp.asarray(emask),
        num_features, num_bins, nb, num_shards, local, binned)
    starts = np.asarray(starts).astype(np.int64)
    if bool(bad):
        raise ValueError("findex or ebin out of range for live entries")
    f1 = num_features + 1
    # [shard, F + 1]: where each feature's run begins in the sorted arrays
    # and, last, where the shard's live entries end
    runs = starts.reshape(num_shards, f1)
    offset = runs[:, 0]
    local_runs = runs - offset[:, None]             # the same, shard-local
    count = local_runs[:, num_features]             # live entries a shard
    nnz_pad = _round_up_some(int(count.max()), _NNZ_TILE, 64)
    # features whose key range [f*nb, (f+1)*nb) intersects each key tile
    kt = np.arange(num_kt, dtype=np.int64)
    flo = np.minimum(kt * _KEY_TILE // nb, num_features)
    fhi = np.minimum(-(-(kt + 1) * _KEY_TILE // nb), num_features)
    begin, stop = local_runs[:, flo], local_runs[:, fhi]
    some = stop > begin
    tstart = np.where(some, begin // _NNZ_TILE, 0)
    tcount = np.where(some, -(-stop // _NNZ_TILE) - tstart, 0)
    binning = {}
    if not binned:
        # the runs on the packed lanes: shard s's begin at s * nnz_pad, and
        # its last, of no feature, is the filler up to the next shard's
        shard0 = np.arange(num_shards, dtype=np.int64)[:, None] * nnz_pad
        binning = dict(
            rstart=jnp.asarray(np.append((local_runs + shard0).reshape(-1),
                                         num_shards * nnz_pad), jnp.int32),
            cuts=cuts, nb=nb, interpret=pallas_interpret())
    gkey_p, rid_p, cspan = _layout_pack(
        carried, rid, jnp.asarray(offset, jnp.int32),
        jnp.asarray(count, jnp.int32), num_shards, nnz_pad, **binning)
    return SparseHistLayout(
        num_features=num_features, num_bins=num_bins,
        num_shards=num_shards, nb=nb, num_kt=num_kt,
        max_tiles=_round_up_some(int(tcount.max()), 1, 16),
        nnz_pad=nnz_pad, nnz_live=jnp.asarray(int(count.sum()), jnp.int32),
        run_bits=int(np.diff(local_runs, axis=1).max()).bit_length(),
        rows_ascend=bool(ascend),
        fstart=jnp.asarray(local_runs.reshape(-1), jnp.int32),
        gkey=gkey_p, rid=rid_p, cspan=cspan,
        tstart=jnp.asarray(tstart.reshape(-1), jnp.int32),
        tcount=jnp.asarray(tcount.reshape(-1), jnp.int32))


# Entry sub-tiles of _NNZ_TILE that one grid step of the sparse kernel loops
# over.  Every key tile owns the steps of the fullest one, and a step that
# runs nothing still costs 0.3 us on a v5e: at the Bosch cell's layout
# (2.18e8 entries, 484 key tiles, the fullest span 2,304 sub-tiles, the
# mean 440) a level of up to 8 nodes took 373 / 189 / 150 / 131 / 125 ms at
# 1 / 4 / 8 / 16 / 32 sub-tiles a step (my chip runs, PR 30).
_SPARSE_STEP_TILES = 32


def _sparse_steps(max_tiles: int) -> int:
    """Grid steps a key tile: the most ``_SPARSE_STEP_TILES``-blocks that a
    span of ``max_tiles`` entry sub-tiles touches, wherever it starts."""
    return (max_tiles + _SPARSE_STEP_TILES - 2) // _SPARSE_STEP_TILES + 1


def _sparse_hist_kernel(n_pad: int, parts: int, tstart_ref, tcount_ref,
                        gkey_ref, rel_ref, gh_ref, out_ref):
    """One (key tile, entry block) step of the sparse histogram; the block
    is ``_SPARSE_STEP_TILES`` sub-tiles of ``_NNZ_TILE`` entries, and for
    each sub-tile of the key tile's span

        out[(part, lane, node), key] += A^T B
        A^T[(part, lane, node), entry] = gh[part, lane, entry]
                                         * [rel[entry] == node]
        B^T[key, entry]                = [gkey[entry] - kt*KEY_TILE == key]

    as ``_hist_kernel`` does it, with entries where its rows are: entries
    stay on the lane axis, as ``gkey``, ``rel`` and ``gh`` arrive, so no
    operand is relaid; A^T and B^T are sublane broadcasts compared with an
    iota, both bfloat16, and one ``dot_general`` contracts the last axis of
    both into float32.  B^T is 0/1 and (grad, hess) go in as the three
    parts of ``_split_bf16x3``, made here from the float32 block (an
    operand of parts with ``nnz`` lanes would be gigabytes), so every
    product is exact and the float32 accumulation gives what
    ``Precision.HIGHEST`` gave in six passes: the parts are rows of one dot
    (``parts`` 3: M = 3 x 2 x n_pad) up to ``_HIST_STACK_NODES`` node
    columns and three dots (``parts`` 1: M = 2 x n_pad) past it.

    The scalar-prefetched span table (``tstart``, ``tcount``, in sub-tiles)
    makes the block index data-dependent: step (kt, et) reads block
    ``tstart[kt] // _SPARSE_STEP_TILES + et`` and runs the sub-tiles of it
    that lie in ``[tstart[kt], tstart[kt] + tcount[kt])`` — entries sorted
    by feature mean that a key tile touches its own features' blocks only,
    and a block past the span costs one empty grid step.  Entries of a
    neighbouring feature in a boundary sub-tile, and the padding lanes
    (``gkey == -1``), mask themselves: their key is outside this tile's
    [0, KEY_TILE), so their column of B^T is zero.  What a block holds
    past the array's end is never read: no span reaches there."""
    kt = pl.program_id(0)
    et = pl.program_id(1)
    first = (tstart_ref[kt] // _SPARSE_STEP_TILES + et) * _SPARSE_STEP_TILES
    stop = tstart_ref[kt] + tcount_ref[kt]

    @pl.when(et == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    node_ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, _NNZ_TILE), 0)
    loc = (jax.lax.broadcasted_iota(jnp.int32, (_KEY_TILE, _NNZ_TILE), 0)
           + kt * _KEY_TILE)
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m = 2 * n_pad

    def sub_tile(j, carry):
        lanes = pl.ds(pl.multiple_of(j * _NNZ_TILE, _NNZ_TILE), _NNZ_TILE)
        mask = jnp.broadcast_to(rel_ref[:, lanes],
                                (n_pad, _NNZ_TILE)) == node_ids
        a_t = jnp.concatenate(
            [jnp.where(mask, jnp.broadcast_to(part[i:i + 1, :],
                                              (n_pad, _NNZ_TILE)), 0.0)
             for part in _split_bf16x3(gh_ref[:, lanes]) for i in range(2)],
            axis=0).astype(jnp.bfloat16)
        hit = loc == jnp.broadcast_to(gkey_ref[:, lanes],
                                      (_KEY_TILE, _NNZ_TILE))
        b_t = jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16)
        if parts == 3:
            out_ref[...] += dot(a_t, b_t)
        else:
            out_ref[...] += sum(dot(a_t[p * m:(p + 1) * m], b_t)
                                for p in range(3))
        return carry

    # the span's sub-tiles in this block; none in a step past the span
    jax.lax.fori_loop(jnp.maximum(tstart_ref[kt] - first, 0),
                      jnp.clip(stop - first, 0, _SPARSE_STEP_TILES),
                      sub_tile, None)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "num_features", "num_bins",
                                    "max_tiles", "interpret"))
def _histogram_gh_sparse_pallas(gkey: jax.Array, rel_e: jax.Array,
                                gh_e: jax.Array, tstart: jax.Array,
                                tcount: jax.Array, n_nodes: int,
                                num_features: int, num_bins: int,
                                max_tiles: int, interpret: bool) -> jax.Array:
    """One shard's kernel call.  gkey/rel_e: [nnz_pad] int32 (nnz_pad a
    multiple of _NNZ_TILE); gh_e: [2, nnz_pad] f32, (grad, hess) already
    gathered onto the entries, lanes first as the kernel reads them (an
    ``[nnz_pad, 2]`` array would have to be transposed here at every
    level); tstart/tcount: [num_kt] int32 spans in sub-tiles of _NNZ_TILE
    entries, none longer than ``max_tiles``.  Returns
    [n_nodes, F, num_bins, 2] f32."""
    nnz_pad = gkey.shape[0]
    nb, num_kt = _sparse_geometry(num_features, num_bins)
    k_pad = num_kt * _KEY_TILE
    f_pad = k_pad // nb
    n_pad = pl.cdiv(n_nodes, 8) * 8
    parts = 3 if n_pad <= _HIST_STACK_NODES else 1
    m_pad = 2 * parts * n_pad
    block = _SPARSE_STEP_TILES * _NNZ_TILE
    with jax.named_scope("ops.hist_layout"):
        gkey2 = gkey.reshape(1, nnz_pad)
        rel2 = rel_e.astype(jnp.int32).reshape(1, nnz_pad)
        gh2 = gh_e.astype(jnp.float32)

    # block index of entry inputs at step (kt, et): steps past the span
    # re-address its last block — a repeated index means no re-fetch, so
    # HBM traffic is that of the blocks that hold a span
    def eidx(kt, et, ts, tc):
        last = jnp.maximum(ts[kt] + tc[kt] - 1, ts[kt])
        return (0, jnp.minimum(ts[kt] // _SPARSE_STEP_TILES + et,
                               last // _SPARSE_STEP_TILES))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_kt, _sparse_steps(max_tiles)),
        in_specs=[
            pl.BlockSpec((1, block), eidx),
            pl.BlockSpec((1, block), eidx),
            pl.BlockSpec((2, block), eidx),
        ],
        out_specs=pl.BlockSpec((m_pad, _KEY_TILE),
                               lambda kt, et, ts, tc: (0, kt)),
    )
    out = pl.pallas_call(
        functools.partial(_sparse_hist_kernel, n_pad, parts),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, k_pad), jnp.float32),
        interpret=interpret,
        name=SPARSE_HIST_KERNEL,
    )(tstart, tcount, gkey2, rel2, gh2)
    with jax.named_scope("ops.hist_layout"):
        return (out.reshape(parts, 2, n_pad, f_pad, nb).sum(0)
                [:, :n_nodes, :num_features, :num_bins]
                .transpose(1, 2, 3, 0))         # [n, F, B, 2]


def histogram_gh_sparse_kernel(gkey, rel_e, gh_e, tstart, tcount,
                               n_nodes: int, num_features: int,
                               num_bins: int, max_tiles: int) -> jax.Array:
    """Raw kernel entry over pre-gathered per-entry arrays:
    ``rel_e = rel[layout.rid]`` (per level) and
    ``gh_e = gh[layout.rid].T`` (per tree, lanes first).  The GBDT
    builder calls this directly so the gh gather hoists out of the level
    loop and — under ``histogram_mesh`` — so the call can sit inside a
    ``shard_map`` body next to its psum.  ``histogram_gh_sparse`` wraps it
    for one-shot use."""
    return _histogram_gh_sparse_pallas(gkey, rel_e, gh_e, tstart, tcount,
                                       n_nodes, num_features, num_bins,
                                       max_tiles, pallas_interpret())


def histogram_gh_sparse(row_id, findex, ebin, emask, rel, gh,
                        n_nodes: int, num_features: int, num_bins: int,
                        force: str | None = None,
                        layout: SparseHistLayout | None = None) -> jax.Array:
    """Sparse (COO) GBDT gradient histogram: ``out[n, f, b, :] = sum of
    gh[row_id[k]] over live entries k with rel[row_id[k]] == n,
    findex[k] == f, ebin[k] == b``.

    row_id/findex/ebin/emask: [nnz] entry arrays (emask 0 marks padding /
    masked lanes); rel: [rows] node ids in [0, n_nodes); gh: [rows, 2]
    (grad, hess).  Returns [n_nodes, F, num_bins, 2].

    force: None/"xla" -> the flattened-key ``jax.ops.segment_sum``
    scatter-add over ``(rel[rid] * F + fi) * B + ebin`` — exactly the
    formulation ``gbdt._build_tree_sparse`` always used, O(nnz) work.

    "pallas" -> the sparse histogram-as-matmul kernel: entries sorted by
    feature once (``layout``; built here when not supplied — pass a
    prebuilt one to amortize the sort over a whole fit), then per
    (key tile, entry sub-tile) A^T = node-masked per-entry (grad, hess)
    [3*2*nodes, NNZ_TILE] contracts against B^T = key one-hot
    [KEY_TILE, NNZ_TILE] over the entries, both bfloat16, on the MXU in
    ONE pass with float32 accumulation.  Float32 exactness comes from the
    operands, as in ``histogram_gh``: (grad, hess) go in as the three
    bfloat16 parts of ``_split_bf16x3``, B^T is 0/1, and the parts'
    partial histograms are added in float32 (on the M axis of one dot up
    to 32 node columns, three dots above).  The scalar-prefetched span
    table means a key tile only reads its own features' entry blocks:
    compare work O(nnz * KEY_TILE) total, independent of ``n_nodes`` and
    of F, vs the dense kernel's O(rows * F * bins).  Against the XLA path
    on a v5e (65,536 rows x 28 features, 256 bins, chip_smoke.py, PR 30):
    max error <= 6e-7 of the largest bucket at 1, 32 and 256 nodes
    (accumulation order), so the backends stay drop-in interchangeable.
    """
    check_force(force, "histogram backend")
    if force == "pallas":
        if layout is None:
            layout = sparse_hist_layout(row_id, findex, ebin, emask,
                                        num_features, num_bins)
        if layout.num_shards != 1:
            raise ValueError(
                "sharded SparseHistLayout must run under shard_map with "
                "per-shard slices (see gbdt's histogram_mesh route); call "
                "histogram_gh_sparse_kernel from the shard_map body")
        if (layout.num_features, layout.num_bins) != (num_features,
                                                      num_bins):
            raise ValueError(
                f"layout built for F={layout.num_features}/"
                f"B={layout.num_bins}, called with F={num_features}/"
                f"B={num_bins}")
        gh_e = entry_values(layout.rid, layout.cspan,
                            gh.astype(jnp.float32), layout.rows_ascend)
        # node ids past 256 are no bfloat16: those take the gather
        rel_e = entry_values(layout.rid, layout.cspan,
                             jnp.asarray(rel, jnp.int32),
                             layout.rows_ascend and n_nodes <= 256)
        out = histogram_gh_sparse_kernel(
            layout.gkey, rel_e, gh_e, layout.tstart, layout.tcount,
            n_nodes, num_features, num_bins, layout.max_tiles)
        return out.astype(gh.dtype)
    rid = jnp.asarray(row_id, jnp.int32)
    fi = jnp.asarray(findex, jnp.int32)
    gh_k = gh[rid] * emask.astype(gh.dtype)[:, None]
    keys = ((jnp.asarray(rel, jnp.int32)[rid] * num_features + fi)
            * num_bins + jnp.asarray(ebin, jnp.int32))
    return jax.ops.segment_sum(
        gh_k, keys, num_segments=n_nodes * num_features * num_bins
    ).reshape(n_nodes, num_features, num_bins, 2)


# ---- values a row, laid onto the feature-sorted entries ---------------------
# ``table[layout.rid]``: a level's slots (once a level below the root) and a
# tree's (grad, hess) (once a tree) reach the 2.18e8 sorted entries of the
# Bosch cell this way.  XLA gathers one element at a time, 8.6 ns an element
# on a v5e (1.88 s a level; PERF.md, PR 27), whatever the table.  The lookup
# below reads the table out of VMEM by two one-hots instead, and what it
# costs does not depend on how thin a feature is: 110.7 ms a level there,
# 350,889 chunk visits, bound by the VPU's issue at about 1 ns a bundle of
# the compiled visit (292 for one plane, 1,360 for six: 410.7 ms for the
# pair); 8, 32 or 64 sub-tiles a grid step read the same (my chip run, PR 46).

_LOOKUP_LO = 128                        # rows a table column: rid % 128
_LOOKUP_CHUNK = 128 * _LOOKUP_LO        # rows a chunk of 128 columns: 16,384
_LOOKUP_STEP_TILES = 32                 # entry sub-tiles a grid step

# The most rows x planes one lookup call takes: its table lies whole in VMEM,
# one buffer, a bfloat16 a row a plane, and is given 16 MiB of a v5e's 128;
# the call's limit (`vmem_limit_bytes`) is the table and 16 MiB more for the
# blocks of row ids and output (2 x 1 MiB each at 32 sub-tiles a step) and a
# chunk visit's temporaries (3 MiB at six planes).  8,388,608: one plane of
# slots up to 8.4 M rows, the six planes of (grad, hess) up to 1.4 M.
_LOOKUP_TABLE_BYTES = 16 << 20
ENTRY_LOOKUP_PLANE_ROWS = _LOOKUP_TABLE_BYTES // 2

_EMPTY_SPAN = 1         # a sub-tile's first chunk 1, its last 0: no visit


def _chunk_spans(rid: jax.Array) -> jax.Array:
    """Of each sub-tile of ``_NNZ_TILE`` lanes of ``rid``, the first and the
    last chunk of ``_LOOKUP_CHUNK`` rows that it names, packed
    ``first | last << 16`` (so rows stay under 2**29).  Where row ids ascend
    within a feature's run that is one or two chunks for a common feature
    and, for a rare one, a share of the table; a sub-tile that holds the end
    of one run and the start of the next spans from the least to the most."""
    chunk = rid.reshape(-1, _NNZ_TILE) >> 14        # // _LOOKUP_CHUNK
    return chunk.min(axis=1) | (chunk.max(axis=1) << 16)


def _entry_lookup_kernel(parts: int, cspan_ref, rid_ref, table_ref, out_ref):
    """One grid step: ``_LOOKUP_STEP_TILES`` sub-tiles of ``_NNZ_TILE`` entry
    lanes.  A row id is ``hi * 128 + lo``, a plane of the table lies
    ``T[lo, hi]``, and for a sub-tile's entries ``e`` and each chunk ``c`` of
    128 ``hi`` values that its span holds

        G[(plane, lo), e] = sum_h T[(plane, lo), c*128 + h] * [hi_e == c*128 + h]
        out[e]           += sum_lo G[lo, e] * [lo_e == lo]

    the first on the MXU (``[128 planes, 128] . [128, 1024]``, bfloat16 into
    float32), the second a select and a sublane sum.  Both one-hots are a
    sublane iota compared with a lane-broadcast row, as `_sparse_hist_kernel`
    builds its own.  An entry's ``hi`` lies in exactly one chunk, so every
    product is a bfloat16 times 0 or 1 and every sum has one term that is not
    zero: a plane's ``out`` is the table's value, exactly.  The planes are
    ``parts`` bfloat16 parts of each of the output's rows, part-major; the
    parts of a row are added in float32, first to last, before the select
    (`_split_bf16x3`'s sum back to the float32 they were split from)."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (_LOOKUP_LO, _NNZ_TILE), 0)
    out_rows = out_ref.shape[0]

    def sub_tile(j, carry):
        lanes = pl.ds(pl.multiple_of(j * _NNZ_TILE, _NNZ_TILE), _NNZ_TILE)
        rid = rid_ref[:, lanes]                                 # [1, tile]
        hi = rid >> 7
        at_lo = ids == jnp.broadcast_to(rid & (_LOOKUP_LO - 1), ids.shape)
        span = cspan_ref[0, j]

        def chunk(c, acc):
            start = pl.multiple_of(c * 128, 128)
            hit = ids == jnp.broadcast_to(hi - start, ids.shape)
            g = jnp.dot(table_ref[:, pl.ds(start, 128)],
                        jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)

            def plane(k):
                return g[k * _LOOKUP_LO:(k + 1) * _LOOKUP_LO]

            return tuple(
                a + jnp.sum(jnp.where(at_lo, sum(
                    (plane(p * out_rows + r) for p in range(1, parts)),
                    start=plane(r)), 0.0), axis=0, keepdims=True)
                for r, a in enumerate(acc))

        acc = jax.lax.fori_loop(
            span & 0xFFFF, (span >> 16) + 1, chunk,
            (jnp.zeros((1, _NNZ_TILE), jnp.float32),) * out_rows)
        for r, a in enumerate(acc):
            out_ref[pl.ds(r, 1), lanes] = a.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, _LOOKUP_STEP_TILES, sub_tile, None)


@functools.partial(jax.jit,
                   static_argnames=("out_rows", "out_dtype", "interpret"))
def _entry_lookup_pallas(rid: jax.Array, cspan: jax.Array, table: jax.Array,
                         out_rows: int, out_dtype, interpret: bool
                         ) -> jax.Array:
    """rid: [nnz_pad] int32 (a multiple of ``_NNZ_TILE``); cspan: rid's
    `_chunk_spans`; table: [parts * out_rows, rows] bfloat16, part-major.
    Returns [out_rows, nnz_pad] of ``out_dtype``: the sum over the parts of
    ``table[:, rid]``, exactly."""
    planes, rows = table.shape
    nnz_pad = rid.shape[0]
    tiles = nnz_pad // _NNZ_TILE
    steps = pl.cdiv(tiles, _LOOKUP_STEP_TILES)
    block = _LOOKUP_STEP_TILES * _NNZ_TILE
    # whole chunks of columns: a slice of 128 never leaves the table
    cols = pl.cdiv(rows, _LOOKUP_CHUNK) * 128
    with jax.named_scope("ops.lookup_layout"):
        # T[(plane, lo), hi] = table[plane, hi * 128 + lo]
        table_t = (jnp.pad(table, ((0, 0), (0, cols * _LOOKUP_LO - rows)))
                   .reshape(planes, cols, _LOOKUP_LO).transpose(0, 2, 1)
                   .reshape(planes * _LOOKUP_LO, cols))
        # a sub-tile past the last, in the last step: an empty span
        cspan3 = jnp.pad(cspan, (0, steps * _LOOKUP_STEP_TILES - tiles),
                         constant_values=_EMPTY_SPAN
                         ).reshape(steps, 1, _LOOKUP_STEP_TILES)
    return pl.pallas_call(
        functools.partial(_entry_lookup_kernel, planes // out_rows),
        grid=(steps,),
        in_specs=[
            # a step's spans: a block whose last two dims are the array's
            pl.BlockSpec((None, 1, _LOOKUP_STEP_TILES), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((out_rows, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((out_rows, nnz_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=table_t.size * 2 + (16 << 20)),
        interpret=interpret,
        name=ENTRY_LOOKUP_KERNEL,
    )(cspan3, rid.reshape(1, nnz_pad), table_t)


def entry_lookup_engages(rows_ascend: bool, plane_rows: int) -> bool:
    """Whether `entry_values` takes the lookup kernel: read off what the
    code can see.  Row ids ascend within every feature's run (else every
    sub-tile would visit every chunk: reckoned 3.9 s a level at the Bosch
    cell's size, worse than the gather), the kernel is compiled for a TPU
    (interpreted it is a test's tool, not a route), and a call's table,
    ``plane_rows`` = planes x rows, fits its share of VMEM."""
    return (rows_ascend and not pallas_interpret()
            and plane_rows <= ENTRY_LOOKUP_PLANE_ROWS)


def entry_values(rid: jax.Array, cspan: jax.Array, table: jax.Array,
                 rows_ascend: bool) -> jax.Array:
    """``table[rid]`` with the entries on the last axis: the rows' values on
    the sorted entries' lanes (``rid``, ``cspan``, ``rows_ascend``: a
    `SparseHistLayout`'s, or one shard's slices of them).

    table: ``[rows]`` int32 slots, of magnitude at most 256 -> ``[nnz]``
    int32, or ``[rows, 2]`` float32 (grad, hess) -> ``[2, nnz]`` float32, as
    `_sparse_hist_kernel` reads them.  Both routes give the same bits, but
    for a ``-0.0``, which the lookup's sums return as ``+0.0`` (a histogram
    takes the same from either).  The gather is XLA's; the lookup
    (`entry_lookup_engages`) hands the kernel bfloat16 planes that hold the
    values exactly: such an integer is a bfloat16 as it stands, a float32 is
    the three parts of `_split_bf16x3` (above 9.9e-32; below it both routes'
    histograms lose the denormal part on the chip).  A non-finite (grad,
    hess), which the gather hands to its own row's entries alone, also
    reaches entries of other rows of its chunk here (inf * 0 on the MXU):
    either way the fit it came from is lost."""
    if entry_lookup_engages(rows_ascend,
                            (1 if table.ndim == 1 else 6) * table.shape[0]):
        return _lookup_values(rid, cspan, table)
    return table[rid] if table.ndim == 1 else table[rid].T


def _lookup_values(rid: jax.Array, cspan: jax.Array, table: jax.Array
                   ) -> jax.Array:
    """`entry_values`' lookup route: the table as bfloat16 planes, through
    the kernel (interpreted off the chip, where only tests come here)."""
    if table.ndim == 1:
        return _entry_lookup_pallas(
            rid, cspan, table.astype(jnp.bfloat16)[None], 1, jnp.int32,
            pallas_interpret())[0]
    parts = jnp.concatenate(_split_bf16x3(table.astype(jnp.float32).T))
    return _entry_lookup_pallas(rid, cspan, parts.astype(jnp.bfloat16), 2,
                                jnp.float32, pallas_interpret())


# ---- values an entry, pushed onto the rows ----------------------------------
# ``zeros(rows).at[layout.rid].add(val)``: the lookup turned round.  A level
# of the sparse tree routes its rows by it: the entries of the features that
# the level's nodes split on carry their row's decision, every other lane 0,
# and the rows read it out of a table that the kernel fills in VMEM.  The
# same two one-hots, contracted over the entries where the lookup contracts
# over the rows; a sub-tile whose lanes are all 0 is not visited, so a level
# costs what the runs of its split features cost, not what 2.18e8 lanes do:
# at the Bosch cell's layout 7.9 ms where one station's seven runs carry,
# 36.6 where 80 dense features' do, 110.9 with every sub-tile live (350,889
# chunk visits of 306 bundles, 1.0 ns a bundle: the lookup's rate), where
# the bisection a row it replaces took 0.64 s a level of 1.18 M rows (my
# chip run, PR 47).

# The most rows one push call takes: its float32 table lies whole in VMEM,
# 4 B a row, and is given 32 MiB of a v5e's 128; the call's limit is the
# table and 16 MiB more for the blocks of row ids and values (2 x 128 KiB
# each) and a chunk visit's temporaries.  8,388,608, what one plane of the
# lookup takes (`ENTRY_LOOKUP_PLANE_ROWS`).
_PUSH_TABLE_BYTES = 32 << 20
ROUTE_PUSH_ROWS = _PUSH_TABLE_BYTES // 4

def run_spans(cspan: jax.Array, fstart: jax.Array, features: jax.Array
              ) -> jax.Array:
    """A layout's ``cspan`` on the sub-tiles that hold an entry of one of
    ``features`` ([n] feature ids; ``fstart``: where each feature's run of
    lanes begins), ``_EMPTY_SPAN`` on every other: n compares a sub-tile,
    fused into their reduction.  A run that is empty marks the sub-tile it
    would lie in, which costs that sub-tile's visit and changes nothing."""
    tile = jnp.arange(cspan.shape[0], dtype=jnp.int32)
    first = fstart[features] // _NNZ_TILE
    last = (fstart[features + 1] - 1) // _NNZ_TILE
    live = jnp.any((tile >= first[:, None]) & (tile <= last[:, None]), axis=0)
    return jnp.where(live, cspan, _EMPTY_SPAN)


def _entry_push_kernel(span_ref, rid_ref, val_ref, out_ref):
    """One grid step: ``_LOOKUP_STEP_TILES`` sub-tiles of ``_NNZ_TILE`` entry
    lanes, as `_entry_lookup_kernel` takes them.  A row id is ``hi * 128 +
    lo``, the table lies ``T[lo, hi]`` whole in VMEM for the call, and for a
    sub-tile's entries ``e`` and each chunk ``c`` of 128 ``hi`` values that
    its span holds

        T[lo, c*128 + h] += sum_e [lo_e == lo] * val_e * [hi_e == c*128 + h]

    one ``[128, 1024] . [1024, 128]`` bfloat16 MXU pass into float32, both
    operands a sublane iota compared with a lane-broadcast row, the entries
    on the lane axis of both as they arrive.  ``val`` is a small integer
    (a bfloat16 as it stands) and the caller sees to it that a row receives
    from one lane at most, so every sum has one term that is not zero and
    the table is exact.  A sub-tile with an empty span costs its scalar
    read and a branch."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = jax.lax.broadcasted_iota(jnp.int32, (_LOOKUP_LO, _NNZ_TILE), 0)

    def sub_tile(j, carry):
        span = span_ref[0, j]
        first, last = span & 0xFFFF, span >> 16

        @pl.when(first <= last)
        def _visit():
            lanes = pl.ds(pl.multiple_of(j * _NNZ_TILE, _NNZ_TILE), _NNZ_TILE)
            rid = rid_ref[:, lanes]                             # [1, tile]
            hi = rid >> 7
            val = val_ref[:, lanes].astype(jnp.float32)
            at_lo = ids == jnp.broadcast_to(rid & (_LOOKUP_LO - 1), ids.shape)
            a = jnp.where(at_lo, jnp.broadcast_to(val, ids.shape),
                          0.0).astype(jnp.bfloat16)             # [lo, tile]

            def chunk(c, carry):
                start = pl.multiple_of(c * 128, 128)
                hit = ids == jnp.broadcast_to(hi - start, ids.shape)
                out_ref[:, pl.ds(start, 128)] += jax.lax.dot_general(
                    a, jnp.where(hit, 1.0, 0.0).astype(jnp.bfloat16),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [lo, h]
                return carry

            jax.lax.fori_loop(first, last + 1, chunk, None)

        return carry

    jax.lax.fori_loop(0, _LOOKUP_STEP_TILES, sub_tile, None)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _entry_push_pallas(rid: jax.Array, span: jax.Array, val: jax.Array,
                       rows: int, interpret: bool) -> jax.Array:
    """rid, val: [nnz_pad] int32 (a multiple of ``_NNZ_TILE``); span: rid's
    `_chunk_spans`, or ``_EMPTY_SPAN`` on a sub-tile to leave out.  Returns
    [rows] float32: ``zeros(rows).at[rid].add(val)`` over the lanes of the
    sub-tiles whose span is not empty, exactly."""
    nnz_pad = rid.shape[0]
    tiles = nnz_pad // _NNZ_TILE
    steps = pl.cdiv(tiles, _LOOKUP_STEP_TILES)
    block = _LOOKUP_STEP_TILES * _NNZ_TILE
    cols = pl.cdiv(rows, _LOOKUP_CHUNK) * 128       # whole chunks, as T's
    with jax.named_scope("ops.lookup_layout"):
        span3 = jnp.pad(span, (0, steps * _LOOKUP_STEP_TILES - tiles),
                        constant_values=_EMPTY_SPAN
                        ).reshape(steps, 1, _LOOKUP_STEP_TILES)
    table_t = pl.pallas_call(
        _entry_push_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((None, 1, _LOOKUP_STEP_TILES), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((_LOOKUP_LO, cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LOOKUP_LO * cols * 4 + (16 << 20)),
        interpret=interpret,
        name=ENTRY_PUSH_KERNEL,
    )(span3, rid.reshape(1, nnz_pad), val.reshape(1, nnz_pad))
    with jax.named_scope("ops.lookup_layout"):
        # T[lo, hi] back to table[hi * 128 + lo]
        return table_t.T.reshape(-1)[:rows]


def route_push_engages(rows_ascend: bool, rows: int) -> bool:
    """Whether a level's routing takes `push_to_rows`, read off what the
    code can see as `entry_lookup_engages` is: a row holds a feature once
    and row ids ascend within every run (a sub-tile's lanes then name few
    chunks, and a row receives from one lane), the kernel is compiled for a
    TPU, and the float32 table of ``rows`` fits its share of VMEM."""
    return (rows_ascend and not pallas_interpret()
            and rows <= ROUTE_PUSH_ROWS)


def push_to_rows(rid: jax.Array, span: jax.Array, val: jax.Array, rows: int
                 ) -> jax.Array:
    """``zeros(rows).at[rid].add(val)`` as float32, by the push kernel
    (interpreted off the chip, where only tests come here).  rid: a
    `SparseHistLayout`'s row ids; span: its ``cspan``, or `run_spans` of it
    where ``val`` is 0 outside some features' runs; val: [nnz] int32 of
    magnitude at most 256, 0 on every lane that has nothing to say (the
    padding lanes among them), and not 0 on one lane a row at most."""
    return _entry_push_pallas(rid, span, val, rows, pallas_interpret())


# ---- bin codes, made on the feature-sorted lanes -----------------------------
# ``1 + #{c: cuts[f, c] <= value}`` an entry.  In entry order every lane has
# another feature, so XLA bins by a bisection of eight gathers an entry
# (20.2 s at the Bosch cell's 2.18e8 entries) or by two sorts of its own
# (2.9 s; `models/gbdt.py:_bin_by_sort`).  Straight after the layout's sort a
# sub-tile of 1,024 lanes holds one feature (213,060 sub-tiles and 968 run
# boundaries there), so that feature's cuts are one column of a table that
# lies in VMEM, and the count is one pass of compares on the VPU.

# The most the cuts may take of VMEM for a call, as a float32 table
# ``[cuts a feature padded to 8, features + 1 padded to 128]``: 16 MiB of a
# v5e's 128, the lookup's share (`_LOOKUP_TABLE_BYTES`).  968 features x 254
# cuts are 1 MiB; 256 bins go up to 16,383 features.
_BIN_TABLE_BYTES = 16 << 20


def _bin_table_shape(num_features: int, num_cuts: int) -> tuple[int, int]:
    """Rows and columns of the kernel's cuts table: a feature's cuts down
    the sublanes, the features and the filler run's column along the lanes."""
    return pl.cdiv(num_cuts, 8) * 8, pl.cdiv(num_features + 1, 128) * 128


def _bin_runs_kernel(nb: int, f1: int, sharded: bool, span_ref, rstart_ref,
                     val_ref, cuts_ref, out_ref):
    """One grid step: ``_LOOKUP_STEP_TILES`` sub-tiles of ``_NNZ_TILE`` value
    lanes, as `_entry_lookup_kernel` takes them.  ``span_ref[:, j]`` names the
    first and the last run that sub-tile ``j`` holds a lane of, ``rstart_ref``
    the lane at which each run begins.  For a run ``r`` of feature ``f``

        count[e] = sum_c [cuts[f, c] <= value[e]]
        gkey[e]  = f * nb + 1 + count[e]      on the lanes of [rstart[r],
                                              rstart[r + 1])

    one pass a run: the feature's column of the table, picked by a lane
    one-hot and summed over the lanes (one term is not zero: the column as it
    stands, infinities too), is compared eight cuts a time with the values,
    which lie on the lanes and are broadcast down the sublanes; float32
    compares, counted in int32.  The table's filler is NaN, at or below
    nothing.  A lane of no run, and of a shard's last run, which is its
    filler, keeps -1."""
    c_rows = cuts_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _NNZ_TILE), 1)
    column = jax.lax.broadcasted_iota(jnp.int32, (c_rows, 128), 1)
    first_lane = pl.program_id(0) * (_LOOKUP_STEP_TILES * _NNZ_TILE)

    def sub_tile(j, carry):
        lanes = pl.ds(pl.multiple_of(j * _NNZ_TILE, _NNZ_TILE), _NNZ_TILE)
        at = lane + (first_lane + j * _NNZ_TILE)
        value = jnp.broadcast_to(val_ref[:, lanes], (8, _NNZ_TILE))

        def run(r, gkey):
            f = jax.lax.rem(r, f1) if sharded else r
            start = pl.multiple_of(f // 128 * 128, 128)
            cuts = jnp.sum(jnp.where(column == f - start,
                                     cuts_ref[:, pl.ds(start, 128)], 0.0),
                           axis=1, keepdims=True)               # [c_rows, 1]
            count = jnp.zeros((8, _NNZ_TILE), jnp.int32)
            for c in range(0, c_rows, 8):
                count += jnp.where(cuts[c:c + 8] <= value, 1, 0)
            code = 1 + jnp.sum(count, axis=0, keepdims=True)
            mine = ((at >= rstart_ref[r]) & (at < rstart_ref[r + 1])
                    & (f < f1 - 1))
            return jnp.where(mine, f * nb + code, gkey)

        out_ref[:, lanes] = jax.lax.fori_loop(
            span_ref[0, j], span_ref[1, j] + 1, run,
            jnp.full((1, _NNZ_TILE), -1, jnp.int32))
        return carry

    jax.lax.fori_loop(0, _LOOKUP_STEP_TILES, sub_tile, None)


@functools.partial(jax.jit,
                   static_argnames=("nb", "num_shards", "interpret"))
def _bin_runs_pallas(value: jax.Array, rstart: jax.Array, cuts: jax.Array,
                     nb: int, num_shards: int, interpret: bool) -> jax.Array:
    """value: [lanes] float32, a multiple of ``_NNZ_TILE``, feature-sorted a
    shard; rstart: [num_shards * (F + 1) + 1] int32, non-decreasing: the lane
    at which each (shard, feature) run begins, a shard's last run being its
    filler, and last ``lanes``; cuts: [F, C] float32, non-decreasing along C.
    Returns [lanes] int32: ``f * nb + 1 + #{c: cuts[f, c] <= value}`` on a
    feature's run, -1 on every other lane."""
    lanes = value.shape[0]
    num_features, num_cuts = cuts.shape
    tiles = lanes // _NNZ_TILE
    steps = pl.cdiv(tiles, _LOOKUP_STEP_TILES)
    block = _LOOKUP_STEP_TILES * _NNZ_TILE
    c_rows, f_cols = _bin_table_shape(num_features, num_cuts)
    table = jnp.pad(cuts.astype(jnp.float32).T,
                    ((0, c_rows - num_cuts), (0, f_cols - num_features)),
                    constant_values=jnp.nan)
    # the runs that hold a sub-tile's first and last lane; a sub-tile past
    # the last, in the last step: no run
    lo = jnp.arange(tiles, dtype=jnp.int32) * _NNZ_TILE
    first = jnp.searchsorted(rstart, lo, side="right") - 1
    last = jnp.searchsorted(rstart, lo + (_NNZ_TILE - 1), side="right") - 1
    none = (0, steps * _LOOKUP_STEP_TILES - tiles)
    span = jnp.stack(
        [jnp.pad(first, none, constant_values=1).reshape(steps, -1),
         jnp.pad(last, none, constant_values=0).reshape(steps, -1)],
        axis=1).astype(jnp.int32)               # [steps, 2, sub-tiles a step]
    return pl.pallas_call(
        functools.partial(_bin_runs_kernel, nb, num_features + 1,
                          num_shards > 1),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((None, 2, _LOOKUP_STEP_TILES), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, lanes), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=table.size * 4 + (16 << 20)),
        interpret=interpret,
        name=BIN_RUNS_KERNEL,
    )(span, rstart, value.reshape(1, lanes), table).reshape(lanes)


def layout_bin_engages(cuts_shape: tuple, num_features: int, lanes: int,
                       num_shards: int) -> bool:
    """Whether `sparse_hist_layout` is given the entries' values and bins
    them after its sort, read off what the code can see as
    `entry_lookup_engages` is: the cuts are these features', the kernel is
    compiled for a TPU, the cuts' table fits its share of VMEM, and the runs
    are not thinner than a sub-tile on the whole.  The kernel makes a pass of
    compares a sub-tile and one more for every run that begins inside one,
    an empty run too: with no more runs than the ``lanes`` that the sort is
    given hold sub-tiles, that is under two passes a sub-tile of those lanes
    (the Bosch cell: 213,060 sub-tiles, 969 runs).  Known before the sort, so
    that where it says no the entries are binned in entry order first, as
    they always were."""
    if len(cuts_shape) != 2 or cuts_shape[0] != num_features:
        return False
    c_rows, f_cols = _bin_table_shape(num_features, cuts_shape[1])
    return (not pallas_interpret()
            and c_rows * f_cols * 4 <= _BIN_TABLE_BYTES
            and num_shards * (num_features + 1) <= lanes // _NNZ_TILE)


def segment_sum(contrib: jax.Array, row_id: jax.Array, num_segments: int,
                force: str | None = None) -> jax.Array:
    """Segment-sum with selectable backend.

    contrib: [nnz] or [nnz, L] (multi-lane statistics share one pass —
    the key/one-hot work is amortized over the lanes).
    force: None/"xla" -> jax.ops.segment_sum (scatter-add);
           "pallas"   -> the tiled one-hot contraction kernel above
                         (interpret mode off-TPU; accumulates in f32,
                         result cast back to contrib's dtype so the two
                         backends stay drop-in interchangeable).
    """
    check_force(force, "segment-sum backend")
    if force == "pallas":
        out = _segment_sum_pallas_diff(contrib, row_id, num_segments,
                                       pallas_interpret())
        return out.astype(contrib.dtype)
    return jax.ops.segment_sum(contrib, row_id, num_segments=num_segments)


# ---- dense histogram: tiling plan and the exact bfloat16 split --------------


@dataclasses.dataclass(frozen=True)
class _HistPlan:
    """How one ``_histogram_gh_pallas`` call is tiled; static, from shapes."""
    nb: int       # key lanes a feature: the power of 2 >= num_bins (>= 64)
    w: int        # key lanes a tile
    fpt: int      # whole features a tile (when q == 1)
    q: int        # tiles a feature (when fpt == 1)
    num_kt: int   # key tiles in all, whole groups of ``tiles``
    live_kt: int  # the first of them that hold a feature; the rest do no work
    n_pad: int    # node columns, padded to 8
    parts: int    # bfloat16 parts that are rows of one dot: 3, else 1
    tiles: int    # key tiles a grid step: all of them, else a power of 2
    fb: int       # feature rows in a block of bins


def _hist_plan(num_features: int, num_bins: int, n_nodes: int) -> _HistPlan:
    """One algorithm; its tiling is chosen by the static shapes alone.

    Keys tile in ``w`` lanes, so bins are laid out on a power-of-2 stride
    ``nb`` >= num_bins: either several whole features per tile (``fpt``) or
    several tiles per feature (``q``).  Bin codes < num_bins never touch the
    padded lanes, which the caller slices off.  The stride has a floor so
    that a tile's unrolled feature loop stays short (tiny num_bins once
    unrolled 256 compare bodies and crashed the TPU compiler outright).  A
    tile is one feature wide where it can be: on a v5e, at 10.5M rows x 28
    features x 256 bins and one node, 256-lane tiles took 52.7 ms a level,
    512-lane ones 58.0, 128-lane slices 205 (my chip runs, PR 28).

    A grid step takes as many key tiles as its out block ``[M, tiles * w]``
    and A^T ``[3 * 2 * n_pad, rows a step]`` leave it (``_HIST_STEP_BYTES``,
    ``_HIST_STEP_TILES``): A^T is built once a step and the bins are read
    once a step.  While that is every key tile, the grid is (1, row tiles)
    and the bins of all F features are in the block.  Past that, key tiles
    return to the grid, outermost, in groups of at most 8 features' tiles,
    a power of two, because bins then stream in 8-feature blocks (the
    smallest legal sublane tile): a group's features never straddle a
    block, and the kernel indexes inside it with pl.ds.  ``num_kt`` is
    rounded up to whole groups; the padding tiles past ``live_kt`` (67
    features of 256 bins: 5 of 72) make the last group short: the kernel
    does nothing for them, and their keys, zeros, are sliced off."""
    nb = max(1 << max(num_bins - 1, 1).bit_length(), _HIST_MIN_STRIDE)
    w = min(max(nb, 256), 512)
    fpt, q = (w // nb, 1) if nb <= w else (1, nb // w)
    num_kt = live_kt = pl.cdiv(num_features * nb, w)
    n_pad = pl.cdiv(n_nodes, 8) * 8
    parts = 3 if n_pad <= _HIST_STACK_NODES else 1

    def fits(tiles):
        return tiles <= _HIST_STEP_TILES and (
            2 * parts * n_pad * tiles * w * 4
            + 6 * n_pad * _HIST_ROW_TILE * 2) <= _HIST_STEP_BYTES

    if fits(num_kt):
        tiles, fb = num_kt, num_features
    else:
        tiles, fb = 8 * q // fpt, min(num_features, 8)
        while tiles > 1 and not fits(tiles):
            tiles //= 2
        num_kt = pl.cdiv(num_kt, tiles) * tiles
    return _HistPlan(nb=nb, w=w, fpt=fpt, q=q, num_kt=num_kt,
                     live_kt=live_kt, n_pad=n_pad, parts=parts, tiles=tiles,
                     fb=fb)


def hist_dead_key_tiles(num_features: int, num_bins: int,
                        n_nodes: int) -> int:
    """Padding key tiles one dense kernel call of these shapes is planned
    with and does no work for (`_hist_plan`: ``num_kt - live_kt``)."""
    p = _hist_plan(num_features, num_bins, n_nodes)
    return p.num_kt - p.live_kt


def _split_bf16x3(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """float32 ``x`` as three float32 arrays that are each exactly a
    bfloat16 and sum to ``x`` bit for bit: the top 16 bits of ``x``, of what
    is left, and the rest (8 significant bits each, 24 in all).  By masking,
    not by rounding: a cast to bfloat16 and back is what XLA may elide as
    excess precision, and rounding the largest float32 up would overflow.
    Exact for zeros and for every finite magnitude from 2**-103 (9.9e-32)
    up; below that the last part is a denormal float32, which the chip
    flushes to zero (an error of at most 2**-126 a value)."""
    def top16(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = top16(x)
    mid = top16(x - hi)
    return hi, mid, x - hi - mid
