"""DeviceStagingIter — the TPU-native piece the reference never had: a
prefetching iterator that turns ragged parsed RowBlocks into *static-shape*
padded CSR batches resident in TPU HBM.

Design (SURVEY.md §7 step 7):
  * the parse→pack→pad pipeline is NATIVE (cpp/src/data/staged_batcher.h):
    a C++ StagedBatcher packs the parser's RowBlocks straight into pooled
    single-allocation arenas one batch ahead of the consumer; Python wraps
    the arena zero-copy (one buffer owner, one finalizer per batch) and
    releases it back to the native pool when the last array dies;
  * rows are packed to a fixed ``batch_size`` (final short batch zero-padded,
    padding rows carry weight 0 so losses ignore them);
  * nonzeros are padded to the next multiple of ``nnz_bucket`` — a handful of
    distinct shapes total, so XLA compiles a handful of executables instead
    of one per batch (ragged shapes would retrace every step);
  * row membership ships as the CSR row pointer (``row_ptr[batch_size+1]``,
    the reference RowBlock's own offset[] layout) — B+1 ints over the host→
    HBM link instead of nnz ints; COO row ids are derived on device inside
    jit (``PaddedBatch.row_ids``), where they fuse into the consumer;
  * padded nnz slots carry value 0 (and derive row ``batch_size-1``) —
    numerically inert in segment-sum compute;
  * ``num_workers > 1`` fans the PARSE over a native sharded worker pool
    (cpp/src/data/sharded_parser.h): each worker drives an independent
    parser over a small virtual InputSplit part and the blocks re-emerge in
    deterministic part order, so every staged batch is bit-identical to the
    single-worker stream while parse throughput scales with cores;
  * the host side is a two-stage pipeline: a pack-driver thread drains the
    native batcher into a host queue (depth ``prefetch_depth``), and a
    dedicated stager thread turns host batches into device arrays through a
    double-buffered ``device_put`` feed — the host→HBM DMA of batch k+1
    overlaps the device compute of batch k;
  * with a mesh, batches are laid out sharded over the data axis via
    ``jax.make_array_from_process_local_data`` (multi-host: each process
    contributes its local InputSplit shard; single host: plain sharded put);
    ``row_ptr`` and ``num_rows`` are replicated.
"""
from __future__ import annotations

import contextlib
import ctypes
import functools
import logging
import queue
import threading
import time
import weakref
from dataclasses import dataclass, replace as _dc_replace
from typing import Iterator, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .._native import check, lib
from .. import telemetry
from .rowblock import Parser  # noqa: F401  (re-exported convenience)

LOGGER = logging.getLogger("dmlc_core_tpu.staging")


def _observability_scope():
    """Arm the env-configured stall watchdog, the always-on time-series
    sampler, and the tracker metrics pusher for this process (all no-ops
    without their env vars — see ``DMLCTPU_WATCHDOG_DEADLINE_S``,
    ``DMLCTPU_TIMESERIES`` and ``DMLC_TRACKER_METRICS_PORT`` in
    doc/observability.md): every epoch driven through a staging iterator
    becomes job-wide observable without touching user code."""
    try:
        from ..tracker import metrics as _metrics
        _metrics.ensure_pusher()
    except Exception:  # tracker package is optional at data-plane runtime
        LOGGER.debug("tracker metrics pusher unavailable", exc_info=True)
    scope = contextlib.ExitStack()
    scope.enter_context(telemetry.timeseries_from_env())
    scope.enter_context(telemetry.watchdog_from_env())
    return scope


def _staged_iter(produce, prefetch: int, depth_gauge: Optional[str] = None,
                 device_feed: bool = False):
    """Drive ``produce(emit)`` on a background thread, yielding emitted items
    up to ``prefetch`` ahead of the consumer.

    ``emit(item) -> bool`` returns False when the consumer has gone away
    (break / generator close): the producer must return promptly, releasing
    any native cursor locks — a plain blocking ``q.put`` here deadlocked
    abandoned iterators (producer parked in put holding the cursor lock).
    Producer exceptions are re-raised in the consumer.

    ``depth_gauge`` names a telemetry gauge kept at the queue's occupancy —
    pipeline state for the flight recorder (a stall with the gauge pinned at
    ``prefetch`` means the consumer wedged; pinned at 0, the producer).

    ``device_feed`` marks the last queue of a feed, the one whose items are
    device batches: the consumer's blocked time in its ``get`` is the span
    ``feed.wait`` and the counter ``h2d.consumer_wait_us`` — what the
    training loop waited for the whole pipeline (host queues are internal
    hand-offs, already counted as ``h2d.wait_us`` by their own consumer).
    Each of its items rides with the time its ``put`` returned, so that the
    consumer can record the span ``feed.handoff`` — put returned to get
    returned, the latency of the queue itself (``_feed_get``, below).
    """
    q: queue.Queue = queue.Queue(maxsize=max(prefetch, 1))
    sentinel = object()
    stop = threading.Event()
    error: list = []

    def emit(item) -> bool:
        if device_feed:
            item = _Handoff(item)
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                if device_feed:
                    item.put_us = telemetry.now_us()
                if depth_gauge is not None:
                    telemetry.gauge_set(depth_gauge, q.qsize())
                return True
            except queue.Full:
                continue
        return False

    def runner():
        try:
            produce(emit)
        except BaseException as e:  # relayed to consumer
            error.append(e)
        finally:
            while True:
                try:
                    q.put(sentinel, timeout=0.1)
                    break
                except queue.Full:
                    # only drop queued batches once the consumer signalled
                    # it is gone — a full queue at normal end-of-stream just
                    # means the consumer has not caught up yet
                    if stop.is_set():
                        try:
                            q.get_nowait()
                        except queue.Empty:
                            pass

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    reached_end = False
    try:
        while True:
            item = _feed_get(q) if device_feed else q.get()
            if depth_gauge is not None:
                telemetry.gauge_set(depth_gauge, q.qsize())
            if item is sentinel:
                reached_end = True
                break
            yield item
        if error:
            raise error[0]
    finally:
        stop.set()
        t.join(timeout=10.0)
        if error and not reached_end:
            # the consumer broke out before draining: surface the producer
            # failure instead of swallowing it in generator close
            LOGGER.warning("staging producer failed after consumer break: %r",
                           error[0])


def _parallel_parts_iter(open_part, num_virtual: int, num_workers: int,
                         reorder: bool, max_buffered: int):
    """Python-level mirror of the native sharded parse pool, for cursors
    that only exist per-part at the Python layer (RecordBatcher).

    ``open_part(j)`` yields the items of virtual part ``j``; workers claim
    parts from a shared cursor and buffer items per part.  reorder=True
    yields strictly in part order (the part currently being drained may
    always buffer, so the in-order drain never deadlocks the pool);
    reorder=False yields in arrival order.  Worker exceptions re-raise in
    the consumer; generator close stops the workers promptly.
    """
    cond = threading.Condition()
    parts: dict[int, list] = {}
    done: set = set()
    state = {"next_claim": 0, "emit": 0, "buffered": 0, "stop": False,
             "error": None}

    def worker():
        try:
            while True:
                with cond:
                    if (state["stop"] or state["error"] is not None
                            or state["next_claim"] >= num_virtual):
                        return
                    j = state["next_claim"]
                    state["next_claim"] += 1
                    parts.setdefault(j, [])
                    cond.notify_all()
                it = open_part(j)
                try:
                    for item in it:
                        with cond:
                            while not (state["stop"]
                                       or state["error"] is not None
                                       or state["buffered"] < max_buffered
                                       or (reorder and j == state["emit"])):
                                cond.wait()
                            if state["stop"] or state["error"] is not None:
                                return
                            parts[j].append(item)
                            state["buffered"] += 1
                            cond.notify_all()
                finally:
                    it.close()
                with cond:
                    done.add(j)
                    cond.notify_all()
        except BaseException as e:
            with cond:
                if state["error"] is None:
                    state["error"] = e
                cond.notify_all()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(num_workers, 1))]
    for t in threads:
        t.start()
    try:
        while True:
            with cond:
                while True:
                    if state["error"] is not None:
                        raise state["error"]
                    if reorder:
                        j = state["emit"]
                        if j >= num_virtual:
                            return
                        q = parts.get(j)
                        if q:
                            item = q.pop(0)
                            state["buffered"] -= 1
                            cond.notify_all()
                            break
                        if q is not None and j in done:
                            del parts[j]
                            done.discard(j)
                            state["emit"] += 1
                            # a worker parked on the full-buffer wait may
                            # have just become the emit part (its wait
                            # exemption turned true): wake it or the pool
                            # wedges with producers and consumer all asleep
                            cond.notify_all()
                            continue
                    else:
                        got = next((j for j, q in parts.items() if q), None)
                        if got is not None:
                            item = parts[got].pop(0)
                            state["buffered"] -= 1
                            cond.notify_all()
                            break
                        drained = [j for j in parts if j in done]
                        for j in drained:
                            del parts[j]
                            done.discard(j)
                        if drained:
                            cond.notify_all()
                        if state["next_claim"] >= num_virtual and not parts:
                            return
                    cond.wait()
            yield item
    finally:
        with cond:
            state["stop"] = True
            cond.notify_all()
        for t in threads:
            t.join(timeout=10.0)


def _pick_virtual_parts(total_bytes: int, num_parts: int,
                        target_bytes: int = 8 << 20,
                        lo: int = 8, hi: int = 1024) -> int:
    """Virtual part count for a Python-level worker pool — same formula as
    the native ShardedParser (a pure function of dataset size and
    num_parts, NEVER of num_workers, so ranks with different worker counts
    still cover the dataset exactly once)."""
    per_part = total_bytes // max(num_parts, 1)
    return int(min(max((per_part + target_bytes - 1) // target_bytes, lo), hi))


def _replicated_sharding(sharding):
    """Fully-replicated sharding on the same mesh (best effort for exotic
    non-Named sharding types: passes the data sharding through)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, PartitionSpec())
    return sharding


def _device_put_maybe_donated(leaves, shardings=None, donate: bool = True):
    """``jax.device_put`` of a staging pytree, donating the host buffers.

    Donation lets the runtime consume the staged leaves instead of
    defensively copying them — the last host-side copy on the zero-copy
    bincache hit path (doc/binned_cache.md).  The staging contract already
    guarantees the leaves are never touched again after the put (the
    repacker hands each batch fresh arena views), so donation is safe;
    arena recycling stays correct because jax holds the leaf references
    until the async transfer completes, which pins the arena finalizer.
    ``donate`` is off under ``DMLCTPU_BINCACHE_DONATE=0``."""
    return jax.device_put(leaves, shardings, donate=donate)


def _multihost_rounds(native, payload_len: int, pack):
    """Coordinate one epoch of multi-host staging: yield (local, gathered)
    per GLOBAL batch, where ``local`` is this process's item (None once
    exhausted) and ``gathered`` is the (process_count, payload_len+1) int64
    allgather of every process's ``[status, payload...]``.

    Status 1 = has data (payload valid, filled by ``pack(local, out)``),
    0 = exhausted (keeps participating so collective counts stay matched),
    -1 = local producer failure (peers raise instead of wedging in their
    next collective; the failing process re-raises its original error).
    Ends when every process reports exhausted.  Must run on the consumer
    thread: collectives issued from a prefetch thread race the consumer's
    own jit collectives and deadlock (cross-process collective order must
    match program order on every process).
    """
    from jax.experimental import multihost_utils
    local_end = False
    try:
        while True:
            local, local_err = None, None
            if not local_end:
                try:
                    local = next(native, None)
                    local_end = local is None
                except Exception as e:  # parse/pack failed on this process
                    local_err, local_end = e, True
            packed = np.zeros(payload_len + 1, np.int64)
            if local_err is not None:
                packed[0] = -1
            elif local is not None:
                packed[0] = 1
                pack(local, packed[1:])
            gathered = np.asarray(multihost_utils.process_allgather(packed))
            if local_err is not None:
                raise local_err
            failed = np.nonzero(gathered[:, 0] < 0)[0]
            if failed.size:
                raise RuntimeError(
                    "multi-host staging failed on process(es) "
                    f"{failed.tolist()}; aborting epoch on all processes")
            if gathered[:, 0].sum() == 0:
                return  # every process exhausted; collective counts matched
            yield local, gathered
    finally:
        native.close()


@functools.partial(jax.jit, static_argnums=1)
def csr_row_ids(row_ptr: jax.Array, nnz_pad: int) -> jax.Array:
    """COO row id of each of ``nnz_pad`` lanes under the CSR ``row_ptr``
    (``[batch + 1]``, non-decreasing, ``row_ptr[0] == 0``): the count of
    rows 1..batch that start at or before the lane, i.e.
    ``searchsorted(row_ptr, lane, side="right") - 1``, clamped to the last
    row for the padding lanes.  One mark a row scattered at its first lane
    and one running sum over the lanes: O(lanes), where the binary search
    is a gather a lane a round — at 2.18e8 lanes over 1.18M rows 0.06 s
    against 105.6 s on a v5e (PERF.md, PR 27).  One program, so that the
    scope reaches the device trace from an op-by-op caller too."""
    with jax.named_scope("batch.row_ids"):
        batch = row_ptr.shape[0] - 1
        starts = jnp.zeros(nnz_pad, jnp.int32).at[row_ptr[1:]].add(
            1, mode="drop")     # a row that starts past the last lane
        return jnp.minimum(jnp.cumsum(starts), batch - 1).astype(jnp.int32)


@dataclass
class PaddedBatch:
    """Static-shape CSR batch (a pytree; arrays live on device after staging).

    Row r's nonzeros span ``index/value[row_ptr[r]:row_ptr[r+1]]`` — the
    reference RowBlock's offset[] layout (include/dmlc/data.h:74).  Padding
    rows have ``weight == 0`` and empty spans; padding nonzero lanes (k >=
    row_ptr[batch_size]) have ``value == 0``.  Use :meth:`row_ids` inside a
    jitted consumer for the flattened COO view.

    Multi-host batches (assembled from per-process nnz_max segments) keep
    every invariant above except one: each segment's pad gap falls inside
    the span of that segment's last row — a weight-0 padding row unless the
    process batch was full, in which case a real row's span gains trailing
    (index 0, value 0) pairs.  Value-weighted reductions are unaffected;
    consumers counting ``row_ptr[r+1]-row_ptr[r]`` should mask by value
    or weight in multi-host mode.
    """

    label: jax.Array    # f32 [batch]
    weight: jax.Array   # f32 [batch]
    row_ptr: jax.Array  # i32 [batch + 1] CSR row pointer
    index: jax.Array    # i32 [nnz_pad] column ids
    value: jax.Array    # f32 [nnz_pad]
    num_rows: jax.Array  # i32 [] true (unpadded) row count
    field: Optional[jax.Array] = None  # i32 [nnz_pad] (libfm)
    qid: Optional[jax.Array] = None    # i32 [batch] query ids (ranking)

    @property
    def batch_size(self) -> int:
        return self.label.shape[0]

    def row_ids(self) -> jax.Array:
        """COO row id per nonzero, derived on device (fuses under jit).

        Padding lanes map to row ``batch_size - 1`` (their value is 0, so
        segment reductions are unaffected).
        """
        return csr_row_ids(self.row_ptr, self.index.shape[0])


jax.tree_util.register_dataclass(
    PaddedBatch,
    data_fields=["label", "weight", "row_ptr", "index", "value", "num_rows",
                 "field", "qid"],
    meta_fields=[])


def bucket_pow2(n: int, lo: int = 1, hi: Optional[int] = None) -> int:
    """Smallest power of two >= max(n, lo), clamped to ``hi`` — but never
    below ``n`` itself (a ceiling must not truncate real data).

    The bucketing rule shared by the serving request packer and the
    geometry-stable predict paths: padding every ad-hoc batch up to a
    pow-2 (rows, nnz) bucket keeps the set of distinct jit geometries
    logarithmic in the request-size range, so XLA compiles each shape
    exactly once instead of retracing per request.
    """
    n = int(n)
    b = 1 << max(0, max(n, int(lo)) - 1).bit_length()
    if hi is not None:
        b = min(b, int(hi))
    return max(b, n)


def pad_batch_to_bucket(batch, row_bucket: Optional[int] = None,
                        nnz_bucket: Optional[int] = None,
                        min_rows: int = 1, min_nnz: int = 8):
    """Pad a :class:`PaddedBatch` (or pre-binned ``BinnedBatch``) up to a
    pow-2 (rows, nnz) bucket geometry, preserving every padding invariant.

    Added rows carry ``weight == 0`` and empty spans (``row_ptr`` repeats
    its last value); added nonzero lanes carry ``value == 0`` (PaddedBatch)
    or ``emask == False`` (BinnedBatch), so segment reductions and sparse
    forest routing are unaffected — real-row outputs are bit-identical to
    scoring the unpadded batch.  ``num_rows`` is left alone.  Explicit
    ``row_bucket``/``nnz_bucket`` override the pow-2 rule (clamped up to
    the real extent, never down).  Returns ``batch`` unchanged when it is
    already on-bucket.
    """
    rows = batch.batch_size
    nnz = int(batch.index.shape[0])
    rb = (bucket_pow2(rows, min_rows) if row_bucket is None
          else max(int(row_bucket), rows))
    nb = (bucket_pow2(nnz, min_nnz) if nnz_bucket is None
          else max(int(nnz_bucket), nnz))
    if rb == rows and nb == nnz:
        return batch
    pr, pn = rb - rows, nb - nnz
    kw = {}
    if pr:
        kw["label"] = jnp.pad(batch.label, (0, pr))
        kw["weight"] = jnp.pad(batch.weight, (0, pr))
        kw["row_ptr"] = jnp.concatenate(
            [batch.row_ptr,
             jnp.full((pr,), batch.row_ptr[-1], batch.row_ptr.dtype)])
        if batch.qid is not None:
            kw["qid"] = jnp.pad(batch.qid, (0, pr))
    if pn:
        kw["index"] = jnp.pad(batch.index, (0, pn))
        if hasattr(batch, "ebin"):
            kw["ebin"] = jnp.pad(batch.ebin, (0, pn))
            kw["emask"] = jnp.pad(batch.emask, (0, pn))
        else:
            kw["value"] = jnp.pad(batch.value, (0, pn))
            if batch.field is not None:
                kw["field"] = jnp.pad(batch.field, (0, pn))
    return _dc_replace(batch, **kw)


class _Handoff:
    """A device batch on its way through the feed's last queue: the stager's
    ``emit`` stamps ``put_us`` once its ``put`` has returned (0 until then:
    a consumer that was waiting can have the batch before the stager runs
    again, and its hand-off then took no time worth a span)."""
    __slots__ = ("item", "put_us")

    def __init__(self, item):
        self.item = item
        self.put_us = 0


def _feed_get(q: queue.Queue):
    """The consumer's ``get`` on the queue of device batches, with what the
    ring and the registry are told of it: ``feed.wait`` (both sinks; counter
    ``h2d.consumer_wait_us`` over the same stretch) under the lineage of the
    batch it got, and ``feed.handoff`` from the stager's ``put`` returning to
    this ``get`` returning.  Returns the batch, or the end-of-stream item.
    (The caller's rebind of its ``item`` drops the previous batch: releasing
    device arrays whose step is still queued took 0.8 ms on a v5e, PERF.md
    PR 37.  It falls after this span and counter, not inside them.)"""
    with telemetry.span("feed.wait", total="h2d.consumer_wait_us") as wait:
        got = q.get()
        got_us = telemetry.now_us()
        batch = isinstance(got, _Handoff)
        if batch:
            wait.lineage = telemetry.lineage(got.item)
    if not batch:
        return got
    put_us = got.put_us or got_us
    telemetry.record_span("feed.handoff", put_us, got_us - put_us,
                          wait.lineage)
    return got.item


class _StagedBatchC(ctypes.Structure):
    _fields_ = [
        ("num_rows", ctypes.c_uint32),
        ("batch_size", ctypes.c_uint64),
        ("nnz_pad", ctypes.c_uint64),
        ("max_index", ctypes.c_int64),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("row_ptr", ctypes.POINTER(ctypes.c_int32)),
        ("index", ctypes.POINTER(ctypes.c_int32)),
        ("value", ctypes.POINTER(ctypes.c_float)),
        ("field", ctypes.POINTER(ctypes.c_int32)),
        ("qid", ctypes.POINTER(ctypes.c_int32)),
    ]


class _StagedBatchOwnedC(ctypes.Structure):
    _fields_ = [
        ("num_rows", ctypes.c_uint32),
        ("batch_size", ctypes.c_uint64),
        ("nnz_pad", ctypes.c_uint64),
        ("max_index", ctypes.c_int64),
        ("batch", ctypes.c_void_p),
        ("arena", ctypes.c_void_p),
        ("arena_bytes", ctypes.c_uint64),
        ("label_off", ctypes.c_uint64),
        ("weight_off", ctypes.c_uint64),
        ("row_ptr_off", ctypes.c_uint64),
        ("index_off", ctypes.c_uint64),
        ("value_off", ctypes.c_uint64),
        ("field_off", ctypes.c_uint64),
        ("qid_off", ctypes.c_uint64),
        ("lineage", ctypes.c_int64),
    ]


_NO_FIELD = (1 << 64) - 1  # field_off sentinel: batch has no field column


def _declare_batcher_sig():
    L = lib()
    if getattr(L, "_staged_batcher_declared", False):
        return L
    L.DmlcTpuStagedBatcherCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_void_p)]
    L.DmlcTpuStagedBatcherCreateEx.argtypes = [
        ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_void_p)]
    L.DmlcTpuStagedBatcherNext.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(_StagedBatchC)]
    L.DmlcTpuStagedBatcherNextOwned.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(_StagedBatchOwnedC)]
    L.DmlcTpuStagedBatchFree.argtypes = [ctypes.c_void_p]
    L.DmlcTpuStagedBatchFree.restype = None
    L.DmlcTpuStagedBatcherBeforeFirst.argtypes = [ctypes.c_void_p]
    L.DmlcTpuStagedBatcherBytesRead.argtypes = [ctypes.c_void_p]
    L.DmlcTpuStagedBatcherBytesRead.restype = ctypes.c_int64
    L.DmlcTpuStagedBatcherFree.argtypes = [ctypes.c_void_p]
    L.DmlcTpuStagedBatcherFree.restype = None
    # live pool retuning (hasattr: tolerate an older .so during rebuilds —
    # set_knobs then degrades to next-epoch-only Python knobs)
    if hasattr(L, "DmlcTpuStagedBatcherSetPoolKnobs"):
        L.DmlcTpuStagedBatcherSetPoolKnobs.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int)]
        L.DmlcTpuStagedBatcherGetPoolKnobs.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int)]
    L._staged_batcher_declared = True
    return L


@dataclass
class RecordBatch:
    """Static-shape packed RecordIO batch (device-resident after staging).

    ``bytes`` is the concatenated payloads zero-padded to ``bytes_cap`` per
    block.  A single-host batch has one block; a multi-host batch has one
    block per process (``blocks == jax.process_count()``), each occupying a
    fixed ``bytes_cap``-sized segment of ``bytes`` and a ``records_cap+1``
    run of ``offsets``.  Use :meth:`spans` for the uniform per-record view:
    record k (k = block*records_cap + j) spans
    ``bytes[starts[k]:ends[k]]``.  Unlike the COO PaddedBatch, byte padding
    must never leak INTO a record's span (appended zeros would corrupt the
    payload), which is why every block boundary is kept exactly.

    Padding records have empty spans; ``record_mask()`` distinguishes real
    records per lane.
    """

    bytes: jax.Array     # u8 [blocks * bytes_cap]
    offsets: jax.Array   # i32 [blocks * (records_cap + 1)]
    num_records: jax.Array  # i32 [] true record count (global total)
    block_num_records: jax.Array  # i32 [blocks] true records per block
    blocks: int = 1      # static: process blocks in this batch

    @property
    def records_cap(self) -> int:
        return self.offsets.shape[0] // self.blocks - 1

    def spans(self):
        """(starts, ends) i32 arrays of shape [blocks * records_cap]; record
        k spans bytes[starts[k]:ends[k]].  Fuses under jit."""
        per = self.offsets.reshape(self.blocks, self.records_cap + 1)
        return per[:, :-1].reshape(-1), per[:, 1:].reshape(-1)

    def record_mask(self) -> jax.Array:
        """bool [blocks * records_cap]: True on real (non-padding) lanes."""
        lane = jnp.arange(self.records_cap, dtype=jnp.int32)
        return (lane[None, :] < self.block_num_records[:, None]).reshape(-1)


jax.tree_util.register_dataclass(
    RecordBatch,
    data_fields=["bytes", "offsets", "num_records", "block_num_records"],
    meta_fields=["blocks"])


class _RecordBatchC(ctypes.Structure):
    _fields_ = [
        ("num_records", ctypes.c_uint32),
        ("records_cap", ctypes.c_uint64),
        ("bytes_cap", ctypes.c_uint64),
        ("bytes_used", ctypes.c_uint64),
        ("bytes", ctypes.POINTER(ctypes.c_char)),
        ("offsets", ctypes.POINTER(ctypes.c_int32)),
    ]


def _declare_record_batcher_sig():
    L = lib()
    if getattr(L, "_record_batcher_declared", False):
        return L
    L.DmlcTpuRecordBatcherCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.POINTER(ctypes.c_void_p)]
    L.DmlcTpuRecordBatcherCreateEx.argtypes = [
        ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p)]
    L.DmlcTpuRecordBatcherNext.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(_RecordBatchC)]
    L.DmlcTpuRecordBatcherBeforeFirst.argtypes = [ctypes.c_void_p]
    L.DmlcTpuRecordBatcherBytesRead.argtypes = [ctypes.c_void_p]
    L.DmlcTpuRecordBatcherBytesRead.restype = ctypes.c_int64
    L.DmlcTpuRecordBatcherFree.argtypes = [ctypes.c_void_p]
    L.DmlcTpuRecordBatcherFree.restype = None
    L._record_batcher_declared = True
    return L


class RecordStagingIter:
    """Stage sharded RecordIO into HBM as fixed-shape packed byte batches.

    The RecordIO analogue of DeviceStagingIter (BASELINE target 2): the
    native RecordBatcher (cpp/src/data/record_batcher.h) reads and packs one
    batch ahead; a background thread device_puts one batch ahead of the
    consumer, so disk read, packing, and H2D DMA all overlap.

    Parameters
    ----------
    uri : recordio dataset URI (same sharding/URI sugar as InputSplit).
    records_cap : max records per batch (offsets array length - 1).
    bytes_cap : byte-buffer capacity per batch (fixed device shape).
    sharding : optional jax sharding for the staged arrays.
    num_workers : reader threads.  > 1 fans the read+pack over a pool of
        per-virtual-part RecordBatcher cursors (Python-level analogue of
        the native sharded parser).  Record ORDER is preserved when
        reorder=True, but batch composition near virtual-part tails may
        differ from the single-worker packing (each part pads its own tail
        batch); consumers that need bit-identical batches across worker
        counts should compare concatenated record streams.
    reorder : yield parts in deterministic order (True) or arrival order.
    prefetch_depth : host batches the pack stage keeps in flight
        (``prefetch`` is the back-compat alias).
    recover : skip corrupt record spans (counted in the
        ``record.corrupt_skipped`` telemetry counter) instead of aborting
        the epoch — doc/robustness.md.
    """

    def __init__(self, uri: str, records_cap: int = 4096,
                 bytes_cap: int = 1 << 22, part: int = 0, num_parts: int = 1,
                 sharding=None, prefetch: int = 2, num_workers: int = 1,
                 reorder: bool = True, prefetch_depth: Optional[int] = None,
                 recover: bool = False, autotune: Optional[bool] = None):
        self._lib = _declare_record_batcher_sig()
        self._handle = ctypes.c_void_p()
        self._recover = bool(recover)
        check(self._lib.DmlcTpuRecordBatcherCreateEx(
            uri.encode(), part, num_parts, records_cap, bytes_cap,
            1 if self._recover else 0, ctypes.byref(self._handle)))
        self._uri = uri
        self._part = part
        self._num_parts = num_parts
        self._sharding = sharding
        self._prefetch = max(prefetch_depth if prefetch_depth is not None
                             else prefetch, 1)
        self._records_cap = records_cap
        self._bytes_cap = bytes_cap
        self._num_workers = max(int(num_workers), 1)
        self._reorder = reorder
        if autotune is None:
            from dmlc_core_tpu import autotune as _at
            autotune = _at.armed()
        self._autotune = bool(autotune)
        self._tuner = None  # lazily attached AutoTuner (see __iter__)
        self._virtual_parts = 0  # resolved lazily on the first parallel epoch
        # Unified byte accounting: every native RecordBatcher — the main
        # handle AND each per-virtual-part parallel cursor — publishes chunk
        # bytes into the process-wide "record.bytes" telemetry counter at
        # the single native counting site (record_batcher.h), so the single
        # and parallel paths share one tally instead of the old hand-rolled
        # _parallel_bytes sum.  The baseline makes bytes_read a
        # per-iterator delta.
        self._telemetry_bytes = telemetry.enabled()
        self._bytes_base = (telemetry.counter_get("record.bytes")
                            if self._telemetry_bytes else 0)
        self._lock = threading.Lock()
        self.batches_staged = 0

    @property
    def bytes_read(self) -> int:
        """Wire bytes consumed since construction (throughput metric).

        Counted process-wide via telemetry, so concurrent RecordStagingIters
        in one process see each other's reads.  With telemetry compiled out
        (DMLCTPU_TELEMETRY=0) this falls back to the main handle's count and
        parallel-worker bytes are not attributed."""
        if self._telemetry_bytes:
            return telemetry.counter_get("record.bytes") - self._bytes_base
        return self._lib.DmlcTpuRecordBatcherBytesRead(self._handle)

    def close(self) -> None:
        # serialize with the producer thread: freeing the native batcher while
        # a Next call is still in flight would be a use-after-free.  Bounded
        # wait — on timeout the producer still owns the cursor, so leak the
        # handle rather than crash it.
        if not self._lock.acquire(timeout=30.0):
            LOGGER.warning("RecordStagingIter.close: producer still busy; "
                           "leaking native handle")
            return
        try:
            handle, self._handle = self._handle, ctypes.c_void_p()
            if handle:
                try:
                    self._lib.DmlcTpuRecordBatcherFree(handle)
                except (AttributeError, TypeError):
                    pass
        finally:
            self._lock.release()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _wrap_host(self, c: _RecordBatchC) -> dict:
        """Host copies of one packed batch (the native buffers are borrowed
        only until the next Next() call)."""
        return {
            "bytes": np.frombuffer(
                ctypes.string_at(c.bytes, int(c.bytes_cap)), dtype=np.uint8),
            "offsets": np.ctypeslib.as_array(
                c.offsets, shape=(int(c.records_cap) + 1,)).copy(),
            "num_records": int(c.num_records),
        }

    def _stage(self, w: dict) -> RecordBatch:
        def put(arr):
            if self._sharding is not None:
                return jax.device_put(arr, self._sharding)
            return jax.device_put(arr)

        batch = RecordBatch(
            bytes=put(w["bytes"]),
            offsets=put(w["offsets"]),
            num_records=jnp.asarray(np.int32(w["num_records"])),
            block_num_records=jnp.asarray(
                np.array([w["num_records"]], np.int32)),
            blocks=1)
        self.batches_staged += 1
        return batch

    # ---- host-side record production ----------------------------------------
    def _resolve_virtual_parts(self) -> int:
        if self._virtual_parts == 0:
            L = self._lib
            h = ctypes.c_void_p()
            check(L.DmlcTpuInputSplitCreate(
                self._uri.encode(), b"", 0, 1, b"recordio", 0, 0, 0,
                ctypes.byref(h)))
            try:
                total = L.DmlcTpuInputSplitTotalSize(h)
            finally:
                L.DmlcTpuInputSplitFree(h)
            self._virtual_parts = _pick_virtual_parts(int(total),
                                                      self._num_parts)
        return self._virtual_parts

    def _open_part(self, j: int):
        """One of THIS worker's virtual parts, on a pool worker thread."""
        yield from self._open_global_part(self._part * self._virtual_parts + j)

    def _open_global_part(self, g: int):
        """One GLOBAL virtual part's packed host batches (g in
        [0, num_parts*V)); shard handoff parses peers' parts by global id
        through the same per-part cursor the pool's re-parse machinery
        uses, so a stolen shard replays deterministically."""
        L = self._lib
        V = self._resolve_virtual_parts()
        h = ctypes.c_void_p()
        check(L.DmlcTpuRecordBatcherCreateEx(
            self._uri.encode(), int(g), self._num_parts * V,
            self._records_cap, self._bytes_cap, 1 if self._recover else 0,
            ctypes.byref(h)))
        try:
            c = _RecordBatchC()
            while check(L.DmlcTpuRecordBatcherNext(h, ctypes.byref(c))) == 1:
                yield self._wrap_host(c)
        finally:
            # bytes flow through the shared "record.bytes" telemetry counter
            # as the cursor reads; nothing to tally here
            L.DmlcTpuRecordBatcherFree(h)

    def host_batches_coordinated(self, epoch: int = 0, client=None,
                                 steal: bool = True) -> Iterator[dict]:
        """Host-side batches under tracker-coordinated shard ownership.

        Registers this worker's virtual parts on the tracker's shard board,
        claims each before parsing, and — once its own list is drained —
        steals pending shards from hosts the tracker has flagged
        (straggler / restarted / stale), keeping job-wide exactly-once
        visitation (see tracker.metrics.coordinated_parts).  ``client``
        defaults to the env contract (DMLC_TRACKER_METRICS_PORT); without a
        tracker this is plain in-order part iteration.
        """
        from dmlc_core_tpu.tracker import metrics as _tracker_metrics
        V = self._resolve_virtual_parts()
        if client is None:
            client = _tracker_metrics.shard_client_from_env(rank=self._part)
        shards = list(range(self._part * V, (self._part + 1) * V))
        yield from _tracker_metrics.coordinated_parts(
            int(epoch), shards, self._open_global_part, client, steal=steal)

    def _produce_host(self, emit) -> None:
        """Drive the native read+pack, emitting host batch dicts."""
        if self._num_workers > 1:
            V = self._resolve_virtual_parts()
            it = _parallel_parts_iter(
                self._open_part, V, self._num_workers, self._reorder,
                max_buffered=self._prefetch + self._num_workers)
            try:
                for w in it:
                    if not emit(w):
                        return
            finally:
                it.close()
            return
        with self._lock:
            check(self._lib.DmlcTpuRecordBatcherBeforeFirst(self._handle))
            c = _RecordBatchC()
            while check(self._lib.DmlcTpuRecordBatcherNext(
                    self._handle, ctypes.byref(c))) == 1:
                if not emit(self._wrap_host(c)):
                    return

    def _iter_multihost(self) -> Iterator[RecordBatch]:
        """Multi-host epoch: every process contributes one fixed
        (bytes_cap,) block per global batch; exact per-block offsets ride
        the coordination allgather (see _multihost_rounds), so no byte of
        padding ever falls inside a record span."""
        nprocs = jax.process_count()
        cap_r, cap_b = self._records_cap, self._bytes_cap
        if nprocs * cap_b > np.iinfo(np.int32).max:
            raise ValueError(
                f"global byte offsets overflow int32: {nprocs} processes x "
                f"bytes_cap={cap_b}; lower bytes_cap below "
                f"{np.iinfo(np.int32).max // nprocs}")

        native = _staged_iter(self._produce_host, self._prefetch,
                              depth_gauge="record.queue_depth")

        def pack(local, out):
            out[0] = local["num_records"]
            out[1:] = local["offsets"]

        repl = _replicated_sharding(self._sharding)
        for local, gathered in _multihost_rounds(native, 1 + cap_r + 1, pack):
            shifts = np.arange(nprocs, dtype=np.int64) * cap_b
            global_offs = (gathered[:, 2:] + shifts[:, None]).reshape(-1)
            block_counts = gathered[:, 1].astype(np.int32)
            raw = (local["bytes"] if local is not None
                   else np.zeros(cap_b, np.uint8))
            put_s = lambda a: jax.make_array_from_process_local_data(  # noqa: E731
                self._sharding, a)
            put_r = lambda a: jax.make_array_from_process_local_data(  # noqa: E731
                repl, np.asarray(a))
            batch = RecordBatch(
                bytes=put_s(raw),
                offsets=put_r(global_offs.astype(np.int32)),
                num_records=put_r(np.int32(block_counts.sum())),
                block_num_records=put_r(block_counts),
                blocks=nprocs)
            self.batches_staged += 1
            yield batch

    # ---- retuning -----------------------------------------------------------
    @property
    def knobs(self) -> dict:
        """Current pipeline knobs (the autotuner's view of this iterator)."""
        return {"num_workers": self._num_workers,
                "prefetch_depth": self._prefetch}

    def set_knobs(self, num_workers: Optional[int] = None,
                  prefetch_depth: Optional[int] = None,
                  **_ignored) -> dict:
        """Retune pipeline knobs; both take effect at the next epoch (the
        record path's Python worker pool and staging queues are rebuilt per
        epoch).  Unknown knobs (buffer_mb/chunk_bytes — parse-side only)
        are accepted and ignored so one autotuner policy drives both
        iterator kinds.  Returns the new knob dict with ``pool_live=False``
        (no native pool on this path)."""
        if num_workers is not None:
            self._num_workers = max(int(num_workers), 1)
        if prefetch_depth is not None:
            self._prefetch = max(int(prefetch_depth), 1)
        return dict(self.knobs, pool_live=False)

    def __iter__(self) -> Iterator[RecordBatch]:
        with _observability_scope():
            from dmlc_core_tpu import autotune as _at
            tuner = _at.maybe_attach(self)
            if tuner is None:
                yield from self._iter_epoch()
                return
            with tuner.epoch():
                for batch in self._iter_epoch():
                    yield batch
                    tuner.on_batch()

    def _iter_epoch(self) -> Iterator[RecordBatch]:
        if self._sharding is not None and jax.process_count() > 1:
            yield from self._iter_multihost()
            return

        # two-stage: the read+pack stage fills a host queue; a dedicated
        # stager thread drains it through a double-buffered device feed
        host_iter = _staged_iter(self._produce_host, self._prefetch,
                                 depth_gauge="record.queue_depth")

        def produce(emit):
            try:
                it = iter(host_iter)
                while True:
                    t0 = time.monotonic()
                    w = next(it, None)
                    t1 = time.monotonic()
                    if w is None:
                        return
                    batch = self._stage(w)
                    t2 = time.monotonic()
                    ok = emit(batch)
                    telemetry.counter_add("h2d.wait_us", int((t1 - t0) * 1e6))
                    telemetry.counter_add("h2d.busy_us", int((t2 - t1) * 1e6))
                    telemetry.counter_add("h2d.batches", 1)
                    if not ok:
                        return
            finally:
                host_iter.close()

        yield from _staged_iter(produce, 2, depth_gauge="h2d.queue_depth",
                                device_feed=True)


# (key of ``DeviceStagingIter.counters``, registry counter, its units to the key's)
_H2D_COUNTERS = (("host_wait_s", "h2d.wait_us", 1e6),
                 ("stage_s", "h2d.busy_us", 1e6),
                 ("emit_wait_s", "h2d.emit_wait_us", 1e6),
                 ("batches", "h2d.batches", 1))


class DeviceStagingIter:
    """Iterate PaddedBatches staged into device memory, one batch ahead.

    Parameters
    ----------
    uri : dataset URI (same sugar as Parser).
    batch_size : rows per emitted batch (global batch when sharded).
    nnz_bucket : pad nonzeros to a multiple of this (shape-bucketing).
    nnz_max : if nonzero, a hard per-batch nonzero cap — rows that would
        exceed it spill into the next batch and every batch has
        ``nnz_pad == nnz_max`` (fully fixed shapes, required for multi-host
        global-array staging where each process must contribute
        identically-shaped shards).  0 = unbounded (bucketed shapes).
    sharding : optional ``jax.sharding.Sharding`` for the staged arrays
        (e.g. NamedSharding(mesh, P('data')) on the leading axis).  Scalars
        and ``num_rows`` are replicated.
    prefetch : back-compat alias for ``prefetch_depth``.
    prefetch_depth : host batches the parse+pack stage keeps queued ahead
        of the stager thread (the device feed itself is double-buffered).
    num_workers : native parse worker threads.  > 1 fans the parse over the
        sharded pool (cpp/src/data/sharded_parser.h); with reorder=True the
        staged batches are bit-identical to the single-worker stream for
        ANY worker count (packing is a pure function of the row stream).
    reorder : deterministic part-ordered re-emission (True, default) or
        arrival order (False; order not reproducible across runs).
    buffer_mb : cap on parsed-but-unconsumed bytes in the worker pool.
    autotune : arm the stall-attribution autotuner (dmlc_core_tpu.autotune)
        on this iterator; None (default) follows DMLCTPU_AUTOTUNE.  Armed
        iterators always build the sharded pool — even at num_workers=1 —
        so every knob stays live-retunable mid-epoch.
    """

    def __init__(self, uri: str, batch_size: int = 4096, nnz_bucket: int = 1 << 16,
                 part: int = 0, num_parts: int = 1, format: str = "auto",  # noqa: A002
                 sharding=None, with_field: bool = False, prefetch: int = 2,
                 nnz_max: int = 0, log_every: int = 0,
                 with_qid: bool = False, num_workers: int = 1,
                 reorder: bool = True, buffer_mb: int = 64,
                 prefetch_depth: Optional[int] = None,
                 autotune: Optional[bool] = None,
                 bin_cache=None, binner=None,
                 bin_cache_codec: Optional[str] = None):
        if bin_cache is not None and binner is None:
            raise ValueError("bin_cache= needs binner= (a QuantileBinner; "
                             "see doc/binned_cache.md)")
        self._lib = _declare_batcher_sig()
        self._handle = ctypes.c_void_p()
        if autotune is None:
            from dmlc_core_tpu import autotune as _at
            autotune = _at.armed()
        self._autotune = bool(autotune)
        nw = int(num_workers)
        if self._autotune and nw <= 1:
            # a 1-worker sharded pool emits the same stream as the single
            # -stream reader but stays live-retunable; negative num_workers
            # forces the pool (see DmlcTpuStagedBatcherCreateEx docs)
            nw = -1
        check(self._lib.DmlcTpuStagedBatcherCreateEx(
            uri.encode(), part, num_parts, format.encode(),
            batch_size, nnz_bucket, nnz_max, int(with_field), int(with_qid),
            nw, int(reorder), int(buffer_mb) << 20,
            ctypes.byref(self._handle)))
        self._uri = uri
        self._part = part
        self._num_parts = num_parts
        self._format = format
        self._batch_size = batch_size
        self._nnz_bucket = nnz_bucket
        self._nnz_max = nnz_max
        self._sharding = sharding
        # binned epoch cache fast path (doc/binned_cache.md): with
        # bin_cache= set, __iter__ serves pre-binned BinnedBatch pytrees
        # from the quantized columnar cache (built on first use) instead of
        # parsing text — epoch 2+ does zero parse and zero binning work
        self._bin_cache = bin_cache
        # block codec the cache build writes under (None defers to the
        # DMLCTPU_BINCACHE_CODEC knob; doc/binned_cache.md "Block codec")
        self._bin_cache_codec = bin_cache_codec
        self._binner = binner
        self._binned = None  # lazily-built BinnedStagingIter delegate
        self._prefetch = max(prefetch_depth if prefetch_depth is not None
                             else prefetch, 1)
        self._num_workers = max(int(num_workers), 1)
        self._buffer_mb = int(buffer_mb)
        self._chunk_bytes = 0  # 0 = the input split's default read size
        self._tuner = None  # lazily attached AutoTuner (see __iter__)
        self._reorder = reorder
        self._with_field = with_field
        self._with_qid = with_qid
        self._max_index = -1
        self.batches_staged = 0
        # the registry's h2d counters when the epoch began, and this
        # iterator's own time in the native Next: what ``counters`` reads
        self._epoch_base = None
        self._native_us = 0
        # throughput self-reporting cadence in batches (0 = off); parity with
        # the reference loaders' MB/sec logs (basic_row_iter.h:70-81)
        self._log_every = log_every
        self._epoch_t0 = 0.0
        self._epoch_bytes0 = 0
        self._epoch_batches0 = 0
        self._lock = threading.Lock()  # one native cursor per handle

    @property
    def bytes_read(self) -> int:
        return self._lib.DmlcTpuStagedBatcherBytesRead(self._handle)

    @property
    def max_index(self) -> int:
        """Largest column id seen so far (after a full epoch: num_features-1)."""
        return self._max_index

    def close(self) -> None:
        # serialize with the producer thread (see RecordStagingIter.close)
        if not self._lock.acquire(timeout=30.0):
            LOGGER.warning("DeviceStagingIter.close: producer still busy; "
                           "leaking native handle")
            return
        try:
            handle, self._handle = self._handle, ctypes.c_void_p()
            if handle:
                try:
                    self._lib.DmlcTpuStagedBatcherFree(handle)
                except (AttributeError, TypeError):
                    pass  # interpreter shutdown already tore down ctypes
        finally:
            self._lock.release()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def counters(self) -> dict:
        """Per-stage pipeline counters for the current/last epoch, seconds,
        plus pipeline configuration and totals.  The stager's three times
        and its batches are the process-wide registry's ``h2d.wait_us`` /
        ``busy_us`` / ``emit_wait_us`` / ``batches`` since the epoch began
        (one set of readings: another feed running in the process at the
        same time shows in them too); ``native_s`` is this iterator's own
        time blocked in the native ``NextOwned`` (the span ``pack.next``).
        No breakdown for a multi-host epoch."""
        c = {}
        if self._epoch_base is not None:
            c = {key: (telemetry.counter_get(name) - base) / scale
                 for (key, name, scale), base
                 in zip(_H2D_COUNTERS, self._epoch_base)}
            c["batches"] = int(c["batches"])
            c["native_s"] = self._native_us / 1e6
        c.update(num_workers=self._num_workers, reorder=self._reorder,
                 prefetch_depth=self._prefetch, bytes_read=self.bytes_read,
                 batches_staged=self.batches_staged)
        return c

    # ---- live retuning ------------------------------------------------------
    @property
    def knobs(self) -> dict:
        """Current pipeline knobs (the autotuner's view of this iterator)."""
        return {"num_workers": self._num_workers,
                "buffer_mb": self._buffer_mb,
                "prefetch_depth": self._prefetch,
                "chunk_bytes": self._chunk_bytes}

    def set_knobs(self, num_workers: Optional[int] = None,
                  buffer_mb: Optional[int] = None,
                  prefetch_depth: Optional[int] = None,
                  chunk_bytes: Optional[int] = None) -> dict:
        """Retune pipeline knobs on a live iterator.

        ``num_workers`` / ``buffer_mb`` / ``chunk_bytes`` reach the native
        sharded pool immediately when one exists (worker growth spawns now,
        shrink retires at the next part boundary; the emitted stream stays
        bit-identical either way).  ``prefetch_depth`` takes effect at the
        next epoch (the staging queues are built per epoch).  Returns the
        new knob dict plus ``pool_live``: False means the native side is a
        single-stream parser (built with num_workers=1 and autotune unarmed)
        so only the Python-side knobs moved.
        """
        if prefetch_depth is not None:
            self._prefetch = max(int(prefetch_depth), 1)
        nw = int(num_workers) if num_workers is not None else 0
        bb = (int(buffer_mb) << 20) if buffer_mb is not None else 0
        cb = int(chunk_bytes) if chunk_bytes is not None else 0
        live = False
        if nw > 0 or bb > 0 or cb > 0:
            if hasattr(self._lib, "DmlcTpuStagedBatcherSetPoolKnobs"):
                applied = ctypes.c_int(0)
                check(self._lib.DmlcTpuStagedBatcherSetPoolKnobs(
                    self._handle, nw, ctypes.c_uint64(bb),
                    ctypes.c_uint64(cb), ctypes.byref(applied)))
                live = bool(applied.value)
            if nw > 0:
                self._num_workers = max(nw, 1)
            if bb > 0:
                self._buffer_mb = int(buffer_mb)
            if cb > 0:
                self._chunk_bytes = cb
        return dict(self.knobs, pool_live=live)

    # ---- staging ------------------------------------------------------------
    def _stage(self, w: dict) -> PaddedBatch:
        # one span per staged batch, in the dmlctpu telemetry trace (shared
        # steady-clock epoch with the native parse/pack spans) and, as
        # ``dmlctpu.h2d.stage_batch``, in a running jax profiler trace
        with telemetry.span("h2d.stage_batch", telemetry.lineage(w)):
            return self._stage_inner(w)

    def _stage_inner(self, w: dict) -> PaddedBatch:
        with_field = w["field"] is not None
        with_qid = w["qid"] is not None
        num_rows = np.int32(w["num_rows"])
        leaves = ((w["label"], w["weight"], w["row_ptr"], w["index"],
                   w["value"], num_rows)
                  + ((w["field"],) if with_field else ())
                  + ((w["qid"],) if with_qid else ()))
        shardings = None
        if self._sharding is not None:
            repl = self._replicated_sharding()
            shardings = ((self._sharding, self._sharding, repl,
                          self._sharding, self._sharding, repl)
                         + ((self._sharding,) if with_field else ())
                         + ((self._sharding,) if with_qid else ()))
        # one batched dispatch for the whole pytree; the call alone is a
        # span of the ring, so that wrap and dispatch separate
        t0 = telemetry.now_us()
        staged = jax.device_put(leaves, shardings)
        telemetry.record_span("h2d.device_put", t0, telemetry.now_us() - t0,
                              telemetry.lineage(w))

        batch = PaddedBatch(
            label=staged[0], weight=staged[1], row_ptr=staged[2],
            index=staged[3], value=staged[4], num_rows=staged[5],
            field=staged[6] if with_field else None,
            qid=staged[6 + int(with_field)] if with_qid else None)
        # lineage rides as a plain (non-pytree) attribute: it is provenance
        # metadata for telemetry.lineage(), not a traced value — a pytree
        # meta field would retrace jitted consumers per lineage id
        batch._lineage = int(w.get("lineage", -1))
        self._max_index = max(self._max_index, w["max_index"])
        self._note_staged()
        return batch

    def _note_staged(self) -> None:
        self.batches_staged += 1
        epoch_batches = self.batches_staged - self._epoch_batches0
        if self._log_every and epoch_batches % self._log_every == 0:
            secs = max(time.monotonic() - self._epoch_t0, 1e-9)
            epoch_mb = (self.bytes_read - self._epoch_bytes0) / (1 << 20)
            LOGGER.info("staged %d batches, %.2f MB/sec -> device",
                        epoch_batches, epoch_mb / secs)

    def _replicated_sharding(self):
        return _replicated_sharding(self._sharding)

    # ---- multi-host staging --------------------------------------------------
    # Each process runs its own DeviceStagingIter over its shard of the data
    # (part=process_index, num_parts=process_count) with the SAME batch_size
    # and a nonzero nnz_max, and the per-process (batch_size,)/(nnz_max,)
    # arrays assemble into one global jax.Array per leaf.  Per global batch,
    # ONE tiny host allgather carries (status, num_rows, max_index, row_ptr)
    # — batch_size+4 ints — from every process; that single collective
    #   * rebuilds the exact global CSR row pointer (each process's rows are
    #     shifted into its fixed nnz_max segment of the concatenated
    #     index/value arrays),
    #   * sums the true global row count, and
    #   * keeps collective counts identical across processes even when the
    #     input split is ragged: a process that ran out of rows keeps
    #     contributing all-padding batches (num_rows=0, weight 0) until every
    #     process is done, so every row is delivered exactly once and nobody
    #     deadlocks.  (Contrast reference src/io/input_split_base.cc, whose
    #     multi-rank contract is per-rank exactly-once but leaves cross-rank
    #     batch-count agreement to the caller.)

    def _wrap_owned(self, c: _StagedBatchOwnedC) -> dict:
        """Zero-copy numpy views over an owned arena; the buf object owns the
        arena (finalizer releases it back to the native pool).  Host-only —
        safe on the producer thread (no jax dispatch, see _iter_multihost)."""
        buf = (ctypes.c_uint8 * int(c.arena_bytes)).from_address(c.arena)
        weakref.finalize(buf, self._lib.DmlcTpuStagedBatchFree,
                         ctypes.c_void_p(c.batch))
        B, nnz = self._batch_size, int(c.nnz_pad)

        def arr(off, count, dtype):
            return np.frombuffer(buf, dtype=dtype, count=count, offset=int(off))

        with_field = self._with_field and c.field_off != _NO_FIELD
        with_qid = self._with_qid and c.qid_off != _NO_FIELD
        return {
            "label": arr(c.label_off, B, np.float32),
            "weight": arr(c.weight_off, B, np.float32),
            "row_ptr": arr(c.row_ptr_off, B + 1, np.int32),
            "index": arr(c.index_off, nnz, np.int32),
            "value": arr(c.value_off, nnz, np.float32),
            "field": arr(c.field_off, nnz, np.int32) if with_field else None,
            "qid": arr(c.qid_off, B, np.int32) if with_qid else None,
            "num_rows": int(c.num_rows),
            "max_index": int(c.max_index),
            "lineage": int(c.lineage),
        }

    def _iter_multihost(self) -> Iterator[PaddedBatch]:
        """Multi-host epoch: the background thread runs ONLY the native
        parse/pack (+ host-side zero-copy wrap); every jax dispatch — the
        per-batch allgather (_multihost_rounds) and the global-array
        assembly — happens here on the consumer thread."""
        if self._nnz_max == 0:
            raise ValueError(
                "multi-process staging needs fixed shapes: pass nnz_max=... "
                "so every process contributes identically-shaped shards")
        B, nnz, nprocs = self._batch_size, self._nnz_max, jax.process_count()

        def produce(emit):
            with self._lock:
                check(self._lib.DmlcTpuStagedBatcherBeforeFirst(self._handle))
                while True:
                    c = _StagedBatchOwnedC()
                    if check(self._lib.DmlcTpuStagedBatcherNextOwned(
                            self._handle, ctypes.byref(c))) != 1:
                        return
                    if not emit(self._wrap_owned(c)):
                        return

        native = _staged_iter(produce, self._prefetch,
                              depth_gauge="pack.queue_depth")

        # payload: [num_rows, max_index, row_ptr[B+1]]
        def pack(local, out):
            out[0] = local["num_rows"]
            out[1] = local["max_index"]
            out[2:] = local["row_ptr"]

        for local, gathered in _multihost_rounds(native, B + 3, pack):
            # Global CSR: each process's row boundaries shift into its
            # fixed nnz_max segment of the concatenated index/value
            # arrays.  The pad gap [local_nnz, nnz_max) of segment p falls
            # into the span of that segment's LAST row — a weight-0
            # padding row whenever the process batch wasn't full; only a
            # full local batch attaches its pad gap (value-0, index-0
            # pairs, inert in value-weighted ops) to a real row's span.
            shifts = np.arange(nprocs, dtype=np.int64) * nnz
            shifted = gathered[:, 3:] + shifts[:, None]
            global_rp = np.concatenate(
                [shifted[:, :-1].reshape(-1),
                 [np.int64(nnz) * nprocs]]).astype(np.int32)
            total_rows = np.int32(gathered[:, 1].sum())
            # every process folds every peer's max id, so the documented
            # "num_features-1 after a full epoch" property holds globally
            # (only status==1 rows carry a valid payload)
            has_data = gathered[:, 0] == 1
            self._max_index = max(
                self._max_index, int(gathered[has_data, 2].max(initial=-1)))
            yield self._assemble_multihost(local, global_rp, total_rows)

    def _assemble_multihost(self, local: dict | None, global_rp: np.ndarray,
                            total_rows: np.int32) -> PaddedBatch:
        """One global batch from this process's shard (``local``; None once
        this process is out of rows — it contributes inert zero padding)."""
        B, nnz = self._batch_size, self._nnz_max
        if local is not None:
            label, weight = local["label"], local["weight"]
            index, value = local["index"], local["value"]
            field = local["field"]
            qid = local["qid"]
            with_field = field is not None
            with_qid = qid is not None
        else:
            label = weight = np.zeros(B, np.float32)
            value = np.zeros(nnz, np.float32)
            index = np.zeros(nnz, np.int32)
            with_field = self._with_field
            field = np.zeros(nnz, np.int32) if with_field else None
            with_qid = self._with_qid
            qid = np.zeros(B, np.int32) if with_qid else None

        repl = self._replicated_sharding()
        put_s = lambda a: jax.make_array_from_process_local_data(  # noqa: E731
            self._sharding, a)
        put_r = lambda a: jax.make_array_from_process_local_data(  # noqa: E731
            repl, np.asarray(a))
        batch = PaddedBatch(
            label=put_s(label), weight=put_s(weight), row_ptr=put_r(global_rp),
            index=put_s(index), value=put_s(value),
            num_rows=put_r(total_rows),
            field=put_s(field) if with_field else None,
            qid=put_s(qid) if with_qid else None)
        if local is not None:
            batch._lineage = int(local.get("lineage", -1))
        self._note_staged()
        return batch

    def __iter__(self) -> Iterator[PaddedBatch]:
        """Yield device-resident batches; parse/pack (C++) and device_put
        (a background thread) run ahead of the consumer.  The epoch runs
        under the env-configured stall watchdog (telemetry.watchdog_from_env)
        and, when launched under a tracker, reports its counters to the
        tracker's metrics channel.

        With ``bin_cache=`` set, yields pre-binned ``BinnedBatch`` pytrees
        served from the epoch cache (built/validated on first use) instead
        of parsing text — see doc/binned_cache.md."""
        if self._bin_cache is not None:
            yield from self._binned_delegate()
            return
        with _observability_scope():
            from dmlc_core_tpu import autotune as _at
            tuner = _at.maybe_attach(self)
            if tuner is None:
                yield from self._iter_epoch()
                return
            with tuner.epoch():
                for batch in self._iter_epoch():
                    yield batch
                    tuner.on_batch()

    def _binned_delegate(self):
        """The cache-hit fast path: a lazily-built BinnedStagingIter over
        the same dataset/knobs serves every epoch from the quantized
        columnar cache (first use builds it)."""
        if self._binned is None:
            from .binned_cache import BinnedStagingIter
            cache = None if self._bin_cache is True else self._bin_cache
            self._binned = BinnedStagingIter(
                self._uri, self._binner, cache=cache,
                batch_size=self._batch_size, nnz_bucket=self._nnz_bucket,
                nnz_max=self._nnz_max, part=self._part,
                num_parts=self._num_parts, format=self._format,
                sharding=self._sharding, prefetch_depth=self._prefetch,
                with_qid=self._with_qid, buffer_mb=self._buffer_mb,
                codec=self._bin_cache_codec)
        yield from self._binned

    def _iter_epoch(self) -> Iterator[PaddedBatch]:
        self._epoch_t0 = time.monotonic()
        self._epoch_bytes0 = self.bytes_read
        self._epoch_batches0 = self.batches_staged

        if self._sharding is not None and jax.process_count() > 1:
            # no producer breakdown on this path; clear any prior epoch's
            # so a stale single-host one is never misattributed
            self._epoch_base = None
            yield from self._iter_multihost()
            return

        # The hand-offs of a batch, each timed once: the reading goes to the
        # registry (``counters`` and ``stall_attribution`` read it there) and,
        # while a trace runs, to the ring as a span under the batch's lineage:
        #   pack.next      blocking in the C++ parse+pack (NextOwned), on the
        #                  pack-driver thread; with num_workers > 1 this is
        #                  mostly reorder-queue waiting, not parse CPU
        #   h2d.host_wait  the stager thread starved for host batches (the
        #                  parse side is the limiter): h2d.wait_us
        #   h2d.stage_batch  wrap + device_put dispatch (async; not
        #                  transfer): h2d.busy_us
        #   h2d.emit_wait  blocked handing off (device queue full = the
        #                  CONSUMER/device is the limiter, not this pipeline):
        #                  h2d.emit_wait_us
        # Cheap enough to keep always on (a few clock reads per multi-MB
        # batch), so a slow epoch pins its own bottleneck instead of
        # inviting guesses.  These go to the ring only: an annotation of a
        # feed thread in the profiler's file would name device gaps that the
        # consumer's spans name.
        self._epoch_base = [telemetry.counter_get(name)
                            for _, name, _ in _H2D_COUNTERS]
        self._native_us = 0

        def produce_host(emit):
            with self._lock:
                check(self._lib.DmlcTpuStagedBatcherBeforeFirst(self._handle))
                c = _StagedBatchOwnedC()
                while True:
                    t0 = telemetry.now_us()
                    rc = check(self._lib.DmlcTpuStagedBatcherNextOwned(
                        self._handle, ctypes.byref(c)))
                    t1 = telemetry.now_us()
                    self._native_us += t1 - t0
                    if rc != 1:
                        return
                    telemetry.record_span("pack.next", t0, t1 - t0,
                                          int(c.lineage))
                    if not emit(self._wrap_owned(c)):
                        return

        # two-stage: the pack driver fills a host queue (depth
        # prefetch_depth); a dedicated stager thread turns host batches
        # into device arrays through a double-buffered feed, so the H2D
        # copy of batch k+1 overlaps the consumer's work on batch k
        host_iter = _staged_iter(produce_host, self._prefetch,
                                 depth_gauge="pack.queue_depth")

        def produce_device(emit):
            try:
                it = iter(host_iter)
                while True:
                    t0 = telemetry.now_us()
                    w = next(it, None)
                    t1 = telemetry.now_us()
                    if w is None:
                        # the wait that found the stream's end: no batch
                        # to tell it of, so no span; the counter has it
                        telemetry.counter_add("h2d.wait_us", t1 - t0)
                        return
                    batch = self._stage(w)
                    t2 = telemetry.now_us()
                    ok = emit(batch)
                    t3 = telemetry.now_us()
                    lineage = telemetry.lineage(batch)
                    # publish H2D feed occupancy into the process-wide
                    # telemetry registry (same us units as the native
                    # stages, so stall_attribution sees the whole pipeline)
                    telemetry.record_span("h2d.host_wait", t0, t1 - t0,
                                          lineage, total="h2d.wait_us")
                    telemetry.record_span("h2d.emit_wait", t2, t3 - t2,
                                          lineage, total="h2d.emit_wait_us")
                    telemetry.counter_add("h2d.busy_us", t2 - t1)
                    telemetry.counter_add("h2d.batches", 1)
                    if not ok:
                        return
            finally:
                host_iter.close()

        yield from _staged_iter(produce_device, 2,
                                depth_gauge="h2d.queue_depth",
                                device_feed=True)


# ---- dense binned pages: host memory to HBM, a few ahead of the kernel ------


# Row slices a page is put in, each its own ``device_put``: the copy of one
# slice into the runtime's staging buffer runs beside the transfer of the
# slice before it.  On a v5e host a 281 MB page took 55-65 ms in one put
# (4.3-5.1 GB/s) and 33.5 ms in eight (8.4 GB/s), against a 60 ms visit
# (PERF.md, PR 48).
_PAGE_PUT_SLICES = 8


class PagePrefetcher:
    """Dense binned pages from host memory to HBM, ``depth`` ahead of the
    program that visits them, on a thread of its own: the feed of
    ``GBDT.fit_paged`` (XGBoost's external-memory ``hist`` with its pages
    ``on_host``), and the first feed in this package whose transfer's
    *bytes* set a pace and not its dispatches.

    ``source``: a replayable source of pages, each a host ``uint8``
    ``[rows <= page_rows, num_features]`` array and all but the last
    ``page_rows`` long — a sequence, or a zero-argument callable that
    returns a fresh iterator; it is replayed ``passes`` times in all, every
    replay the same pages in the same order.  A short last page is padded
    with zero rows on the host, so that every visit is one program.

    Residency: at most ``depth + 1`` pages lie on the device at once — the
    one the kernel holds and ``depth`` staged or on their way — whatever the
    consumer does.  A page arrives as a tuple of ``_PAGE_PUT_SLICES`` row
    slices (fewer for a page of fewer rows), the same cuts for every page,
    which the visiting program concatenates.  The consumer takes a pass's
    pages from :meth:`pages` as ``(index, rows, page)`` and hands each back
    with :meth:`release` and a ``token``, an array that the program which
    visited the page returns:
    the stager puts no further page before it has waited for the oldest
    released page's token and deleted that page, so a page's buffer goes
    back to the allocator when its visit has RUN, not when it was queued,
    and nothing is held past its visit.  The most pages resident at once is
    the gauge ``page.resident_max`` (reset here).

    What it tells: the span ``page.h2d`` and the counters ``page.h2d_pages``,
    ``page.h2d_bytes``, ``page.h2d_busy_us`` around a page's ``device_put``
    to its end (``block_until_ready``: the transfer, not its dispatch); the
    span ``page.wait`` and the counter ``page.wait_us`` where the consumer
    found no page staged and blocked."""

    _END = object()

    def __init__(self, source, passes: int, page_rows: int,
                 num_features: int, depth: int = 2, device=None):
        if depth < 1:
            raise ValueError("PagePrefetcher: depth must be at least 1")
        self._replay = source if callable(source) else (lambda: iter(source))
        self._passes, self._page_rows = int(passes), int(page_rows)
        self._features, self._device = int(num_features), device
        self._slots = depth + 1
        k = min(_PAGE_PUT_SLICES, self._page_rows)
        self._cuts = [self._page_rows * i // k for i in range(k + 1)]
        self._ready: queue.Queue = queue.Queue()
        self._released: list = []       # (page, token), oldest first
        self._cv = threading.Condition()
        self._resident = 0      # pages put and not deleted; never falls
        self._stop = False
        telemetry.gauge_set("page.resident_max", 0)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def __enter__(self) -> "PagePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- the stager thread ----------------------------------------------------

    def _run(self) -> None:
        try:
            for _ in range(self._passes):
                short = False
                for index, host in enumerate(self._replay()):
                    if short:
                        raise ValueError(
                            "PagePrefetcher: only a source's last page may "
                            f"hold fewer than {self._page_rows} rows")
                    short = self._check(host)
                    if not self._take_slot():
                        return
                    self._ready.put((index, host.shape[0], self._put(host)))
                self._ready.put(self._END)
        except BaseException as e:      # relayed to the consumer
            self._ready.put(e)

    def _check(self, host) -> bool:
        """Whether ``host`` is a short page; raises on any other shape."""
        if (getattr(host, "dtype", None) != np.uint8 or host.ndim != 2
                or host.shape[1] != self._features
                or not 0 < host.shape[0] <= self._page_rows):
            raise ValueError(
                "PagePrefetcher: a page is a host uint8 array of at most "
                f"[{self._page_rows}, {self._features}], not "
                f"{getattr(host, 'dtype', type(host).__name__)} "
                f"{list(getattr(host, 'shape', ()))}")
        return host.shape[0] < self._page_rows

    def _take_slot(self) -> bool:
        """Room for one more page on the device: a free slot, else the
        oldest released page's, once its visit has run.  False when the
        consumer has closed the feed."""
        with self._cv:
            while not self._stop:
                if self._resident < self._slots or self._released:
                    break
                self._cv.wait(0.1)
            if self._stop:
                return False
            if self._resident < self._slots:
                # (a released page whose visit has not run still counts)
                self._resident += 1
                telemetry.gauge_set("page.resident_max", self._resident)
                return True
            page, token = self._released.pop(0)
        self._delete(page, token)       # its slot is the next page's
        return True

    @staticmethod
    def _delete(page: tuple, token=None) -> None:
        jax.block_until_ready(token)
        for part in page:
            part.delete()

    def _put(self, host: np.ndarray) -> tuple:
        if host.shape[0] < self._page_rows:
            host = np.concatenate([host, np.zeros(
                (self._page_rows - host.shape[0], self._features), np.uint8)])
        with telemetry.span("page.h2d", total="page.h2d_busy_us"):
            page = jax.block_until_ready(tuple(
                jax.device_put(host[lo:hi], self._device)
                for lo, hi in zip(self._cuts, self._cuts[1:])))
        telemetry.counter_add("page.h2d_pages", 1)
        telemetry.counter_add("page.h2d_bytes", host.nbytes)
        return page

    # -- the consumer ---------------------------------------------------------

    def pages(self) -> Iterator[tuple]:
        """One pass: ``(index, rows, page)`` of every page in order, ``page``
        its row slices on the device, ``page_rows`` long together, ``rows``
        of them the source's."""
        while True:
            try:
                item = self._ready.get_nowait()
            except queue.Empty:
                with telemetry.span("page.wait", total="page.wait_us"):
                    item = self._ready.get()
            if item is self._END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def release(self, page: tuple, token) -> None:
        """The consumer has queued ``page``'s visit; ``token`` is an array
        that visit returns (and no later program is given as a donation)."""
        with self._cv:
            self._released.append((page, token))
            self._cv.notify()

    def close(self) -> None:
        """Stop the stager; wait for every queued visit and delete its page,
        and every page staged and not taken."""
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=30.0)
        while True:
            try:
                item = self._ready.get_nowait()
            except queue.Empty:
                break
            if isinstance(item, tuple):
                self._delete(item[2])
        for page, token in self._released:
            self._delete(page, token)
        self._released = []
