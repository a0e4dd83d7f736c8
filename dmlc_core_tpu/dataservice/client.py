"""Trainer-side client of the staging service.

:class:`DataServiceIter` is a drop-in sibling of
:class:`~dmlc_core_tpu.data.binned_cache.BinnedStagingIter`: it yields the
same :class:`~dmlc_core_tpu.data.binned_cache.BinnedBatch` pytrees through
the same repack + donated-``device_put`` staging path — the only difference
is that the host blocks arrive off the data side channel instead of a local
cache mmap.  On the pre-binned fast path the worker ships the cache blocks
byte-for-byte as stored, the client walks the global virtual parts in the
same order with the same :class:`_Repacker` geometry, and the resulting
batch stream is **bit-identical** to a local cache-hit epoch (GBDT forests
match exactly).  The staged text fallback ships packed parse batches and
bins on the client with the adopted cuts — row-identical semantics, batch
boundaries set by the service's virtual part split.

Every epoch the client registers a lease ledger with the tracker's
LeaseBoard and walks parts ``0..V-1``: assign -> fetch -> done.  Failover
is whole-shard: a part's blocks are buffered until its END trailer checks
out and only then fed to the (stateful) repacker, so a worker dying
mid-stream costs a discard + ``lease_fail`` + re-fetch from a survivor —
never a duplicated or dropped row.  Both hops honor the deterministic
fault points ``dataservice.connect`` and ``dataservice.block.drop``
(doc/robustness.md).
"""
from __future__ import annotations

import itertools
import logging
import os
import socket
import time
from typing import Iterator, List, Optional

import numpy as np

from dmlc_core_tpu import faultinject, telemetry
from dmlc_core_tpu.tracker import metrics as tracker_metrics

from . import protocol

LOGGER = logging.getLogger(__name__)

_CLIENT_SEQ = itertools.count()


def _fire(point: str) -> None:
    mode = faultinject.fire(point)
    if mode:
        raise ConnectionError(
            f"fault injected: {point}={faultinject.MODE_NAMES.get(mode)}")


class DataServiceIter:
    """Stream pre-binned batches from the staging fleet into device memory.

    ``binner``: a ``QuantileBinner``.  Unfitted, it ADOPTS the service
    cache's cuts on first contact (digest-checked), exactly like a local
    cache open; fitted, its digest must match the service's.  With
    ``mode="staged"`` (text fallback) the binner must already be fitted —
    the client bins the shipped parse batches itself.

    ``shard_client``: the tracker 0xff98 connection carrying the lease
    RPCs; defaults to the env contract
    (:func:`~dmlc_core_tpu.tracker.metrics.shard_client_from_env`).
    """

    def __init__(self, uri: str, binner, *, batch_size: int = 4096,
                 nnz_bucket: int = 1 << 16, nnz_max: int = 0,
                 format: str = "auto",  # noqa: A002
                 with_qid: bool = False, sharding=None, prefetch: int = 2,
                 mode: str = "binned", client_id: Optional[str] = None,
                 shard_client: Optional[tracker_metrics.ShardClient] = None,
                 retries: Optional[int] = None,
                 timeout_s: Optional[float] = None,
                 codec: Optional[str] = None):
        if mode not in ("binned", "staged"):
            raise ValueError(f"mode must be 'binned' or 'staged', not {mode!r}")
        # codec negotiation: the requested block codec rides the dataset
        # spec (None defers to DMLCTPU_BINCACHE_CODEC), the worker builds
        # its cache under it and ships the stored — possibly compressed —
        # frames verbatim; THIS side decodes.  resolve_codec also drops to
        # raw when the local libdmlctpu cannot decode (DMLCTPU_CODEC=0).
        from dmlc_core_tpu.data.binned_cache import resolve_codec
        self._codec = resolve_codec(codec) if mode == "binned" else "raw"
        self._binner = binner
        self._mode = mode
        self._sharding = sharding
        self._prefetch = max(int(prefetch), 1)
        self._retries = int(
            retries if retries is not None
            else os.environ.get("DMLCTPU_DATASERVICE_RETRIES", "4"))
        self._timeout_s = float(
            timeout_s if timeout_s is not None
            else os.environ.get("DMLCTPU_DATASERVICE_TIMEOUT_S", "30"))
        # instance nonce: two iterators in one process (different datasets
        # or modes) must not share an epoch ledger on the board
        self.client_id = client_id or (
            f"c-{socket.gethostname()}-{os.getpid()}-{next(_CLIENT_SEQ)}")
        self._shard_client = shard_client
        self._spec = {
            "uri": uri, "format": format, "batch_size": int(batch_size),
            "nnz_bucket": int(nnz_bucket), "nnz_max": int(nnz_max),
            "with_qid": bool(with_qid), "codec": self._codec,
            "binner": None if mode == "staged" else {
                "num_bins": int(binner.num_bins),
                "missing_aware": bool(binner.missing_aware),
                "sketch_size": int(binner.sketch_size),
                "sketch_seed": int(binner.sketch_seed)},
        }
        if mode == "staged" and binner.cuts is None:
            raise ValueError("staged (text-fallback) mode needs a fitted "
                             "binner; the service has no cuts to adopt")
        self._meta: Optional[dict] = None
        self._virtual_parts = 0
        self._epoch = 0
        self.batches_staged = 0

    # -- dispatcher plumbing --------------------------------------------------

    def _data(self) -> tracker_metrics.ShardClient:
        if self._shard_client is None:
            self._shard_client = tracker_metrics.shard_client_from_env()
            if self._shard_client is None:
                raise RuntimeError(
                    "no tracker metrics channel in the environment; pass "
                    "shard_client= or run under a tracker "
                    "(doc/dataservice.md)")
        return self._shard_client

    def _any_worker(self) -> dict:
        """Pick any alive worker (for the meta bootstrap — fetches proper
        go through lease_assign's rendezvous placement)."""
        delay = 0.05
        for attempt in range(self._retries + 1):
            state = self._data().data_req({"op": "state"})
            alive = {w: e for w, e in state.get("workers", {}).items()
                     if not e.get("dead")}
            if alive:
                wid = sorted(alive)[0]
                e = alive[wid]
                return {"id": wid, "host": e["host"], "port": e["port"]}
            if attempt == self._retries:
                break
            time.sleep(delay)
            delay = min(delay * 2, 2.0)
        raise RuntimeError("data service has no alive staging workers")

    def _req_reply(self, worker: dict, req: dict) -> dict:
        _fire("dataservice.connect")
        ctx = telemetry.trace_context_wire()
        if ctx is not None:
            req = dict(req, trace=ctx)
        sock = socket.create_connection((worker["host"], worker["port"]),
                                        timeout=self._timeout_s)
        try:
            sock.settimeout(self._timeout_s)
            protocol.client_handshake(sock)
            protocol.send_req(sock, req)
            return protocol.read_req(sock)
        finally:
            sock.close()

    def ensure_meta(self) -> None:
        """Bootstrap the dataset geometry (and cuts, on the binned path)
        from the service — builds the worker-side cache on first contact."""
        if self._virtual_parts:
            return
        from dmlc_core_tpu.data.binned_cache import (_cuts_from_meta,
                                                     cuts_digest_of)
        import jax.numpy as jnp
        reply = self._req_reply(self._any_worker(),
                                {"op": "meta", "spec": self._spec})
        if not reply.get("ok"):
            raise RuntimeError("staging worker could not serve the dataset: "
                               + str(reply.get("error")))
        if self._mode == "binned":
            meta = reply["meta"]
            served_codec = meta.get("codec", "raw")
            if served_codec != "raw":
                # the worker's cache IS compressed: a client whose native
                # library cannot decode must fail loudly here, not on the
                # first corrupt-looking block
                from dmlc_core_tpu.data.binned_cache import \
                    _declare_binned_cache_sig
                L = _declare_binned_cache_sig()
                if not int(L.DmlcTpuBlockCodecEnabled()):
                    raise RuntimeError(
                        f"service cache is {served_codec}-compressed but "
                        "this client's libdmlctpu was built with "
                        "DMLCTPU_CODEC=0 and cannot decode it")
            if self._binner.cuts is None:
                self._binner.cuts = jnp.asarray(_cuts_from_meta(meta))
            elif cuts_digest_of(self._binner.cuts) != meta["cuts_digest"]:
                raise ValueError(
                    "fitted binner cuts do not match the service cache "
                    f"(digest {meta['cuts_digest']}); use an unfitted "
                    "binner to adopt, or matching cuts")
            self._meta = meta
            self._virtual_parts = int(meta["virtual_parts"])
        else:
            self._virtual_parts = int(reply["virtual_parts"])

    @property
    def meta(self) -> Optional[dict]:
        return self._meta

    # -- leased shard fetch ---------------------------------------------------

    def _fetch_from(self, worker: dict, part: int) -> List:
        """One whole shard off one worker, fully buffered; raises on ANY
        break so the caller can fail the lease and re-fetch elsewhere."""
        from dmlc_core_tpu.data.binned_cache import (decode_block_payload,
                                                     unpack_block)
        _fire("dataservice.connect")
        req = {"op": "fetch", "spec": self._spec, "part": int(part)}
        # the epoch's trace context rides the fetch request so the worker's
        # parse/pack spans link under this client's epoch span in the
        # job-trace merge
        ctx = telemetry.trace_context_wire()
        if ctx is not None:
            req["trace"] = ctx
        sock = socket.create_connection((worker["host"], worker["port"]),
                                        timeout=self._timeout_s)
        blocks: List = []
        nbytes = 0
        try:
            sock.settimeout(self._timeout_s)
            protocol.client_handshake(sock)
            protocol.send_req(sock, req)
            while True:
                kind, payload = protocol.read_frame(sock)
                if kind == protocol.FRAME_END:
                    if int(payload.get("blocks", -1)) != len(blocks):
                        raise ConnectionError(
                            f"part {part} trailer says "
                            f"{payload.get('blocks')} blocks, got "
                            f"{len(blocks)}")
                    break
                if kind == protocol.FRAME_ERROR:
                    raise ConnectionError(
                        f"worker error on part {part}: {payload.get('error')}")
                _fire("dataservice.block.drop")
                nbytes += len(payload)
                if kind == protocol.FRAME_BLOCK:
                    # frames carry stored bytes; compressed records decode
                    # here (never on the worker), counted in cache.codec.*
                    blocks.append(unpack_block(decode_block_payload(
                        np.frombuffer(payload, np.uint8))))
                elif kind == protocol.FRAME_STAGED:
                    blocks.append(protocol.unwrap_staged_wire(payload))
                else:
                    raise ConnectionError(f"unknown frame kind {kind}")
        finally:
            sock.close()
        telemetry.counter_add("dataservice.fetch_blocks", len(blocks))
        telemetry.counter_add("dataservice.fetch_bytes", nbytes)
        return blocks

    def _fetch_part(self, epoch: int, part: int) -> List:
        """assign -> fetch -> done, with whole-shard failover: a failed
        fetch marks the worker dead on the board (requeueing its leases)
        and re-assigns this part to a survivor."""
        data = self._data()
        base = {"client": self.client_id, "epoch": int(epoch),
                "part": int(part)}
        failures = 0
        delay = 0.05
        while True:
            r = data.data_req(dict(base, op="lease_assign"))
            if r.get("done"):
                return []  # replay of a completed part: nothing to serve
            if r.get("wait"):
                failures += 1
                if failures > self._retries:
                    raise RuntimeError(
                        f"no alive staging workers for part {part} after "
                        f"{self._retries} retries")
                time.sleep(delay)
                delay = min(delay * 2, 2.0)
                continue
            worker = r["worker"]
            try:
                with telemetry.span("dataservice.fetch"):
                    blocks = self._fetch_from(worker, part)
            except (ConnectionError, OSError, ValueError) as e:
                telemetry.counter_add("dataservice.errors", 1)
                LOGGER.warning("fetch of part %d from %s failed (%s); "
                               "failing the lease", part, worker["id"], e)
                data.data_req(dict(base, op="lease_fail",
                                   worker=worker["id"]))
                failures += 1
                if failures > self._retries:
                    raise
                time.sleep(delay)
                delay = min(delay * 2, 2.0)
                continue
            data.data_req(dict(base, op="lease_done", worker=worker["id"]))
            return blocks

    # -- host-side batch production -------------------------------------------

    def _produce_host(self, emit) -> None:
        """Binned fast path: remote cache blocks through the local repacker
        — same part order, same geometry, bit-identical batches."""
        from dmlc_core_tpu.data.binned_cache import _Repacker
        epoch = self._epoch
        pad_bin = int(self._meta.get("pad_bin", 1))
        rp = _Repacker(self._spec["batch_size"], self._spec["nnz_bucket"],
                       self._spec["nnz_max"], pad_bin,
                       self._spec["with_qid"])
        for g in range(self._virtual_parts):
            for blk in self._fetch_part(epoch, g):
                for b in rp.feed(blk):
                    if not emit(b):
                        return
        for b in rp.flush():
            if not emit(b):
                return

    def _produce_host_staged(self, emit) -> None:
        """Text fallback: worker-packed parse batches, binned here with the
        fitted cuts — the remote twin of BinnedStagingIter's degraded
        mode."""
        from dmlc_core_tpu.data.binned_cache import bin_entries_np
        epoch = self._epoch
        cuts = np.ascontiguousarray(np.asarray(self._binner.cuts),
                                    np.float32)
        for g in range(self._virtual_parts):
            for w in self._fetch_part(epoch, g):
                v = np.asarray(w["value"], np.float32)
                out = {
                    "num_rows": w["num_rows"],
                    "label": np.asarray(w["label"]),
                    "weight": np.asarray(w["weight"]),
                    "qid": (np.asarray(w["qid"]) if w["qid"] is not None
                            else None),
                    "row_ptr": np.asarray(w["row_ptr"]),
                    "index": np.asarray(w["index"]),
                    "ebin": bin_entries_np(cuts, w["index"], v),
                    "emask": (v != 0) & ~np.isnan(v),
                }
                if not emit(out):
                    return

    # -- staging --------------------------------------------------------------

    def _stage(self, w: dict):
        """Identical to BinnedStagingIter._stage — one donated device_put of
        the repacked host batch (bit-identity hinges on sharing this path)."""
        from dmlc_core_tpu.data.binned_cache import (BinnedBatch,
                                                     cuts_digest_of)
        from dmlc_core_tpu.data.staging import (_device_put_maybe_donated,
                                                _replicated_sharding)
        with_qid = w["qid"] is not None
        num_rows = np.int32(w["num_rows"])
        leaves = ((w["label"], w["weight"], w["row_ptr"], w["index"],
                   w["ebin"], w["emask"], num_rows)
                  + ((w["qid"],) if with_qid else ()))
        donate = os.environ.get("DMLCTPU_BINCACHE_DONATE", "1") != "0"
        if self._sharding is None:
            staged = _device_put_maybe_donated(leaves, donate=donate)
        else:
            sh, repl = self._sharding, _replicated_sharding(
                self._sharding)
            shardings = ((sh, sh, repl, sh, sh, sh, repl)
                         + ((sh,) if with_qid else ()))
            staged = _device_put_maybe_donated(leaves, shardings,
                                               donate=donate)
        batch = BinnedBatch(
            label=staged[0], weight=staged[1], row_ptr=staged[2],
            index=staged[3], ebin=staged[4], emask=staged[5],
            num_rows=staged[6],
            qid=staged[7] if with_qid else None,
            cuts_digest=(self._meta or {}).get(
                "cuts_digest", cuts_digest_of(self._binner.cuts)))
        self.batches_staged += 1
        return batch

    def __iter__(self) -> Iterator:
        from dmlc_core_tpu.data.staging import _staged_iter
        # mint this epoch's trace context: every fetch request carries it,
        # so the fleet's parse/pack spans land under one trace id in the
        # tracker's job-trace merge.  The epoch span itself is recorded
        # below so the merged trace has the client-side root to hang the
        # remote spans off.
        trace_id = telemetry.new_trace_id()
        telemetry.set_trace_context(trace_id, trace_id)
        self.ensure_meta()
        self._data().data_req({
            "op": "lease_register", "client": self.client_id,
            "epoch": int(self._epoch),
            "parts": list(range(self._virtual_parts))})
        produce = (self._produce_host if self._mode == "binned"
                   else self._produce_host_staged)
        host_iter = _staged_iter(produce, self._prefetch,
                                 depth_gauge="cache.queue_depth")

        def produce_device(emit):
            try:
                for w in host_iter:
                    batch = self._stage(w)
                    telemetry.counter_add("h2d.batches", 1)
                    if not emit(batch):
                        return
            finally:
                host_iter.close()

        try:
            with telemetry.span("dataservice.epoch"):
                yield from _staged_iter(produce_device, 2,
                                        depth_gauge="h2d.queue_depth",
                                        device_feed=True)
        finally:
            telemetry.clear_trace_context()
            self._epoch += 1
