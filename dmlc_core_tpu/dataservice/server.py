"""Staging worker: the CPU-only serving half of the data service.

One worker holds a registry of **served datasets** keyed by the client's
dataset spec (uri + binner config + batch geometry).  The first request for
a spec builds its binned epoch cache — sharded text parse, quantile sketch,
native bin+write — exactly once; every later fetch, from any client, streams
the quantized uint8+CSR blocks straight from the cache's mmap view.  That is
the fleet-wide "one parse per dataset, ever" property: ``cache.rebuilds``
on a worker stays at its single-build value no matter how many trainers
subscribe.  Specs without a binner are served through the text fallback —
the worker runs the native parse+pack pipeline per fetch and ships packed
staged batches over the wire codec instead.

Workers are elastic: they register with the tracker's LeaseBoard over the
0xff98 metrics channel, heartbeat on an interval, and ``close()`` drains
gracefully (leases requeue to survivors).  A worker killed outright is
discovered by the client's failed fetch (``lease_fail``) — either way the
epoch completes on the remaining fleet with exactly-once visitation.

Run one with ``python -m dmlc_core_tpu.dataservice.server`` under a
tracker env contract, or let ``dmlc-submit --data-service N`` spawn the
fleet next to the job.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import logging
import os
import socket
import threading
import time
from pathlib import Path
from typing import Dict, Optional

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.tracker import metrics as tracker_metrics

from . import protocol

LOGGER = logging.getLogger(__name__)

PORT_ENV = "DMLCTPU_DATASERVICE_PORT"
HOST_ENV = "DMLCTPU_DATASERVICE_HOST"
CACHE_DIR_ENV = "DMLCTPU_DATASERVICE_CACHE_DIR"


def spec_key(spec: dict) -> str:
    """Stable digest of a dataset spec — the served-dataset registry key
    and the cache file name, so equal specs share one cache.  ``codec``
    (absent = raw, the pre-codec wire) is part of the key: clients asking
    for differently-compressed caches must not collide on one file."""
    canon_dict = {k: spec.get(k) for k in ("uri", "format", "batch_size",
                                           "nnz_bucket", "nnz_max",
                                           "with_qid", "binner")}
    canon_dict["codec"] = spec.get("codec") or "raw"
    canon = json.dumps(canon_dict, sort_keys=True)
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


class _TokenBucket:
    """Outbound-bandwidth pacer for A/B benches: every sent payload is
    charged against a shared MB/s budget (50 ms burst allowance), so a
    loopback fetch behaves like a capped network link.  Enabled by the
    ``DMLCTPU_DATASERVICE_THROTTLE_MBPS`` env knob (doc/analysis.md)."""

    def __init__(self, mbps: float):
        self._rate = float(mbps) * 1e6
        self._cap = max(self._rate * 0.05, float(1 << 16))
        self._tokens = self._cap
        self._t = time.monotonic()
        self._lock = threading.Lock()

    def charge(self, nbytes: int) -> None:
        with self._lock:
            now = time.monotonic()
            self._tokens = min(self._cap,
                               self._tokens + (now - self._t) * self._rate)
            self._t = now
            self._tokens -= nbytes
            if self._tokens < 0:
                # pay the debt down before the next send; holding the lock
                # serializes all senders against the one simulated pipe
                time.sleep(-self._tokens / self._rate)
                self._t = time.monotonic()
                self._tokens = 0.0


_THROTTLES: Dict[str, _TokenBucket] = {}


def _throttle() -> Optional[_TokenBucket]:
    mbps = os.environ.get("DMLCTPU_DATASERVICE_THROTTLE_MBPS", "")
    if not mbps or float(mbps) <= 0:
        return None
    tb = _THROTTLES.get(mbps)
    if tb is None:
        tb = _THROTTLES[mbps] = _TokenBucket(float(mbps))
    return tb


class _ServedDataset:
    """One spec's serving state: the cache (built at most once, under the
    lock) or the text-fallback geometry."""

    def __init__(self, spec: dict, cache_dir: Path):
        self.spec = dict(spec)
        self.lock = threading.Lock()
        self.binned = spec.get("binner") is not None
        self.cache_path = str(cache_dir / (spec_key(spec) + ".bincache"))
        self._iter = None          # BinnedStagingIter, binned mode
        self._virtual_parts = 0    # staged mode

    def ensure(self) -> dict:
        """Build-once, then describe: returns the meta reply for this spec
        (cache meta + part ids on the binned path, just the virtual part
        count on the staged path)."""
        from dmlc_core_tpu.data.binned_cache import (BinnedStagingIter,
                                                     _source_total_bytes)
        from dmlc_core_tpu.data.staging import _pick_virtual_parts
        spec = self.spec
        with self.lock:
            if self.binned:
                if self._iter is None:
                    from dmlc_core_tpu.models import QuantileBinner
                    b = spec["binner"]
                    binner = QuantileBinner(
                        num_bins=int(b["num_bins"]),
                        missing_aware=bool(b["missing_aware"]),
                        sketch_size=int(b["sketch_size"]),
                        sketch_seed=int(b["sketch_seed"]))
                    it = BinnedStagingIter(
                        spec["uri"], binner, cache=self.cache_path,
                        batch_size=int(spec["batch_size"]),
                        nnz_bucket=int(spec["nnz_bucket"]),
                        nnz_max=int(spec.get("nnz_max", 0)),
                        format=spec.get("format", "auto"),
                        with_qid=bool(spec.get("with_qid", False)),
                        codec=spec.get("codec", "raw"))
                    it.ensure_cache()
                    if it._fallback_text:
                        raise RuntimeError(
                            "staging worker could not build the bin cache; "
                            "ask for the staged (text) mode instead")
                    self._iter = it
                it = self._iter
                return {"ok": True, "meta": it.meta,
                        "parts": {str(g): int(e["records"])
                                  for g, e in sorted(it._part_map.items())}}
            if not self._virtual_parts:
                total = _source_total_bytes(spec["uri"],
                                            spec.get("format", "auto"))
                self._virtual_parts = _pick_virtual_parts(total, 1)
            return {"ok": True, "virtual_parts": self._virtual_parts}

    def serve_fetch(self, sock: socket.socket, part: int) -> None:
        if self.binned:
            self._serve_blocks(sock, part)
        else:
            self._serve_staged(sock, part)

    def _serve_blocks(self, sock: socket.socket, part: int) -> None:
        """Stream one global virtual part's cache blocks exactly as stored,
        zero-copy from the reader's mmap view straight into sendall.

        ``set_decode(False)`` keeps compressed records compressed on the
        wire — the CLIENT decodes (``decode_block_payload``), so the codec's
        bandwidth win survives the hop and the worker never spends decode
        CPU on the serve path."""
        from dmlc_core_tpu.data.binned_cache import _NativeReader
        it = self._iter
        ent = it._part_map.get(int(part))
        tb = _throttle()
        sent = 0
        if ent is not None:
            r = _NativeReader(self.cache_path)
            try:
                r.set_decode(False)
                r.seek_to(int(ent["offset"]))
                for _ in range(int(ent["records"])):
                    buf = r.next_block_view()
                    if buf is None:
                        break
                    if tb is not None:
                        tb.charge(int(buf.nbytes) + 12)
                    protocol.write_frame(sock, protocol.FRAME_BLOCK,
                                         memoryview(buf))
                    sent += 1
                    telemetry.counter_add("dataservice.serve_blocks", 1)
                    telemetry.counter_add("dataservice.serve_bytes",
                                          int(buf.nbytes))
            finally:
                r.close()
        protocol.write_json_frame(sock, protocol.FRAME_END, {"blocks": sent})

    def _serve_staged(self, sock: socket.socket, part: int) -> None:
        """Text fallback: parse+pack one global virtual part natively and
        ship each owned batch through the wire codec."""
        import ctypes

        from dmlc_core_tpu._native import check
        from dmlc_core_tpu.data.staging import (_declare_batcher_sig,
                                                _StagedBatchOwnedC)
        spec = self.spec
        L = _declare_batcher_sig()
        h = ctypes.c_void_p()
        fmt = spec.get("format", "auto")
        check(L.DmlcTpuStagedBatcherCreate(
            spec["uri"].encode(), int(part), int(self._virtual_parts),
            ("libsvm" if fmt == "auto" else fmt).encode(),
            int(spec["batch_size"]), int(spec["nnz_bucket"]),
            int(spec.get("nnz_max", 0)), 0,
            1 if spec.get("with_qid") else 0, ctypes.byref(h)))
        sent = 0
        try:
            while True:
                c = _StagedBatchOwnedC()
                if check(L.DmlcTpuStagedBatcherNextOwned(
                        h, ctypes.byref(c))) != 1:
                    break
                try:
                    hdr, arena = protocol.pack_staged_wire(c)
                    tb = _throttle()
                    if tb is not None:
                        tb.charge(len(hdr) + len(arena) + 12)
                    protocol.write_frame(sock, protocol.FRAME_STAGED,
                                         hdr, arena)
                finally:
                    L.DmlcTpuStagedBatchFree(ctypes.c_void_p(c.batch))
                sent += 1
                telemetry.counter_add("dataservice.serve_blocks", 1)
                telemetry.counter_add("dataservice.serve_bytes",
                                      len(hdr) + int(c.arena_bytes))
        finally:
            L.DmlcTpuStagedBatcherFree(h)
        protocol.write_json_frame(sock, protocol.FRAME_END, {"blocks": sent})


class StagingWorker:
    """Accept loop + dispatcher registration for one staging worker."""

    def __init__(self, tracker_uri: Optional[str] = None,
                 metrics_port: Optional[int] = None,
                 host: Optional[str] = None, port: Optional[int] = None,
                 cache_dir: Optional[str] = None,
                 worker_id: Optional[str] = None,
                 heartbeat_s: Optional[float] = None,
                 register: bool = True):
        host = host or os.environ.get("DMLCTPU_DATASERVICE_HOST", "127.0.0.1")
        port = (int(os.environ.get("DMLCTPU_DATASERVICE_PORT", "0"))
                if port is None else port)
        self.cache_dir = Path(
            cache_dir or os.environ.get("DMLCTPU_DATASERVICE_CACHE_DIR")
            or (Path.home() / ".cache" / "dmlctpu" / "dataservice"))
        self.cache_dir.mkdir(parents=True, exist_ok=True)
        self._heartbeat_s = float(
            heartbeat_s if heartbeat_s is not None
            else os.environ.get("DMLCTPU_DATASERVICE_HEARTBEAT_S", "2.0"))
        self._timeout_s = float(
            os.environ.get("DMLCTPU_DATASERVICE_TIMEOUT_S", "30"))

        family = socket.getaddrinfo(host, None)[0][0]
        sock = socket.socket(family, socket.SOCK_STREAM)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        sock.bind((host, port))
        sock.listen(64)
        self.sock = sock
        self.host = host
        self.port = sock.getsockname()[1]
        self.worker_id = worker_id or \
            f"w-{socket.gethostname()}:{self.port}-{os.getpid()}"
        self._served: Dict[str, _ServedDataset] = {}
        self._lock = threading.Lock()
        self._closed = threading.Event()

        self._client: Optional[tracker_metrics.ShardClient] = None
        if register:
            mport = metrics_port if metrics_port is not None else \
                os.environ.get(tracker_metrics.METRICS_PORT_ENV)
            if mport:
                self._client = tracker_metrics.ShardClient(
                    tracker_uri or os.environ.get("DMLC_TRACKER_URI",
                                                  "127.0.0.1"),
                    int(mport), rank=tracker_metrics._env_rank())
                self._client.data_req({
                    "op": "worker_register", "worker": self.worker_id,
                    "host": self.host, "port": self.port})
                self._hb_thread = threading.Thread(
                    target=self._heartbeat_loop,
                    name="dmlctpu-dataservice-heartbeat", daemon=True)
                self._hb_thread.start()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="dmlctpu-dataservice-worker",
            daemon=True)
        self._accept_thread.start()
        LOGGER.info("staging worker %s serving on %s:%d",
                    self.worker_id, self.host, self.port)

    # ---- dispatcher liveness ------------------------------------------------

    def _heartbeat_loop(self) -> None:
        while not self._closed.wait(self._heartbeat_s):
            try:
                r = self._client.data_req({"op": "worker_heartbeat",
                                           "worker": self.worker_id})
                if not r.get("ok"):  # tracker restarted: introduce ourselves
                    self._client.data_req({
                        "op": "worker_register", "worker": self.worker_id,
                        "host": self.host, "port": self.port})
            except (OSError, ConnectionError, ValueError):
                pass  # tracker briefly away; lease_fail covers true death

    # ---- serving ------------------------------------------------------------

    def _accept_loop(self) -> None:
        while True:
            try:
                fd, _addr = self.sock.accept()
            except OSError:
                return  # closed
            t = threading.Thread(target=self._handle_conn, args=(fd,),
                                 daemon=True)
            t.start()

    def _handle_conn(self, fd: socket.socket) -> None:
        try:
            fd.settimeout(self._timeout_s)
            protocol.server_handshake(fd)
            req = protocol.read_req(fd)
            telemetry.counter_add("dataservice.requests", 1)
            self._handle_req(fd, req)
        except (ConnectionError, OSError, ValueError, KeyError) as e:
            telemetry.counter_add("dataservice.errors", 1)
            LOGGER.debug("dropped data-service request: %s", e)
        finally:
            try:
                fd.close()
            except OSError:
                pass

    def _handle_req(self, fd: socket.socket, req: dict) -> None:
        op = req.get("op")
        # adopt the client's trace context (when it sent one) so every
        # native parse/pack span this request triggers carries the client's
        # trace id and links causally under its epoch span in the tracker's
        # job-trace merge.  Advisory labeling: concurrent requests race on
        # the ambient context, last writer wins (doc/observability.md).
        # Restore (not clear) on the way out: an in-process worker must not
        # wipe the client's own epoch context.
        prev = telemetry.get_trace_context()
        adopted = telemetry.adopt_trace_context(req.get("trace"))
        try:
            if op == "ping":
                protocol.send_req(fd, {"ok": True, "worker": self.worker_id})
                return
            served = self._dataset(req["spec"])
            if op == "meta":
                try:
                    protocol.send_req(fd, served.ensure())
                except Exception as e:  # build failed: tell client, not TCP
                    telemetry.counter_add("dataservice.errors", 1)
                    protocol.send_req(fd, {"ok": False,
                                           "error": str(e)[-500:]})
                return
            if op == "fetch":
                served.ensure()
                try:
                    with telemetry.span("dataservice.serve"):
                        served.serve_fetch(fd, int(req["part"]))
                except (ConnectionError, OSError):
                    raise  # client went away mid-stream; nothing to send
                except Exception as e:
                    telemetry.counter_add("dataservice.errors", 1)
                    protocol.write_json_frame(fd, protocol.FRAME_ERROR,
                                              {"error": str(e)[-500:]})
                return
            protocol.send_req(fd, {"ok": False,
                                   "error": f"unknown op {op!r}"})
        finally:
            if adopted:
                telemetry.set_trace_context(*prev)

    def _dataset(self, spec: dict) -> _ServedDataset:
        key = spec_key(spec)
        with self._lock:
            served = self._served.get(key)
            if served is None:
                served = self._served[key] = _ServedDataset(spec,
                                                            self.cache_dir)
            return served

    def close(self, leave: bool = True) -> None:
        """Graceful drain: deregister (requeueing any leases) and stop."""
        if self._closed.is_set():
            return
        self._closed.set()
        if leave and self._client is not None:
            try:
                self._client.data_req({"op": "worker_leave",
                                       "worker": self.worker_id})
            except (OSError, ConnectionError, ValueError):
                pass
        try:
            self.sock.close()
        except OSError:
            pass
        self._accept_thread.join(timeout=5)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description="dmlctpu data-service staging worker")
    parser.add_argument("--host", default=None,
                        help=f"bind/advertise host (or ${HOST_ENV})")
    parser.add_argument("--port", type=int, default=None,
                        help=f"data channel port, 0 = ephemeral "
                             f"(or ${PORT_ENV})")
    parser.add_argument("--cache-dir", default=None,
                        help=f"bin cache directory (or ${CACHE_DIR_ENV})")
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    from dmlc_core_tpu.parallel import pin_host_only
    pin_host_only()
    worker = StagingWorker(host=args.host, port=args.port,
                           cache_dir=args.cache_dir)
    print(f"DATASERVICE_READY {worker.host}:{worker.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        worker.close()


if __name__ == "__main__":
    main()
