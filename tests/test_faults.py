"""Deterministic fault injection + the retrying IO/staging substrate.

Exercises doc/robustness.md end to end from Python: armed fault points and
real server misbehavior (5xx storms, mid-body drops) must be absorbed by
the retry substrate with byte-exact results and visible counters; corrupt
RecordIO spans must degrade to skips only when ``recover=True`` is asked
for; the sharded staging pool must re-parse faulted parts bit-identically.
"""
import contextlib
import socket
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np
import pytest

import dmlc_core_tpu as dt
from dmlc_core_tpu import faultinject, telemetry
from dmlc_core_tpu._native import NativeError
from dmlc_core_tpu.io import RecordIOReader, RecordIOWriter, open_seek_stream

faults_on = pytest.mark.skipif(
    not faultinject.compiled_in(),
    reason="native library built with -DDMLCTPU_FAULTS=0")


# ---- the fault-point API itself ---------------------------------------------


def test_fault_api_compiled_out_contract():
    if faultinject.compiled_in():
        pytest.skip("fault injection compiled in")
    # stubs: nonempty spec refuses, snapshot reports disabled
    with pytest.raises(NativeError):
        faultinject.arm("io.ranged.read=err@1.0")
    assert faultinject.snapshot() == {"enabled": False}
    assert faultinject.injected_total() == 0
    faultinject.disarm()  # no-op, must not raise


@faults_on
def test_fault_arm_snapshot_and_atomicity():
    faultinject.arm("io.ranged.read=err@0.5:n=3;seed=42")
    try:
        snap = faultinject.snapshot()
        assert snap["enabled"] and snap["armed"]
        assert snap["seed"] == 42
        points = {p["name"]: p for p in snap["points"]}
        assert points["io.ranged.read"]["armed"]
        assert points["io.ranged.read"]["mode"] == "err"
        # malformed spec: raises and leaves the previous arming untouched
        with pytest.raises(NativeError, match="unknown mode"):
            faultinject.arm("io.ranged.read=wat@0.5")
        snap2 = faultinject.snapshot()
        assert snap2["armed"] and snap2["seed"] == 42
    finally:
        faultinject.disarm()
    assert not faultinject.snapshot()["armed"]


@faults_on
def test_armed_context_manager_disarms_on_error():
    with pytest.raises(RuntimeError, match="boom"):
        with faultinject.armed("recordio.magic=corrupt@0.1;seed=1"):
            assert faultinject.snapshot()["armed"]
            raise RuntimeError("boom")
    assert not faultinject.snapshot()["armed"]


# ---- an HTTP range server the native http:// backend can read ---------------
#
# The ranged-read path (HttpFileSystem -> RangedReadStream) HEADs for the
# size, then GETs with "Range: bytes=N-"; the server must answer 206 with
# the suffix.  Class attributes script misbehavior for one test at a time.


class _RangeHandler(BaseHTTPRequestHandler):
    payload = b""
    storm_503 = 0    # next N GETs answer 503 (with Retry-After)
    drop_after = 0   # next GET claims the full length but sends this many bytes
    gets = 0

    def log_message(self, *args):  # noqa: D102 — silence request logging
        pass

    def do_HEAD(self):
        self.send_response(200)
        self.send_header("Content-Length", str(len(type(self).payload)))
        self.end_headers()

    def do_GET(self):
        cls = type(self)
        cls.gets += 1
        if cls.storm_503 > 0:
            cls.storm_503 -= 1
            self.send_response(503)
            self.send_header("Retry-After", "0")
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        body, start = cls.payload, 0
        rng = self.headers.get("Range")
        if rng and rng.startswith("bytes="):
            start = int(rng.split("=", 1)[1].split("-", 1)[0])
            body = cls.payload[start:]
            self.send_response(206)
            self.send_header(
                "Content-Range",
                f"bytes {start}-{len(cls.payload) - 1}/{len(cls.payload)}")
        else:
            self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if cls.drop_after and len(body) > cls.drop_after:
            sent, cls.drop_after = body[:cls.drop_after], 0
            self.wfile.write(sent)
            self.wfile.flush()
            with contextlib.suppress(OSError):
                self.connection.shutdown(socket.SHUT_RDWR)
            self.close_connection = True
            return
        self.wfile.write(body)


@contextlib.contextmanager
def _range_server(payload, **behavior):
    class Handler(_RangeHandler):  # fresh class: no cross-test state
        pass

    Handler.payload = payload
    for key, value in behavior.items():
        setattr(Handler, key, value)
    srv = HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        yield srv.server_address[1], Handler
    finally:
        srv.shutdown()
        srv.server_close()


_PAYLOAD = b"".join(b"line-%d-%s\n" % (i, b"x" * (i % 53)) for i in range(4000))


@faults_on
def test_ranged_read_fault_point_is_absorbed():
    with _range_server(_PAYLOAD) as (port, handler):
        before = telemetry.counter_get("io.retry")
        # rate 1.0 with n=2: the first two ranged reads fail, deterministic
        # regardless of seed, and can never exhaust the 4-attempt budget
        with faultinject.armed("io.ranged.read=err@1.0:n=2;seed=7"):
            with open_seek_stream(f"http://127.0.0.1:{port}/data.txt") as s:
                got = s.read()
            injected = faultinject.injected_total()
        assert got == _PAYLOAD
        assert injected >= 2
        assert telemetry.counter_get("io.retry") >= before + 2


def test_http_5xx_storm_absorbed():
    # no fault point needed: the server itself throttles.  A storm shorter
    # than the retry budget must be invisible to the caller.
    with _range_server(_PAYLOAD, storm_503=2) as (port, handler):
        before = telemetry.counter_get("io.retry")
        with open_seek_stream(f"http://127.0.0.1:{port}/data.txt") as s:
            got = s.read()
        assert got == _PAYLOAD
        assert handler.storm_503 == 0  # the storm really happened
        assert telemetry.counter_get("io.retry") >= before + 2


def test_http_midbody_drop_resumes_at_cursor():
    with _range_server(_PAYLOAD, drop_after=len(_PAYLOAD) // 3) as (
            port, handler):
        with open_seek_stream(f"http://127.0.0.1:{port}/data.txt") as s:
            got = s.read()
        assert got == _PAYLOAD
        assert handler.gets >= 2  # initial + resumed request


# ---- RecordIO recover mode --------------------------------------------------


def _frame_offset(payloads, k):
    """Frame offset of record k (cflag-0 records, magic-free payloads)."""
    off = 0
    for r in payloads[:k]:
        off += 8 + ((len(r) + 3) & ~3)
    return off


@pytest.fixture
def corrupt_recordio(tmp_path):
    payloads = [b"rec-%d-%s" % (i, b"q" * (i % 17)) for i in range(120)]
    path = tmp_path / "corrupt.rec"
    with RecordIOWriter(str(path)) as w:
        for r in payloads:
            w.write(r)
    raw = bytearray(path.read_bytes())
    raw[_frame_offset(payloads, 11)] ^= 0x5A  # break record 11's magic
    path.write_bytes(bytes(raw))
    return str(path), payloads


def test_recordio_recover_skips_corrupt_span(corrupt_recordio):
    path, payloads = corrupt_recordio
    with pytest.raises(NativeError):
        with RecordIOReader(path) as r:  # strict: corrupt span is fatal
            list(r)
    before = telemetry.counter_get("record.corrupt_skipped")
    with RecordIOReader(path, recover=True) as r:
        got = list(r)
        assert r.corrupt_skipped >= 1
    assert got == payloads[:11] + payloads[12:]
    assert telemetry.counter_get("record.corrupt_skipped") > before


def test_record_staging_recover_completes(corrupt_recordio):
    path, payloads = corrupt_recordio
    with pytest.raises(NativeError):
        for _ in dt.RecordStagingIter(path, records_cap=32, bytes_cap=1 << 12):
            pass
    it = dt.RecordStagingIter(path, records_cap=32, bytes_cap=1 << 12,
                              recover=True)
    got = []
    for batch in it:
        host = np.asarray(batch.bytes)
        offs = np.asarray(batch.offsets)
        for k in range(int(batch.num_records)):
            got.append(host[offs[k]:offs[k + 1]].tobytes())
    assert got == payloads[:11] + payloads[12:]


# ---- sharded staging under worker faults ------------------------------------


def _drain_bits(it):
    return [tuple(np.asarray(x).tobytes() for x in
                  (b.label, b.weight, b.row_ptr, b.index, b.value))
            for b in it]


@pytest.fixture
def libsvm_file(tmp_path):
    rows = []
    for i in range(1000):
        feats = " ".join(f"{(i * 7 + j) % 64}:{0.25 * (j + 1)}"
                         for j in range(1 + i % 5))
        rows.append(f"{i % 2} {feats}")
    p = tmp_path / "faults.libsvm"
    p.write_text("\n".join(rows) + "\n")
    return str(p)


@faults_on
def test_sharded_staging_reparse_is_bit_identical(libsvm_file):
    ref = _drain_bits(dt.DeviceStagingIter(libsvm_file, batch_size=128,
                                           nnz_bucket=512))
    before = telemetry.counter_get("shard.part_retries")
    # The parser's pool starts when the iterator is made and again at the
    # epoch's ``BeforeFirst``; on a busy host the first, discarded start
    # gets far enough to take both faults, and the epoch that counts then
    # sees none (ROADMAP D11: 13 of 150 epochs under 16 busy loops).  So an
    # epoch is run again until one re-parses; every one must be identical.
    for _ in range(8):
        with faultinject.armed("shard.worker.chunk=err@1.0:n=2;seed=3"):
            got = _drain_bits(dt.DeviceStagingIter(
                libsvm_file, batch_size=128, nnz_bucket=512, num_workers=3))
        assert got == ref, "faulted epoch diverged from clean epoch"
        if telemetry.counter_get("shard.part_retries") > before:
            break
    assert telemetry.counter_get("shard.part_retries") >= before + 1
    assert telemetry.counter_get("fault.injected") >= 2


# ---- the binned-cache build under write faults ------------------------------


def _drain_binned_bits(it):
    return [tuple(np.asarray(x).tobytes() for x in
                  (b.label, b.weight, b.row_ptr, b.index, b.ebin, b.emask))
            for b in it]


def _binned_iter(path, cache, **kw):
    from dmlc_core_tpu.models import QuantileBinner
    binner = QuantileBinner(num_bins=16, missing_aware=True, sketch_size=64,
                            sketch_seed=3)
    return dt.BinnedStagingIter(path, binner, cache=cache, batch_size=128,
                                nnz_bucket=512, **kw)


@faults_on
def test_cache_write_short_one_shot_retries_build(libsvm_file, tmp_path):
    """A single injected short write (crash mid-frame) must cost one failed
    attempt, then the in-place retry builds a VALID cache and the epoch
    stream is bit-identical to a fault-free run."""
    ref = _drain_binned_bits(_binned_iter(libsvm_file,
                                          str(tmp_path / "clean.bincache")))
    cache = tmp_path / "faulted.bincache"
    it = _binned_iter(libsvm_file, str(cache))
    failed0 = telemetry.counter_get("cache.build_failed")
    with faultinject.armed("cache.write.short=err@1.0:n=1;seed=7"):
        got = _drain_binned_bits(it)
    assert got == ref
    assert telemetry.counter_get("cache.build_failed") == failed0 + 1
    assert not it._fallback_text
    assert cache.exists()  # the retry's build survived and was renamed in
    # and the survivor serves plain hits from here on
    rebuilds0 = telemetry.counter_get("cache.rebuilds")
    assert _drain_binned_bits(it) == ref
    assert telemetry.counter_get("cache.rebuilds") == rebuilds0


@faults_on
def test_cache_write_short_sustained_degrades_to_text(libsvm_file, tmp_path):
    """With the fault sustained, both build attempts die; the epoch must
    degrade to the text-parse path with a bit-identical batch stream, leave
    no cache behind, and the NEXT epoch (fault gone) builds normally."""
    ref = _drain_binned_bits(_binned_iter(libsvm_file,
                                          str(tmp_path / "clean.bincache")))
    cache = tmp_path / "doomed.bincache"
    it = _binned_iter(libsvm_file, str(cache))
    failed0 = telemetry.counter_get("cache.build_failed")
    with faultinject.armed("cache.write.short=err@1.0;seed=7"):
        got = _drain_binned_bits(it)
    assert got == ref, "degraded text epoch diverged from the cached stream"
    assert it._fallback_text
    assert telemetry.counter_get("cache.build_failed") >= failed0 + 2
    assert not cache.exists()  # tmp file cleaned up, nothing torn left over
    # disarmed: the same iterator recovers by building for real
    assert _drain_binned_bits(it) == ref
    assert not it._fallback_text
    assert cache.exists()


@faults_on
def test_cache_codec_corrupt_degrades_to_text_not_torn(libsvm_file, tmp_path):
    """``cache.codec.corrupt`` flips one bit in a compressed record AFTER
    compression: the build succeeds and framing stays intact, but the first
    serve hits a digest mismatch.  The iterator must degrade — one counted
    rebuild, cache invalidated, the epoch served bit-identically from the
    text path — and never emit a torn stream.  The next epoch (fault gone)
    rebuilds for real and serves from the cache."""
    from dmlc_core_tpu.data.binned_cache import resolve_codec
    if resolve_codec("lz4") != "lz4":
        pytest.skip("libdmlctpu built with -DDMLCTPU_CODEC=0")
    ref = _drain_binned_bits(_binned_iter(libsvm_file,
                                          str(tmp_path / "clean.bincache")))
    cache = tmp_path / "poisoned.bincache"
    it = _binned_iter(libsvm_file, str(cache), codec="lz4")
    rebuilds0 = telemetry.counter_get("cache.rebuilds")
    with faultinject.armed("cache.codec.corrupt=err@1.0:n=1;seed=5"):
        got = _drain_binned_bits(it)
    assert got == ref, "degraded epoch diverged: a torn stream escaped"
    assert telemetry.counter_get("cache.rebuilds") == rebuilds0 + 1
    assert not cache.exists()  # the poisoned artifact was invalidated
    # disarmed: the rebuild is a first build (uncounted) and serves clean
    assert _drain_binned_bits(it) == ref
    assert telemetry.counter_get("cache.rebuilds") == rebuilds0 + 1
    assert cache.exists()


# ---- tracker-side degradation -----------------------------------------------


def test_metrics_pusher_counts_drops_and_backs_off():
    from dmlc_core_tpu.tracker.metrics import MetricsPusher
    # grab a port nothing listens on
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()
    before = telemetry.counter_get("tracker.pushes_dropped")
    pusher = MetricsPusher("127.0.0.1", dead_port, rank=0, interval_s=30.0)
    try:
        assert pusher.push() is False
        assert pusher.pushes_dropped >= 1
        assert telemetry.counter_get("tracker.pushes_dropped") > before
        # consecutive failures widen the loop's cadence beyond interval_s
        assert pusher._next_delay() > pusher.interval_s
        pusher._failure_streak = 0
        assert pusher._next_delay() == pusher.interval_s
    finally:
        pusher.close(final_push=False)
