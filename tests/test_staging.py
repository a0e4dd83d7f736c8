"""DeviceStagingIter: static shapes, padding semantics, sharded layout."""
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dmlc_core_tpu as dt
from dmlc_core_tpu.parallel import make_mesh, data_sharding


@pytest.fixture
def libsvm_file(tmp_path):
    rows = []
    for i in range(1000):
        nnz = 1 + (i % 5)
        feats = " ".join(f"{(i * 7 + j) % 64}:{0.25 * (j + 1)}" for j in range(nnz))
        rows.append(f"{i % 2} {feats}")
    p = tmp_path / "stage.libsvm"
    p.write_text("\n".join(rows) + "\n")
    return str(p)


def test_static_shapes_and_bucketing(libsvm_file):
    it = dt.DeviceStagingIter(libsvm_file, batch_size=256, nnz_bucket=512)
    shapes = set()
    rows_total = 0
    for batch in it:
        assert batch.label.shape == (256,)
        assert batch.row_ptr.shape == (257,)
        assert batch.index.shape == batch.value.shape == batch.row_ids().shape
        assert batch.index.shape[0] % 512 == 0
        shapes.add(batch.index.shape[0])
        rows_total += int(batch.num_rows)
    assert rows_total == 1000
    # bucketing must keep the number of distinct nnz shapes tiny
    assert len(shapes) <= 3


def test_padding_is_inert(libsvm_file):
    """Sum of w[index]*value per row must ignore padding slots."""
    it = dt.DeviceStagingIter(libsvm_file, batch_size=128, nnz_bucket=1024)
    w = jnp.ones(64, jnp.float32)
    with dt.Parser(libsvm_file, 0, 1, "libsvm") as parser:
        expected_rows = []
        for block in parser:
            vals = block.values_or_ones()
            for r in range(block.size):
                lo, hi = int(block.offset[r]), int(block.offset[r + 1])
                expected_rows.append(vals[lo:hi].sum())
    got = []
    for batch in it:
        per_row = jax.ops.segment_sum(w[batch.index] * batch.value, batch.row_ids(),
                                      num_segments=batch.batch_size)
        got.extend(np.asarray(per_row)[: int(batch.num_rows)].tolist())
        # padding rows have weight 0
        np.testing.assert_array_equal(
            np.asarray(batch.weight)[int(batch.num_rows):], 0.0)
    np.testing.assert_allclose(got, expected_rows, rtol=1e-5)


def test_sharded_staging_over_mesh(libsvm_file):
    mesh = make_mesh()
    assert mesh.devices.size == 8, "conftest must provide 8 virtual devices"
    sharding = data_sharding(mesh)
    it = dt.DeviceStagingIter(libsvm_file, batch_size=512, nnz_bucket=4096,
                              sharding=sharding)
    batch = next(iter(it))
    assert batch.label.sharding.is_equivalent_to(sharding, ndim=1)
    # each device holds 512/8 rows of the label array
    shard_sizes = {s.data.shape[0] for s in batch.label.addressable_shards}
    assert shard_sizes == {64}


def test_multirank_staging_union(libsvm_file):
    """Two ranks' staged batches together cover all 1000 rows exactly once."""
    total = 0
    label_sum = 0.0
    for part in range(2):
        it = dt.DeviceStagingIter(libsvm_file, batch_size=128, part=part, num_parts=2,
                                  format="libsvm")
        for batch in it:
            total += int(batch.num_rows)
            label_sum += float(jnp.sum(batch.label * jnp.where(batch.weight > 0, 1.0, 0.0)))
    assert total == 1000
    assert label_sum == 500.0  # labels alternate 0/1


@pytest.fixture
def recordio_file(tmp_path):
    from dmlc_core_tpu.io import RecordIOWriter
    p = tmp_path / "stage.rec"
    payloads = [f"record-{i}-".encode() + bytes([i % 251]) * (i % 97)
                for i in range(800)]
    with RecordIOWriter(str(p)) as w:
        for r in payloads:
            w.write(r)
    return str(p), payloads


def test_record_staging_static_shapes_and_roundtrip(recordio_file):
    uri, payloads = recordio_file
    it = dt.RecordStagingIter(uri, records_cap=128, bytes_cap=1 << 14)
    got = []
    for batch in it:
        # static device shapes, always
        assert batch.bytes.shape == (1 << 14,)
        assert batch.bytes.dtype == jnp.uint8
        assert batch.offsets.shape == (129,)
        assert batch.offsets.dtype == jnp.int32
        host_bytes = np.asarray(batch.bytes)
        offs = np.asarray(batch.offsets)
        n = int(batch.num_records)
        assert 1 <= n <= 128
        for k in range(n):
            got.append(host_bytes[offs[k]:offs[k + 1]].tobytes())
        # padding offsets repeat the end; padding bytes are zero
        assert (offs[n:] == offs[n]).all()
        assert not host_bytes[offs[n]:].any()
    assert got == payloads
    assert it.bytes_read > 0


def test_record_staging_multirank_union(recordio_file):
    uri, payloads = recordio_file
    seen = []
    for part in range(3):
        it = dt.RecordStagingIter(uri, records_cap=64, bytes_cap=1 << 13,
                                  part=part, num_parts=3)
        for batch in it:
            host = np.asarray(batch.bytes)
            offs = np.asarray(batch.offsets)
            for k in range(int(batch.num_records)):
                seen.append(host[offs[k]:offs[k + 1]].tobytes())
    assert sorted(seen) == sorted(payloads)


def test_abandoned_iterator_does_not_deadlock(libsvm_file):
    """Breaking out of a staging loop must release the native cursor so a
    fresh iteration can start (regression: producer blocked in q.put while
    holding the cursor lock)."""
    import time
    it = dt.DeviceStagingIter(libsvm_file, batch_size=64, nnz_bucket=256,
                              prefetch=1)
    for batch in it:
        break  # abandon with the prefetch queue full
    t0 = time.monotonic()
    total = sum(int(b.num_rows) for b in it)  # must not hang
    assert total == 1000
    assert time.monotonic() - t0 < 30


def test_with_qid_stages_query_ids(tmp_path):
    """with_qid=True carries the libsvm qid: column per row (the ranking
    use case qid exists for, reference include/dmlc/data.h Row::qid)."""
    import numpy as np
    f = tmp_path / "ranked.libsvm"
    lines = []
    expect = []
    for q in (7, 7, 7, 12, 12, 30):
        y = len(lines) % 3
        lines.append(f"{y} qid:{q} 1:0.5 3:1.5")
        expect.append(q)
    f.write_text("\n".join(lines) + "\n")
    from dmlc_core_tpu.data import DeviceStagingIter
    it = DeviceStagingIter(str(f), batch_size=8, nnz_bucket=8, with_qid=True)
    batches = list(it)
    assert len(batches) == 1
    b = batches[0]
    assert b.qid is not None and b.qid.shape == (8,)
    got = np.asarray(b.qid)
    assert got[:6].tolist() == expect
    assert (got[6:] == 0).all()  # padding rows carry qid 0
    # default: no qid column staged
    it2 = DeviceStagingIter(str(f), batch_size=8, nnz_bucket=8)
    assert next(iter(it2)).qid is None


def test_cachefile_uri_sugar_through_staging(tmp_path):
    """`uri#cachefile` flows through the staged pipeline: epoch 1 tees
    chunks into the cache, epoch 2 replays from it — pinned by deleting
    the source file between epochs (reference cached_input_split.h)."""
    import numpy as np
    src = tmp_path / "train.libsvm"
    rng = np.random.default_rng(0)
    lines = [f"{i % 2} {int(rng.integers(0, 9))}:1 9:{i}.5"
             for i in range(200)]
    src.write_text("\n".join(lines) + "\n")
    cache = tmp_path / "train.cache"
    from dmlc_core_tpu.data import DeviceStagingIter
    it = DeviceStagingIter(f"{src}#{cache}", batch_size=64, nnz_bucket=64)

    def epoch_sums():
        rows = 0
        vsum = 0.0
        for b in it:
            rows += int(np.asarray(b.weight).sum())
            vsum += float(np.asarray(b.value).sum())
        return rows, vsum

    first = epoch_sums()
    assert first[0] == 200
    # parser-fed pipelines cache at the CHUNK level with a distinct suffix
    # (DiskRowIter owns the un-suffixed name for its parsed-page cache);
    # the finalized cache exists only under its real name (write-then-
    # rename: an interrupted first pass leaves only a .tmp file behind)
    chunk_cache = cache.with_name(cache.name + ".chunks")
    assert chunk_cache.exists() and chunk_cache.stat().st_size > 0
    assert not chunk_cache.with_name(chunk_cache.name + ".tmp").exists()
    src.unlink()  # epoch 2 must come from the cache
    second = epoch_sums()
    assert second[0] == 200
    np.testing.assert_allclose(second[1], first[1], rtol=1e-6)



# ---- parallel sharded staging (num_workers > 1) -----------------------------


def _drain_bits(it):
    """Every staged array of every batch, as bytes (bit-exact comparison)."""
    out = []
    for b in it:
        out.append(tuple(np.asarray(x).tobytes() for x in
                         (b.label, b.weight, b.row_ptr, b.index, b.value)))
    return out


def test_parallel_workers_bitwise_deterministic(libsvm_file):
    """reorder=True: staged batches are BIT-IDENTICAL for any worker count
    (packing is a pure function of the row stream, and the sharded pool
    re-emits parsed blocks in virtual-part order)."""
    ref = _drain_bits(dt.DeviceStagingIter(libsvm_file, batch_size=128,
                                           nnz_bucket=512))
    assert len(ref) == 8
    for nw in (2, 4):
        got = _drain_bits(dt.DeviceStagingIter(
            libsvm_file, batch_size=128, nnz_bucket=512, num_workers=nw))
        assert got == ref, f"num_workers={nw} diverged from single-worker"


def _parser_rows(uri):
    """Flattened per-row stream of a native parser (block boundaries differ
    across nthread, so rows — not blocks — are the unit of comparison)."""
    import ctypes

    from dmlc_core_tpu import _native
    L = _native.lib()
    h = ctypes.c_void_p()
    _native.check(L.DmlcTpuParserCreate(uri.encode(), 0, 1, b"libsvm",
                                        ctypes.byref(h)))
    blk = _native.RowBlockC()
    rows = []
    while _native.check(L.DmlcTpuParserNext(h, ctypes.byref(blk))) == 1:
        n = int(blk.size)
        off = np.ctypeslib.as_array(blk.offset, shape=(n + 1,))
        lab = np.ctypeslib.as_array(blk.label, shape=(n,))
        idx = np.ctypeslib.as_array(blk.index, shape=(int(off[n]),))
        val = np.ctypeslib.as_array(blk.value, shape=(int(off[n]),))
        for i in range(n):
            s, e = int(off[i]), int(off[i + 1])
            rows.append((lab[i].tobytes(), idx[s:e].tobytes(),
                         val[s:e].tobytes()))
    L.DmlcTpuParserFree(h)
    return rows


def test_parse_pool_nthread_bitwise_deterministic(libsvm_file):
    """The persistent parse pool must not change the row stream: splitting a
    chunk over 2 or 4 pool workers yields bit-identical rows to nthread=1."""
    ref = _parser_rows(f"{libsvm_file}?nthread=1")
    assert len(ref) == 1000
    for nt in (2, 4):
        got = _parser_rows(f"{libsvm_file}?nthread={nt}")
        assert got == ref, f"nthread={nt} diverged from nthread=1"


def test_parse_pool_under_sharded_staging_deterministic(libsvm_file):
    """nthread x num_workers grid: staged batches stay bit-identical when the
    parse pool and the sharded worker pool are combined."""
    ref = _drain_bits(dt.DeviceStagingIter(libsvm_file, batch_size=128,
                                           nnz_bucket=512))
    for nt in (2, 4):
        for nw in (1, 4):
            got = _drain_bits(dt.DeviceStagingIter(
                f"{libsvm_file}?nthread={nt}", batch_size=128,
                nnz_bucket=512, num_workers=nw))
            assert got == ref, f"nthread={nt} num_workers={nw}"


def test_parallel_workers_counters_and_completion_order(libsvm_file):
    """counters exposes the per-stage pipeline breakdown; reorder=False
    still covers every row exactly once (order unspecified)."""
    it = dt.DeviceStagingIter(libsvm_file, batch_size=128, nnz_bucket=512,
                              num_workers=4, prefetch_depth=3)
    rows = sum(int(b.num_rows) for b in it)
    assert rows == 1000
    c = it.counters
    assert c["num_workers"] == 4 and c["reorder"] and c["prefetch_depth"] == 3
    assert c["batches"] == 8 and c["batches_staged"] >= 8
    assert c["bytes_read"] > 0
    for k in ("native_s", "host_wait_s", "stage_s", "emit_wait_s"):
        assert c[k] >= 0.0, k
    it2 = dt.DeviceStagingIter(libsvm_file, batch_size=128, nnz_bucket=512,
                               num_workers=4, reorder=False)
    assert sum(int(b.num_rows) for b in it2) == 1000


def test_parallel_abandoned_iterator_does_not_deadlock(libsvm_file):
    """Early break with a 4-worker pool: the pool must shut down cleanly
    and the next epoch must restart from the top (BeforeFirst over the
    sharded pool), not hang on blocked producers."""
    import time
    it = dt.DeviceStagingIter(libsvm_file, batch_size=64, nnz_bucket=256,
                              num_workers=4, prefetch=1)
    for batch in it:
        break  # abandon with workers mid-flight and the queue full
    t0 = time.monotonic()
    total = sum(int(b.num_rows) for b in it)
    assert total == 1000
    assert time.monotonic() - t0 < 30


def test_parallel_native_error_propagates(tmp_path):
    """A parse error inside ONE pool worker must surface to the consumer
    as the original native error, not wedge the other workers."""
    f = tmp_path / "bad.libsvm"
    f.write_text("\n".join(["1 1:1"] * 200 + ["1 3000000000:1"]
                           + ["1 2:1"] * 200) + "\n")
    it = dt.DeviceStagingIter(str(f), batch_size=64, nnz_bucket=64,
                              num_workers=4)
    with pytest.raises(RuntimeError, match="feature id"):
        for _ in it:
            pass


def test_record_staging_parallel_deterministic(recordio_file):
    """RecordStagingIter's Python-side part pool: record stream identical
    across worker counts (reorder=True)."""
    uri, payloads = recordio_file

    def drain(nw):
        it = dt.RecordStagingIter(uri, records_cap=64, bytes_cap=1 << 13,
                                  num_workers=nw)
        got = []
        for b in it:
            host = np.asarray(b.bytes)
            offs = np.asarray(b.offsets)
            for k in range(int(b.num_records)):
                got.append(host[offs[k]:offs[k + 1]].tobytes())
        return got

    ref = drain(1)
    assert ref == payloads
    assert drain(2) == ref
    assert drain(4) == ref


def test_parallel_parts_pool_order_error_and_close():
    """The shared worker-pool machinery itself: deterministic part-order
    re-emission, arrival-order coverage, worker-exception propagation,
    and prompt shutdown when the consumer closes early."""
    import time
    from dmlc_core_tpu.data.staging import _parallel_parts_iter

    def open_part(j):
        yield from range(10 * j, 10 * j + 3)

    want = [v for j in range(5) for v in range(10 * j, 10 * j + 3)]
    for nw in (1, 2, 4):
        got = list(_parallel_parts_iter(open_part, 5, nw, True, 4))
        assert got == want, f"num_workers={nw}"
    # arrival order: unspecified order, exact multiset coverage
    got = list(_parallel_parts_iter(open_part, 5, 3, False, 4))
    assert sorted(got) == want

    def bad_part(j):
        if j == 3:
            raise ValueError("boom in part 3")
        yield j

    with pytest.raises(ValueError, match="boom in part 3"):
        list(_parallel_parts_iter(bad_part, 6, 4, True, 4))

    it = _parallel_parts_iter(open_part, 64, 4, True, 2)
    assert next(it) == 0
    t0 = time.monotonic()
    it.close()  # workers blocked on a full buffer must unblock and join
    assert time.monotonic() - t0 < 10


def test_parallel_parts_pool_full_buffer_part_boundary():
    """Regression: with the buffer saturated across a part boundary, the
    consumer's emit-part advance must wake producers whose full-buffer
    exemption just became true, or the pool wedges with every thread
    asleep.  max_buffered=1 makes a full buffer at every boundary the
    common case rather than a scheduling fluke."""
    from dmlc_core_tpu.data.staging import _parallel_parts_iter

    def open_part(j):
        yield from ((j, k) for k in range(7))

    want = [(j, k) for j in range(16) for k in range(7)]
    for _ in range(20):
        for nw in (2, 4):
            got = list(_parallel_parts_iter(open_part, 16, nw, True,
                                            max_buffered=1))
            assert got == want


# ---- stall watchdog over live staging ---------------------------------------

def test_watchdog_no_false_positive_on_slow_epoch(libsvm_file):
    """A slow-but-progressing epoch must never trip the watchdog: the
    deadline is measured from the LAST progress event, not epoch start.
    buffer_mb=1 keeps the pool starved so the pipeline runs as slowly as it
    ever will, and the consumer adds its own think time per batch."""
    from dmlc_core_tpu import telemetry

    stalls0 = telemetry.watchdog_stall_count()
    with telemetry.watchdog(deadline_s=2.0, poll_s=0.1):
        it = dt.DeviceStagingIter(libsvm_file, batch_size=64, nnz_bucket=256,
                                  num_workers=2, buffer_mb=1)
        rows = 0
        for b in it:
            rows += int(b.num_rows)
            time.sleep(0.05)  # a "slow" consumer, still far under 2 s
        assert rows == 1000
    assert telemetry.watchdog_stall_count() == stalls0


def test_watchdog_flags_paused_consumer(libsvm_file, tmp_path):
    """Acceptance: injecting a stall by pausing the consumer mid-epoch
    produces a flight-record JSON naming the stalled stage."""
    from dmlc_core_tpu import telemetry

    if not telemetry.enabled():
        pytest.skip("watchdog is compiled out")
    dump = tmp_path / "flight.json"
    stalls0 = telemetry.watchdog_stall_count()
    with telemetry.watchdog(deadline_s=0.5, poll_s=0.1, policy="warn",
                            dump_path=str(dump)):
        it = dt.DeviceStagingIter(libsvm_file, batch_size=64, nnz_bucket=256,
                                  num_workers=2)
        rows = 0
        for i, b in enumerate(it):
            rows += int(b.num_rows)
            if i == 2:
                # consumer pauses: every queue upstream tops off, then
                # nothing moves until the watchdog deadline expires
                deadline = time.monotonic() + 15.0
                while (telemetry.watchdog_stall_count() == stalls0
                       and time.monotonic() < deadline):
                    time.sleep(0.1)
        assert rows == 1000  # pipeline resumes after the pause: warn policy
    assert telemetry.watchdog_stall_count() > stalls0
    rec = json.loads(dump.read_text())
    # staged batches sat ready in the device feed while nothing progressed,
    # so the record names the h2d handoff, not whichever upstream stage
    # happened to fill its buffer first
    assert rec["stalled_stage"] == "h2d"
    assert rec["enabled"] is True
    assert {s["stage"] for s in rec["stages"]} == {
        "split", "parse", "shard", "pack", "record", "h2d"}
    last = telemetry.last_flight_record()
    assert last is not None and last["stalled_stage"] == rec["stalled_stage"]


# ---- batch lineage ----------------------------------------------------------


def test_lineage_minted_untraced_and_tracing_bit_identity(libsvm_file):
    """Lineage ids are a pure function of the partitioning: present with
    tracing off, identical with tracing on — and the staged batches
    themselves are bit-identical either way (instrumentation never
    touches data)."""
    from dmlc_core_tpu import telemetry

    def drain(it):
        bits, lin = [], []
        for b in it:
            bits.append(tuple(np.asarray(x).tobytes() for x in
                              (b.label, b.weight, b.row_ptr, b.index,
                               b.value)))
            lin.append(telemetry.lineage(b))
        return bits, lin

    ref_bits, ref_lin = drain(dt.DeviceStagingIter(
        libsvm_file, batch_size=128, nnz_bucket=512, num_workers=2))
    assert len(ref_bits) == 8
    # minted even with tracing off; first batch = virtual part 0, chunk 0
    assert all(lin >= 0 for lin in ref_lin)
    assert ref_lin[0] == 0
    telemetry.trace_start()
    try:
        got_bits, got_lin = drain(dt.DeviceStagingIter(
            libsvm_file, batch_size=128, nnz_bucket=512, num_workers=2))
    finally:
        telemetry.trace_stop()
    assert got_bits == ref_bits, "tracing changed staged bytes"
    assert got_lin == ref_lin, "tracing changed lineage ids"


FEED_SPANS = ("pack.next", "h2d.host_wait", "h2d.stage_batch",
              "h2d.device_put", "h2d.emit_wait", "feed.handoff", "feed.wait")


def test_every_feed_span_carries_its_batch_lineage(tmp_path):
    """A two-batch file through the sharded pool with the ring on: each
    hand-off of each batch is one span under the batch's lineage id, and the
    native stages behind it carry the chunk's (from the chunk in hand, no
    trace context set)."""
    from dmlc_core_tpu import telemetry
    if not telemetry.enabled():
        pytest.skip("tracing is compiled out")
    path = tmp_path / "two.libsvm"
    path.write_text("".join(
        f"{i % 2} {i % 50}:1 {50 + i % 7}:0.5\n" for i in range(512)))
    it = dt.DeviceStagingIter(str(path), batch_size=256, nnz_bucket=512,
                              num_workers=2)
    telemetry.trace_start()
    try:
        lineages = [telemetry.lineage(b) for b in it]
    finally:
        telemetry.trace_stop()
    assert len(lineages) == 2 and len(set(lineages)) == 2
    assert all(x >= 0 for x in lineages)
    assert telemetry.get_trace_context()[0] == 0
    spans = {}
    for e in telemetry.trace_dump()["traceEvents"]:
        spans.setdefault(e["name"], []).append(
            e.get("args", {}).get("lineage", -1))
    for name in FEED_SPANS:
        got = [x for x in spans[name] if x >= 0]
        assert sorted(got) == sorted(lineages), (name, spans[name])
    # (the batcher packs ahead from its making on and again after the
    # epoch's rewind: a batch can be packed twice)
    assert set(spans["pack.batch"]) - {-1} == set(lineages)
    # the stream's end is a wait and a pack too, for no batch
    assert spans["feed.wait"].count(-1) == 1
    for lineage in lineages:
        assert lineage in spans["parse.chunk"], spans["parse.chunk"]
        assert (lineage >> 32) << 32 in spans["shard.part"]
    assert -1 not in spans["parse.chunk"] + spans["shard.part"]


def test_counters_are_the_registrys_readings(libsvm_file):
    """``counters`` serves the stager's breakdown from the registry's own
    h2d counters since the epoch began, not from a second set of clocks."""
    from dmlc_core_tpu import telemetry
    if not telemetry.enabled():
        pytest.skip("counters read 0 with telemetry compiled out")
    it = dt.DeviceStagingIter(libsvm_file, batch_size=128, nnz_bucket=512)
    assert "batches" not in it.counters         # no epoch yet
    names = ("h2d.wait_us", "h2d.busy_us", "h2d.emit_wait_us", "h2d.batches")
    for _epoch in range(2):
        before = [telemetry.counter_get(n) for n in names]
        assert sum(int(b.num_rows) for b in it) == 1000
        moved = [telemetry.counter_get(n) - b for n, b in zip(names, before)]
        c = it.counters
        assert c["batches"] == moved[3] == 8
        assert [c["host_wait_s"], c["stage_s"], c["emit_wait_s"]] == [
            m / 1e6 for m in moved[:3]]
        assert c["native_s"] >= 0.0
