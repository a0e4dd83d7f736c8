"""Pipeline telemetry: snapshots, stall attribution, traces, log capture.

Every test also passes against a library built with ``DMLCTPU_TELEMETRY=0``:
value assertions are gated on :func:`telemetry.enabled`, while the API shape
(snapshots parse, traces are valid JSON, log capture works — the sink is
independent of the telemetry macro) is asserted unconditionally.
"""
import json
import time

import numpy as np
import pytest

import dmlc_core_tpu as dt
from dmlc_core_tpu import _native, telemetry


@pytest.fixture
def libsvm_file(tmp_path):
    rows = []
    for i in range(2000):
        nnz = 1 + (i % 4)
        feats = " ".join(f"{(i * 3 + j) % 32}:{0.5 * (j + 1)}" for j in range(nnz))
        rows.append(f"{i % 2} {feats}")
    p = tmp_path / "telemetry.libsvm"
    p.write_text("\n".join(rows) + "\n")
    return str(p)


@pytest.fixture
def recordio_file(tmp_path):
    p = tmp_path / "telemetry.rec"
    payloads = [bytes([i % 251]) * (20 + i % 60) for i in range(300)]
    with dt.RecordIOWriter(str(p)) as w:
        for r in payloads:
            w.write(r)
    return str(p), payloads


def drain(uri, **kw):
    with dt.Parser(uri, 0, 1, "libsvm") as parser:
        return sum(block.size for block in parser)


def test_snapshot_shape():
    snap = telemetry.snapshot()
    assert isinstance(snap, dict)
    assert snap["enabled"] == telemetry.enabled()
    if telemetry.enabled():
        assert isinstance(snap["counters"], dict)
        assert isinstance(snap["gauges"], dict)
        assert isinstance(snap["histograms"], dict)
        for h in snap["histograms"].values():
            assert set(h) == {"count", "sum", "buckets"}
            assert len(h["buckets"]) == 32


def test_counter_roundtrip():
    telemetry.counter_add("test.py_roundtrip", 5)
    telemetry.counter_add("test.py_roundtrip", 2)
    v = telemetry.counter_get("test.py_roundtrip")
    assert v >= 7 if telemetry.enabled() else v == 0


def test_counters_grow_during_parse(libsvm_file):
    before = telemetry.snapshot()
    assert drain(libsvm_file) == 2000
    delta = telemetry.counters_delta(before, telemetry.snapshot())
    if not telemetry.enabled():
        assert delta == {}
        return
    assert delta["parse.rows"] == 2000
    assert delta["parse.nnz"] == sum(1 + (i % 4) for i in range(2000))
    assert delta["parse.bytes"] > 0
    assert delta["split.bytes"] >= delta["parse.bytes"]
    assert delta["parse.chunks"] >= 1


def test_trace_during_staging_is_valid_chrome_json(libsvm_file):
    telemetry.trace_start()
    it = dt.DeviceStagingIter(libsvm_file, batch_size=256, nnz_bucket=512,
                              num_workers=2)
    rows = sum(int(b.num_rows) for b in it)
    telemetry.trace_stop()
    assert rows == 2000

    text = telemetry.trace_dump_json()
    doc = json.loads(text)  # acceptance: loads as Chrome trace-event JSON
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert set(ev) >= {"name", "cat", "ph", "pid", "tid", "ts", "dur"}
        assert ev["ts"] >= 0 and ev["dur"] >= 0
    if telemetry.enabled():
        names = {ev["name"] for ev in doc["traceEvents"]}
        assert "parse.block" in names
        assert "shard.part" in names
        assert "pack.batch" in names
        assert "h2d.stage_batch" in names
    else:
        assert doc["traceEvents"] == []


def test_python_spans_share_native_timeline():
    telemetry.trace_start()
    with telemetry.span("test.py_span"):
        pass
    telemetry.record_span("test.py_manual", 1234, 56)
    telemetry.trace_stop()
    events = telemetry.trace_dump()["traceEvents"]
    if not telemetry.enabled():
        assert events == []
        return
    by_name = {ev["name"]: ev for ev in events}
    assert "test.py_span" in by_name
    assert by_name["test.py_manual"]["ts"] == 1234
    assert by_name["test.py_manual"]["dur"] == 56
    # a new trace clears the buffer
    telemetry.trace_start()
    telemetry.trace_stop()
    assert telemetry.trace_dump()["traceEvents"] == []


def test_stall_attribution_staging(libsvm_file):
    before = telemetry.snapshot()
    it = dt.DeviceStagingIter(libsvm_file, batch_size=256, nnz_bucket=512,
                              num_workers=2)
    rows = sum(int(b.num_rows) for b in it)
    assert rows == 2000
    attr = telemetry.stall_attribution(before, telemetry.snapshot(), wall_s=1.0)

    assert set(attr) == {"stages", "bound", "bound_stage", "table", "wall_s",
                         "restarted", "io"}
    assert attr["restarted"] is False
    # local file, nothing armed: no retries, so the io pseudo-stage stays out
    # of the table and the raw totals are all zero
    assert attr["io"] == {"retry": 0, "giveup": 0, "retry_wait_s": 0.0,
                          "corrupt_skipped": 0, "part_retries": 0}
    assert set(attr["stages"]) == {"parse", "shard", "pack", "h2d"}
    for st in attr["stages"].values():
        assert st["busy_s"] >= 0.0 and st["wait_s"] >= 0.0
    if telemetry.enabled():
        # the sharded pool ran: parse is folded into shard, shares sum to 100
        assert attr["bound_stage"] in {"shard", "pack", "h2d"}
        assert abs(sum(attr["bound"].values()) - 100.0) < 1.0
        assert "-bound" in attr["table"]
        assert attr["bound_stage"] in attr["table"]
    else:
        assert attr["bound"] == {} and attr["table"] == ""
    text = telemetry.format_stall_table(attr)
    assert "stage" in text and "busy_s" in text


def test_unified_bytes_read(recordio_file):
    import os
    uri, payloads = recordio_file
    size = os.path.getsize(uri)
    for nw in (1, 2):
        before = telemetry.counter_get("record.bytes")
        it = dt.RecordStagingIter(uri, records_cap=64, bytes_cap=1 << 13,
                                  num_workers=nw)
        n = sum(int(b.num_records) for b in it)
        assert n == len(payloads)
        # telemetry-backed accounting covers the parallel per-part cursors
        # too, so both worker modes attribute at least one full pass of the
        # file to this iterator (the main handle's eager prefetch may add a
        # partial extra window; exact equality is deliberately not promised)
        assert it.bytes_read > 0
        if telemetry.enabled():
            assert it.bytes_read >= size
            # an iterator never reports more than the process-wide delta
            # spanning its lifetime
            assert it.bytes_read <= telemetry.counter_get("record.bytes") - before


def test_capture_logs():
    with telemetry.capture_logs(min_severity=2) as records:
        _native.log_emit(2, "warning line")
        _native.log_emit(3, "error line")
        _native.log_emit(1, "info line (below threshold)")
    assert [(s, m) for s, _, m in records] == [(2, "warning line"),
                                              (3, "error line")]
    # sink restored: emitting after the context must not append
    _native.log_emit(3, "after exit")
    assert len(records) == 2


def test_capture_logs_forward():
    seen = []
    with telemetry.capture_logs(min_severity=3,
                                forward=lambda s, w, m: seen.append(s)):
        _native.log_emit(2, "warn")
        _native.log_emit(3, "err")
    assert seen == [2, 3]  # forward sees everything, records are filtered


def test_reset_zeroes_counters(libsvm_file):
    drain(libsvm_file)
    telemetry.reset()
    snap = telemetry.snapshot()
    if telemetry.enabled():
        assert all(v == 0 for v in snap["counters"].values())
        assert all(h["count"] == 0 for h in snap["histograms"].values())


def test_gauge_roundtrip():
    telemetry.gauge_set("test.py_gauge", 7)
    telemetry.gauge_add("test.py_gauge", -3)
    v = telemetry.gauge_get("test.py_gauge")
    assert v == (4 if telemetry.enabled() else 0)
    if telemetry.enabled():
        assert telemetry.snapshot()["gauges"]["test.py_gauge"] == 4


def test_counters_delta_clamps_worker_restart():
    # a worker restart re-registers counters from zero; the delta must clamp
    # at zero (not report a huge negative interval) and the snapshots must
    # be taggable as restarted so callers don't silently trust them
    before = {"counters": {"parse.rows": 1000, "split.bytes": 500}}
    after = {"counters": {"parse.rows": 40, "split.bytes": 700}}
    assert telemetry.counters_delta(before, after) == {"parse.rows": 0,
                                                       "split.bytes": 200}
    assert telemetry.snapshot_restarted(before, after) is True
    assert telemetry.snapshot_restarted(after, after) is False
    # counters appearing for the first time are growth, not a restart
    assert telemetry.snapshot_restarted({"counters": {}}, after) is False
    attr = telemetry.stall_attribution(before, after, wall_s=1.0)
    assert attr["restarted"] is True


def test_window_measures_an_epoch(libsvm_file):
    with telemetry.window() as w:
        assert not w.closed and isinstance(w.before, dict)
        assert drain(libsvm_file) == 2000
    assert w.closed and w.wall_s > 0
    assert w.restarted is False
    assert w.attribution is not None and "bound_stage" in w.attribution
    if telemetry.enabled():
        assert w.delta["parse.rows"] == 2000
        assert w.bytes_processed() > 0
        assert w.mb_per_s() > 0
    else:
        assert w.delta == {} and w.mb_per_s() == 0.0


def test_window_restart_mid_window_clamps_and_flags(monkeypatch):
    # a worker restart mid-window re-registers counters from zero; the
    # closed window must clamp the backwards deltas, raise the restarted
    # flag, and carry it into the attribution so a consumer (the autotuner)
    # can refuse the poisoned sample instead of acting on a garbage rate
    snaps = iter([
        {"counters": {"parse.rows": 1000, "parse.busy_us": 9_000_000,
                      "h2d.busy_us": 50}},
        {"counters": {"parse.rows": 40, "parse.busy_us": 70_000,
                      "h2d.busy_us": 90}},
    ])
    monkeypatch.setattr(telemetry, "snapshot", lambda: next(snaps))
    with telemetry.window() as w:
        pass
    assert w.restarted is True
    assert w.delta["parse.rows"] == 0          # backwards counters clamp...
    assert w.delta["parse.busy_us"] == 0
    assert w.delta["h2d.busy_us"] == 40        # ...honest ones still count
    assert w.attribution["restarted"] is True


def test_stall_attribution_across_restart_keeps_surviving_stages():
    # the clamped stage contributes nothing; attribution falls to whatever
    # really moved in the interval instead of a giant negative artifact
    before = {"counters": {"parse.busy_us": 5_000_000, "parse.rows": 100}}
    after = {"counters": {"parse.busy_us": 1_000, "parse.rows": 2,
                          "h2d.busy_us": 2_000_000}}
    attr = telemetry.stall_attribution(before, after, wall_s=1.0)
    assert attr["restarted"] is True
    assert attr["stages"]["parse"]["busy_s"] == 0.0
    assert attr["bound_stage"] == "h2d"


def test_merge_snapshots_and_conservative_quantile():
    h_a = {"count": 1, "sum": 3, "buckets": [0] * 32}
    h_a["buckets"][2] = 1          # one observation of 3 (upper bound 4)
    h_b = {"count": 1, "sum": 100, "buckets": [0] * 32}
    h_b["buckets"][7] = 1          # one observation of 100 (upper bound 128)
    a = {"enabled": True, "counters": {"parse.rows": 5, "only.a": 1},
         "gauges": {"depth": 2}, "histograms": {"lat": h_a}}
    b = {"enabled": True, "counters": {"parse.rows": 7},
         "gauges": {"depth": 3}, "histograms": {"lat": h_b}}
    m = telemetry.merge_snapshots([a, b])
    assert m["counters"] == {"parse.rows": 12, "only.a": 1}
    assert m["gauges"] == {"depth": 5}
    lat = m["histograms"]["lat"]
    assert lat["count"] == 2 and lat["sum"] == 103
    assert lat["buckets"][2] == 1 and lat["buckets"][7] == 1
    # bucket upper bounds survive the merge, so quantile estimates are
    # conservative: never below the true quantile of the pooled events
    assert telemetry.histogram_quantile(lat, 0.5) >= 3    # true median: 3
    assert telemetry.histogram_quantile(lat, 1.0) >= 100  # true max: 100
    assert telemetry.histogram_quantile({"count": 0, "sum": 0,
                                         "buckets": [0] * 32}, 0.5) is None
    overflow = {"count": 1, "sum": 1, "buckets": [0] * 31 + [1]}
    assert telemetry.histogram_quantile(overflow, 0.5) == float("inf")


def test_watchdog_context_arms_and_disarms():
    assert telemetry.watchdog_running() is False
    with telemetry.watchdog(deadline_s=30.0):
        assert telemetry.watchdog_running() is telemetry.enabled()
        with telemetry.watchdog(deadline_s=1.0):  # nested: refcounts
            assert telemetry.watchdog_running() is telemetry.enabled()
        assert telemetry.watchdog_running() is telemetry.enabled()
    assert telemetry.watchdog_running() is False
    with pytest.raises(ValueError):
        with telemetry.watchdog(policy="explode"):
            pass


def test_flight_record_shape():
    rec = telemetry.flight_record("unit test")
    assert rec["enabled"] == telemetry.enabled()
    if not telemetry.enabled():
        return
    assert rec["reason"] == "unit test"
    stages = {s["stage"] for s in rec["stages"]}
    assert stages == {"split", "parse", "shard", "pack", "record", "h2d"}
    for s in rec["stages"]:
        assert s["age_us"] == -1  # unarmed: progress ages are meaningless
    assert rec["registry"]["enabled"] is True
    assert isinstance(rec["trace"]["traceEvents"], list)


def test_watchdog_detects_injected_stall(tmp_path):
    if not telemetry.enabled():
        pytest.skip("watchdog is compiled out")
    dump = tmp_path / "flight.json"
    stalls0 = telemetry.watchdog_stall_count()
    with telemetry.capture_logs(min_severity=3) as records:
        with telemetry.watchdog(deadline_s=0.2, poll_s=0.05, policy="warn",
                                dump_path=str(dump)):
            # one h2d batch, then nothing: the pipeline "wedged" right
            # after the device feed emitted its last batch
            telemetry.counter_add("h2d.batches", 1)
            deadline = time.monotonic() + 10.0
            while (telemetry.watchdog_stall_count() == stalls0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
    assert telemetry.watchdog_stall_count() > stalls0
    rec = telemetry.last_flight_record()
    assert rec is not None and rec["stalled_stage"] == "h2d"
    on_disk = json.loads(dump.read_text())
    assert on_disk["stalled_stage"] == "h2d"
    assert any("pipeline stall" in msg and "h2d" in msg
               for _, where, msg in records if where.startswith("watchdog"))


def test_telemetry_http_endpoints(libsvm_file):
    import urllib.error
    from urllib.request import urlopen

    from dmlc_core_tpu import telemetry_http

    drain(libsvm_file)  # make sure the registry has pipeline families
    with telemetry_http.serve(port=0) as srv:
        with urlopen(srv.url + "/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        _assert_prometheus_wellformed(text)
        if telemetry.enabled():
            assert "dmlctpu_parse_rows_total" in text
        with urlopen(srv.url + "/trace", timeout=10) as resp:
            assert "traceEvents" in json.loads(resp.read().decode())
        with urlopen(srv.url + "/flight?fresh=1", timeout=10) as resp:
            rec = json.loads(resp.read().decode())
            assert rec["enabled"] == telemetry.enabled()
        with urlopen(srv.url + "/snapshot", timeout=10) as resp:
            snap = json.loads(resp.read().decode())
            assert snap["enabled"] == telemetry.enabled()
        with urlopen(srv.url + "/healthz", timeout=10) as resp:
            assert resp.status == 200
            assert resp.read().decode() == "ok\n"
        # a worker endpoint has no trace_provider: /jobtrace must 404
        # with a pointer at /trace, not crash
        with pytest.raises(urllib.error.HTTPError) as ei:
            urlopen(srv.url + "/jobtrace", timeout=10)
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urlopen(srv.url + "/nope", timeout=10)
        assert ei.value.code == 404

    # with a trace_provider attached (the tracker's case), /jobtrace
    # serves the merged dump
    merged = {"traceEvents": [], "displayTimeUnit": "ms",
              "otherData": {"hosts": 0}}
    with telemetry_http.serve(port=0, trace_provider=lambda: merged) as srv:
        with urlopen(srv.url + "/jobtrace", timeout=10) as resp:
            assert json.loads(resp.read().decode()) == merged


def _assert_prometheus_wellformed(text):
    """Strict validity check for the classic text exposition format.

    Beyond line-shape this enforces what a real Prometheus scraper
    enforces: every sample belongs to its declared contiguous family and
    carries the right suffix for the family's type, no duplicate
    (name, labelset) samples, label syntax is valid, values parse as
    floats, and histogram series satisfy the format's invariants —
    `le` values strictly increasing with `+Inf` last, cumulative bucket
    counts non-decreasing, and `_count` exactly equal to the `+Inf`
    bucket."""
    import re

    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    label_re = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"$')
    typed = {}
    fam_order = []  # families in TYPE-line order, for contiguity
    cur_fam = None
    seen_samples = set()
    hist = {}  # (fam, labels-sans-le) -> [(le_float, cum_value)]
    hist_count = {}  # (fam, labels-sans-le) -> _count value
    for line in text.rstrip("\n").split("\n"):
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4, f"bad TYPE line: {line!r}"
            name, mtype = parts[2:4]
            assert name_re.match(name), f"bad family name {name!r}"
            assert mtype in ("counter", "gauge", "histogram")
            assert name not in typed, f"duplicate TYPE for {name}"
            typed[name] = mtype
            fam_order.append(name)
            cur_fam = name
        elif line.startswith("#"):
            continue
        else:
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                         r"(\{[^{}]*\})? (\S+)$", line)
            assert m, f"bad sample line: {line!r}"
            metric, labelblob, value = m.groups()
            float(value)  # must parse (raises on garbage)
            labels = ()
            if labelblob:
                parts = re.findall(r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]'
                                   r'|\\.)*"', labelblob[1:-1])
                rebuilt = ",".join(parts)
                assert rebuilt == labelblob[1:-1], \
                    f"bad label syntax: {labelblob!r}"
                for p in parts:
                    assert label_re.match(p), f"bad label pair {p!r}"
                labels = tuple(sorted(parts))
            key = (metric, labels)
            assert key not in seen_samples, f"duplicate sample {key}"
            seen_samples.add(key)
            fam = cur_fam or ""
            assert fam, f"sample {metric} before any TYPE line"
            mtype = typed[fam]
            if mtype == "histogram":
                assert metric in (fam + "_bucket", fam + "_sum",
                                  fam + "_count"), \
                    f"sample {metric} not a histogram series of {fam}"
                base = tuple(p for p in labels if not p.startswith('le='))
                if metric == fam + "_bucket":
                    le = [p for p in labels if p.startswith('le=')]
                    assert len(le) == 1, f"bucket without le: {line!r}"
                    raw = le[0][4:-1]
                    lef = float("inf") if raw == "+Inf" else float(raw)
                    hist.setdefault((fam, base), []).append(
                        (lef, float(value)))
                elif metric == fam + "_count":
                    hist_count[(fam, base)] = float(value)
            else:
                assert metric == fam, \
                    f"sample {metric} outside its family block {fam}"
                if mtype == "counter":
                    assert fam.endswith("_total"), \
                        f"counter family {fam} missing _total"
                    assert float(value) >= 0, f"negative counter: {line!r}"
    assert fam_order == sorted(set(fam_order)), "families not contiguous"
    for key, buckets in hist.items():
        les = [le for le, _ in buckets]
        assert les == sorted(les), f"le not increasing for {key}"
        assert les[-1] == float("inf"), f"missing +Inf bucket for {key}"
        cums = [c for _, c in buckets]
        assert cums == sorted(cums), f"buckets not cumulative for {key}"
        assert key in hist_count, f"histogram {key} missing _count"
        assert hist_count[key] == cums[-1], \
            f"_count != +Inf bucket for {key}"
    if telemetry.enabled():
        assert typed, "no TYPE lines in exposition"


def test_capture_logs_interleaved_thread_ordering():
    """Native and Python emitters racing on several threads: the captured
    stream must preserve each thread's emission order (the sink serializes
    under one mutex, so per-thread subsequences stay sorted)."""
    import threading

    n_per_thread = 200
    with telemetry.capture_logs(min_severity=2) as records:
        def native_emitter(tag):
            for i in range(n_per_thread):
                _native.log_emit(2, f"{tag}:{i}")

        def python_emitter(tag):
            # the Python-side path: route through the same sink via the
            # C API's log_emit — what telemetry.capture_logs forwards
            for i in range(n_per_thread):
                _native.log_emit(3, f"{tag}:{i}")

        threads = [threading.Thread(target=native_emitter, args=(f"n{t}",))
                   for t in range(2)]
        threads += [threading.Thread(target=python_emitter, args=(f"p{t}",))
                    for t in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(records) == 4 * n_per_thread
    by_tag = {}
    for _, _, msg in records:
        tag, idx = msg.rsplit(":", 1)
        by_tag.setdefault(tag, []).append(int(idx))
    assert set(by_tag) == {"n0", "n1", "p0", "p1"}
    for tag, seq in by_tag.items():
        assert seq == list(range(n_per_thread)), \
            f"thread {tag} order scrambled"


# ---- distributed tracing: context, lineage, exposition hardening ------------


def test_prometheus_text_strict_validity_multisource():
    """The exposition generator against a strict format parser: multiple
    labeled sources, hostile label values, and a histogram whose separate
    count atomic raced the bucket reads — the output must still satisfy
    every invariant a real scraper checks (in particular _count == +Inf
    bucket, derived from the buckets, not the racing count field)."""
    from dmlc_core_tpu.telemetry_http import prometheus_text

    hist = {"count": 999, "sum": 123,  # count deliberately != sum(buckets)
            "buckets": [2, 3] + [0] * 30}
    sources = [
        ({"rank": "0", "host": 'evil"host\\name\nline'},
         {"enabled": True, "counters": {"parse.rows": 7},
          "gauges": {"h2d.queue_depth": -2},
          "histograms": {"parse.chunk_us": hist}}),
        ({"rank": "1", "host": "h1"},
         {"enabled": True, "counters": {"parse.rows": 9},
          "gauges": {}, "histograms": {"parse.chunk_us": hist}}),
    ]
    text = prometheus_text(sources)
    _assert_prometheus_wellformed(text)
    # the hardened count: derived from the buckets (5), not the field (999)
    count_lines = [ln for ln in text.splitlines()
                   if ln.startswith("dmlctpu_parse_chunk_us_count")]
    assert len(count_lines) == 2
    assert all(ln.endswith(" 5") for ln in count_lines)
    # newline in a label value must be escaped, never raw
    assert "\nline" not in text.replace("\\n", "")


def test_trace_context_helpers_roundtrip():
    ids = {telemetry.new_trace_id() for _ in range(64)}
    assert 0 not in ids and len(ids) == 64  # never 0, never repeating
    tid = telemetry.new_trace_id()
    try:
        telemetry.set_trace_context(tid, tid, 42)
        assert telemetry.get_trace_context() == (tid, tid, 42)
        wire = telemetry.trace_context_wire()
        assert wire == {"id": format(tid, "016x"),
                        "span": format(tid, "016x"), "lineage": 42}
        telemetry.clear_trace_context()
        assert telemetry.get_trace_context()[0] == 0
        assert telemetry.trace_context_wire() is None
        # adopting the wire dict restores the full context
        before = telemetry.snapshot()
        assert telemetry.adopt_trace_context(wire)
        assert telemetry.get_trace_context() == (tid, tid, 42)
        if telemetry.enabled():
            delta = telemetry.counters_delta(before, telemetry.snapshot())
            assert delta.get("trace.ctx_propagated", 0) == 1
    finally:
        telemetry.clear_trace_context()


def test_adopt_trace_context_malformed_ignored():
    telemetry.clear_trace_context()
    for bad in (None, 17, "nope", {}, {"id": "xyz", "span": "0"},
                {"id": "10", "span": []}, {"id": "0", "span": "0"}):
        assert not telemetry.adopt_trace_context(bad)
        assert telemetry.get_trace_context()[0] == 0


def test_trace_context_stamps_span_args():
    if not telemetry.enabled():
        pytest.skip("tracing is compiled out")
    tid = telemetry.new_trace_id()
    telemetry.trace_start()
    try:
        with telemetry.span("test.unlabeled"):
            pass
        telemetry.set_trace_context(tid, tid, 7)
        with telemetry.span("test.labeled"):
            pass
    finally:
        telemetry.clear_trace_context()
        telemetry.trace_stop()
    events = {e["name"]: e for e in telemetry.trace_dump()["traceEvents"]
              if e.get("ph") == "X"}
    lab = events["test.labeled"]
    assert lab["args"]["trace_id"] == format(tid, "016x")
    assert lab["args"]["parent"] == format(tid, "016x")
    assert lab["args"]["lineage"] == 7
    assert "trace_id" not in events["test.unlabeled"].get("args", {})


def test_now_us_tracks_monotonic():
    lo = time.monotonic_ns() // 1000
    t = telemetry.now_us()
    hi = time.monotonic_ns() // 1000
    assert lo <= t <= hi  # no skew injected in this process


def test_json_validate():
    assert telemetry.json_validate('{"a": [1, 2.5, "x"], "b": null}')
    assert telemetry.json_validate("[]")
    # native parser rejects; the FATAL log line it prints is expected noise
    assert not telemetry.json_validate('{"a": ')
    assert not telemetry.json_validate("not json")
    assert not telemetry.json_validate('{"a": 1} trailing')


def test_lineage_helper():
    assert telemetry.lineage({"lineage": 99}) == 99
    assert telemetry.lineage({}) == -1

    class B:
        _lineage = (3 << 32) | 5
    assert telemetry.lineage(B()) == (3 << 32) | 5
    assert telemetry.lineage(object()) == -1


# ---- the ring beside a jax.profiler session: lineage, sync marks, the clock map

def test_a_span_carries_its_lineage_without_a_trace_context():
    if not telemetry.enabled():
        pytest.skip("tracing is compiled out")
    telemetry.trace_start()
    try:
        telemetry.record_span("test.recorded", 10, 5, (3 << 32) | 4)
        telemetry.record_span("test.bare", 10, 5)
        with telemetry.span("test.known", 8):
            pass
        with telemetry.span("test.found") as found:
            found.lineage = 9       # the batch in hand is known only inside
    finally:
        telemetry.trace_stop()
    events = {e["name"]: e for e in telemetry.trace_dump()["traceEvents"]}
    assert events["test.recorded"]["args"] == {"lineage": (3 << 32) | 4}
    assert events["test.known"]["args"] == {"lineage": 8}
    assert events["test.found"]["args"] == {"lineage": 9}
    assert "args" not in events["test.bare"]


@pytest.mark.parametrize("marks, at, want_ns, want_drift, want_err", [
    # two marks: the line through both, no distance left but the microsecond
    ([(1_000_000, 5_000_000_000), (3_000_000, 7_000_000_400)],
     2_000_000, 6_000_000_200, 2e-7, 1.0),
    # three: the middle one 30 us late pulls the line 10 us and stands 20 off
    ([(1_000_000, 5_000_000_000), (2_000_000, 6_000_030_000),
      (3_000_000, 7_000_000_000)], 1_000_000, 5_000_010_000, 0.0, 21.0),
    # marks under 0.1 s apart say nothing of drift: offset alone, held at 1
    ([(1_000_000, 5_000_000_000), (1_000_004, 5_000_006_000)],
     2_000_000, 6_000_001_000, 0.0, 2.0),
])
def test_clock_fit_on_made_up_marks(marks, at, want_ns, want_drift, want_err):
    offset_ns, drift, err_us = telemetry.clock_fit(marks)
    assert drift == pytest.approx(want_drift, abs=1e-12)
    assert err_us == pytest.approx(want_err, abs=1e-6)
    assert telemetry.to_profiler_ns(at, marks) == pytest.approx(
        want_ns, abs=1e-3)
    assert offset_ns + (1 + drift) * 1000 * at == pytest.approx(
        want_ns, abs=1e-3)
    # the order the marks come in is not the order they were written in
    assert telemetry.clock_fit(marks[::-1]) == (offset_ns, drift, err_us)


def test_clock_fit_needs_two_marks():
    with pytest.raises(ValueError):
        telemetry.clock_fit([(1_000_000, 5_000_000_000)])


def _ring_names():
    return [e["name"] for e in telemetry.trace_dump()["traceEvents"]]


def test_the_ring_follows_a_profiler_session(tmp_path, monkeypatch):
    """While a ``jax.profiler`` session is live the ring records, from the
    first span that sees the session to the first that sees it gone, and
    sync marks go into both.  Every step waits on what the code did, none on
    the clock: a mark a span is forced by setting the marks' period to 0."""
    if not telemetry.enabled():
        pytest.skip("tracing is compiled out")
    import jax
    telemetry.trace_stop()
    with telemetry.span("test.before"):
        pass
    assert not telemetry._ring_on
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("test.first", 5):
            pass
        assert telemetry._ring_on and telemetry._ring_ours
        assert len(telemetry.trace_dump()["otherData"]["clock_sync"]) == 1
        telemetry.record_span("test.ring_only", telemetry.now_us(), 3, 5)
        monkeypatch.setattr(telemetry, "_SYNC_EVERY_US", -1)
        with telemetry.span("test.second"):
            pass
        marks = telemetry.trace_dump()["otherData"]["clock_sync"]
        assert len(marks) == 2 and marks[0] <= marks[1] <= telemetry.now_us()
    finally:
        jax.profiler.stop_trace()
    with telemetry.span("test.after"):
        pass
    assert not telemetry._ring_on and not telemetry._ring_ours
    assert _ring_names() == ["test.first", "test.ring_only", "test.second"]
    assert telemetry.trace_dump()["otherData"]["clock_sync"] == marks
    # what reached the profiler's file: the spans of ``span`` and the marks,
    # by name; nothing that ``record_span`` alone wrote
    files = list(tmp_path.rglob("*.xplane.pb"))
    if files:       # ROADMAP D11: beside other workers the file has been missing
        data = jax.profiler.ProfileData.from_file(str(files[0]))
        host = [e.name for p in data.planes if p.name == "/host:CPU"
                for line in p.lines for e in line.events
                if e.name.startswith("dmlctpu.")]
        assert sorted(host) == sorted(
            ["dmlctpu.test.first", "dmlctpu.test.second"]
            + [f"dmlctpu.clock_sync.{m}" for m in marks])


def test_a_callers_trace_outlives_a_profiler_session(monkeypatch):
    """``trace_start()`` by hand: a session that comes and goes neither
    clears nor stops it, and still leaves its marks."""
    if not telemetry.enabled():
        pytest.skip("tracing is compiled out")
    import jax      # noqa: F401  (telemetry follows jax only where it is loaded)
    from jax._src import profiler as jax_profiler
    telemetry.trace_start()
    try:
        with telemetry.span("test.mine"):
            pass
        monkeypatch.setattr(jax_profiler._profile_state, "profile_session",
                            object())
        with telemetry.span("test.during"):
            pass
        assert telemetry._ring_on and not telemetry._ring_ours
        assert len(telemetry.trace_dump()["otherData"]["clock_sync"]) == 1
        monkeypatch.setattr(jax_profiler._profile_state, "profile_session",
                            None)
        with telemetry.span("test.later"):
            pass
        assert telemetry._ring_on
        assert _ring_names() == ["test.mine", "test.during", "test.later"]
    finally:
        telemetry.trace_stop()


# ---- spans that keep their own total; what came before a trace -----------------

def _moved(names, before):
    return {k: telemetry.counter_get(k) - before[k] for k in names}


@pytest.mark.parametrize("ring", ["off", "on"])
def test_a_span_adds_its_duration_to_its_total(ring):
    """``total=`` on ``span`` and ``record_span``: the span's own duration
    goes to the counter, whether the ring records or not, and the ring's
    copy (when there is one) holds the same number."""
    if not telemetry.enabled():
        pytest.skip("counters are compiled out")
    names = ("test.total_us", "test.recorded_total_us")
    before = {k: telemetry.counter_get(k) for k in names}
    telemetry.trace_stop()
    if ring == "on":
        telemetry.trace_start()
    try:
        with telemetry.span("test.total", total="test.total_us"):
            time.sleep(0.002)
        telemetry.record_span("test.recorded_total", telemetry.now_us(), 41,
                              total="test.recorded_total_us")
        with telemetry.span("test.no_total"):
            pass
    finally:
        telemetry.trace_stop()
    got = _moved(names, before)
    assert got["test.total_us"] >= 2000
    assert got["test.recorded_total_us"] == 41
    events = {e["name"]: e for e in telemetry.trace_dump()["traceEvents"]}
    if ring == "on":
        assert events["test.total"]["dur"] == got["test.total_us"]
        assert events["test.recorded_total"]["dur"] == 41
        assert "test.no_total" in events
    else:       # an earlier trace's events stay in the ring; none of these
        assert not {"test.total", "test.recorded_total",
                    "test.no_total"} & set(events)


def test_nested_spans_add_to_main_span_us_once_and_other_threads_never():
    if not telemetry.enabled():
        pytest.skip("counters are compiled out")
    import threading
    names = ("main.span_us", "test.outer_us", "test.inner_us",
             "test.thread_us")
    before = {k: telemetry.counter_get(k) for k in names}
    with telemetry.span("test.outer", total="test.outer_us"):
        with telemetry.span("test.inner", total="test.inner_us"):
            time.sleep(0.002)
        with telemetry.span("test.inner", total="test.inner_us"):
            time.sleep(0.001)
    got = _moved(names, before)
    assert got["test.inner_us"] >= 3000
    assert got["test.outer_us"] >= got["test.inner_us"]
    assert got["main.span_us"] == got["test.outer_us"]     # outermost only

    def on_a_thread():
        with telemetry.span("test.thread", total="test.thread_us"):
            time.sleep(0.002)

    worker = threading.Thread(target=on_a_thread)
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive()
    after = _moved(names, before)
    assert after["test.thread_us"] >= 2000
    assert after["main.span_us"] == got["main.span_us"]
    # a span that raises still closes: depth and total
    with pytest.raises(KeyError):
        with telemetry.span("test.outer", total="test.outer_us"):
            raise KeyError("in the body")
    assert telemetry._main_depth == 0
    assert _moved(names, before)["main.span_us"] >= after["main.span_us"]


def _feed_epoch(tmp_path):
    path = tmp_path / "feed.libsvm"
    path.write_text("".join(
        f"{i % 2} {i % 7}:1.5 {7 + i % 5}:0.5\n" for i in range(1500)))
    it = dt.DeviceStagingIter(str(path), batch_size=64, nnz_bucket=128,
                              num_workers=2)
    assert sum(int(b.num_rows) for b in it) == 1500


def _page_pass(tmp_path):
    from dmlc_core_tpu.data import PagePrefetcher
    import jax.numpy as jnp

    def source():       # slower than its consumer: every page is waited for
        for i in range(3):
            time.sleep(0.01)
            yield np.full((8, 3), i, np.uint8)

    with PagePrefetcher(source, passes=1, page_rows=8, num_features=3,
                        depth=1) as feed:
        for _index, _rows, page in feed.pages():
            feed.release(page, jnp.zeros(()))


def _entry_sort(tmp_path):
    import jax.numpy as jnp
    from dmlc_core_tpu.models import GBDT
    rng = np.random.default_rng(0)
    n = 200
    model = GBDT(num_features=6, num_trees=1, max_depth=2, num_bins=8,
                 missing_aware=True, histogram="pallas")
    layout = model._sparse_fit_layout(
        jnp.asarray(np.sort(rng.integers(0, 40, n)), jnp.int32),
        jnp.asarray(rng.integers(0, 6, n), jnp.int32),
        jnp.asarray(rng.integers(1, 8, n), jnp.int32),
        jnp.ones(n, bool), rows=40)
    assert layout is not None


@pytest.mark.parametrize("span, counter, drive, exact", [
    ("feed.wait", "h2d.consumer_wait_us", _feed_epoch, True),
    # the stager's last wait, which finds the stream's end, has no batch to
    # be a span of: the counter holds it besides
    ("h2d.host_wait", "h2d.wait_us", _feed_epoch, False),
    ("h2d.emit_wait", "h2d.emit_wait_us", _feed_epoch, True),
    ("page.wait", "page.wait_us", _page_pass, True),
    ("page.h2d", "page.h2d_busy_us", _page_pass, True),
    ("gbdt.entry_sort", "gbdt.entry_sort_us", _entry_sort, True),
])
def test_a_rewired_site_moves_its_counter_by_its_spans(tmp_path, span,
                                                        counter, drive, exact):
    """The six places that timed one stretch twice: the counter each kept
    by hand is its span's ``total=`` now and reads what the ring reads."""
    if not telemetry.enabled():
        pytest.skip("counters are compiled out")
    before = telemetry.counter_get(counter)
    telemetry.trace_start()
    try:
        drive(tmp_path)
    finally:
        telemetry.trace_stop()
    moved = telemetry.counter_get(counter) - before
    durs = [e["dur"] for e in telemetry.trace_dump()["traceEvents"]
            if e["name"] == span]
    assert durs and moved > 0
    if exact:
        assert moved == sum(durs)
    else:
        assert moved >= sum(durs)


def test_registry_at_start_holds_what_came_before_the_trace():
    if not telemetry.enabled():
        pytest.skip("counters are compiled out")
    telemetry.trace_stop()
    with telemetry.span("test.setup_phase", total="test.setup_phase_us"):
        time.sleep(0.001)
    telemetry.counter_add("test.bumped_before", 3)
    held = telemetry.counter_get("test.bumped_before")
    late = telemetry.counter_get("test.bumped_after")
    telemetry.trace_start()
    try:
        telemetry.counter_add("test.bumped_before", 5)
        telemetry.counter_add("test.bumped_after", 7)
        at_start = telemetry.trace_dump()["otherData"]["registry_at_start"]
    finally:
        telemetry.trace_stop()
    assert at_start["test.bumped_before"] == held
    assert at_start.get("test.bumped_after", 0) == late
    assert at_start["test.setup_phase_us"] >= 1000
    assert at_start == telemetry.trace_dump()["otherData"]["registry_at_start"]
    assert all(isinstance(v, int) for v in at_start.values())   # counters only


def test_the_ring_beside_a_profiler_keeps_the_registry_of_its_start(
        monkeypatch):
    """The benchmark's case: set-up, then a profiler session; the first span
    that sees the session starts the ring and keeps the snapshot — with what
    closed before it, without itself."""
    if not telemetry.enabled():
        pytest.skip("counters are compiled out")
    import jax      # noqa: F401  (telemetry follows jax only where it is loaded)
    from jax._src import profiler as jax_profiler
    telemetry.trace_stop()
    with telemetry.span("test.in_setup", total="test.in_setup_us"):
        time.sleep(0.001)
    in_setup = telemetry.counter_get("test.in_setup_us")
    in_window = telemetry.counter_get("test.in_window_us")
    monkeypatch.setattr(jax_profiler._profile_state, "profile_session",
                        object())
    try:
        with telemetry.span("test.in_window", total="test.in_window_us"):
            time.sleep(0.001)
        assert telemetry._ring_on and telemetry._ring_ours
        at_start = telemetry.trace_dump()["otherData"]["registry_at_start"]
    finally:
        monkeypatch.setattr(jax_profiler._profile_state, "profile_session",
                            None)
        with telemetry.span("test.after_window"):
            pass
    assert at_start["test.in_setup_us"] == in_setup
    assert at_start.get("test.in_window_us", 0) == in_window
    assert telemetry.counter_get("test.in_window_us") >= in_window + 1000
