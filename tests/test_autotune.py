"""The stall-attribution autotuner: hill-climbing policy decisions from
synthetic windows, restart-window hygiene, live pool retuning through the
staging iterators, and decision observability (log + /autotune endpoint).

Policy tests drive :meth:`AutoTuner.decide` directly with hand-built
:class:`telemetry.Window` objects, so they are deterministic regardless of
machine speed or whether native telemetry is compiled in.
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import dmlc_core_tpu as dt
from dmlc_core_tpu import autotune, telemetry, telemetry_http


class FakeTarget:
    """Minimal knob surface (what both staging iterators expose)."""

    def __init__(self, **knobs):
        self.knobs = dict({"num_workers": 1, "buffer_mb": 4,
                           "prefetch_depth": 1, "chunk_bytes": 0}, **knobs)
        self.calls = []

    def set_knobs(self, **kw):
        self.calls.append(dict(kw))
        self.knobs.update(kw)
        return dict(self.knobs, pool_live=True)


def make_window(mb=100.0, wall=1.0, stage="shard", restarted=False):
    w = telemetry.Window()
    w.before = {"counters": {}}
    w.after = {"counters": {}}
    w.wall_s = wall
    w.delta = {"shard.bytes": int(mb * (1 << 20) * wall)}
    w.attribution = {
        "stages": {}, "bound": {stage: 100.0} if stage else {},
        "bound_stage": stage,
        "table": f"{stage}-bound 100%" if stage else "",
        "wall_s": wall, "restarted": restarted, "io": {}}
    w.restarted = restarted
    return w


def tuner(tgt, **kw):
    kw.setdefault("window_batches", 0)
    kw.setdefault("max_workers", 4)
    kw.setdefault("max_buffer_mb", 64)
    kw.setdefault("max_prefetch", 4)
    kw.setdefault("margin", 0.05)
    return autotune.AutoTuner(tgt, **kw)


# ---- policy ---------------------------------------------------------------

def test_shard_bound_climbs_workers_then_buffer():
    tgt = FakeTarget()
    t = tuner(tgt)
    rec = t.decide(make_window(mb=50, stage="shard"))
    assert rec["action"] == "step" and rec["knob"] == "num_workers"
    assert (rec["frm"], rec["to"]) == (1, 2)
    assert tgt.knobs["num_workers"] == 2
    # throughput improved -> the step is accepted and the climb continues
    rec = t.decide(make_window(mb=90, stage="shard"))
    assert rec["action"] == "step" and rec["knob"] == "num_workers"
    assert rec["settled"]["action"] == "accept"
    assert tgt.knobs["num_workers"] == 4
    # at max workers the ladder moves to the buffer, then the chunk size
    rec = t.decide(make_window(mb=120, stage="shard"))
    assert rec["knob"] == "buffer_mb" and tgt.knobs["buffer_mb"] == 8


def test_regression_reverts_and_blocks_that_knob():
    tgt = FakeTarget(num_workers=2)
    t = tuner(tgt)
    t.decide(make_window(mb=100, stage="shard"))       # step 2 -> 4
    assert tgt.knobs["num_workers"] == 4
    rec = t.decide(make_window(mb=50, stage="shard"))  # >5% regression
    # the step was reverted and the next proposal skips the blocked knob
    assert tgt.knobs["num_workers"] == 2
    assert rec["settled"]["action"] == "revert"
    assert rec["action"] == "step" and rec["knob"] == "buffer_mb"


def test_tolerated_regressions_cannot_ratchet_the_baseline_down():
    """Each step may sit up to `margin` below the baseline, but a CHAIN of
    such steps must trip the revert — accepting one must not lower the bar
    the next is judged against."""
    tgt = FakeTarget(num_workers=2)
    t = tuner(tgt, max_workers=64)
    t.decide(make_window(mb=100, stage="shard"))       # step 2 -> 4
    t.decide(make_window(mb=97, stage="shard"))        # -3%: accept, 4 -> 8
    assert t.accepts == 1 and tgt.knobs["num_workers"] == 8
    rec = t.decide(make_window(mb=94, stage="shard"))  # -6% vs the ORIGINAL
    assert rec["settled"]["action"] == "revert"
    assert tgt.knobs["num_workers"] == 4


def test_chunk_ceiling_zero_freezes_the_knob():
    tgt = FakeTarget(num_workers=4, buffer_mb=64)      # workers/buffer at max
    t = tuner(tgt, max_chunk_mb=0)
    rec = t.decide(make_window(mb=100, stage="shard"))
    assert rec["action"] == "hold"                     # nothing left to step
    assert tgt.knobs["chunk_bytes"] == 0


def test_default_bounds_are_the_documented_constants():
    """A tuner armed by DMLCTPU_AUTOTUNE=1 alone (``maybe_attach`` passes no
    bound) runs with the ceilings and margin doc/autotune.md states."""
    t = autotune.AutoTuner(FakeTarget())
    assert t.max_workers == max(4, os.cpu_count() or 1)
    assert (t.max_buffer_mb, t.max_prefetch, t.max_chunk_bytes, t.margin) \
        == (256, 8, 16 << 20, 0.05)
    assert autotune._DECISIONS.maxlen == 256


def test_bottleneck_move_clears_the_block():
    tgt = FakeTarget(num_workers=2)
    t = tuner(tgt)
    t.decide(make_window(mb=100, stage="shard"))
    t.decide(make_window(mb=10, stage="shard"))        # revert + block
    assert ("num_workers", "shard") in t._blocked
    t.decide(make_window(mb=100, stage="h2d"))         # bound moved
    assert not t._blocked


def test_restart_window_never_drives_a_decision():
    tgt = FakeTarget()
    t = tuner(tgt)
    t.decide(make_window(mb=100, stage="shard"))       # step pending
    before = dict(tgt.knobs)
    rec = t.decide(make_window(mb=1, stage="shard", restarted=True))
    assert rec["action"] == "skip_restart"
    assert tgt.knobs == before                         # nothing moved
    assert t.summary()["pending"] is not None          # step still in flight
    assert t.skipped_restart == 1
    # the next CLEAN window settles the pending step normally
    rec = t.decide(make_window(mb=150, stage="shard"))
    assert rec["settled"]["action"] == "accept"


def test_io_bound_grows_buffer_not_workers():
    tgt = FakeTarget(num_workers=2, buffer_mb=8)
    t = tuner(tgt)
    rec = t.decide(make_window(mb=40, stage="io"))
    assert rec["knob"] == "buffer_mb" and tgt.knobs["buffer_mb"] == 16
    assert tgt.knobs["num_workers"] == 2


def test_consumer_bound_raises_prefetch():
    tgt = FakeTarget()
    t = tuner(tgt)
    rec = t.decide(make_window(mb=40, stage="h2d"))
    assert rec["knob"] == "prefetch_depth"
    assert tgt.knobs["prefetch_depth"] == 2
    rec = t.decide(make_window(mb=60, stage="pack"))
    assert rec["knob"] == "prefetch_depth"
    assert tgt.knobs["prefetch_depth"] == 3


def test_no_bottleneck_holds_and_converges():
    tgt = FakeTarget()
    t = tuner(tgt)
    assert t.decide(make_window(mb=50, stage=None))["action"] == "hold"
    assert not t.converged
    assert t.decide(make_window(mb=50, stage=None))["action"] == "hold"
    assert t.converged


def test_tiny_window_is_skipped():
    tgt = FakeTarget()
    t = tuner(tgt)
    rec = t.decide(make_window(mb=0.001, wall=0.005, stage="shard"))
    assert rec["action"] == "skip_short"
    assert tgt.knobs["num_workers"] == 1


# ---- live retuning through the real pipeline ------------------------------

@pytest.fixture
def libsvm_file(tmp_path):
    rows = []
    for i in range(2000):
        nnz = 1 + (i % 4)
        feats = " ".join(f"{(i * 3 + j) % 32}:{0.5 * (j + 1)}"
                         for j in range(nnz))
        rows.append(f"{i % 2} {feats}")
    p = tmp_path / "autotune.libsvm"
    p.write_text("\n".join(rows) + "\n")
    return str(p)


def _digest(it, schedule=None):
    out = []
    for i, b in enumerate(it):
        if schedule and i in schedule:
            r = it.set_knobs(**schedule[i])
            assert r["pool_live"], r
        out.append((int(b.num_rows), float(np.asarray(b.label).sum()),
                    int(np.asarray(b.index).sum()),
                    float(np.asarray(b.value).sum())))
    return out


def test_live_resize_mid_epoch_is_transparent(libsvm_file):
    """Worker growth, lazy shrink, buffer and chunk moves mid-stream must
    neither deadlock the pool nor change a single staged batch."""
    ref = _digest(dt.DeviceStagingIter(
        libsvm_file, batch_size=128, nnz_bucket=512, num_workers=1,
        buffer_mb=4, autotune=False))
    it = dt.DeviceStagingIter(
        libsvm_file, batch_size=128, nnz_bucket=512, num_workers=1,
        buffer_mb=4, autotune=True)  # armed: pool forced even at 1 worker
    tuned = _digest(it, schedule={
        1: dict(num_workers=4, buffer_mb=16),
        5: dict(num_workers=1, chunk_bytes=1 << 20),   # lazy retire + chunk
        9: dict(num_workers=3, buffer_mb=8),
    })
    assert tuned == ref
    assert it.knobs["num_workers"] == 3 and it.knobs["buffer_mb"] == 8


def test_env_armed_iterator_attaches_and_decides(monkeypatch, libsvm_file):
    monkeypatch.setenv("DMLCTPU_AUTOTUNE", "1")
    monkeypatch.setenv("DMLCTPU_AUTOTUNE_WINDOW", "4")
    it = dt.DeviceStagingIter(libsvm_file, batch_size=128, nnz_bucket=512,
                              num_workers=1, buffer_mb=4)
    n1 = sum(1 for _ in it)
    n2 = sum(1 for _ in it)
    assert n1 == n2 and n1 > 0
    t = it._tuner
    assert t is not None and t.epochs == 2
    assert t.windows >= 2  # mid-epoch windows + the epoch boundaries
    assert autotune.decision_log()  # observable in the shared log


def test_record_iter_knobs_apply_next_epoch(tmp_path):
    f = tmp_path / "knobs.rec"
    with dt.RecordIOWriter(str(f)) as w:
        for j in range(300):
            w.write(bytes([j % 251]) * (20 + j % 40))
    it = dt.RecordStagingIter(str(f), records_cap=8, bytes_cap=1024,
                              autotune=False)
    first = [int(b.num_records) for b in it]
    r = it.set_knobs(num_workers=2, prefetch_depth=3, buffer_mb=99)
    assert r["pool_live"] is False  # record path: Python pool, next epoch
    assert it.knobs == {"num_workers": 2, "prefetch_depth": 3}
    second = [int(b.num_records) for b in it]  # now through the 2-way pool
    assert sum(second) == sum(first) == 300


# ---- observability --------------------------------------------------------

def test_decisions_surface_in_counters_and_endpoint():
    c0 = telemetry.counter_get("autotune.decisions")
    tgt = FakeTarget()
    t = tuner(tgt)
    t.decide(make_window(mb=80, stage="shard"))
    if telemetry.enabled():
        assert telemetry.counter_get("autotune.decisions") == c0 + 1
        assert telemetry.gauge_get("autotune.num_workers") == 2
    with telemetry_http.serve(port=0) as srv:
        body = urllib.request.urlopen(srv.url + "/autotune",
                                      timeout=10).read()
    st = json.loads(body)
    assert st["decisions"], st
    assert any(d.get("knob") == "num_workers" for d in st["decisions"])
    assert any(s["epochs"] == 0 for s in st["tuners"])


def test_decision_span_lands_in_trace():
    telemetry.trace_start()
    t = tuner(FakeTarget())
    t.decide(make_window(mb=80, stage="shard"))
    telemetry.trace_stop()
    doc = json.loads(telemetry.trace_dump_json())
    if telemetry.enabled():
        assert any(ev.get("name") == "autotune.decision"
                   for ev in doc["traceEvents"])
