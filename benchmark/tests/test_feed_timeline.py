"""readers/feed_timeline.py on hand-made span lists: a wait is split by what
the feed was doing with the batch it ended with; the three shares sum to 100;
the ring's steady clock reaches the window through the sync marks; a dropped
event, or a program that writes no marks, makes the reader return nothing.
Also the metric files this reader and PR 37's counters brought: there, in
their order, each over the two streamed cells of its day and whichever
joined since."""
import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import feed_timeline as ft

HERE = Path(__file__).resolve().parents[1]
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
STREAMED = ["criteo-ffm.stream-train", "criteo-tb-ftrl.stream-train"]
WAIT = [{"what": "wait_pct", "of": part} for part in ft.PARTS]


def span(name, start, end, lineage=-1):
    e = {"name": name, "ts": start, "dur": end - start}
    if lineage >= 0:
        e["args"] = {"lineage": lineage}
    return e


def batch(lineage, chunk, staging, staged, put, got, wait_from):
    """The spans of one batch's life, steady-clock microseconds."""
    return [span("parse.chunk", chunk, chunk + 300, lineage),
            span("pack.next", chunk + 300, staging - 10, lineage),
            span("h2d.host_wait", staging - 200, staging, lineage),
            span("h2d.stage_batch", staging, staged, lineage),
            span("h2d.device_put", staging + 100, staged - 100, lineage),
            span("h2d.emit_wait", staged, put, lineage),
            span("feed.handoff", put, got, lineage),
            span("feed.wait", wait_from, got + 2, lineage)]


def shares(events, lo=0, hi=10**9):
    got = ft.timeline(events, lo, hi)["wait_us"]
    whole = sum(got.values())
    return {k: round(100 * v / whole, 6) for k, v in got.items()}


@pytest.mark.parametrize("wait_from, want", [
    # the consumer began to wait while the stager was inside stage_batch
    (1_500, {"native": 0.0, "h2d": 100 * 500 / 552, "handoff": 100 * 52 / 552}),
    # ... while the stager was starved for the batch: upstream was busy
    (400, {"native": 100 * 600 / 1652, "h2d": 100 * 1000 / 1652,
           "handoff": 100 * 52 / 1652}),
    # ... when the batch had long been staged and lay in the queue
    (2_040, {"native": 0.0, "h2d": 0.0, "handoff": 100.0}),
])
def test_a_wait_is_split_by_where_its_batch_was(wait_from, want):
    events = batch(7, chunk=100, staging=1_000, staged=2_000, put=2_010,
                   got=2_050, wait_from=wait_from)
    got = shares(events)
    assert got == pytest.approx(want) and sum(got.values()) == pytest.approx(100)
    assert ft.timeline(events, 0, 10**9)["lead_ms"] == pytest.approx(1.95)
    assert ft.timeline(events, 0, 10**9)["span_us"]["h2d.device_put"] == (800, 1)


def test_a_lineage_that_comes_back_takes_its_own_epochs_spans():
    """A replayed file brings every lineage back an epoch later, and a chunk
    larger than a batch gives two batches one lineage: a wait's batch is the
    one whose hand-off ends inside the wait."""
    first = batch(7, chunk=100, staging=1_000, staged=2_000, put=2_010,
                  got=2_050, wait_from=1_500)                   # h2d 500 of 552
    again = batch(7, chunk=10_100, staging=11_000, staged=12_000, put=12_010,
                  got=40_000, wait_from=39_000)                 # handoff 1,002
    other = batch(9, chunk=5_000, staging=6_000, staged=7_000, put=7_010,
                  got=7_020, wait_from=5_500)       # native 500, h2d 1,000, 22
    got = ft.timeline(first + again + other, 0, 10**9)
    assert got["wait_us"] == {"native": 500.0, "h2d": 1_500.0,
                              "handoff": 52.0 + 1_002.0 + 22.0}
    assert got["lead_ms"] == pytest.approx(2.02)    # of 1.95, 29.9 and 2.02
    assert got["span_us"]["feed.wait"] == (552 + 1_002 + 1_522, 3)


def test_spans_are_clipped_to_the_window_and_a_wait_for_no_batch_is_left_out():
    events = batch(7, chunk=100, staging=1_000, staged=2_000, put=2_010,
                   got=2_050, wait_from=400)
    events += [span("feed.wait", 3_000, 3_500)]         # the stream's end
    events += batch(9, chunk=90_000, staging=91_000, staged=92_000, put=92_010,
                    got=92_050, wait_from=91_500)       # past the window
    got = ft.timeline(events, 1_200, 50_000)
    assert got["wait_us"] == {"native": 0.0, "h2d": 800.0, "handoff": 52.0}
    assert got["span_us"]["h2d.stage_batch"] == (800, 1)
    assert got["span_us"]["feed.wait"] == (852 + 500, 2)
    assert "parse.chunk" not in got["span_us"]
    # a batch staged before the ring began, taken after: the wait is not told
    late = [span("feed.handoff", 100, 900, 3), span("feed.wait", 50, 902, 3)]
    assert sum(ft.timeline(late, 0, 10**9)["wait_us"].values()) == 0
    assert ft.timeline(late, 0, 10**9)["lead_ms"] is None


class Run:
    def __init__(self, host_spans, window_ns):
        self.trace = tr.Trace(window_ns, [], host_spans)


def marks(offset_ns, *steady_us):
    return [(f"{ft.SYNC_PREFIX}{us}", offset_ns + 1000 * us,
             offset_ns + 1000 * us) for us in steady_us]


@pytest.fixture
def ring(monkeypatch):
    """What ``telemetry.trace_dump()`` hands the reader, set by the test."""
    from dmlc_core_tpu import telemetry
    doc = {"traceEvents": [], "otherData": {"dropped_events": 0}}
    monkeypatch.setattr(telemetry, "trace_dump", lambda: doc)
    return doc


def test_the_reader_lays_the_ring_on_the_window_through_the_marks(ring):
    ring["traceEvents"] = batch(7, chunk=100, staging=1_000, staged=2_000,
                                put=2_010, got=2_050, wait_from=400)
    offset = 5_000_000_000
    host = marks(offset, 0, 1_000_000, 2_000_000) + [
        ("dmlctpu.feed.wait", offset + 400_000, offset + 2_052_000)]
    run = Run(host, (offset + 1_200_000, offset + 50_000_000))
    assert ft.sync_marks(host) == [(0, offset), (1_000_000, offset + 10**9),
                                   (2_000_000, offset + 2 * 10**9)]
    got = [ft.read(args, run) for args in WAIT]
    assert got == pytest.approx([0.0, 100 * 800 / 852, 100 * 52 / 852])
    assert sum(got) == pytest.approx(100)
    assert ft.read({"what": "sync_err_us"}, run) == pytest.approx(1.0)
    assert ft.read({"what": "lead_ms"}, run) == pytest.approx(1.95)
    assert ft.read({"what": "span_us", "span": "h2d.device_put"},
                   run) == pytest.approx(700)
    assert ft.read({"what": "span_us", "span": "no.such"}, run) is None
    with pytest.raises(ValueError):
        ft.read({"what": "other"}, run)


@pytest.mark.parametrize("case", ["dropped", "no marks", "one mark",
                                  "no trace", "no wait"])
def test_the_reader_returns_nothing_rather_than_a_partial_answer(ring, case):
    ring["traceEvents"] = batch(7, chunk=100, staging=1_000, staged=2_000,
                                put=2_010, got=2_050, wait_from=400)
    host = marks(0, 0, 1_000_000)
    if case == "dropped":
        ring["otherData"]["dropped_events"] = 1
    elif case == "no marks":        # the parent: its program writes none
        host = [("dmlctpu.feed.wait", 400_000, 2_052_000)]
    elif case == "one mark":
        host = host[:1]
    elif case == "no wait":
        ring["traceEvents"] = []
    run = Run(host, (0, 50_000_000))
    if case == "no trace":
        run.trace = None
    assert [ft.read(args, run) for args in WAIT] == [None] * 3
    assert ft.read({"what": "lead_ms"}, run) is None
    if case != "no wait":
        assert ft.read({"what": "sync_err_us"}, run) is None


def test_the_new_metric_files_name_their_readers_and_cells():
    names = [
        "h2d_host_wait_us_per_batch.train", "h2d_emit_wait_us_per_batch.train",
        "pack_input_wait_us_per_row.train", "native_spans_dropped.train",
        "feed_wait_h2d_pct.train", "feed_wait_native_pct.train",
        "feed_wait_handoff_pct.train", "feed_lead_ms.train",
        "h2d_device_put_us_per_batch.train", "clock_sync_err_us.train",
        "sgd_scatter_tiles_per_step", "hist_nodes_built_per_round",
        "hist_nodes_derived_per_round", "sparse_hist_blocks_per_round",
        "sparse_hist_grid_steps_per_round"]
    every = [m["name"] for m in BENCH["per_layer"]]
    assert [n for n in every if n in names] == names       # in this order
    mine = {n: BENCH["per_layer"][every.index(n)] for n in names}
    for name, m in mine.items():
        spec = json.loads(
            (HERE / "layer_metrics" / f"{name}.json").read_text())
        assert spec["name"] == name and spec["layer"] == m["layer"]
        assert m["moves"] == "train_rows_per_s"
        if spec["reader"] == "feed_timeline":
            assert m["source"] == "program_span"
            assert m["workloads"][:2] == STREAMED
            assert spec["args"]["what"] in ("wait_pct", "lead_ms", "span_us",
                                            "sync_err_us")
        else:
            assert spec["reader"] == "counter_delta"
            assert m["source"] == "program_counter"
        if name.endswith(".train"):
            assert m["workloads"][:2] == STREAMED
    assert [spec["of"] for spec in (
        json.loads((HERE / "layer_metrics" / f"{n}.json").read_text())["args"]
        for n in mine if n.startswith("feed_wait_"))] == ["h2d", "native",
                                                          "handoff"]
