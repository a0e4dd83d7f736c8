"""Reader ``feed_timeline``: a batch's life in the feed, from the program's own
ring of spans (``telemetry.trace_dump()``, steady clock, this process) laid
on the profiler's clock through the sync marks the program writes into the
profiler's file (``dmlctpu.clock_sync.<steady us>``, found in
``run.trace.host_spans`` with their profiler-clock start), and clipped to
``bench.window``.

args: ``what`` =
  ``wait_pct``     share of the consumer's ``feed.wait`` time spent in the
                   part ``of`` the batch's life that the wait overlapped:
                   ``native`` (before its ``h2d.stage_batch`` began: the
                   stager starved, the batch still in ``pack.next`` or under
                   it in ``parse.chunk`` / ``shard.part``), ``h2d`` (inside
                   its ``h2d.stage_batch``), ``handoff`` (staged: on its way
                   through the device queue, ``feed.handoff``).  The three
                   sum to 100.  A wait's batch is found by lineage: its
                   ``feed.handoff`` ends inside the wait, its
                   ``h2d.stage_batch`` is the last of that lineage to end
                   before the hand-off began (a replayed file brings a
                   lineage back every epoch);
  ``lead_ms``      median age of a batch when the consumer takes it, from the
                   start of its first row's ``parse.chunk``;
  ``span_us``      ring time of the span ``span`` inside the window, a call;
  ``sync_err_us``  the bound on the clock map's error (``telemetry.clock_fit``).
Returns nothing where the program writes no sync marks (fewer than two in the
window), where the ring overwrote an event (``otherData.dropped_events``: the
timeline is partial), and where the window holds nothing of what was asked.
"""
import bisect
import statistics

SYNC_PREFIX = "dmlctpu.clock_sync."
PARTS = ("native", "h2d", "handoff")


def sync_marks(host_spans: list) -> list:
    """``[(steady_us, profiler_ns), ...]`` from the profiler's host events."""
    return [(int(name[len(SYNC_PREFIX):]), start)
            for name, start, _end in host_spans
            if name.startswith(SYNC_PREFIX)
            and name[len(SYNC_PREFIX):].isdigit()]


def _by_lineage(events: list, name: str, key) -> dict:
    """lineage -> the spans ``name`` of it as ``(key, start, end)``, sorted."""
    out: dict = {}
    for e in events:
        if e["name"] == name:
            start, end = e["ts"], e["ts"] + e["dur"]
            lineage = e.get("args", {}).get("lineage", -1)
            out.setdefault(lineage, []).append(
                (end if key == "end" else start, start, end))
    for spans in out.values():
        spans.sort()
    return out


def _last_before(spans: list, at) -> tuple | None:
    """The last of ``spans`` (sorted by key) whose key is <= ``at``."""
    i = bisect.bisect_right(spans, (at, float("inf"), float("inf")))
    return spans[i - 1] if i else None


def timeline(events: list, lo_us: float, hi_us: float) -> dict:
    """What the ring says of the window ``[lo_us, hi_us]`` (steady clock):
    ``wait_us`` by part, ``lead_ms`` (or None), ``span_us`` by span name as
    ``(total, calls)``.  ``events``: the ring's ``traceEvents``."""
    handoffs = _by_lineage(events, "feed.handoff", "end")
    stages = _by_lineage(events, "h2d.stage_batch", "end")
    chunks = _by_lineage(events, "parse.chunk", "start")
    wait_us = dict.fromkeys(PARTS, 0.0)
    leads, span_us = [], {}
    for e in events:
        start, end = e["ts"], e["ts"] + e["dur"]
        if end < lo_us or start > hi_us:
            continue
        a, b = max(start, lo_us), min(end, hi_us)
        slot = span_us.setdefault(e["name"], [0.0, 0])
        slot[0] += b - a
        slot[1] += 1
        lineage = e.get("args", {}).get("lineage", -1)
        if e["name"] != "feed.wait" or lineage < 0:
            continue
        # the batch this wait ended with: its hand-off ends inside the wait
        handoff = _last_before(handoffs.get(lineage, []), end)
        if handoff is None or handoff[2] < start:
            continue
        put = handoff[1]
        stage = _last_before(stages.get(lineage, []), put)
        if stage is None:
            if put > a:
                continue        # staged before the ring began: not told
            stage = (put, put, put)
        _, staging, staged = stage
        wait_us["native"] += max(min(b, staging) - a, 0.0)
        wait_us["h2d"] += max(min(b, staged) - max(a, staging), 0.0)
        wait_us["handoff"] += max(b - max(a, staged), 0.0)
        chunk = _last_before(chunks.get(lineage, []), staging)
        if chunk is not None and end <= hi_us:
            leads.append((handoff[2] - chunk[1]) / 1e3)
    return {"wait_us": wait_us,
            "lead_ms": statistics.median(leads) if leads else None,
            "span_us": {k: tuple(v) for k, v in span_us.items()}}


def _of_run(run) -> dict | None:
    """The run's timeline, made once and kept on the record."""
    if hasattr(run, "_feed_timeline"):
        return run._feed_timeline
    run._feed_timeline = None
    trace = run.trace
    marks = sync_marks(trace.host_spans) if trace is not None else []
    if len(marks) < 2:
        return None
    from dmlc_core_tpu import telemetry
    ring = telemetry.trace_dump()
    if ring.get("otherData", {}).get("dropped_events", 0):
        return None
    offset_ns, drift, err_us = telemetry.clock_fit(marks)
    to_us = lambda ns: (ns - offset_ns) / (1000.0 * (1.0 + drift))  # noqa: E731
    out = timeline(ring.get("traceEvents", []), to_us(trace.window_ns[0]),
                   to_us(trace.window_ns[1]))
    out["sync_err_us"] = err_us
    run._feed_timeline = out
    return out


def read(args: dict, run):
    got = _of_run(run)
    if got is None:
        return None
    what = args["what"]
    if what == "sync_err_us":
        return got["sync_err_us"]
    if what == "lead_ms":
        return got["lead_ms"]
    if what == "span_us":
        total, calls = got["span_us"].get(args["span"], (0.0, 0))
        return total / calls if calls else None
    if what == "wait_pct":
        whole = sum(got["wait_us"].values())
        return 100.0 * got["wait_us"][args["of"]] / whole if whole else None
    raise ValueError(f"feed_timeline: unknown what={what!r}")
