"""Pipeline telemetry: counters, trace spans, and stall attribution.

Python face of ``dmlctpu/telemetry.h``.  The native runtime keeps one
process-wide registry of relaxed-atomic counters/gauges/histograms that
every pipeline stage (InputSplit readers, the text-parse pool, the
ShardedParser worker pool, the StagedBatcher, and — via this module — the
H2D device feed) updates as it runs.  This module reads snapshots, drives
trace recording, and turns two snapshots plus a wall-clock interval into a
stall-attribution table ("parse-bound 71%, h2d-bound 22%").

Everything degrades to cheap no-ops when the native library was compiled
with ``DMLCTPU_TELEMETRY=0``: :func:`enabled` returns ``False``, snapshots
report ``{"enabled": False}``, counters read 0, and traces are empty.

See ``doc/observability.md`` for the metric name contract and how to read
the attribution table.
"""
from __future__ import annotations

import contextlib
import ctypes
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from . import _native

__all__ = [
    "enabled", "snapshot", "reset", "counter_add", "counter_get",
    "gauge_set", "gauge_add", "gauge_get",
    "counters_delta", "snapshot_restarted", "merge_snapshots",
    "histogram_quantile", "trace_start", "trace_stop", "trace_dump_json",
    "trace_dump", "record_span", "span", "now_us", "trace_armed",
    "clock_fit", "to_profiler_ns", "new_trace_id",
    "set_trace_context", "get_trace_context", "clear_trace_context",
    "trace_context_wire", "adopt_trace_context", "lineage", "json_validate",
    "stall_attribution",
    "format_stall_table", "window", "Window", "capture_logs",
    "watchdog", "watchdog_from_env", "watchdog_running",
    "watchdog_stall_count", "flight_record", "last_flight_record",
    "timeseries_start", "timeseries_stop", "timeseries_active",
    "timeseries_sample", "timeseries_json", "timeseries_tail_json",
    "timeseries", "timeseries_from_env", "resource_sample",
]


def enabled() -> bool:
    """True when the native library was built with telemetry compiled in."""
    out = ctypes.c_int()
    _native.check(_native.lib().DmlcTpuTelemetryEnabled(ctypes.byref(out)))
    return bool(out.value)


def snapshot() -> dict:
    """Parsed JSON snapshot: ``{"enabled", "counters", "gauges",
    "histograms"}`` (the latter three absent when telemetry is compiled
    out)."""
    out = ctypes.c_char_p()
    _native.check(
        _native.lib().DmlcTpuTelemetrySnapshotJson(ctypes.byref(out)))
    return json.loads((out.value or b"{}").decode())


def reset() -> None:
    """Zero every registered metric (they stay registered)."""
    _native.check(_native.lib().DmlcTpuTelemetryReset())


def counter_add(name: str, delta: int) -> None:
    """Add ``delta`` (>=0) to the named process-wide counter, creating it on
    first use.  This is how the staging loop publishes H2D occupancy."""
    _native.check(
        _native.lib().DmlcTpuTelemetryCounterAdd(name.encode(), int(delta)))


def counter_get(name: str) -> int:
    out = ctypes.c_int64()
    _native.check(
        _native.lib().DmlcTpuTelemetryCounterGet(name.encode(),
                                                 ctypes.byref(out)))
    return int(out.value)


def gauge_set(name: str, value: int) -> None:
    """Set the named process-wide gauge (created on first use).  This is how
    the staging loop publishes H2D queue depth for the flight recorder."""
    _native.check(
        _native.lib().DmlcTpuTelemetryGaugeSet(name.encode(), int(value)))


def gauge_add(name: str, delta: int) -> None:
    _native.check(
        _native.lib().DmlcTpuTelemetryGaugeAdd(name.encode(), int(delta)))


def gauge_get(name: str) -> int:
    out = ctypes.c_int64()
    _native.check(
        _native.lib().DmlcTpuTelemetryGaugeGet(name.encode(),
                                               ctypes.byref(out)))
    return int(out.value)


def counters_delta(before: dict, after: dict) -> Dict[str, int]:
    """Per-counter difference between two :func:`snapshot` results (counters
    are monotonic, so this is the activity in the interval).

    A counter that went BACKWARDS — a worker process restarted mid-epoch and
    re-registered from zero — is clamped to 0 rather than reported as a
    negative interval; :func:`snapshot_restarted` detects that case so
    callers can tag the interval instead of silently mis-attributing it.
    """
    b = before.get("counters", {})
    return {k: max(v - b.get(k, 0), 0)
            for k, v in after.get("counters", {}).items()}


def snapshot_restarted(before: dict, after: dict) -> bool:
    """True when any counter moved backwards between the snapshots — the
    signature of a process restart (counters are otherwise monotonic)."""
    b = before.get("counters", {})
    return any(v < b.get(k, 0)
               for k, v in after.get("counters", {}).items())


def merge_snapshots(snaps: List[dict]) -> dict:
    """Fold per-process :func:`snapshot` dicts into one job-wide view
    (Python face of the native ``telemetry::Snapshot::Merge``).

    Counters and histogram buckets add exactly (both are event tallies);
    gauges add so a merged level reads as the job-wide total.  Because every
    histogram bucket keeps its upper bound, quantiles read off the merged
    buckets (:func:`histogram_quantile`) are conservative — they never
    understate the true quantile of the pooled events."""
    merged: dict = {"enabled": any(s.get("enabled") for s in snaps),
                    "counters": {}, "gauges": {}, "histograms": {}}
    for s in snaps:
        for k, v in s.get("counters", {}).items():
            merged["counters"][k] = merged["counters"].get(k, 0) + v
        for k, v in s.get("gauges", {}).items():
            merged["gauges"][k] = merged["gauges"].get(k, 0) + v
        for k, h in s.get("histograms", {}).items():
            m = merged["histograms"].setdefault(
                k, {"count": 0, "sum": 0, "buckets": [0] * len(h["buckets"])})
            m["count"] += h["count"]
            m["sum"] += h["sum"]
            m["buckets"] = [a + b for a, b in zip(m["buckets"], h["buckets"])]
    return merged


def histogram_quantile(hist: dict, q: float) -> Optional[float]:
    """Upper bound of the ``q``-quantile from a snapshot histogram dict
    (``{"count", "sum", "buckets"}``): the bucket upper bound (``2**i``)
    where the cumulative count crosses ``q * count``.  ``inf`` when it lands
    in the overflow bucket; ``None`` for an empty histogram."""
    count = hist.get("count", 0)
    if count <= 0:
        return None
    buckets = hist["buckets"]
    target = max(q * count, 1.0)  # >=1: even q=0 points at a real event
    cum = 0
    for i, n in enumerate(buckets):
        cum += n
        if cum >= target:
            return float("inf") if i == len(buckets) - 1 else float(2 ** i)
    return float("inf")


# ---- traces -----------------------------------------------------------------

# Test hook: DMLCTPU_CLOCK_SKEW_US shifts every Python-side steady-clock
# read (spans recorded via span()/now_us() AND the clock probes the
# MetricsPusher answers offset estimation with) by a fixed amount, faking a
# host whose clock runs ahead/behind.  Native spans are NOT shifted — the
# two-process tests run the whole traced pipeline in the skewed child, so
# its entire dump (native + Python spans) is offset-corrected as one unit
# by the tracker merge.  See doc/analysis.md for the knob registry entry.
_CLOCK_SKEW_US = int(os.environ.get("DMLCTPU_CLOCK_SKEW_US", "0") or "0")


def now_us() -> int:
    """Steady-clock microseconds on the span timeline (same epoch as the
    native ``NowUs()``), plus the ``DMLCTPU_CLOCK_SKEW_US`` test skew."""
    return time.monotonic_ns() // 1000 + _CLOCK_SKEW_US


# True once trace_start() ran in this process: the MetricsPusher uses it
# to decide whether a push should carry the trace buffers to the tracker
# (it stays True after trace_stop() so the final push ships the completed
# trace; a fresh trace_start() simply re-arms it).
_trace_armed = False


def trace_armed() -> bool:
    """True when this process recorded (or is recording) a trace worth
    shipping to the tracker's job-trace merge."""
    return _trace_armed


def trace_start() -> None:
    """Start buffering spans (clears spans from any previous trace)."""
    global _trace_armed, _ring_ours
    _ring_start()
    _trace_armed = True
    _ring_ours = False      # the caller's trace: no session's end stops it


def _ring_start() -> None:
    global _ring_on, _registry_at_start
    # what every counter read when recording began: a span closed before
    # this trace left no event, but its ``total=`` is in here
    _registry_at_start = snapshot().get("counters", {})
    _native.check(_native.lib().DmlcTpuTelemetryTraceStart())
    _ring_on = True
    _sync_marks.clear()


def trace_stop() -> None:
    global _ring_on, _ring_ours
    _native.check(_native.lib().DmlcTpuTelemetryTraceStop())
    _ring_on = _ring_ours = False


def trace_dump_json() -> str:
    """Chrome trace-event JSON (load in chrome://tracing or Perfetto)."""
    out = ctypes.c_char_p()
    _native.check(
        _native.lib().DmlcTpuTelemetryTraceDumpJson(ctypes.byref(out)))
    return (out.value or b"{}").decode()


def trace_dump() -> dict:
    """The ring's Chrome trace, parsed; ``otherData.clock_sync`` lists the
    steady-clock reading of every sync mark written since the ring started
    (see :func:`to_profiler_ns`), and ``otherData.registry_at_start`` every
    counter as it read at that start: what the spans that closed BEFORE the
    trace added to their ``total=`` counters (a span still open then is in
    neither)."""
    doc = json.loads(trace_dump_json())
    other = doc.setdefault("otherData", {})
    other["clock_sync"] = list(_sync_marks)
    if _registry_at_start is not None:
        other["registry_at_start"] = dict(_registry_at_start)
    return doc


def record_span(name: str, ts_us: int, dur_us: int, lineage: int = -1,
                total: Optional[str] = None) -> None:
    """Record one complete span into the active trace.  Timestamps are
    steady-clock microseconds — ``time.monotonic_ns() // 1000`` on Linux
    shares an epoch with the native spans, so Python and C++ spans line up
    on one timeline.  ``lineage`` (see :func:`lineage`) is the batch the
    span handled and goes out as ``args.lineage``.  ``total`` names the
    counter that keeps this span's total: ``dur_us`` is added to it in the
    same native call, tracing on or off."""
    _span_end(name, ts_us, dur_us, lineage, total, False)


def _span_end(name: str, ts_us: int, dur_us: int, lineage: int,
              total: Optional[str], main_outermost: bool) -> None:
    _native.check(_native.lib().DmlcTpuTelemetryRecordSpanTotal(
        name.encode(), int(ts_us), int(dur_us), int(lineage),
        total.encode() if total else None, int(main_outermost)))


# ---- the ring beside a jax.profiler session -----------------------------------
#
# While a ``jax.profiler`` session is live the ring records too, so that one
# traced run holds the host's side twice over: the few spans that may name a
# device gap as annotations in the profiler's file, and every stage and
# hand-off of the feed, native ones included, in the ring.  Sync marks tie the
# ring's steady clock to the profiler's.

_JAX_PROFILER = "jax._src.profiler"     # where jax keeps its live session
_SYNC_PREFIX = "dmlctpu.clock_sync."
_SYNC_EVERY_US = 1_000_000
_sync_marks: List[int] = []     # steady-clock us of every mark of this trace
_ring_on = False                # the ring is recording
_ring_seen = None               # the profiler session last seen, or None
_ring_ours = False              # the ring was started for it: stop it with it
_ring_lock = threading.Lock()
_registry_at_start: Optional[Dict[str, int]] = None     # see trace_dump()


def _follow_profiler(profiler) -> None:
    """Start the ring the first time a span sees a live profiler session
    (unless the caller's own trace is recording: that one stays), stop it
    when the session is gone, and write a sync mark at the start and whenever
    the last is older than a second.  An attribute read while none runs."""
    global _ring_seen, _ring_ours
    state = getattr(sys.modules.get(_JAX_PROFILER), "_profile_state", None)
    session = getattr(state, "profile_session", None)
    if session is _ring_seen:
        if session is not None:
            last = _sync_marks[-1:]     # a caller's trace_start may clear it
            if not last or now_us() - last[0] > _SYNC_EVERY_US:
                _sync_mark(profiler)
        return
    with _ring_lock:
        if session is _ring_seen:
            return
        if _ring_ours:
            trace_stop()
        if session is not None:
            if not _ring_on:
                _ring_start()
                _ring_ours = True
            _sync_mark(profiler)
        _ring_seen = session


def _sync_mark(profiler) -> None:
    """One reading of the steady clock, written into the profiler's file as
    the NAME of an annotation of no length: the profiler stamps its own clock
    on the annotation, so each mark is one point of the map between the two.
    (Only a host event's name, start and end reach the benchmark's readers.)"""
    reading = now_us()
    with profiler.TraceAnnotation(_SYNC_PREFIX + str(reading)):
        pass
    _sync_marks.append(reading)


def clock_fit(marks) -> Tuple[float, float, float]:
    """``(offset_ns, drift, err_us)`` of the straight line through sync
    marks ``[(steady_us, profiler_ns), ...]`` (two or more, by least
    squares): ``profiler_ns = offset_ns + (1 + drift) * 1000 * steady_us``.
    ``err_us`` bounds the map's error at the marks: the largest distance of
    a mark from the line plus the one microsecond a reading is cut to."""
    marks = sorted(marks)
    if len(marks) < 2:
        raise ValueError("clock_fit needs two sync marks or more")
    x0, y0 = marks[0]
    xs = [1000.0 * (x - x0) for x, _ in marks]      # ns since the first mark
    ys = [float(y - y0) for _, y in marks]
    n = len(marks)
    mx, my = sum(xs) / n, sum(ys) / n
    if xs[-1] < 1e8:
        slope = 1.0     # under 0.1 s apart: rounding, not drift, would set it
    else:
        slope = (sum((x - mx) * (y - my) for x, y in zip(xs, ys))
                 / sum((x - mx) ** 2 for x in xs))
    at0 = my - slope * mx                   # the line at the first mark
    err_ns = max(abs(at0 + slope * x - y) for x, y in zip(xs, ys))
    return (y0 + at0 - slope * 1000.0 * x0, slope - 1.0,
            err_ns / 1000.0 + 1.0)


def to_profiler_ns(ts_us: float, marks) -> float:
    """A steady-clock time of the ring (microseconds) on the profiler's clock
    (nanoseconds), through the sync marks ``[(steady_us, profiler_ns), ...]``
    read off the profiler's file: the one map between the two timelines."""
    offset_ns, drift, _ = clock_fit(marks)
    return offset_ns + (1.0 + drift) * 1000.0 * ts_us


def _profiler_annotation(name: str):
    """``jax.profiler.TraceAnnotation("dmlctpu.<name>")`` in a process that
    has imported jax, else None.  Never imports jax: the tracker, the
    dataservice workers and load-generating children stay JAX-free."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    _follow_profiler(profiler)
    return profiler.TraceAnnotation("dmlctpu." + name)


class _Span:
    """What :func:`span` yields: set ``lineage`` inside the body when the
    batch in hand is known only there."""
    __slots__ = ("lineage",)

    def __init__(self, lineage: int):
        self.lineage = lineage


_main_depth = 0     # spans open on the main thread (only it writes this)
_main_ident = threading.main_thread().ident


@contextlib.contextmanager
def span(name: str, lineage: int = -1,
         total: Optional[str] = None) -> Iterator[_Span]:
    """One span, two sinks.  The body is recorded into the native ring
    (steady clock; ``trace_dump()``, Perfetto) when tracing is on, and — in
    a process where jax is already imported — also as the profiler
    annotation ``dmlctpu.<name>``, which a running ``jax.profiler`` trace
    puts on the device trace's clock (a flag test while none runs).  The
    ring's copy carries ``lineage``, the batch the span handled.

    ``total`` names the counter that keeps the span's total: its duration,
    the one pair of clock reads taken here, is added to it at exit whether
    tracing is on or off, so a stretch is never timed twice to have both.
    The outermost span open on the main thread adds the same duration to
    ``main.span_us``: nested spans count once, other threads' not at all."""
    global _main_depth
    note = _profiler_annotation(name)
    this = _Span(lineage)
    on_main = threading.get_ident() == _main_ident
    if on_main:
        _main_depth += 1
    t0 = now_us()
    try:
        if note is None:
            yield this
        else:
            with note:
                yield this
    finally:
        dur_us = now_us() - t0
        if on_main:
            _main_depth -= 1
        _span_end(name, t0, dur_us, this.lineage, total,
                  on_main and _main_depth == 0)


# ---- trace context (job-wide causality) -------------------------------------
#
# A trace context is (trace_id, parent_span_id, lineage) — three integers a
# client mints once per epoch/request and every downstream process adopts
# before doing traced work on its behalf.  The native span recorder stamps
# the ambient context onto every span it buffers, so after the tracker
# merges per-host dumps (``MetricsAggregator.job_trace``) a remote worker's
# parse/pack spans carry the same ``trace_id`` as the client's epoch span
# and Perfetto queries can walk the causal chain.  The context is advisory
# labeling, not a synchronization edge; trace_id 0 means "no context".
# Wire format: ``{"id": "<16-hex>", "span": "<16-hex>", "lineage": int}``
# — ids travel as hex strings because the JSON consumers include
# JavaScript, which corrupts integers past 2**53.

_trace_id_lock = threading.Lock()
_trace_id_counter = 0


def new_trace_id() -> int:
    """Mint a fresh nonzero 64-bit trace id: 32 bits of pid-seeded entropy,
    32 bits of process-local counter — collision-free within a process and
    unlikely to collide across the job's hosts."""
    global _trace_id_counter
    with _trace_id_lock:
        _trace_id_counter += 1
        low = _trace_id_counter & 0xFFFFFFFF
    high = (os.getpid() ^ int.from_bytes(os.urandom(4), "little")) & 0xFFFFFFFF
    tid = (high << 32) | low
    return tid or 1


def set_trace_context(trace_id: int, parent_span: int = 0,
                      lineage_id: int = -1) -> None:
    """Install the ambient trace context stamped onto subsequently recorded
    native spans.  ``trace_id`` 0 clears it (spans stop carrying args)."""
    _native.check(_native.lib().DmlcTpuTelemetrySetTraceContext(
        int(trace_id) & 0xFFFFFFFFFFFFFFFF,
        int(parent_span) & 0xFFFFFFFFFFFFFFFF, int(lineage_id)))


def get_trace_context() -> Tuple[int, int, int]:
    """Current ambient ``(trace_id, parent_span, lineage)`` (0, 0, -1 when
    unset or when telemetry is compiled out)."""
    tid = ctypes.c_uint64()
    parent = ctypes.c_uint64()
    lin = ctypes.c_int64()
    _native.check(_native.lib().DmlcTpuTelemetryGetTraceContext(
        ctypes.byref(tid), ctypes.byref(parent), ctypes.byref(lin)))
    return int(tid.value), int(parent.value), int(lin.value)


def clear_trace_context() -> None:
    set_trace_context(0, 0, -1)


def trace_context_wire() -> Optional[dict]:
    """The ambient context as its wire dict (attach under a ``"trace"`` key
    in a request frame), or ``None`` when no context is installed."""
    tid, parent, lin = get_trace_context()
    if not tid:
        return None
    return {"id": format(tid, "016x"), "span": format(parent, "016x"),
            "lineage": lin}


def adopt_trace_context(wire: Optional[dict]) -> bool:
    """Install a context received off the wire (the dict form produced by
    :func:`trace_context_wire`; malformed/absent input is ignored).  Bumps
    ``trace.ctx_propagated`` on every successful adoption so the job-trace
    health row can count cross-process hops."""
    if not isinstance(wire, dict):
        return False
    try:
        tid = int(str(wire.get("id", "0")), 16)
        parent = int(str(wire.get("span", "0")), 16)
        lin = int(wire.get("lineage", -1))
    except (TypeError, ValueError):
        return False
    if not tid:
        return False
    set_trace_context(tid, parent, lin)
    counter_add("trace.ctx_propagated", 1)
    return True


def lineage(batch) -> int:
    """Lineage id of a staged batch: ``(global virtual part << 32) | chunk
    index``, minted by the sharded parser at the split chunk and threaded
    through the staged batcher, the 0xff9a wire, and H2D staging.  ``-1``
    when the batch predates lineage tracking or came off a non-sharded
    source.  Accepts a ``PaddedBatch`` (plain ``_lineage`` attribute) or
    the raw staged dict (``"lineage"`` key)."""
    if isinstance(batch, dict):
        return int(batch.get("lineage", -1))
    return int(getattr(batch, "_lineage", -1))


def json_validate(text: str) -> bool:
    """True when ``text`` is one complete JSON value per the native
    ``JSONReader`` (the same parser the C++ side loads snapshots with) —
    the check.sh jobtrace tier validates merged traces through this so the
    contract is the native reader's, not Python's."""
    ok = ctypes.c_int()
    _native.check(_native.lib().DmlcTpuJsonValidate(
        text.encode(), ctypes.byref(ok)))
    return bool(ok.value)


# ---- stall attribution ------------------------------------------------------

# (stage, busy counter, wait counter) — the contract with the native
# instrumentation; see doc/observability.md for what each pair means.
_STAGES: Tuple[Tuple[str, str, str], ...] = (
    ("parse", "parse.busy_us", "parse.input_wait_us"),
    ("shard", "shard.part_us", "shard.producer_wait_us"),
    ("pack", "pack.busy_us", "pack.input_wait_us"),
    ("h2d", "h2d.busy_us", "h2d.wait_us"),
)


def stall_attribution(before: dict, after: dict,
                      wall_s: Optional[float] = None) -> dict:
    """Derive per-stage busy/wait seconds and a bottleneck ranking from two
    snapshots.

    Returns ``{"stages": {name: {"busy_s", "wait_s"}}, "bound": {...},
    "bound_stage": str|None, "table": str, "wall_s": float|None}``.

    ``bound`` shares are each candidate stage's busy seconds over the busy
    total.  ``parse`` is excluded from the candidates whenever the sharded
    pool ran (its workers' parse time is already inside ``shard`` busy);
    ``shard`` busy is part wall time minus producer stalls.

    ``restarted`` is True when any counter moved backwards between the
    snapshots (a worker restart re-registered from zero): the clamped
    deltas then under-count the interval, so treat the attribution as a
    lower bound rather than silently trusting it.

    When the retry substrate was active in the interval (any of
    ``io.retry`` / ``io.giveup`` / ``io.retry_wait_us`` moved) an ``io``
    pseudo-stage joins the table, with backoff sleep time as its busy
    seconds — a flaky source then shows up as "io-bound" instead of being
    silently folded into the reading stage's busy time.  The raw interval
    totals are always in the result's ``io`` dict.

    Likewise a ``cache`` stage joins the table when the interval served
    batches from the binned epoch cache (``cache.busy_us`` /
    ``cache.wait_us`` / ``cache.hit_bytes`` moved) — a cache-hit epoch
    then attributes its read time instead of showing an idle parse stage.
    """
    d = counters_delta(before, after)
    us = lambda k: d.get(k, 0) / 1e6  # noqa: E731

    stages: Dict[str, Dict[str, float]] = {}
    for name, busy_key, wait_key in _STAGES:
        busy, wait = us(busy_key), us(wait_key)
        if name == "shard":
            busy = max(busy - wait, 0.0)
        stages[name] = {"busy_s": round(busy, 6), "wait_s": round(wait, 6)}

    io = {
        "retry": d.get("io.retry", 0),
        "giveup": d.get("io.giveup", 0),
        "retry_wait_s": round(us("io.retry_wait_us"), 6),
        "corrupt_skipped": d.get("record.corrupt_skipped", 0),
        "part_retries": d.get("shard.part_retries", 0),
    }
    if io["retry"] or io["giveup"] or io["retry_wait_s"]:
        # pseudo-stage only when retries actually happened, so quiet runs
        # keep the classic four-stage table
        stages["io"] = {"busy_s": io["retry_wait_s"], "wait_s": 0.0}

    # binned epoch cache (doc/binned_cache.md): when the interval served
    # from cache (hit bytes or read time moved), the cache read stage joins
    # the table in place of the parse work it replaced; text-parse epochs
    # keep the classic table.  copy_ratio = bytes copied host-side per byte
    # served — the zero-copy hit path's proof metric (~0 when the mmap
    # backend serves borrowed views; >=1 when every block goes through
    # decode buffers, i.e. the streaming fallback engaged)
    cache_busy, cache_wait = us("cache.busy_us"), us("cache.wait_us")
    cache_hit = d.get("cache.hit_bytes", 0)
    if cache_busy or cache_wait or cache_hit:
        cache_stage = {"busy_s": round(cache_busy, 6),
                       "wait_s": round(cache_wait, 6),
                       "copy_ratio": round(
                           d.get("cache.bytes_copied", 0) / cache_hit, 4)
                       if cache_hit else 0.0}
        # block-codec decode accounting (doc/binned_cache.md "Block
        # codec"): when compressed records decoded in the interval,
        # codec_ratio = decompressed bytes out per stored byte in (the
        # compression ratio as observed at serve time) and decode_s the
        # decode wall time — already INSIDE busy_s, the decode runs in the
        # repack stage, so it is a breakdown, not a fifth stage
        codec_in = d.get("cache.codec.bytes_in", 0)
        if codec_in:
            cache_stage["codec_ratio"] = round(
                d.get("cache.codec.bytes_out", 0) / codec_in, 4)
            cache_stage["decode_s"] = round(us("cache.codec.decode_us"), 6)
        stages["cache"] = cache_stage

    # online scoring (doc/serving.md): when the interval served /score
    # traffic (device scoring time or micro-batch queueing moved), a
    # ``serve`` stage joins the table — busy is time inside the jitted
    # predict dispatch, wait the requests' time parked in the micro-batch
    # queue, so a latency-bound server shows up as serve-bound instead of
    # an idle training pipeline
    serve_busy, serve_wait = us("serve.score_busy_us"), us("serve.queue_wait_us")
    if serve_busy or serve_wait or d.get("serve.rows", 0):
        stages["serve"] = {"busy_s": round(serve_busy, 6),
                           "wait_s": round(serve_wait, 6)}

    sharded = d.get("shard.parts", 0) > 0
    candidates = [n for n in stages if not (sharded and n == "parse")]
    total_busy = sum(stages[n]["busy_s"] for n in candidates)
    bound = {
        n: round(100.0 * stages[n]["busy_s"] / total_busy, 1)
        for n in candidates
    } if total_busy > 0 else {}
    bound_stage = max(bound, key=bound.get) if bound else None
    table = ", ".join(f"{n}-bound {bound[n]:.0f}%"
                      for n in sorted(bound, key=bound.get, reverse=True)
                      if bound[n] >= 0.5)
    return {
        "stages": stages,
        "bound": bound,
        "bound_stage": bound_stage,
        "table": table,
        "wall_s": None if wall_s is None else round(wall_s, 6),
        "restarted": snapshot_restarted(before, after),
        "io": io,
    }


class Window:
    """One measured telemetry interval (see :func:`window`).

    Inside the ``with`` body only ``before`` is set; on exit the window is
    closed and carries ``after``, ``wall_s``, the clamped counter ``delta``,
    the full :func:`stall_attribution` result, and the ``restarted`` flag
    (True when a counter moved backwards mid-window — treat the deltas as a
    lower bound and do not let them drive tuning decisions).
    """

    __slots__ = ("before", "after", "wall_s", "delta", "attribution",
                 "restarted", "_t0")

    def __init__(self) -> None:
        self._t0 = time.monotonic()
        self.before: dict = {}
        self.after: Optional[dict] = None
        self.wall_s: Optional[float] = None
        self.delta: Dict[str, int] = {}
        self.attribution: Optional[dict] = None
        self.restarted = False

    @property
    def closed(self) -> bool:
        return self.after is not None

    @property
    def bound_stage(self) -> Optional[str]:
        return self.attribution["bound_stage"] if self.attribution else None

    def bytes_processed(self) -> int:
        """Pipeline bytes moved in the window: the max of the per-path byte
        counters (shard/parse/record), which never double-counts — the
        sharded pool's inner parsers feed both shard.bytes and parse.bytes
        with the same bytes."""
        return max(self.delta.get("shard.bytes", 0),
                   self.delta.get("parse.bytes", 0),
                   self.delta.get("record.bytes", 0))

    def mb_per_s(self) -> float:
        """Window throughput in MB/s (0.0 for an unclosed/instant window)."""
        if not self.wall_s or self.wall_s <= 0:
            return 0.0
        return self.bytes_processed() / (1 << 20) / self.wall_s

    def close(self) -> None:
        """Close the window now (idempotent; the context manager calls it)."""
        if self.after is not None:
            return
        self.wall_s = time.monotonic() - self._t0
        self.after = snapshot()
        self.delta = counters_delta(self.before, self.after)
        self.attribution = stall_attribution(self.before, self.after,
                                             wall_s=self.wall_s)
        self.restarted = self.attribution["restarted"]

    def open(self) -> "Window":
        self.before = snapshot()
        self._t0 = time.monotonic()
        return self


@contextlib.contextmanager
def window() -> Iterator[Window]:
    """Snapshot-pair context manager: one :class:`Window` measuring the
    body.  Replaces hand-rolled before/after snapshot plumbing, as the
    telemetry tests and (through :class:`Window`) the autotuner use it::

        with telemetry.window() as w:
            run_epoch()
        print(w.mb_per_s(), w.attribution["table"])
    """
    w = Window().open()
    try:
        yield w
    finally:
        w.close()


def format_stall_table(attr: dict) -> str:
    """Render a :func:`stall_attribution` result as an aligned text table."""
    lines = ["stage     busy_s    wait_s   bound%"]
    for name, st in attr["stages"].items():
        pct = attr["bound"].get(name)
        lines.append(f"{name:<8}{st['busy_s']:>9.3f}{st['wait_s']:>10.3f}"
                     f"{'' if pct is None else f'{pct:>8.1f}'}")
    cache = attr["stages"].get("cache", {})
    if "codec_ratio" in cache:
        lines.append(f"codec   {cache['codec_ratio']:.2f}x expansion, "
                     f"{cache['decode_s']:.3f}s decode (inside cache busy)")
    if attr["table"]:
        lines.append(attr["table"])
    return "\n".join(lines)


# ---- stall watchdog + flight recorder ---------------------------------------

_watchdog_lock = threading.Lock()
_watchdog_depth = 0


@contextlib.contextmanager
def watchdog(deadline_s: float = 30.0, poll_s: Optional[float] = None,
             policy: str = "warn", dump_path: Optional[str] = None,
             ) -> Iterator[None]:
    """Arm the native stall watchdog for the duration of the body.

    When NO pipeline progress counter (split/parse/shard/pack/record/h2d)
    moves for ``deadline_s``, the watchdog dumps a flight record — stalled
    stage, per-stage progress ages, every gauge, the trace buffers — to
    ``dump_path`` (when given) and the log sink, then either keeps running
    re-armed (``policy="warn"``) or aborts the process (``policy="abort"``).

    Nesting refcounts: the outermost ``watchdog()`` arms (its options win)
    and the last exit disarms, so the staging iterators can arm it per
    epoch while a caller holds a longer-lived one.  No-op when telemetry is
    compiled out."""
    if policy not in ("warn", "abort"):
        raise ValueError(f"watchdog policy must be 'warn' or 'abort', "
                         f"got {policy!r}")
    global _watchdog_depth
    with _watchdog_lock:
        _watchdog_depth += 1
        if _watchdog_depth == 1:
            _native.check(_native.lib().DmlcTpuWatchdogStart(
                max(int(deadline_s * 1000), 1),
                0 if poll_s is None else max(int(poll_s * 1000), 1),
                1 if policy == "abort" else 0,
                (dump_path or "").encode()))
    try:
        yield
    finally:
        with _watchdog_lock:
            _watchdog_depth -= 1
            if _watchdog_depth == 0:
                _native.check(_native.lib().DmlcTpuWatchdogStop())


def watchdog_from_env() -> contextlib.AbstractContextManager:
    """Watchdog configured from the environment, or a no-op context when
    ``DMLCTPU_WATCHDOG_DEADLINE_S`` is unset — how the staging iterators
    arm it without new call-site plumbing.  Knobs:

    * ``DMLCTPU_WATCHDOG_DEADLINE_S`` — deadline seconds (required)
    * ``DMLCTPU_WATCHDOG_POLICY`` — ``warn`` (default) or ``abort``
    * ``DMLCTPU_WATCHDOG_DUMP`` — flight-record file path
    """
    deadline = os.environ.get("DMLCTPU_WATCHDOG_DEADLINE_S")
    if not deadline:
        return contextlib.nullcontext()
    return watchdog(
        deadline_s=float(deadline),
        policy=os.environ.get("DMLCTPU_WATCHDOG_POLICY", "warn"),
        dump_path=os.environ.get("DMLCTPU_WATCHDOG_DUMP") or None)


def watchdog_running() -> bool:
    out = ctypes.c_int()
    _native.check(_native.lib().DmlcTpuWatchdogRunning(ctypes.byref(out)))
    return bool(out.value)


def watchdog_stall_count() -> int:
    """Stalls detected since process start (across arm/disarm cycles)."""
    out = ctypes.c_int64()
    _native.check(
        _native.lib().DmlcTpuWatchdogStallCount(ctypes.byref(out)))
    return int(out.value)


def flight_record(reason: str = "manual") -> dict:
    """Build a flight record right now (same JSON the watchdog dumps):
    stalled stage + per-stage progress ages (when armed), the full registry
    snapshot, and the trace buffers."""
    out = ctypes.c_char_p()
    _native.check(_native.lib().DmlcTpuFlightRecordJson(
        reason.encode(), ctypes.byref(out)))
    return json.loads((out.value or b"{}").decode())


def last_flight_record() -> Optional[dict]:
    """The record from the most recent watchdog stall, or None."""
    out = ctypes.c_char_p()
    _native.check(
        _native.lib().DmlcTpuWatchdogLastRecordJson(ctypes.byref(out)))
    raw = (out.value or b"").decode()
    return json.loads(raw) if raw else None


# ---- always-on time-series sampler ------------------------------------------

_timeseries_lock = threading.Lock()
_timeseries_depth = 0


def timeseries_start(tick_ms: int = 0, fine_slots: int = 0,
                     coarse_every: int = 0, coarse_slots: int = 0) -> None:
    """Start (or restart with new options) the native background sampler.

    Every ``tick_ms`` the sampler snapshots each registered counter/gauge
    into a fixed-size fine ring (newest ``fine_slots`` ticks) and, every
    ``coarse_every`` ticks, rolls the window up into a coarse ring
    (``coarse_slots`` slots) — bounded memory regardless of run length.
    Args <= 0 fall back to ``DMLCTPU_TS_TICK_MS`` (1000),
    ``DMLCTPU_TS_FINE_SLOTS`` (600), 30, and ``DMLCTPU_TS_COARSE_SLOTS``
    (960).  Starting also installs the crash-forensics black box (fatal-log
    hook + SIGABRT/SIGTERM flight-file dump).  No-op when telemetry is
    compiled out."""
    _native.check(_native.lib().DmlcTpuTimeseriesStart(
        int(tick_ms), int(fine_slots), int(coarse_every), int(coarse_slots)))


def timeseries_stop() -> None:
    """Stop the sampler thread; rings are kept and still served."""
    _native.check(_native.lib().DmlcTpuTimeseriesStop())


def timeseries_active() -> bool:
    out = ctypes.c_int()
    _native.check(_native.lib().DmlcTpuTimeseriesActive(ctypes.byref(out)))
    return bool(out.value)


def timeseries_sample() -> None:
    """Force one synchronous sampler tick (tests / deterministic drains)."""
    _native.check(_native.lib().DmlcTpuTimeseriesSample())


def timeseries_json() -> str:
    """Raw JSON document with every series' full fine+coarse rings."""
    out = ctypes.c_char_p()
    _native.check(_native.lib().DmlcTpuTimeseriesJson(ctypes.byref(out)))
    return (out.value or b"{}").decode()


def timeseries_tail_json(points: int = 60) -> str:
    """Raw JSON with only the newest ``points`` fine points per series —
    the bounded tail that rides metric pushes and flight records."""
    out = ctypes.c_char_p()
    _native.check(_native.lib().DmlcTpuTimeseriesTailJson(
        int(points), ctypes.byref(out)))
    return (out.value or b"{}").decode()


def timeseries(points: int = 0) -> dict:
    """Parsed time-series document: ``{"enabled", "active", "tick_ms",
    "series": {name: {"kind", "rate_per_s"?, "fine": [[t_us, v], ...],
    "coarse": [...]}}}``.  ``points > 0`` limits each ring to the newest
    ``points`` entries."""
    raw = timeseries_tail_json(points) if points > 0 else timeseries_json()
    return json.loads(raw)


@contextlib.contextmanager
def timeseries_from_env() -> Iterator[None]:
    """Arm the sampler for the duration of the body when
    ``DMLCTPU_TIMESERIES=1`` (any non-empty value other than ``0``), else a
    no-op — how the staging iterators get always-on sampling without call-
    site plumbing.  Tick/ring knobs come from ``DMLCTPU_TS_TICK_MS`` /
    ``DMLCTPU_TS_FINE_SLOTS`` / ``DMLCTPU_TS_COARSE_SLOTS``.  Nesting
    refcounts like :func:`watchdog`: the outermost entry starts, the last
    exit stops."""
    armed = os.environ.get("DMLCTPU_TIMESERIES", "")
    if not armed or armed == "0":
        yield
        return
    global _timeseries_depth
    with _timeseries_lock:
        _timeseries_depth += 1
        if _timeseries_depth == 1:
            timeseries_start()
    try:
        resource_sample()
        yield
    finally:
        with _timeseries_lock:
            _timeseries_depth -= 1
            if _timeseries_depth == 0:
                timeseries_stop()


def resource_sample() -> dict:
    """Publish device-memory gauges from jax and return what was set.

    Sets ``resource.hbm_bytes_in_use`` / ``resource.hbm_bytes_limit`` from
    the first device that reports ``memory_stats()`` (TPU/GPU backends; CPU
    returns nothing and the gauges stay untouched).  Host-side gauges
    (``resource.rss_bytes``, ``resource.fd_count``, ``resource.cpu_ms``)
    are published by the native sampler itself each tick."""
    published: Dict[str, int] = {}
    try:
        import jax
        for dev in jax.devices():
            stats = getattr(dev, "memory_stats", lambda: None)()
            if not stats:
                continue
            in_use = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit")
            if in_use is not None:
                gauge_set("resource.hbm_bytes_in_use", int(in_use))
                published["resource.hbm_bytes_in_use"] = int(in_use)
            if limit is not None:
                gauge_set("resource.hbm_bytes_limit", int(limit))
                published["resource.hbm_bytes_limit"] = int(limit)
            break
    except Exception:  # pragma: no cover - jax backend quirks must not raise
        pass
    return published


# ---- log capture ------------------------------------------------------------

@contextlib.contextmanager
def capture_logs(min_severity: int = 2,
                 forward: Optional[Callable[[int, str, str], None]] = None,
                 ) -> Iterator[List[Tuple[int, str, str]]]:
    """Capture native log lines at or above ``min_severity`` (0=DEBUG 1=INFO
    2=WARNING 3=ERROR) as ``(severity, where, message)`` tuples instead of
    letting them hit stderr.  Restores the stderr sink on exit.  The sink is
    process-wide: nesting or concurrent captures see whichever was installed
    last."""
    records: List[Tuple[int, str, str]] = []

    def sink(severity: int, where: str, message: str) -> None:
        if severity >= min_severity:
            records.append((severity, where, message))
        if forward is not None:
            forward(severity, where, message)

    _native.set_log_callback(sink)
    try:
        yield records
    finally:
        _native.set_log_callback(None)
