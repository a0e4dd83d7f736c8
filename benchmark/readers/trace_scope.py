"""Reader ``trace_scope``: device time per program scope.

An ``XLA Ops`` event's *name* is the HLO text; the scope path the program
gave the instruction (``jax.named_scope``, the jitted functions' names) is in
the event's **metadata**: the stat ``tf_op``, e.g.
``jit(_build_tree)/gbdt.route/gather:``.  ``jax.profiler.ProfileData`` shows
an event's own stats only, so this reads the run's xplane file itself, by
the protobuf wire format (six messages, no TensorFlow import), and joins each
event to its metadata **by the event's metadata id**, not by its name: two
programs can hold the same HLO text under different scopes.

args: ``scope`` (regex on ``tf_op``), ``exclude`` (optional regex), ``per``
(a key of the generator's counts), ``scale``: the *union* of device time of
the matching events inside the traced window, a chip's mean, per count — a
``while`` and its body are one stretch of time, not two.
``"what": "unscoped_pct"`` with ``scoped`` (a list of regexes): the share of
the busy union that no event matching any of them covers.

The trace is ``<cell.cache_dir>/trace/**/*.xplane.pb``, still on disk when
readers run; parsed once a run.  Returns nothing when no event matches: a
program without scopes (the parent of the PR that brought them) has nothing
to read here.
"""
from __future__ import annotations

import glob
import lzma
import re

from benchmark.trace_reduce import union_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SCOPE_STAT = "tf_op"


def fields(buf: bytes):
    """``(field number, wire type, value)`` of one protobuf message: varints
    as ints, length-delimited fields as bytes, fixed 64/32 as bytes."""
    at, end = 0, len(buf)

    def varint() -> int:
        nonlocal at
        value = shift = 0
        while True:
            b = buf[at]
            at += 1
            value |= (b & 0x7F) << shift
            if b < 0x80:
                return value
            shift += 7

    while at < end:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = varint()
        elif wire in (1, 2, 5):
            size = varint() if wire == 2 else 8 if wire == 1 else 4
            value = buf[at:at + size]
            at += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf: bytes) -> tuple:
    key, value = 0, b""
    for n, _w, v in fields(buf):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def _stat_text(buf: bytes, stat_names: dict) -> tuple:
    """XStat -> ``(metadata id, text)``: a ``str_value``, or the name of the
    stat metadata a ``ref_value`` points to; ``None`` for a number."""
    meta, text = 0, None
    for n, _w, v in fields(buf):
        if n == 1:
            meta = v
        elif n == 5:
            text = v.decode("utf-8", "replace")
        elif n == 7:
            text = stat_names.get(v, "")
    return meta, text


def plane_ops(buf: bytes) -> list:
    """XPlane -> ``[(scope, name, start ns, end ns), ...]`` of a TPU plane's
    ``XLA Ops`` lines (nothing for any other plane).  Start and end are
    worked out as ``jax.profiler.ProfileData`` gives them (whole nanoseconds
    of the picosecond offsets), so that sums here equal ``trace_reduce``'s."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for n, _w, v in fields(buf):
        if n == 2:
            name = v.decode()
        elif n == 3:
            lines.append(v)
        elif n == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif n == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (x.decode("utf-8", "replace") for m, _, x in fields(value)
                 if m == 2), "")
    if not DEVICE_PLANE.match(name):
        return []
    scope_ids = {k for k, s in stat_names.items() if s == SCOPE_STAT}
    described = {}          # metadata id -> (scope, HLO text)
    for key, value in event_meta.items():
        text, scope = "", ""
        for n, _w, v in fields(value):
            if n == 2:
                text = v.decode("utf-8", "replace")
            elif n == 5:
                meta, got = _stat_text(v, stat_names)
                if meta in scope_ids and got is not None:
                    scope = got
        described[key] = (scope, text)
    ops = []
    for line in lines:
        line_name, t0, events = "", 0, []
        for n, _w, v in fields(line):
            if n == 2:
                line_name = v.decode()
            elif n == 3:
                t0 = _signed(v)
            elif n == 4:
                events.append(v)
        if line_name != OPS_LINE:
            continue
        for event in events:
            meta = offset = duration = 0
            for n, _w, v in fields(event):
                if n == 1:
                    meta = v
                elif n == 2:
                    offset = _signed(v)
                elif n == 3:
                    duration = _signed(v)
            start = t0 + offset // 1000
            scope, text = described.get(meta, ("", ""))
            ops.append((scope, text, start, start + duration // 1000))
    return ops


def load(path: str) -> list:
    """Per chip that ran anything: ``[(scope, name, start, end), ...]``."""
    opener = lzma.open if path.endswith(".xz") else open
    with opener(path, "rb") as f:
        space = f.read()
    chips = [plane_ops(v) for n, _w, v in fields(space) if n == 1]
    return [c for c in chips if c]


def scope_s(chips: list, window: tuple, scope: str,
            exclude: str | None = None) -> float:
    """Seconds (union, a chip's mean) of events inside ``window`` whose
    scope matches ``scope`` and not ``exclude``."""
    want = re.compile(scope)
    drop = re.compile(exclude) if exclude else None
    lo, hi = window
    total, ran = 0, 0
    for chip in chips:
        inside = [(max(s, lo), min(e, hi), sc) for sc, _, s, e in chip
                  if e > lo and s < hi]
        if not inside:
            continue
        ran += 1
        total += union_ns([(s, e) for s, e, sc in inside if want.search(sc)
                           and not (drop and drop.search(sc))])
    return total / ran / 1e9 if ran else 0.0


def chips_of(run) -> list:
    """The run's xplane, parsed once: kept on the run's ``Trace``."""
    chips = getattr(run.trace, "scoped_chips", None)
    if chips is None:
        files = sorted(glob.glob(str(run.cell.cache_dir / "trace" / "**"
                                     / "*.xplane.pb"), recursive=True))
        chips = run.trace.scoped_chips = load(files[0]) if files else []
    return chips


def read(args: dict, run):
    if run.trace is None or not run.trace.chips:
        return None
    chips = chips_of(run)
    window = run.trace.window_ns
    if args.get("what") == "unscoped_pct":
        busy = scope_s(chips, window, "")
        scoped = scope_s(chips, window, "|".join(args["scoped"]))
        if not busy or not scoped:
            return None
        # an unscoped event that runs inside a scoped ``while`` is covered
        return 100.0 * (1.0 - scoped / busy)
    seconds = scope_s(chips, window, args["scope"], args.get("exclude"))
    per = run.counts.get(args["per"], 0)
    if not seconds or not per:
        return None
    return args.get("scale", 1.0) * seconds / per
