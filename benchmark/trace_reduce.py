"""From a profiler trace (xplane) to numbers: device busy union, idle share,
device time per event-name pattern, the longest idle gaps named by the host
span that covers them.

What a TPU trace holds (read off ``testdata/record_trace.py``'s output, PR 23):
one plane ``/device:TPU:<n>`` a chip with the lines ``XLA Modules`` (one event
a program run), ``XLA Ops`` (one event an HLO instruction; a ``while`` covers
its body's events, so durations overlap and busy time is a union, never a
sum) and ``Async XLA Ops`` (copies in flight, which overlap compute and are
not counted busy); and the plane ``/host:CPU`` whose ``python`` line holds
every ``TraceAnnotation`` of this process on the same clock.  An instruction's
event is named by its HLO text, ``%name = shape op(...)``; a Pallas kernel
appears as ``%<jitted wrapper>.<n> = ... custom-call(...)``.

The window is the ``bench.window`` annotation that ``run.py`` holds open around
the measured window; events are clipped to it.  Several chips: busy seconds
are averaged over the chips that ran anything.
"""
from __future__ import annotations

import dataclasses
import lzma
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# host spans that may name a gap, in the order they are preferred: the
# benchmark's own and the program's staging annotations
GAP_SPANS = re.compile(r"^(bench\.|dmlctpu\.)")


def short_name(name: str) -> str:
    """``%fusion.5 = f32[8,4]{...} fusion(...)`` -> ``%fusion.5 f32[8,4] fusion``;
    a tuple-shaped result keeps only its name and kind."""
    m = re.match(r"^(%[\w.\-]+) = (.*)$", name)
    if not m:
        return name[:80]
    shape = re.match(r"^\w+\[[\d,]*\]", m.group(2))
    kind = re.search(r"\s([a-z][\w\-]*)\(", " " + m.group(2))
    parts = [m.group(1), shape.group(0) if shape else "",
             kind.group(1) if kind else ""]
    return " ".join(p for p in parts if p)[:80]


def union_ns(intervals: list) -> int:
    """Total length covered by ``[(start, end), ...]``."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_of(intervals: list, lo: int, hi: int) -> list:
    """The uncovered stretches of ``[lo, hi]``."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Trace:
    window_ns: tuple                 # (start, end) of the traced window
    chips: list                      # per chip: [(name, start, end), ...]
    host_spans: list                 # [(name, start, end), ...]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an instruction ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(union_ns([(s, e) for _, s, e in c])
                   for c in self.chips) / len(self.chips) / 1e9

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def pattern_s(self, pattern: str) -> float:
        """Device seconds (union, a chip's mean) of events whose name
        matches ``pattern``."""
        rx = re.compile(pattern)
        if not self.chips:
            return 0.0
        return sum(union_ns([(s, e) for n, s, e in c if rx.search(n)])
                   for c in self.chips) / len(self.chips) / 1e9

    def top_ops(self, k: int) -> list:
        """``[[name, seconds], ...]``: instructions by summed duration, all
        chips together.  A ``while`` is listed beside its body."""
        total: dict = {}
        for c in self.chips:
            for n, s, e in c:
                key = short_name(n)
                total[key] = total.get(key, 0) + (e - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns / 1e9] for n, ns in top]

    def top_gaps(self, k: int) -> list:
        """``[[what the host was doing, seconds], ...]``: idle stretches of
        the first chip, summed by the innermost benchmark or staging span
        that covers the gap's middle (``no span`` where none does)."""
        if not self.chips:
            return []
        lo, hi = self.window_ns
        spans = [x for x in self.host_spans if x[0] != WINDOW_SPAN]
        total: dict = {}
        for s, e in gaps_of([(s, e) for _, s, e in self.chips[0]], lo, hi):
            mid = (s + e) // 2
            covering = [(ee - ss, n) for n, ss, ee in spans if ss <= mid < ee]
            name = min(covering)[1] if covering else "no span"
            total[name] = total.get(name, 0) + (e - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns / 1e9] for n, ns in top]


def _clip(events: list, lo: int, hi: int) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce_data(data) -> Trace:
    chips, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips.append([(e.name, int(e.start_ns),
                                   int(e.start_ns + e.duration_ns))
                                  for e in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                         for e in line.events
                         if GAP_SPANS.match(e.name)]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if windows:
        lo, hi = max(windows, key=lambda w: w[1] - w[0])
    else:
        every = [x for c in chips for x in c]
        if not every:
            return Trace((0, 1), [], host)
        lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)
    chips = [_clip(c, lo, hi) for c in chips]
    return Trace((lo, hi), [c for c in chips if c], _clip(host, lo, hi))


def reduce(path: str) -> Trace:
    """``path``: an ``.xplane.pb`` file, or one compressed to ``.xz``."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        with lzma.open(path) as f:
            return reduce_data(ProfileData.from_serialized_xspace(f.read()))
    return reduce_data(ProfileData.from_file(path))
