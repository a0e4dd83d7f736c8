"""readers/trace_scope.py on a hand-made plane (written here by the protobuf
wire format) and on the two recorded scoped TPU traces
(``testdata/record_scoped_trace.py``, a v5e, PR 24): one GBDT fit of 2 trees
on 65,536 x 28 rows, three FFM steps of 4,096 x 39 entries."""
import dataclasses
import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark.readers import trace_scope as ts

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
GBDT = str(TESTDATA / "gbdt_fit_scoped.xplane.pb.xz")
FFM = str(TESTDATA / "ffm_steps_scoped.xplane.pb.xz")
UNSCOPED = str(TESTDATA / "gbdt_fit_65536.xplane.pb.xz")     # PR 23's


# ---- a plane by hand ---------------------------------------------------------

def varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name: str, t0_ns: int, events: list, stats_by_ref: bool) -> bytes:
    """``events``: ``[(HLO text, tf_op or None, offset ps, duration ps)]``.
    Each gets an event metadata of its own; ``tf_op`` is stat metadata 7,
    stored as ``str_value`` or, with ``stats_by_ref``, as a ``ref_value``
    to a stat metadata whose name is the string."""
    out = field(2, name)
    out += field(5, field(1, 7) + field(2, field(1, 7) + field(2, "tf_op")))
    out += field(5, field(1, 8) + field(2, field(1, 8) + field(2, "flops")))
    line = field(2, "XLA Ops") + field(3, t0_ns)
    other = field(2, "XLA Modules") + field(3, t0_ns)
    for i, (text, scope, offset, duration) in enumerate(events, 1):
        meta = field(1, i) + field(2, text) + field(5, field(1, 8) + field(3, 99))
        if scope is not None and stats_by_ref:
            ref = 100 + i
            out += field(5, field(1, ref)
                         + field(2, field(1, ref) + field(2, scope)))
            meta += field(5, field(1, 7) + field(7, ref))
        elif scope is not None:
            meta += field(5, field(1, 7) + field(5, scope))
        out += field(4, field(1, i) + field(2, meta))
        event = field(1, i) + field(2, offset) + field(3, duration)
        line += field(4, event)
        other += field(4, event)
    return out + field(3, line) + field(3, other)


SAME_TEXT = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"
EVENTS = [
    (SAME_TEXT, "jit(a)/x.one/add:", 100_000, 200_000),                # 100-300
    (SAME_TEXT, "jit(b)/x.two/add:", 400_000, 100_000),                # 400-500
    ("%while.1 = () while(() %t)", "jit(b)/x.two/while:", 600_000, 300_999),
    ("%add.2 = f32[] add(f32[] %a, f32[] %b)", "jit(b)/x.two/body/add:",
     650_000, 100_000),                                     # inside the while
    ("%copy.3 = f32[8]{0} copy(f32[8]{0} %q)", None, 950_000, 50_000),
]


@pytest.mark.parametrize("by_ref", [False, True])
def test_hand_made_plane_joins_scope_by_metadata_id(tmp_path, by_ref):
    space = (field(1, plane("/device:TPU:0", 1000, EVENTS, by_ref))
             + field(1, plane("/host:CPU", 1000, EVENTS, by_ref)))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space)
    chips = ts.load(str(path))
    assert len(chips) == 1 and len(chips[0]) == len(EVENTS)
    # two events with one HLO text keep their own scopes
    assert [sc for sc, text, _, _ in chips[0] if text == SAME_TEXT] == [
        "jit(a)/x.one/add:", "jit(b)/x.two/add:"]
    # whole nanoseconds of the picosecond offsets, on the line's clock
    assert [(s, e) for _, _, s, e in chips[0]][2] == (1600, 1900)
    window = (1000, 2000)
    assert ts.scope_s(chips, window, r"x\.one") == pytest.approx(200e-9)
    # the while and its body are one stretch: 100 + 300, not 100 + 300 + 100
    assert ts.scope_s(chips, window, r"x\.two") == pytest.approx(400e-9)
    assert ts.scope_s(chips, window, r"x\.two",
                      exclude="while") == pytest.approx(200e-9)
    assert ts.scope_s(chips, window, "") == pytest.approx(650e-9)
    assert ts.scope_s(chips, (1000, 1450), r"x\.") == pytest.approx(250e-9)
    assert ts.scope_s(chips, window, r"x\.three") == 0.0


@dataclasses.dataclass
class FakeCell:
    cache_dir: Path


@dataclasses.dataclass
class FakeRun:
    cell: FakeCell
    trace: tr.Trace
    counts: dict


def fake_run(tmp_path, events, counts) -> FakeRun:
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "now"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(
        field(1, plane("/device:TPU:0", 1000, events, False)))
    chip = [(text, 1000 + o // 1000, 1000 + (o + d) // 1000)
            for text, _, o, d in events]
    return FakeRun(FakeCell(tmp_path), tr.Trace((1000, 2000), [chip], []),
                   counts)


def test_read_per_count_unscoped_share_and_nothing_to_read(tmp_path):
    run = fake_run(tmp_path, EVENTS, {"steps": 4})
    assert ts.read({"scope": r"x\.two", "per": "steps", "scale": 1e9},
                   run) == pytest.approx(100.0)
    # busy 650 ns, of which x.one and x.two cover 600: the copy is unscoped
    assert ts.read({"what": "unscoped_pct", "scoped": [r"x\.one", r"x\.two"]},
                   run) == pytest.approx(100.0 * 50 / 650)
    # a program without the scope: nothing, not zero
    assert ts.read({"scope": r"y\.", "per": "steps"}, run) is None
    assert ts.read({"what": "unscoped_pct", "scoped": [r"y\."]}, run) is None
    assert ts.read({"scope": r"x\.two", "per": "rounds"}, run) is None
    # parsed once: the file may go, the run still answers
    for f in (tmp_path / "trace").rglob("*.pb"):
        f.unlink()
    assert ts.read({"scope": r"x\.one", "per": "steps", "scale": 1e9},
                   run) == pytest.approx(50.0)


def test_no_device_events_or_no_file_reads_nothing(tmp_path):
    run = FakeRun(FakeCell(tmp_path), tr.Trace((0, 1), [], []), {"steps": 1})
    assert ts.read({"scope": "x", "per": "steps"}, run) is None
    run = FakeRun(FakeCell(tmp_path), tr.Trace(
        (0, 10), [[("%a = f32[] add()", 0, 5)]], []), {"steps": 1})
    assert ts.read({"scope": "x", "per": "steps"}, run) is None


# ---- the recorded traces -----------------------------------------------------

@pytest.mark.parametrize("path", [GBDT, FFM, UNSCOPED],
                         ids=["gbdt", "ffm", "unscoped"])
def test_recorded_busy_union_equals_trace_reduce(path):
    trace = tr.reduce(path)
    chips = ts.load(path)
    assert len(chips) == len(trace.chips) == 1
    assert ts.scope_s(chips, trace.window_ns, "") == pytest.approx(
        trace.busy_s, rel=1e-6)
    assert ts.scope_s(chips, trace.window_ns, ".*") == pytest.approx(
        trace.busy_s, rel=1e-6)
    lo, hi = trace.window_ns
    mine = sorted((n, max(s, lo), min(e, hi)) for _, n, s, e in chips[0]
                  if e > lo and s < hi)
    assert mine == sorted(trace.chips[0])


def metric_args(metric) -> dict:
    """A layer metric's ``args`` by its name; ``args`` given as they are
    stand for a scope of the recorded program that no metric reads."""
    if isinstance(metric, dict):
        return metric
    return json.loads((TESTDATA.parent / "layer_metrics" / f"{metric}.json")
                      .read_text())["args"]


def recorded_run(path: str, counts: dict) -> FakeRun:
    """A run whose trace is a recorded file (parsed here: ``read`` looks for
    the file of a run in flight, under the cell's cache directory)."""
    trace = tr.reduce(path)
    trace.scoped_chips = ts.load(path)
    return FakeRun(FakeCell(TESTDATA / "no-run-in-flight"), trace, counts)


# seconds in the recorded window, by the layer metrics' own patterns
RECORDED = [
    (GBDT, "route_ms_per_round", 0.008262985),
    (GBDT, "hist_layout_ms_per_round", 2.2948e-05),
    (GBDT, "split_ms_per_round", 0.000220785),
    (GBDT, "leaf_ms_per_round", 0.000933495),
    (GBDT, "boost_ms_per_round", 0.000246712),
    (FFM, "ffm_row_ids_ms_per_step", 0.044489405),
    # scoring's and the dense step's scope; its metric went with PR 52
    (FFM, {"scope": "ffm\\.gather", "per": "steps", "scale": 1000.0},
     0.04633146),
    (FFM, "ffm_reduce_ms_per_step", 0.132409872),
    (FFM, "ffm_loss_ms_per_step", 2.044e-06),
    (FFM, "ffm_update_ms_per_step", 0.001768117),
]


@pytest.mark.parametrize("path,metric,seconds", RECORDED,
                         ids=[m if isinstance(m, str) else m["scope"]
                              for _, m, _ in RECORDED])
def test_recorded_time_per_scope(path, metric, seconds):
    args = metric_args(metric)
    trace = tr.reduce(path)
    got = ts.scope_s(ts.load(path), trace.window_ns, args["scope"],
                     args.get("exclude"))
    assert got > 0
    assert got == pytest.approx(seconds, rel=1e-6)
    # and through read(): per count, scaled
    run = recorded_run(path, {"rounds": 2, "steps": 3})
    assert ts.read(args, run) == pytest.approx(
        1000.0 * seconds / run.counts[args["per"]], rel=1e-6)


def test_recorded_gbdt_kernel_sits_under_its_scope_and_parts_add_up():
    trace, chips = tr.reduce(GBDT), ts.load(GBDT)
    w = trace.window_ns
    kernel = trace.pattern_s("^%_histogram_gh_pallas")
    assert kernel == pytest.approx(0.051928078, rel=1e-6)
    # the kernel's events are found by scope path too, one a level
    assert ts.scope_s(chips, w, r"gbdt\.hist/.*pallas_call") == pytest.approx(
        kernel, rel=1e-9)
    assert sum(1 for sc, *_ in chips[0] if sc.endswith("pallas_call:")) == 12
    parts = kernel + sum(s for p, _, s in RECORDED if p == GBDT)
    assert parts == pytest.approx(trace.busy_s, rel=0.01)
    unscoped = ts.read(metric_args("device_unscoped_pct.train"),
                       recorded_run(GBDT, {}))
    # the driver's eager ops (0.25 ms) and compiler-made reduce-windows
    assert unscoped == pytest.approx(0.8795, abs=1e-3)


def test_recorded_ffm_while_is_not_counted_twice_with_its_body():
    """``%while.10`` (the searchsorted loop of ``row_ids``) carries no scope
    itself and covers its body's events, which do: the scope's time is the
    body's union, and summing the while and its body would double it."""
    trace, chips = tr.reduce(FFM), ts.load(FFM)
    row_ids = ts.scope_s(chips, trace.window_ns, r"batch\.row_ids")
    both = sum(e - s for sc, n, s, e in chips[0]
               if "batch.row_ids" in sc or n.startswith("%while.10 ")) / 1e9
    assert both == pytest.approx(0.088979738, rel=1e-6)
    assert row_ids == pytest.approx(0.044489405, rel=1e-6)
    # backward ops keep the forward scope: the gather's scatter twin
    assert ts.scope_s(
        chips, trace.window_ns,
        r"transpose\(jvp\(sgd\.loss\)\)/ffm\.gather") == pytest.approx(
            0.036431178, rel=1e-6)
    # 71.8 ms of compiler-made instructions (scatter expansion, zero fills,
    # layout loops) carry no tf_op; the while is covered by its body
    unscoped = ts.read(metric_args("device_unscoped_pct.train"),
                       recorded_run(FFM, {}))
    assert unscoped == pytest.approx(10.32, abs=0.05)


def test_a_trace_without_scopes_reads_nothing():
    """PR 23's recording, a program before the scopes: every scope metric
    reads nothing; the program selector still finds the eager ops."""
    run = recorded_run(UNSCOPED, {"rounds": 2})
    assert ts.read(metric_args("route_ms_per_round"), run) is None
    assert ts.read(metric_args("device_unscoped_pct.train"), run) is None
    assert ts.read(metric_args("boost_ms_per_round"), run) > 0
