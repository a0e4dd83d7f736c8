"""trace_reduce.py on hand-made intervals and on the recorded TPU trace
(one GBDT fit of 2 trees on 65,536 x 28 rows, ``testdata/record_trace.py``).
The recorded file's numbers were worked out a second way, by marking a
10 ns bitmap of the window, when the file was recorded (PR 23)."""
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

RECORDED = str(Path(__file__).resolve().parents[1] / "testdata"
               / "gbdt_fit_65536.xplane.pb.xz")
HIST = "^%_histogram_gh_pallas"


def test_union_counts_overlap_once():
    assert tr.union_ns([(0, 10), (5, 12), (20, 30), (22, 25)]) == 22
    assert tr.union_ns([]) == 0


def test_gaps_are_what_the_union_leaves():
    assert tr.gaps_of([(2, 4), (3, 6), (8, 9)], 0, 10) == [
        (0, 2), (6, 8), (9, 10)]
    assert tr.gaps_of([], 0, 5) == [(0, 5)]


def test_short_name_keeps_op_shape_and_kind():
    name = ("%_histogram_gh_pallas.11 = f32[64,7168]{1,0:T(8,128)S(1)} "
            "custom-call(s32[32,65536]{1,0:T(8,128)S(1)} %pad.43)")
    assert tr.short_name(name) == (
        "%_histogram_gh_pallas.11 f32[64,7168] custom-call")


def test_synthetic_trace_idle_pattern_and_gap_names():
    trace = tr.Trace(
        window_ns=(0, 1000),
        chips=[[("%k.1 = f32[1] custom-call(x)", 100, 300),
                ("%while.1 = () while(x)", 400, 800),
                ("%k.2 = f32[1] custom-call(x)", 450, 550)]],
        host_spans=[("bench.next", 300, 400), ("bench.fit", 0, 1000)])
    assert trace.busy_s == pytest.approx(600e-9)
    assert trace.idle_pct == pytest.approx(40.0)
    assert trace.pattern_s(r"^%k\.") == pytest.approx(300e-9)
    # the gap 300-400 lies in bench.next (the innermost span), the rest in
    # bench.fit alone
    assert trace.top_gaps(5) == [["bench.fit", 300e-9], ["bench.next", 100e-9]]
    assert trace.top_ops(1) == [["%while.1 while", 400e-9]]


def test_busy_seconds_are_a_mean_over_the_chips_that_ran():
    """The result line's ``busy_s`` is defined as the mean over the chips
    used; a four-chip cell of a later PR cannot edit this file."""
    trace = tr.Trace(
        window_ns=(0, 1000),
        chips=[[("%k.1 = f32[1] custom-call(x)", 0, 400)],
               [("%k.1 = f32[1] custom-call(x)", 0, 200),
                ("%add.1 = f32[1] add(x)", 100, 300)]],
        host_spans=[])
    assert trace.busy_s == pytest.approx(350e-9)
    assert trace.pattern_s(r"^%k\.") == pytest.approx(300e-9)


def test_recorded_trace_busy_idle_and_kernel_time():
    trace = tr.reduce(RECORDED)
    assert len(trace.chips) == 1
    assert trace.window_s == pytest.approx(0.081709862, rel=1e-9)
    assert trace.busy_s == pytest.approx(0.061974518, rel=1e-6)
    assert trace.busy_s == pytest.approx(0.06197504, rel=1e-4)    # bitmap
    assert trace.idle_pct == pytest.approx(24.1530, abs=1e-3)
    # 2 trees x 6 levels, one kernel event each
    assert sum(1 for n, _, _ in trace.chips[0] if n.startswith(HIST[1:])) == 12
    assert trace.pattern_s(HIST) == pytest.approx(0.051928071, rel=1e-6)
    assert trace.pattern_s(HIST) == pytest.approx(0.05192807, rel=1e-4)
    assert trace.top_ops(1)[0][0] == (
        "%_histogram_gh_pallas.11 f32[64,7168] custom-call")
    assert trace.top_gaps(3) == [["bench.fit", pytest.approx(0.019735344)]]
