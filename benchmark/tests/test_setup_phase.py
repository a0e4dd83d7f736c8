"""readers/setup_phase.py over hand-made dumps (sum, ratio, scale, a missing
snapshot, a denominator of 0), through a real ring, and the metric files it
brought: each names this reader, counters that doc/observability.md lists,
and the cells BENCHMARK.json gives it."""
import json
import re
import types
from pathlib import Path

import pytest

from benchmark.readers import setup_phase as sp

HERE = Path(__file__).resolve().parents[1]
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
GBDT = [c for c in CELLS if re.search("gbdt|lgbm|xgb", c)]
STREAMED = [c for c in CELLS if "stream" in c]
# None: no ``workloads`` key, so every cell that reports ``setup_s`` reads it
MINE = {
    "setup_program_s": None, "setup_compile_trace_s": None,
    "setup_compile_backend_s": None, "setup_compile_fetch_s": None,
    "setup_cache_misses": None, "setup_binner_s": GBDT,
    "setup_init_s": STREAMED, "setup_warmup_s": GBDT,
    "setup_stage_s": ["bosch-gbdt.fit-sparse"],
}
AT_START = {"main.span_us": 12_500_000, "compile.trace_us": 2_000_000,
            "compile.lower_us": 500_000, "split.bytes": 2_400_000_000,
            "h2d.consumer_wait_us": 12_000_000, "compile.cache_misses": 0}


@pytest.mark.parametrize("args, want", [
    ({"num": ["main.span_us"], "scale": 1e-6}, 12.5),
    ({"num": ["compile.trace_us", "compile.lower_us"], "scale": 1e-6}, 2.5),
    ({"num": ["split.bytes"], "den": ["h2d.consumer_wait_us"]}, 200.0),
    ({"num": ["compile.cache_misses"]}, 0.0),       # a count of 0 is a reading
    ({"num": ["no.such_us"], "scale": 1e-6}, 0.0),  # never bumped: 0
    ({"num": ["split.bytes"], "den": ["compile.cache_misses"]}, None),
    ({"num": ["split.bytes"], "den": ["no.such_us"]}, None),
])
def test_value_sums_divides_and_scales(args, want):
    got = sp.value(args, AT_START)
    assert got == (want if want is None else pytest.approx(want))


def test_a_program_without_the_snapshot_reads_nothing():
    """The parent of PR 50: its ``trace_dump()`` has no ``registry_at_start``."""
    for args in ({"num": ["main.span_us"]},
                 {"num": ["split.bytes"], "den": ["h2d.consumer_wait_us"]}):
        assert sp.value(args, None) is None


def test_read_takes_the_snapshot_of_the_programs_ring_once(monkeypatch):
    from dmlc_core_tpu import telemetry
    if not telemetry.enabled():
        pytest.skip("counters are compiled out")
    telemetry.trace_stop()
    with telemetry.span("test.setup_reader", total="test.setup_reader_us"):
        pass
    telemetry.counter_add("test.setup_reader_us", 1_500_000)
    held = telemetry.counter_get("test.setup_reader_us")
    telemetry.trace_start()
    telemetry.counter_add("test.setup_reader_us", 7)    # the window's: not read
    telemetry.trace_stop()
    run = types.SimpleNamespace()
    args = {"num": ["test.setup_reader_us"], "scale": 1e-6}
    assert sp.read(args, run) == pytest.approx(held / 1e6)
    calls = []
    monkeypatch.setattr(telemetry, "trace_dump",
                        lambda: calls.append(1) or {})
    assert sp.read(args, run) == pytest.approx(held / 1e6)
    assert not calls        # kept on the record: one dump a run
    # and a dump without the key, as the parent's: nothing, and no error
    assert sp.read(args, types.SimpleNamespace()) is None


def test_the_metric_files_name_the_reader_their_counters_and_their_cells():
    doc = (HERE.parent / "doc" / "observability.md").read_text()
    contract = doc[doc.index("## Metric name contract"):
                   doc.index("## Stall attribution")]
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [n for n in entries if n in MINE] == list(MINE)      # in order
    for name, cells in MINE.items():
        spec = json.loads(
            (HERE / "layer_metrics" / f"{name}.json").read_text())
        entry = entries[name]
        assert spec["name"] == name and spec["reader"] == "setup_phase"
        assert spec["layer"] == entry["layer"]
        assert entry["moves"] == "setup_s"
        assert entry.get("workloads") == cells
        counters = spec["args"]["num"] + spec["args"].get("den", [])
        assert counters
        for counter in counters:
            assert f"`{counter}`" in contract, (name, counter)
        assert set(spec["args"]) <= {"num", "den", "scale"}
        if entry["unit"] == "s":    # a span's total, microseconds
            assert spec["args"]["scale"] == 1e-6
            assert all(c.endswith("_us") for c in spec["args"]["num"])
        else:
            assert "scale" not in spec["args"]
