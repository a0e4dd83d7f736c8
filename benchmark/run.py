#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one process on a machine that holds the cell's chips.  It builds or
loads the native library, makes its data from ``--seed``, warms up exactly the
shapes its window uses (all of that is ``setup_s``, clocked from the moment
JAX has found the chip), measures for ``--seconds``, then checks what the
timed path produced against the cell's plain reference.  Progress goes to
standard error; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` in a traced run).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.

It exits non-zero and prints no result line when JAX finds no TPU or fewer
chips than the cell asks for, when the device is not in ``peaks.json``, or
when a program was compiled (or fetched from the compile cache) inside the
measured window.  ``--rehearse-cpu`` (with ``JAX_PLATFORMS=cpu``) walks a
cell at tiny sizes with interpreted kernels; its line says ``"rehearsal":
true`` and carries no metric.

Everything that belongs to one cell, configuration, traffic kind or per-layer
metric is a file of its own, found by the names in ``BENCHMARK.json``; see
``benchmark/README.md``.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402
from benchmark.harness import BenchFailure, log  # noqa: E402


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold ``-``)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchFailure(f"no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(benchmark: dict, group: str, cell_name: str,
               reported: set | None = None) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``, or that carry no such key and (for a
    per-layer metric) move an end-to-end metric the cell reports."""
    out = []
    for m in benchmark[group]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def device_check(cell: harness.Cell, peaks: dict):
    import jax
    backend = jax.default_backend()
    if cell.rehearse:
        if os.environ.get("JAX_PLATFORMS") != "cpu" or backend != "cpu":
            raise BenchFailure("--rehearse-cpu needs JAX_PLATFORMS=cpu")
    elif backend != "tpu":
        raise BenchFailure(f"no TPU: jax.default_backend() is {backend!r}; "
                           "a measured run never falls back")
    devices = jax.devices()
    if len(devices) < cell.chips:
        raise BenchFailure(f"cell asks for {cell.chips} chip(s), JAX found "
                           f"{len(devices)}")
    kind = devices[0].device_kind
    if not cell.rehearse and kind not in peaks["devices"]:
        raise BenchFailure(f"device kind {kind!r} is not in peaks.json")
    return devices


def memory_peak(devices) -> dict:
    """What the fullest chip held when the window closed.  This TPU runtime
    keeps two books: live arrays (``bytes_in_use``, with their own lifetime
    peak ``peak_bytes_in_use``, which set-up's data making may have set) and
    the scratch it holds reserved for the temporaries of the programs it has
    loaded (``bytes_reserved``), which no array can be put into:
    ``largest_free_block_bytes`` shrinks by both.  ``memory_peak_bytes`` is
    the larger of the arrays' own peak and arrays plus scratch at the
    window's end; the two parts are reported beside it under their own
    names (PERF.md section 2 has the readings that show whose the scratch
    is)."""
    best = {"memory_peak_bytes": 0}
    for d in devices:
        stats = d.memory_stats() or {}
        arrays_peak = int(stats.get("peak_bytes_in_use", 0))
        arrays = int(stats.get("bytes_in_use", 0))
        scratch = int(stats.get("bytes_reserved", 0))
        peak = max(arrays_peak, arrays + scratch)
        if peak >= best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": peak,
                    "peak_bytes_in_use": arrays_peak,
                    "bytes_in_use": arrays, "bytes_reserved": scratch}
    return best


def run(args) -> int:
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(HERE, args.workload, args.seed,
                             args.rehearse_cpu)
    peaks = json.loads((HERE / "peaks.json").read_text())
    devices = device_check(cell, peaks)
    # setup_s runs from here: everything the program and the benchmark do
    # before the window.  What came before (interpreter, ``import jax``, the
    # TPU client) is no code of this repository and took 9.5 to 21.4 s from
    # one run to the next on the same machine (PR 23), against 16.7 +- 0.3 s
    # for all the rest of the GBDT cell's set-up; it is logged, not counted.
    ready = time.perf_counter()
    log(f"the process was {harness.process_age_s():.1f}s old when the device "
        "was found (not in setup_s)")
    import jax
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"cell {cell.name} seed {cell.seed} on {device}")

    import dmlc_core_tpu
    from dmlc_core_tpu import compile_cache, telemetry
    native = dmlc_core_tpu.native_build_info()
    if not telemetry.enabled():
        raise BenchFailure("native runtime built without telemetry")
    compile_cache.configure()
    meter = harness.CompileMeter()
    spans = harness.Spans()

    generator = load_module("traffic", cell.generator)
    reference = load_module("references", cell.reference)
    state = generator.setup(cell, spans)
    setup_compile = meter.since((0.0, 0, 0))
    log(f"set-up done: {setup_compile}")

    trace_dir = cell.cache_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    spans.reset()
    mark = meter.snapshot()
    before = telemetry.snapshot()
    setup_s = time.perf_counter() - ready
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        measured = generator.window(state, args.seconds, spans)
        window_s = time.perf_counter() - t0
    after = telemetry.snapshot()
    in_window = meter.since(mark)
    if args.trace:
        jax.profiler.stop_trace()
    memory = memory_peak(devices)
    harness.log_memory("window closed", devices[0])
    log(f"window {window_s:.3f}s: {measured['metrics']} "
        f"counts {measured['counts']}")
    if in_window["programs"]:
        raise BenchFailure(
            f"{in_window['programs']} program(s) compiled or fetched inside "
            "the measured window: the warm-up missed a shape")

    comparisons = generator.check(state, reference, control=args.control)
    generator.teardown(state)
    limits = cell.config["tolerance"]["limits"]
    correct = True
    for c in comparisons:
        if c["name"].startswith("control."):
            limit = limits[c["name"][len("control."):]]
            log(f"control {c['name']}: {c['value']!r} limit {limit!r} "
                f"{'inside' if c['value'] <= limit else 'fails it'}")
            continue
        limit = limits[c["name"]]
        ok = c["value"] <= limit
        correct &= bool(ok)
        log(f"compared {c['name']}: {c['value']!r} limit {limit!r} "
            f"{'ok' if ok else 'FAILED'}")

    e2e = metrics_of(benchmark, "end_to_end", cell.name)
    values = dict(measured["metrics"], setup_s=setup_s)
    if sorted(values) != sorted(m["name"] for m in e2e):
        raise BenchFailure(
            f"cell reports {sorted(values)}, BENCHMARK.json gives it "
            f"{sorted(m['name'] for m in e2e)}")
    out = {"correct": correct, "attempted": measured["attempted"],
           "failed": measured["failed"], "metrics": {}, "device": device}
    device.update(memory)
    if args.trace:
        from benchmark import trace_reduce
        files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise BenchFailure("the profiler wrote no xplane file")
        trace = trace_reduce.reduce(files[0])
        record = harness.RunRecord(
            cell=cell, peaks=peaks["devices"].get(device["kind"]),
            counters=telemetry.counters_delta(before, after),
            spans=spans.totals(), trace=trace, setup=setup_compile,
            native=native, window_s=window_s, counts=measured["counts"])
        for m in metrics_of(benchmark, "per_layer", cell.name, set(values)):
            spec = json.loads(
                (HERE / "layer_metrics" / f"{m['name']}.json").read_text())
            value = load_module("readers", spec["reader"]).read(
                spec.get("args", {}), record)
            if value is None:
                log(f"layer metric {m['name']}: nothing to read")
                continue
            out["metrics"][m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        out["breakdown"] = {"device_ops": trace.top_ops(10),
                            "idle_gaps": trace.top_gaps(10)}
    else:
        for m in e2e:
            out["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                         "unit": m["unit"]}
    if cell.rehearse:
        # a rehearsal proves the walk, not a device: no number leaves it
        log(f"rehearsal numbers (not results): {out['metrics']}")
        out["metrics"] = {}
        out["rehearsal"] = True
        out.pop("breakdown", None)
    shutil.rmtree(trace_dir, ignore_errors=True)    # the seed's data stays
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes, interpreted kernels, JAX_PLATFORMS=cpu; "
                         "reports no metric")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also log the lower-precision control's numbers "
                         "(the driver never sets this)")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except BenchFailure as exc:
        log(f"FAILED: {exc}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
