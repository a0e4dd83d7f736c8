#!/usr/bin/env python
"""bench.py — headline benchmark on the reference's own instrument.

BASELINE.md config 1: the reference's only headline bench is
test/libsvm_parser_test.cc — MB/sec of parse into RowBlocks (CPU, no
device).  The headline here is the identical measurement through our native
parser (same file, same machine, same work: parse -> RowBlock stream),
vs the reference driver compiled from /root/reference.

Extras in the same JSON line (the TPU-native value-add, BASELINE config 2):
the full parse -> pack/pad -> device_put staging path into TPU HBM, end to
end.  Every device phase runs in ONE child process on whatever backend JAX
finds there and names it (``platform`` in each phase, ``device`` at the top);
a device phase that fails makes this script exit non-zero.  Nothing is
retried on another backend and no earlier result is carried over.  With
``JAX_PLATFORMS=cpu`` set the same phases run on the CPU backend and say so.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "MB/s", "vs_baseline": R, ...extras}
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
CACHE = Path(os.environ.get("DMLCTPU_BENCH_CACHE", "/tmp/dmlctpu_bench"))
# 192MB: at ~300 MB/s a measured epoch runs ~0.7s — enough wall clock that
# scheduler noise stops dominating the rate (64MB drained in ~0.25s and
# produced 1.5-2x run-to-run swings on this shared rig)
DATA_MB = int(os.environ.get("DMLCTPU_BENCH_MB", "192"))
REF_SRC = Path("/root/reference")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_dataset() -> Path:
    """Synthetic agaricus-style libsvm: binary labels, ~20 binary features/row."""
    CACHE.mkdir(parents=True, exist_ok=True)
    path = CACHE / f"agaricus_{DATA_MB}mb.libsvm"
    if path.exists() and path.stat().st_size >= DATA_MB << 20:
        return path
    import numpy as np
    rng = np.random.default_rng(42)
    target = DATA_MB << 20
    with open(path, "w") as f:
        written = 0
        while written < target:
            rows = []
            for _ in range(4096):
                y = int(rng.integers(0, 2))
                nnz = int(rng.integers(12, 28))
                feats = np.unique(rng.integers(0, 127, size=nnz))
                rows.append(f"{y} " + " ".join(f"{j}:1" for j in feats))
            chunk = "\n".join(rows) + "\n"
            f.write(chunk)
            written += len(chunk)
    return path


def make_float_libsvm_dataset() -> Path:
    """Float-valued libsvm (~10 text bytes/entry): the continuous-feature
    workload quantile binning exists for.  make_dataset's agaricus-style
    `j:1` rows are a degenerate binning case whose text encoding is already
    as small as the binned cache; this is the honest substrate for the
    bincache phase."""
    CACHE.mkdir(parents=True, exist_ok=True)
    mb = min(DATA_MB, 96)  # string-formatting generation cost, one-time
    path = CACHE / f"float_{mb}mb.libsvm"
    if path.exists() and path.stat().st_size >= mb << 20:
        return path
    import numpy as np
    rng = np.random.default_rng(7)
    target = mb << 20
    with open(path, "w") as f:
        written = 0
        while written < target:
            rows = []
            for _ in range(4096):
                y = int(rng.integers(0, 2))
                nnz = int(rng.integers(8, 24))
                feats = np.unique(rng.integers(0, 127, size=nnz))
                vals = rng.standard_normal(feats.size)
                rows.append(f"{y} " + " ".join(
                    f"{j}:{v:.6f}" for j, v in zip(feats, vals)))
            chunk = "\n".join(rows) + "\n"
            f.write(chunk)
            written += len(chunk)
    return path


def ensure_reference_binary() -> Path | None:
    exe = CACHE / "ref_libsvm_parser_test"
    if exe.exists():
        return exe
    if not REF_SRC.exists():
        return None
    srcs = [REF_SRC / "test/libsvm_parser_test.cc", REF_SRC / "src/io.cc",
            REF_SRC / "src/data.cc", REF_SRC / "src/recordio.cc"]
    srcs += [REF_SRC / "src/io" / n for n in
             ("filesys.cc", "local_filesys.cc", "input_split_base.cc",
              "line_split.cc", "recordio_split.cc", "indexed_recordio_split.cc")]
    cmd = ["g++", "-O2", "-std=c++17", f"-I{REF_SRC}/include",
           *map(str, srcs), "-o", str(exe), "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        log(f"[bench] reference build failed: {e}")
        return None
    return exe


def ensure_reference_csv_binary() -> Path | None:
    """The reference's own csv_parser_test is hardwired to int payloads; for
    a like-for-like float comparison, compile a minimal driver that runs the
    reference's float CSV parser (same library code, same drain loop)."""
    exe = CACHE / "ref_csv_parser_float"
    if exe.exists():
        return exe
    if not REF_SRC.exists():
        return None
    driver = CACHE / "ref_csv_driver.cc"
    driver.write_text(
        '#include <cstdio>\n#include <cstdlib>\n#include <memory>\n'
        '#include <dmlc/data.h>\n#include <dmlc/timer.h>\n'
        'int main(int argc, char** argv) {\n'
        '  if (argc < 4) return 1;\n'
        '  std::unique_ptr<dmlc::Parser<unsigned, float> > parser(\n'
        '      dmlc::Parser<unsigned, float>::Create(argv[1], atoi(argv[2]),\n'
        '                                            atoi(argv[3]), "csv"));\n'
        '  double t0 = dmlc::GetTime();\n'
        '  size_t rows = 0;\n'
        '  while (parser->Next()) rows += parser->Value().size;\n'
        '  double mb = parser->BytesRead() / (1024.0 * 1024.0);\n'
        '  printf("%lu rows, %.3f MB/sec\\n", rows, mb / (dmlc::GetTime() - t0));\n'
        '  return 0;\n}\n')
    srcs = [driver, REF_SRC / "src/io.cc", REF_SRC / "src/data.cc",
            REF_SRC / "src/recordio.cc"]
    srcs += [REF_SRC / "src/io" / n for n in
             ("filesys.cc", "local_filesys.cc", "input_split_base.cc",
              "line_split.cc", "recordio_split.cc", "indexed_recordio_split.cc")]
    cmd = ["g++", "-O2", "-std=c++17", f"-I{REF_SRC}/include",
           *map(str, srcs), "-o", str(exe), "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        log(f"[bench] reference csv driver build failed: {e}")
        return None
    return exe


def run_rate(cmd: list) -> float | None:
    """Run a driver binary; return the last MB/sec it printed."""
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return None
    rates = re.findall(r"([0-9.]+) MB/sec", proc.stdout)
    return float(rates[-1]) if rates else None


def run_reference(exe: Path, data: Path) -> float | None:
    nthread = max(os.cpu_count() or 1, 1)
    return run_rate([str(exe), str(data), "0", "1", str(nthread)])


def pick_backend():
    """(jax, platform of the backend JAX found) — device child only."""
    import jax
    return jax, jax.devices()[0].platform


def make_csv_dataset() -> Path:
    """Higgs-style dense CSV: label + 28 float features per row."""
    CACHE.mkdir(parents=True, exist_ok=True)
    path = CACHE / f"higgs_{DATA_MB}mb.csv"
    if path.exists() and path.stat().st_size >= DATA_MB << 20:
        return path
    import numpy as np
    rng = np.random.default_rng(7)
    target = DATA_MB << 20
    with open(path, "w") as f:
        written = 0
        while written < target:
            rows = rng.random((2048, 29), dtype=np.float32)
            rows[:, 0] = (rows[:, 0] > 0.5)
            chunk = "\n".join(",".join(f"{x:.6f}" for x in r) for r in rows) + "\n"
            f.write(chunk)
            written += len(chunk)
    return path


def run_parse(data: Path, fmt: str = "libsvm", repeats: int = 4) -> dict:
    """Our native parse -> RowBlock drain: the reference instrument, 1:1."""
    import ctypes

    from dmlc_core_tpu._native import RowBlockC, check, lib
    L = lib()
    uri = str(data) if fmt == "libsvm" else f"{data}?format={fmt}&label_column=0"
    ptype = b"libsvm" if fmt == "libsvm" else b"auto"
    best = {"mb_s": 0.0}
    for _ in range(repeats):
        h = ctypes.c_void_p()
        check(L.DmlcTpuParserCreate(uri.encode(), 0, 1, ptype, ctypes.byref(h)))
        check(L.DmlcTpuParserBeforeFirst(h))
        c = RowBlockC()
        t0 = time.monotonic()
        rows = 0
        while check(L.DmlcTpuParserNext(h, ctypes.byref(c))) == 1:
            rows += c.size
        secs = time.monotonic() - t0
        nbytes = L.DmlcTpuParserBytesRead(h)
        L.DmlcTpuParserFree(h)
        rate = (nbytes / (1 << 20)) / secs
        if rate > best["mb_s"]:
            best = {"mb_s": rate, "rows": rows, "secs": secs}

    # pool-scaling sweep: the persistent parse pool is judged on scaling,
    # not just the headline rate, so land MB/s per nthread in BENCH_* too
    sep = "&" if "?" in uri else "?"
    sweep = {}
    for nt in (1, 2, 4):
        nt_rate = 0.0
        for _ in range(2):
            h = ctypes.c_void_p()
            check(L.DmlcTpuParserCreate(f"{uri}{sep}nthread={nt}".encode(),
                                        0, 1, ptype, ctypes.byref(h)))
            check(L.DmlcTpuParserBeforeFirst(h))
            c = RowBlockC()
            t0 = time.monotonic()
            while check(L.DmlcTpuParserNext(h, ctypes.byref(c))) == 1:
                pass
            secs = time.monotonic() - t0
            nbytes = L.DmlcTpuParserBytesRead(h)
            L.DmlcTpuParserFree(h)
            nt_rate = max(nt_rate, (nbytes / (1 << 20)) / secs)
        sweep[f"nthread{nt}"] = round(nt_rate, 2)
    best["nthread_mb_s"] = sweep
    return best


# ---- telemetry overhead gate ------------------------------------------------
# The observability contract (doc/observability.md): leaving the counters on
# costs <=2% on the libsvm parse headline.  Measured by rebuilding the runtime
# with -DDMLCTPU_TELEMETRY=0 and racing two fresh subprocesses over the same
# dataset — same code path, only the instrumentation differs.  Reported as a
# soft extra (telemetry_overhead_pct / telemetry_overhead_ok): a regression
# must show up red in the round artifact, not crash the bench.

_PARSE_RATE_CHILD = r"""
import ctypes, sys, time
from dmlc_core_tpu._native import RowBlockC, check, lib
L = lib()
uri, repeats = sys.argv[1], int(sys.argv[2])
best = 0.0
for _ in range(repeats):
    h = ctypes.c_void_p()
    check(L.DmlcTpuParserCreate(uri.encode(), 0, 1, b"libsvm",
                                ctypes.byref(h)))
    check(L.DmlcTpuParserBeforeFirst(h))
    c = RowBlockC()
    t0 = time.monotonic()
    while check(L.DmlcTpuParserNext(h, ctypes.byref(c))) == 1:
        pass
    secs = time.monotonic() - t0
    nbytes = L.DmlcTpuParserBytesRead(h)
    L.DmlcTpuParserFree(h)
    best = max(best, (nbytes / (1 << 20)) / max(secs, 1e-9))
print("RATE %.6f" % best, flush=True)
"""


def build_variant_so(variant: str, defines: tuple[str, ...]) -> Path | None:
    """Build build/<variant>/libdmlctpu.so with extra -D flags, mirroring
    _native.py's direct-g++ fallback flags.  Cached on source mtimes (the
    -O3 rebuild costs minutes on a 1-core box)."""
    import shutil
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        return None
    so = REPO / "build" / variant / "libdmlctpu.so"
    sources = sorted(
        str(p) for sub in ("cpp/src", "cpp/src/io", "cpp/src/data")
        for p in (REPO / sub).glob("*.cc"))
    deps = [Path(s) for s in sources] + list(
        (REPO / "cpp" / "include").rglob("*.h"))
    newest = max(p.stat().st_mtime for p in deps)
    if so.exists() and so.stat().st_mtime >= newest:
        return so
    so.parent.mkdir(parents=True, exist_ok=True)
    cmd = [cxx, "-O3", "-g", "-std=c++20", "-fPIC", "-shared", "-pthread",
           "-fvisibility-inlines-hidden", *defines,
           "-I", str(REPO / "cpp/include"), *sources, "-o", str(so)]
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
    if proc.returncode != 0:
        log(f"[bench] {variant} build failed: {proc.stderr[-300:]}")
        return None
    return so


def build_notelemetry_so() -> Path | None:
    return build_variant_so("notelemetry", ("-DDMLCTPU_TELEMETRY=0",))


def run_telemetry_overhead(data: Path, repeats: int = 3) -> dict:
    """Compare the libsvm parse headline with telemetry on vs compiled out."""
    so = build_notelemetry_so()
    if so is None:
        return {"error": "no compiler for the notelemetry build"}

    def child_rate(library_path: str | None) -> float | None:
        env = dict(os.environ)
        env.pop("DMLCTPU_LIBRARY_PATH", None)
        if library_path is not None:
            env["DMLCTPU_LIBRARY_PATH"] = library_path
        proc = subprocess.run(
            [sys.executable, "-c", _PARSE_RATE_CHILD, str(data),
             str(repeats)], env=env, capture_output=True, text=True,
            timeout=900, cwd=REPO)
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("RATE "):
                return float(line.split()[1])
        log(f"[bench] telemetry-overhead child failed "
            f"(rc={proc.returncode}): {proc.stderr[-300:]}")
        return None

    rate_on = child_rate(None)
    rate_off = child_rate(str(so))
    if not rate_on or not rate_off:
        return {"error": "overhead child produced no rate"}
    pct = (rate_off - rate_on) / rate_off * 100.0
    out = {"mb_s_on": round(rate_on, 2), "mb_s_off": round(rate_off, 2),
           "telemetry_overhead_pct": round(pct, 2),
           "telemetry_overhead_ok": pct <= 2.0}
    if not out["telemetry_overhead_ok"]:
        # soft assert: flag it red in the artifact instead of crashing the
        # round (noisy 1-core boxes wobble more than the 2% budget)
        log(f"[bench] WARNING: telemetry overhead {pct:.2f}% exceeds the "
            f"2% budget ({rate_on:.1f} vs {rate_off:.1f} MB/s)")
    return out


_TRACE_RATE_CHILD = r"""
import ctypes, sys, time
from dmlc_core_tpu import telemetry
from dmlc_core_tpu._native import RowBlockC, check, lib
L = lib()
uri, repeats, armed = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "1"
if armed:
    telemetry.trace_start()
best = 0.0
for _ in range(repeats):
    h = ctypes.c_void_p()
    check(L.DmlcTpuParserCreate(uri.encode(), 0, 1, b"libsvm",
                                ctypes.byref(h)))
    check(L.DmlcTpuParserBeforeFirst(h))
    c = RowBlockC()
    t0 = time.monotonic()
    while check(L.DmlcTpuParserNext(h, ctypes.byref(c))) == 1:
        pass
    secs = time.monotonic() - t0
    nbytes = L.DmlcTpuParserBytesRead(h)
    L.DmlcTpuParserFree(h)
    best = max(best, (nbytes / (1 << 20)) / max(secs, 1e-9))
spans = 0
if armed:
    spans = len(telemetry.trace_dump().get("traceEvents", []))
    # merge sanity in the same armed process: push the trace (with clock
    # probes) to a local aggregator and read back the job-trace stats
    from dmlc_core_tpu.tracker import metrics as tm
    agg = tm.MetricsAggregator()
    p = tm.MetricsPusher("127.0.0.1", agg.port, rank=0, interval_s=3600.0)
    ok = all(p.push() for _ in range(3))
    od = agg.job_trace()["otherData"]
    agg.close()
    print("MERGE %d %d %d %d" % (int(ok), od["spans"], od["hosts"],
                                 od["max_abs_offset_us"]), flush=True)
print("RATE %.6f SPANS %d" % (best, spans), flush=True)
"""


def run_trace_overhead(data: Path, repeats: int = 3) -> dict:
    """Compare the libsvm parse headline with tracing armed vs off on the
    SAME build: a span is two steady-clock reads and a lock-free
    per-thread buffer write, so arming ``trace_start()`` must cost <=2%
    (doc/observability.md "Distributed tracing").  The armed child also
    pushes its trace to a local aggregator and reports the job-trace
    merge stats, so every round proves the merge path live."""

    def child(armed: bool):
        proc = subprocess.run(
            [sys.executable, "-c", _TRACE_RATE_CHILD, str(data),
             str(repeats), "1" if armed else "0"],
            env=dict(os.environ), capture_output=True, text=True,
            timeout=900, cwd=REPO)
        rate, spans, merge = None, 0, None
        for line in proc.stdout.splitlines():
            if line.startswith("RATE "):
                parts = line.split()
                rate, spans = float(parts[1]), int(parts[3])
            elif line.startswith("MERGE "):
                ok, sp, hosts, off = line.split()[1:]
                merge = {"pushes_ok": bool(int(ok)), "spans": int(sp),
                         "hosts": int(hosts), "max_abs_offset_us": int(off)}
        if rate is None:
            log(f"[bench] trace-overhead child failed "
                f"(rc={proc.returncode}): {proc.stderr[-300:]}")
        return rate, spans, merge

    # interleave off/on pairs and keep the best of each: this box's
    # run-to-run wobble (scheduler + page cache) dwarfs the span cost, and
    # best-of-interleaved cancels the drift a fixed ordering bakes in
    rates_off, rates_on, spans, merge = [], [], 0, None
    for _ in range(2):
        r_off, _, _ = child(False)
        r_on, sp, mg = child(True)
        rates_off.append(r_off)
        rates_on.append(r_on)
        spans, merge = max(spans, sp), merge or mg
    rates_off = [r for r in rates_off if r]
    rates_on = [r for r in rates_on if r]
    if not rates_on or not rates_off:
        return {"error": "trace-overhead child produced no rate"}
    rate_off, rate_on = max(rates_off), max(rates_on)
    pct = (rate_off - rate_on) / rate_off * 100.0
    out = {"mb_s_armed": round(rate_on, 2), "mb_s_off": round(rate_off, 2),
           "trace_overhead_pct": round(pct, 2),
           "trace_overhead_ok": pct <= 2.0,
           "spans_recorded": spans, "merge": merge}
    if not out["trace_overhead_ok"]:
        # soft assert, same policy as the telemetry gate: flag it red in
        # the round artifact instead of crashing the bench
        log(f"[bench] WARNING: tracing overhead {pct:.2f}% exceeds the "
            f"2% budget ({rate_on:.1f} vs {rate_off:.1f} MB/s)")
    return out


_TIMESERIES_RATE_CHILD = r"""
import ctypes, sys, time
from dmlc_core_tpu import telemetry
from dmlc_core_tpu._native import RowBlockC, check, lib
L = lib()
uri, repeats, armed = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "1"
if armed:
    # aggressive 50 ms ticks: 20x the default sampling pressure, so a pass
    # here bounds the shipping 1 s tick with a wide margin
    telemetry.timeseries_start(tick_ms=50, fine_slots=1024, coarse_every=10,
                               coarse_slots=256)
best = 0.0
for _ in range(repeats):
    h = ctypes.c_void_p()
    check(L.DmlcTpuParserCreate(uri.encode(), 0, 1, b"libsvm",
                                ctypes.byref(h)))
    check(L.DmlcTpuParserBeforeFirst(h))
    c = RowBlockC()
    t0 = time.monotonic()
    while check(L.DmlcTpuParserNext(h, ctypes.byref(c))) == 1:
        pass
    secs = time.monotonic() - t0
    nbytes = L.DmlcTpuParserBytesRead(h)
    L.DmlcTpuParserFree(h)
    best = max(best, (nbytes / (1 << 20)) / max(secs, 1e-9))
ticks = 0
if armed:
    doc = telemetry.timeseries()
    ticks = doc.get("ticks", 0)
    telemetry.timeseries_stop()
print("RATE %.6f TICKS %d" % (best, ticks), flush=True)
"""


def run_timeseries_overhead(data: Path, repeats: int = 3) -> dict:
    """Compare the libsvm parse headline with the background sampler armed
    (aggressive 50 ms ticks) vs off on the SAME build: a tick snapshots the
    registry off the hot path, so always-on sampling must cost <=1%
    (doc/observability.md "Always-on operation")."""

    def child(armed: bool):
        proc = subprocess.run(
            [sys.executable, "-c", _TIMESERIES_RATE_CHILD, str(data),
             str(repeats), "1" if armed else "0"],
            env=dict(os.environ), capture_output=True, text=True,
            timeout=900, cwd=REPO)
        rate, ticks = None, 0
        for line in proc.stdout.splitlines():
            if line.startswith("RATE "):
                parts = line.split()
                rate, ticks = float(parts[1]), int(parts[3])
        if rate is None:
            log(f"[bench] timeseries-overhead child failed "
                f"(rc={proc.returncode}): {proc.stderr[-300:]}")
        return rate, ticks

    # interleaved best-of pairs, same policy as the trace gate: this box's
    # run-to-run wobble dwarfs a sampler tick, and best-of-interleaved
    # cancels the drift a fixed ordering bakes in
    rates_off, rates_on, ticks = [], [], 0
    for _ in range(2):
        r_off, _ = child(False)
        r_on, tk = child(True)
        rates_off.append(r_off)
        rates_on.append(r_on)
        ticks = max(ticks, tk)
    rates_off = [r for r in rates_off if r]
    rates_on = [r for r in rates_on if r]
    if not rates_on or not rates_off:
        return {"error": "timeseries-overhead child produced no rate"}
    rate_off, rate_on = max(rates_off), max(rates_on)
    pct = (rate_off - rate_on) / rate_off * 100.0
    out = {"mb_s_armed": round(rate_on, 2), "mb_s_off": round(rate_off, 2),
           "timeseries_overhead_pct": round(pct, 2),
           "timeseries_overhead_ok": pct <= 1.0,
           "sampler_ticks": ticks}
    if not out["timeseries_overhead_ok"]:
        # soft assert, same policy as the other overhead gates
        log(f"[bench] WARNING: sampler overhead {pct:.2f}% exceeds the "
            f"1% budget ({rate_on:.1f} vs {rate_off:.1f} MB/s)")
    return out


def run_faults_overhead(data: Path, repeats: int = 3) -> dict:
    """Compare the libsvm parse headline with the fault-injection points
    compiled in (but unarmed — the shipping default) vs -DDMLCTPU_FAULTS=0.
    The robustness contract (doc/robustness.md): an unarmed point is one
    relaxed atomic load, <=1% on the parse headline.  Telemetry stays ON in
    both builds so only the fault points differ."""
    so = build_variant_so("nofaults", ("-DDMLCTPU_FAULTS=0",))
    if so is None:
        return {"error": "no compiler for the nofaults build"}

    def child_rate(library_path: str | None) -> float | None:
        env = dict(os.environ)
        env.pop("DMLCTPU_LIBRARY_PATH", None)
        env.pop("DMLCTPU_FAULTS", None)  # the gate measures UNARMED points
        if library_path is not None:
            env["DMLCTPU_LIBRARY_PATH"] = library_path
        proc = subprocess.run(
            [sys.executable, "-c", _PARSE_RATE_CHILD, str(data),
             str(repeats)], env=env, capture_output=True, text=True,
            timeout=900, cwd=REPO)
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("RATE "):
                return float(line.split()[1])
        log(f"[bench] faults-overhead child failed "
            f"(rc={proc.returncode}): {proc.stderr[-300:]}")
        return None

    rate_on = child_rate(None)
    rate_off = child_rate(str(so))
    if not rate_on or not rate_off:
        return {"error": "overhead child produced no rate"}
    pct = (rate_off - rate_on) / rate_off * 100.0
    out = {"mb_s_on": round(rate_on, 2), "mb_s_off": round(rate_off, 2),
           "faults_overhead_pct": round(pct, 2),
           "faults_overhead_ok": pct <= 1.0}
    if not out["faults_overhead_ok"]:
        log(f"[bench] WARNING: fault-point overhead {pct:.2f}% exceeds the "
            f"1% budget ({rate_on:.1f} vs {rate_off:.1f} MB/s)")
    return out


_ALLREDUCE_CHILD = r"""
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from jax.sharding import Mesh
from dmlc_core_tpu.parallel import collective_bench, collective_sweep
mesh = Mesh(np.asarray(jax.devices()), ("data",))
out = collective_bench(mesh, "allreduce", mib_per_device=16.0, iters=5)
# primary metric goes out FIRST: a failure in the extra ops must never
# cost the allreduce number
print("ALLREDUCE " + json.dumps(out), flush=True)
others = {}
for op in ("allgather", "reducescatter", "ppermute"):
    try:
        others[op] = round(collective_bench(mesh, op, mib_per_device=8.0,
                                            iters=3)["bus_gbps"], 3)
    except Exception as e:  # noqa: BLE001
        others[op] = f"error: {str(e)[-120:]}"
try:
    # small/large payload sweep: the latency- vs bandwidth-bound regimes
    others["allreduce_sweep"] = [
        {"payload_mib": round(r["bytes"] / (1 << 20), 3),
         "bus_gbps": round(r["bus_gbps"], 3)}
        for r in collective_sweep(mesh, "allreduce", (0.25, 16.0), iters=3)]
except Exception as e:  # noqa: BLE001
    others["allreduce_sweep"] = f"error: {str(e)[-120:]}"
print("EXTRAS " + json.dumps(others), flush=True)
"""


def run_allreduce() -> dict:
    """BASELINE config 4: psum bandwidth over the device mesh (the rabit
    tree/ring-allreduce equivalent).

    A real >=2-device mesh is measured by the device child's "allreduce"
    phase (the parent never opens a backend: the chip belongs to the one
    child); this function is the same psum bench on a virtual 8-device CPU
    mesh in its own CPU-pinned child, labeled ``"platform": "cpu"``."""
    result: dict = {}
    # virtual 8-CPU host mesh in a clean subprocess
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags +
                            " --xla_force_host_platform_device_count=8").strip()
    try:
        proc = subprocess.run([sys.executable, "-c", _ALLREDUCE_CHILD],
                              capture_output=True, text=True, timeout=240,
                              env=env, cwd=str(REPO))
        for line in proc.stdout.splitlines():
            if line.startswith("ALLREDUCE "):
                result = json.loads(line[len("ALLREDUCE "):])
            elif line.startswith("EXTRAS "):
                result["others"] = json.loads(line[len("EXTRAS "):])
        if not result:
            result = {"error": proc.stderr[-300:]}
    except subprocess.TimeoutExpired:
        result = {"error": "virtual-mesh allreduce timed out"}
    result["platform"] = "cpu"
    result["note"] = ("single real device: ICI allreduce unavailable; "
                     "measured on a virtual 8-device CPU host mesh")
    return result


def mesh_collective_scaling(devices, counts=None,
                            payloads_mib=(0.25, 16.0),
                            iters: int = 5, warmup: int = 2) -> dict:
    """1->N scale-out curves for the MeshPlan collectives: flat psum vs
    the hierarchical ppermute route (reduce-scatter -> host tree ->
    allgather) at a small and a large payload per device count, plus the
    2-D (host, chip) plan at the full count.

    The hier >= 1.5x flat expectation at the large payload is a SOFT
    gate: on the virtual CPU mesh every "device" shares one memory bus
    and XLA's flat psum is a shared-memory reduction, so the hierarchy
    has no ICI/DCN asymmetry to exploit.  The gate targets real
    multi-host pods; off-hardware it is reported, never enforced."""
    from dmlc_core_tpu.parallel import MeshPlan, plan_allreduce_bench
    devices = list(devices)
    if counts is None:
        counts = [n for n in (1, 2, 4, 8) if n <= len(devices)]
    rows = []

    def row(plan, n, mib, axes):
        flat = plan_allreduce_bench(plan, strategy="flat",
                                    mib_per_device=mib, iters=iters,
                                    warmup=warmup)
        hier = plan_allreduce_bench(plan, strategy="hier",
                                    mib_per_device=mib, iters=iters,
                                    warmup=warmup)
        rows.append({"devices": n, "axes": axes, "payload_mib": mib,
                     "flat_bus_gbps": round(flat["bus_gbps"], 3),
                     "hier_bus_gbps": round(hier["bus_gbps"], 3)})

    for n in counts:
        plan = MeshPlan.build(devices=devices[:n])
        for mib in payloads_mib:
            row(plan, n, mib, list(plan.axes))
    nmax = counts[-1]
    if nmax >= 4:  # 2-D (host, chip) plan: the hierarchical route's home
        plan2 = MeshPlan.build(devices=devices[:nmax], hosts=2)
        for mib in payloads_mib:
            row(plan2, nmax, mib, list(plan2.axes))
    big = max(payloads_mib)
    large = [r for r in rows
             if r["devices"] == nmax and r["payload_mib"] == big
             and r["flat_bus_gbps"] > 0]
    ratio = max((r["hier_bus_gbps"] / r["flat_bus_gbps"] for r in large),
                default=0.0)
    out = {"platform": devices[0].platform, "devices": nmax,
           "rows": rows, "hier_vs_flat_large": round(ratio, 3),
           "hier_gate_ok": ratio >= 1.5}
    if not out["hier_gate_ok"]:
        out["hier_gate_note"] = (
            "soft gate: hier < 1.5x flat at the large payload — expected "
            "off-hardware (virtual CPU mesh has no ICI/DCN asymmetry; "
            "flat psum is a shared-memory reduction)")
    return out


def mesh_gbdt_scaling(devices, histogram: str = "xla", counts=None,
                      rows: int = 40960, num_features: int = 16,
                      num_bins: int = 64, trees: int = 3,
                      depth: int = 5) -> dict:
    """Trees/s scaling curve for the plan-routed GBDT fit over 1->N
    devices, plus the chunked-overlap A/B at the full count.  The
    overlap route (DMLCTPU_MESH_OVERLAP_CHUNKS > 1) must keep the
    forest BIT-identical to the unchunked explicit route — checked here
    on every run, not just in tests."""
    import time

    import numpy as np

    import jax

    from dmlc_core_tpu.models import GBDT
    from dmlc_core_tpu.parallel import MeshPlan
    devices = list(devices)
    if counts is None:
        counts = [n for n in (1, 2, 4, 8) if n <= len(devices)]
    rng = np.random.default_rng(5)
    # pre-binned u8 codes, as QuantileBinner.transform would hand over —
    # GBDT.fit takes bin codes, not raw features
    x = rng.integers(0, num_bins, (rows, num_features)).astype(np.uint8)
    y = (rng.random(rows) < 0.5).astype(np.float32)

    def fit_rate(plan):
        m = GBDT(num_features=num_features, num_trees=trees,
                 max_depth=depth, num_bins=num_bins, learning_rate=0.4,
                 histogram=histogram, histogram_mesh=plan)
        b = jax.device_put(x, plan.data_sharding())
        lab = jax.device_put(y, plan.data_sharding())
        jax.block_until_ready(m.fit(b, lab)["leaf"])  # warmup/compile
        t0 = time.monotonic()
        forest = m.fit(b, lab)
        jax.block_until_ready(forest["leaf"])
        return round(rows * trees / (time.monotonic() - t0)), forest

    out = {"rows": rows, "platform": devices[0].platform,
           "histogram": histogram, "scaling": []}
    nmax = counts[-1]
    f1 = None
    for n in counts:
        plan = MeshPlan.build(devices=devices[:n], overlap_chunks=1)
        rate, forest = fit_rate(plan)
        out["scaling"].append({"devices": n, "row_trees_s": rate})
        if n == nmax:
            out["row_trees_s_unchunked"], f1 = rate, forest
    plan_k4 = MeshPlan.build(devices=devices[:nmax], overlap_chunks=4)
    rate4, f4 = fit_rate(plan_k4)
    out["row_trees_s_overlap"] = rate4
    out["overlap_chunks"] = plan_k4.overlap_chunks
    out["overlap_forest_identical"] = all(
        bool((np.asarray(f1[k]) == np.asarray(f4[k])).all())
        for k in ("feature", "threshold", "leaf"))
    return out


_MESH_CHILD = r"""
import json
import jax
jax.config.update("jax_platforms", "cpu")
import bench
devices = jax.devices()
# collectives first: a slow GBDT sweep must never cost the bandwidth rows
out = bench.mesh_collective_scaling(devices, iters=3)
print("MESHSCALE " + json.dumps(out), flush=True)
out = bench.mesh_gbdt_scaling(devices, histogram="xla")
print("MESHGBDT " + json.dumps(out), flush=True)
"""


def run_mesh_virtual() -> dict:
    """Scale-out fallback on the virtual 8-device CPU host mesh — real
    1->N bus-GB/s and trees/s rows every round, even on a one-chip rig.
    Subprocess-isolated for the same reason as ``run_allreduce``: the
    forced host platform must not leak into the parent's jax."""
    note = ("virtual 8-device CPU host mesh (one real device); curves "
            "show plan routing, not ICI bandwidth")
    result: dict = {"gbdt_mesh": {}, "mesh_scaleout": {}}
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags +
                            " --xla_force_host_platform_device_count=8").strip()
    try:
        proc = subprocess.run([sys.executable, "-c", _MESH_CHILD],
                              capture_output=True, text=True, timeout=600,
                              env=env, cwd=str(REPO))
        for line in proc.stdout.splitlines():
            if line.startswith("MESHSCALE "):
                result["mesh_scaleout"] = json.loads(line[len("MESHSCALE "):])
            elif line.startswith("MESHGBDT "):
                result["gbdt_mesh"] = json.loads(line[len("MESHGBDT "):])
        if not result["mesh_scaleout"] and not result["gbdt_mesh"]:
            result = {"gbdt_mesh": {"error": proc.stderr[-300:]},
                      "mesh_scaleout": {"error": proc.stderr[-300:]}}
    except subprocess.TimeoutExpired:
        err = {"error": "virtual mesh scale-out timed out"}
        result = {"gbdt_mesh": dict(err), "mesh_scaleout": dict(err)}
    for sub in result.values():
        sub["note"] = note
    return result


def make_recordio_dataset() -> Path:
    """RecordIO dataset salted with embedded magic words (the reference's
    adversarial recordio_test.cc pattern) — measures the escape/reassembly
    path, not just clean payloads."""
    CACHE.mkdir(parents=True, exist_ok=True)
    path = CACHE / f"records_{DATA_MB}mb.rec"
    if path.exists() and path.stat().st_size >= (DATA_MB << 20) // 2:
        return path
    import numpy as np

    from dmlc_core_tpu.io import RecordIOWriter
    rng = np.random.default_rng(3)
    magic = (0xCED7230A).to_bytes(4, "little")  # RecordIOWriter::kMagic (recordio.h:23)
    target = DATA_MB << 20
    written = 0
    t0 = time.monotonic()
    with RecordIOWriter(str(path)) as w:
        i = 0
        while written < target:
            body = rng.bytes(int(rng.integers(64, 2048)))
            if i % 5 == 0:
                body = magic + body + magic  # force escape splits
            w.write(body)
            written += len(body) + 8
            i += 1
    rate = (written / (1 << 20)) / (time.monotonic() - t0)
    log(f"[bench] recordio dataset written at {rate:.1f} MB/s")
    return path


def run_recordio_staging(path: Path) -> dict:
    """BASELINE config 2: RecordIO -> packed static-shape batches -> HBM."""
    jax, platform = pick_backend()
    from dmlc_core_tpu.data import RecordStagingIter

    it = RecordStagingIter(str(path), records_cap=8192, bytes_cap=8 << 20)

    def drain(warmup_batches: int = 0) -> dict:
        t0 = time.monotonic()
        records = None  # device-side accumulation (see run_staging)
        last = None
        n = 0
        for batch in it:
            records = (batch.num_records if records is None
                       else records + batch.num_records)
            last = batch
            n += 1
            if warmup_batches and n >= warmup_batches:
                break
        jax.block_until_ready((records, last.bytes, last.offsets))
        secs = time.monotonic() - t0
        records = int(records)
        nbytes = it.bytes_read - drain.bytes0
        drain.bytes0 = it.bytes_read
        return {"records": records, "bytes": nbytes, "secs": secs,
                "mb_s": (nbytes / (1 << 20)) / secs,
                "records_s": records / secs}

    drain.bytes0 = 0
    drain(warmup_batches=3)  # truncated warmup (see run_staging)
    result = drain()
    result["platform"] = platform
    return result


def run_gbdt() -> dict:
    """Value-add phase (no reference counterpart; BASELINE target 5's model):
    histogram-GBDT training throughput — the XGBoost-hist workload the
    reference's data layer exists to feed.  Two measurements: the dense
    binned path (Higgs-style, 28 dense features) and the sparse-native
    fit_batch path (O(nnz) COO histograms, 8%-dense 100-feature data).
    Reported as row-trees/s (rows x trees / fit seconds), steady-state
    (second fit, so the per-shape jit compile is excluded)."""
    jax, platform = pick_backend()
    import numpy as np

    from dmlc_core_tpu.models import GBDT, QuantileBinner

    rows, features = (100_000, 28)
    rng = np.random.default_rng(12)
    x = rng.standard_normal((rows, features)).astype(np.float32)
    y = ((x[:, 0] * x[:, 1] > 0) ^ (x[:, 2] > 0.4)).astype(np.float32)
    bins = QuantileBinner(num_bins=256).fit_transform(x)
    label = jax.numpy.asarray(y)
    def timed_fit(m):
        """warmup fit + steady-state timed fit; seconds for the latter."""
        jax.block_until_ready(m.fit(bins, label)["leaf"])
        t0 = time.monotonic()
        p = m.fit(bins, label)
        jax.block_until_ready(p["leaf"])
        return time.monotonic() - t0

    model = GBDT(num_features=features, num_trees=5, max_depth=6,
                 num_bins=256, learning_rate=0.4)  # histogram="auto"
    secs = timed_fit(model)

    # sparse-native: same rows, 100 features at ~8% density
    from dmlc_core_tpu.data.staging import PaddedBatch
    jnp = jax.numpy
    sf, density = 100, 0.08
    nnz_per_row = max(int(sf * density), 1)
    sp_idx = np.sort(rng.integers(0, sf, (rows, nnz_per_row)),
                     axis=1).astype(np.int32).reshape(-1)
    sp_val = rng.uniform(0.1, 2.0, rows * nnz_per_row).astype(np.float32)
    row_ptr = (np.arange(rows + 1) * nnz_per_row).astype(np.int32)
    sy = (rng.random(rows) < 0.5).astype(np.float32)
    batch = PaddedBatch(label=jnp.asarray(sy),
                        weight=jnp.ones(rows, jnp.float32),
                        row_ptr=jnp.asarray(row_ptr),
                        index=jnp.asarray(sp_idx),
                        value=jnp.asarray(sp_val),
                        num_rows=jnp.asarray(np.int32(rows)), field=None)
    binner = QuantileBinner(num_bins=256, missing_aware=True)
    binner.fit_sparse(sp_idx, sp_val, num_features=sf)
    smodel = GBDT(num_features=sf, num_trees=5, max_depth=6, num_bins=256,
                  learning_rate=0.4, missing_aware=True)
    jax.block_until_ready(smodel.fit_batch(batch, binner)["leaf"])  # warmup
    t0 = time.monotonic()
    sparams = smodel.fit_batch(batch, binner)
    jax.block_until_ready(sparams["leaf"])
    sparse_secs = time.monotonic() - t0

    # histogram-backend A/B: the SAME binned data through
    # XLA scatter-add and the Pallas one-hot-contraction kernel.  On TPU:
    # two full steady-state fits, row-trees/s each.  Off-TPU the kernel
    # only exists in interpret mode (a correctness tool), so a tiny
    # histogram_gh A/B records correctness + an honest interpret timing.
    hist_ab = {}
    if platform == "tpu":
        for impl in ("xla", "pallas"):
            m = GBDT(num_features=features, num_trees=5, max_depth=6,
                     num_bins=256, learning_rate=0.4, histogram=impl)
            jax.block_until_ready(m.fit(bins, label)["leaf"])  # warmup
            t0 = time.monotonic()
            p = m.fit(bins, label)
            jax.block_until_ready(p["leaf"])
            hist_ab[f"row_trees_s_{impl}"] = round(
                rows * m.num_trees / (time.monotonic() - t0))
    else:
        import jax.numpy as hnp
        from dmlc_core_tpu.ops.pallas_segment import histogram_gh
        hb, hn, hf, hrows = 32, 8, 4, 2048
        hbins = hnp.asarray(rng.integers(0, hb, (hrows, hf)).astype(np.int32))
        hrel = hnp.asarray(rng.integers(0, hn, hrows).astype(np.int32))
        hgh = hnp.asarray(rng.standard_normal((hrows, 2)).astype(np.float32))
        times = {}
        outs = {}
        for impl in ("xla", "pallas"):
            force = impl
            # block: async dispatch would fold the warmup tail into t0
            jax.block_until_ready(
                histogram_gh(hbins, hrel, hgh, hn, hb, force=force))
            t0 = time.monotonic()
            outs[impl] = histogram_gh(hbins, hrel, hgh, hn, hb, force=force)
            jax.block_until_ready(outs[impl])
            times[impl] = round((time.monotonic() - t0) * 1e3, 2)
        hist_ab = {"interpret_ms_pallas": times["pallas"],
                   "xla_ms": times["xla"],
                   "max_abs_err": round(float(
                       hnp.max(hnp.abs(outs["xla"] - outs["pallas"]))), 7),
                   "note": "off-TPU pallas runs in interpret mode; "
                           "timing not comparable"}
    # sparse-histogram-backend A/B (ISSUE 14): the SAME COO fit_batch data
    # through XLA scatter and the feature-sorted sparse Pallas kernel.  On
    # TPU: two full steady-state fits and their ratio as
    # `sparse_hist_speedup`.  Off-TPU the kernel only exists in interpret
    # mode, so a tiny histogram_gh_sparse A/B records correctness + an
    # honest interpret timing.
    sparse_hist_ab = {}
    if platform == "tpu":
        sp_times = {}
        for impl in ("xla", "pallas"):
            m = GBDT(num_features=sf, num_trees=5, max_depth=6,
                     num_bins=256, learning_rate=0.4,
                     missing_aware=True, histogram=impl)
            jax.block_until_ready(
                m.fit_batch(batch, binner)["leaf"])  # warmup
            t0 = time.monotonic()
            p = m.fit_batch(batch, binner)
            jax.block_until_ready(p["leaf"])
            sp_times[impl] = time.monotonic() - t0
            sparse_hist_ab[f"row_trees_s_{impl}"] = round(
                rows * m.num_trees / sp_times[impl])
        sparse_hist_ab["sparse_hist_speedup"] = round(
            sp_times["xla"] / sp_times["pallas"], 3)
    else:
        import jax.numpy as hnp
        from dmlc_core_tpu.ops.pallas_segment import histogram_gh_sparse
        hn, hf, hb, hnnz, hrows = 8, 5, 16, 4096, 512
        srid = hnp.asarray(rng.integers(0, hrows, hnnz).astype(np.int32))
        sfi = hnp.asarray(rng.integers(0, hf, hnnz).astype(np.int32))
        seb = hnp.asarray(rng.integers(1, hb, hnnz).astype(np.int32))
        sem = hnp.ones(hnnz, bool)
        srel = hnp.asarray(rng.integers(0, hn, hrows).astype(np.int32))
        sgh = hnp.asarray(rng.standard_normal((hrows, 2)).astype(np.float32))
        times = {}
        outs = {}
        for impl in ("xla", "pallas"):
            jax.block_until_ready(histogram_gh_sparse(
                srid, sfi, seb, sem, srel, sgh, hn, hf, hb, force=impl))
            t0 = time.monotonic()
            outs[impl] = histogram_gh_sparse(
                srid, sfi, seb, sem, srel, sgh, hn, hf, hb, force=impl)
            jax.block_until_ready(outs[impl])
            times[impl] = round((time.monotonic() - t0) * 1e3, 2)
        sparse_hist_ab = {
            "interpret_ms_pallas": times["pallas"],
            "xla_ms": times["xla"],
            "max_abs_err": round(float(
                hnp.max(hnp.abs(outs["xla"] - outs["pallas"]))), 7),
            "note": "off-TPU pallas runs in interpret mode; "
                    "timing not comparable"}
    return {"rows": rows, "trees": model.num_trees,
            "depth": model.max_depth, "secs": round(secs, 3),
            "row_trees_s": round(rows * model.num_trees / secs),
            "sparse_row_trees_s": round(rows * smodel.num_trees
                                        / sparse_secs),
            "sparse_nnz": rows * nnz_per_row,
            "sparse_features": sf,
            "hist_ab": hist_ab,
            "sparse_hist_ab": sparse_hist_ab,
            "platform": platform}


def run_models() -> dict:
    """Model-family throughput: steady-state train-step rate for the
    linear / FM / field-aware FM families on one synthetic staged-shape
    batch (value-add breadth metric; the GBDT flagship has its own
    phase).  Rows/s = batch_rows * steps / seconds over `iters` jitted
    steps after one warmup."""
    jax, platform = pick_backend()
    import numpy as np

    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models import (FactorizationMachine,
                                      FieldAwareFactorizationMachine,
                                      SparseLinearModel)
    jnp = jax.numpy
    rows, F, nnz_row, A = 65536, 1000, 16, 8
    rng = np.random.default_rng(3)
    nnz = rows * nnz_row
    batch = PaddedBatch(
        label=jnp.asarray((rng.random(rows) < 0.5).astype(np.float32)),
        weight=jnp.ones(rows, jnp.float32),
        row_ptr=jnp.asarray((np.arange(rows + 1) * nnz_row).astype(np.int32)),
        index=jnp.asarray(rng.integers(0, F, nnz).astype(np.int32)),
        value=jnp.asarray(rng.random(nnz).astype(np.float32)),
        num_rows=jnp.asarray(np.int32(rows)),
        field=jnp.asarray(rng.integers(0, A, nnz).astype(np.int32)))
    out = {"rows": rows, "nnz": nnz, "platform": platform}
    iters = 10
    for name, m in (
            ("linear", SparseLinearModel(num_features=F)),
            ("fm", FactorizationMachine(num_features=F, num_factors=16)),
            ("ffm", FieldAwareFactorizationMachine(
                num_features=F, num_fields=A, num_factors=4))):
        params = m.init()
        params, _ = m.train_step(params, batch)  # compile warmup
        jax.block_until_ready(params)
        t0 = time.monotonic()
        for _ in range(iters):
            params, loss = m.train_step(params, batch)
        jax.block_until_ready(loss)
        out[f"{name}_rows_s"] = round(
            rows * iters / (time.monotonic() - t0))
    return out


def run_staging(data: Path, fmt: str = "auto", num_workers: int = 4) -> dict:
    """Extra: the full native parse -> pad -> HBM staging path, single-worker
    (the schema-stable headline numbers) THEN through the sharded worker
    pool, with the per-stage counters and an order-identity check.

    DETAIL-line schema: top-level rows/bytes/secs/mb_s/rows_s and
    producer_breakdown are the single-worker run (unchanged keys);
    ``parallel`` holds the pooled run — num_workers, mb_s/rows_s, speedup
    vs single-worker, order_identical (first batches bit-compare against
    the 1-worker stream), counters (per-stage seconds from
    DeviceStagingIter.counters), and cpu_count: on a 1-core container the
    workers timeshare one core, so speedup ~<=1 there is expected and the
    honest result — scaling needs real cores."""
    jax, platform = pick_backend()
    from dmlc_core_tpu.data import DeviceStagingIter

    uri = str(data) if fmt == "auto" else f"{data}?format={fmt}&label_column=0"

    def epoch(nw: int) -> tuple:
        it = DeviceStagingIter(uri, batch_size=131072, nnz_bucket=1 << 18,
                               prefetch=4, num_workers=nw)

        def drain(warmup_batches: int = 0) -> dict:
            t0 = time.monotonic()
            rows = None  # device-side accumulation: a per-batch int()
            last = None  # readback would block the pipeline on a D2H sync
            n = 0
            for batch in it:
                rows = batch.num_rows if rows is None else rows + batch.num_rows
                last = batch
                n += 1
                if warmup_batches and n >= warmup_batches:
                    break
            jax.block_until_ready((rows, last.label, last.index, last.value))
            secs = time.monotonic() - t0
            rows = int(rows)
            nbytes = it.bytes_read - drain.bytes0
            drain.bytes0 = it.bytes_read
            return {"rows": rows, "bytes": nbytes, "secs": secs,
                    "mb_s": (nbytes / (1 << 20)) / secs, "rows_s": rows / secs}

        drain.bytes0 = 0
        # truncated warmup: enough to compile device_put layouts and warm
        # the page cache
        drain(warmup_batches=3)
        out = drain()
        return out, it

    def first_batch_sigs(nw: int, limit: int = 4) -> list:
        """Bit-level signature of the first batches (order-identity probe
        kept off the timed epochs)."""
        import hashlib
        import numpy as np
        it = DeviceStagingIter(uri, batch_size=131072, nnz_bucket=1 << 18,
                               num_workers=nw)
        sigs = []
        for i, b in enumerate(it):
            h = hashlib.sha1()
            for a in (b.label, b.row_ptr, b.index, b.value):
                h.update(np.asarray(a).tobytes())
            sigs.append((int(b.num_rows), h.hexdigest()))
            if i + 1 >= limit:
                break
        it.close()
        return sigs

    result, it1 = epoch(1)
    result["platform"] = platform
    # producer-side breakdown (BASELINE target 3 diagnosis): shows whether
    # a slow epoch was parse-bound (native_s), dispatch-bound (stage_s), or
    # consumer/device-bound (emit_wait_s) — measured, not guessed
    if getattr(it1, "profile", None):
        result["producer_breakdown"] = {
            k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in it1.profile.items()}

    # stall attribution over the pooled epoch: telemetry.window() brackets
    # the epoch with registry snapshots and turns the native busy/wait
    # counters into per-stage seconds and a bottleneck ranking
    # (doc/observability.md) — the "parse-bound 71%" headline
    from dmlc_core_tpu import telemetry
    with telemetry.window() as w:
        par, itp = epoch(num_workers)
    attr = w.attribution
    counters = {k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in itp.counters.items()}
    result["parallel"] = {
        "num_workers": num_workers,
        "mb_s": par["mb_s"], "rows_s": par["rows_s"], "secs": par["secs"],
        "speedup": round(par["rows_s"] / max(result["rows_s"], 1e-9), 3),
        "order_identical": first_batch_sigs(1) == first_batch_sigs(num_workers),
        "counters": counters,
        "cpu_count": os.cpu_count(),
        "stall_attribution": attr,
    }
    # Job-table view of the pooled epoch: push this process's snapshot
    # through the REAL tracker aggregation channel (loopback aggregator +
    # one wire push) and record the rendered per-host table — the bench
    # artifact shows exactly what a job operator sees from the tracker,
    # and exercises the push/merge/format path on every bench run.
    try:
        from dmlc_core_tpu.tracker.metrics import MetricsAggregator, push_once
        agg = MetricsAggregator(host_ip="127.0.0.1", port=0)
        try:
            push_once("127.0.0.1", agg.port, rank=0)
            job = agg.job_snapshot()
            result["parallel"]["job_table"] = agg.format_job_table()
            result["parallel"]["job_num_hosts"] = job["num_hosts"]
        finally:
            agg.close()
    except Exception as e:  # observability must never sink the bench round
        result["parallel"]["job_table"] = ("error: " + str(e))[-200:]
    return result


def run_autotune_convergence(data: Path, epochs: int = 3) -> dict:
    """The closing-the-loop gate (doc/autotune.md): from deliberately bad
    knobs (num_workers=1, buffer_mb=4, prefetch_depth=1) the armed
    stall-attribution controller must reach >=90% of the hand-tuned staging
    rate within `epochs` epochs on the libsvm workload; and leaving the
    armed controller on at already-converged knobs must cost <=1% vs the
    identical static run.  Both are soft asserts — a miss goes red in the
    round artifact (converged_ok / armed_overhead_ok) instead of crashing
    the bench (a 1-core box timeshares the pool workers, so the absolute
    rates wobble; the ratios are what the gate watches)."""
    jax, platform = pick_backend()
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.data import DeviceStagingIter

    uri = str(data)
    tuned = dict(num_workers=4, buffer_mb=32, prefetch=4)

    def epoch_mb_s(it) -> float:
        with telemetry.window() as w:
            t0 = time.monotonic()
            bytes0 = it.bytes_read
            rows = None
            last = None
            for batch in it:
                rows = batch.num_rows if rows is None else rows + batch.num_rows
                last = batch
            jax.block_until_ready((rows, last.label, last.index, last.value))
            secs = time.monotonic() - t0
        # native byte counters when compiled in; wall-clock fallback keeps
        # the gate meaningful against a -DDMLCTPU_TELEMETRY=0 runtime
        return w.mb_per_s() or ((it.bytes_read - bytes0) / (1 << 20)
                                / max(secs, 1e-9))

    def with_env(overrides: dict, fn):
        old = {k: os.environ.get(k) for k in overrides}
        os.environ.update({k: str(v) for k, v in overrides.items()})
        try:
            return fn()
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    out: dict = {"platform": platform}
    ref_it = DeviceStagingIter(uri, batch_size=131072, nnz_bucket=1 << 18,
                               autotune=False, **tuned)
    epoch_mb_s(ref_it)  # warmup: device_put compile + page cache
    ref = epoch_mb_s(ref_it)
    out["hand_tuned_mb_s"] = round(ref, 2)

    def converge():
        it = DeviceStagingIter(uri, batch_size=131072, nnz_bucket=1 << 18,
                               num_workers=1, buffer_mb=4, prefetch=1,
                               autotune=True)
        return [round(epoch_mb_s(it), 2) for _ in range(epochs)], it

    # mid-epoch windows (every 8 batches) so the hill-climb gets several
    # decisions per epoch — epoch-only would give it just `epochs` steps
    rates, it = with_env({"DMLCTPU_AUTOTUNE": "1",
                          "DMLCTPU_AUTOTUNE_WINDOW": "8"}, converge)
    out["epoch_mb_s"] = rates
    out["knobs_final"] = it.knobs
    out["tuner"] = it._tuner.summary() if it._tuner else None
    ratio = max(rates) / max(ref, 1e-9)
    out["convergence_ratio"] = round(ratio, 3)
    out["converged_ok"] = ratio >= 0.9
    if not out["converged_ok"]:
        log(f"[bench] WARNING: autotune reached {ratio:.0%} of the "
            f"hand-tuned rate in {epochs} epochs (want >=90%): {rates} "
            f"vs {ref:.1f} MB/s")

    def make_armed():
        it = DeviceStagingIter(uri, batch_size=131072, nnz_bucket=1 << 18,
                               autotune=True, **tuned)
        epoch_mb_s(it)  # warmup; the tuner attaches under the capped env
        return it

    # knob ceilings pinned to the hand-tuned values (chunk frozen outright):
    # the armed controller still snapshots/decides every window but every
    # proposal holds at the cap, so the measurement isolates the
    # controller's own cost.  The caps only matter during the first
    # iteration — the tuner reads the env when it attaches.
    armed_it = with_env({"DMLCTPU_AUTOTUNE": "1",
                         "DMLCTPU_AUTOTUNE_WINDOW": "8",
                         "DMLCTPU_AUTOTUNE_MAX_WORKERS": tuned["num_workers"],
                         "DMLCTPU_AUTOTUNE_MAX_BUFFER_MB": tuned["buffer_mb"],
                         "DMLCTPU_AUTOTUNE_MAX_PREFETCH": tuned["prefetch"],
                         "DMLCTPU_AUTOTUNE_MAX_CHUNK_MB": 0},
                        make_armed)
    # alternate measured epochs and compare best-of-2: on a shared 1-core
    # box the epoch-to-epoch spread of IDENTICAL configs dwarfs the 1%
    # budget, so a single pair would gate on scheduler noise (same
    # rationale as run_parse's best-of-repeats)
    static_rates, armed_rates = [], []
    for _ in range(2):
        static_rates.append(epoch_mb_s(ref_it))
        armed_rates.append(epoch_mb_s(armed_it))
    armed, static = max(armed_rates), max(static_rates)
    out["armed_epoch_mb_s"] = [round(r, 2) for r in armed_rates]
    out["static_epoch_mb_s"] = [round(r, 2) for r in static_rates]
    pct = (static - armed) / max(static, 1e-9) * 100.0
    out["armed_mb_s"] = round(armed, 2)
    out["static_mb_s"] = round(static, 2)
    out["armed_overhead_pct"] = round(pct, 2)
    out["armed_overhead_ok"] = pct <= 1.0
    if not out["armed_overhead_ok"]:
        log(f"[bench] WARNING: armed-but-converged autotune overhead "
            f"{pct:.2f}% exceeds the 1% budget "
            f"({armed:.1f} vs {static:.1f} MB/s)")
    return out


def run_bincache(data: Path) -> dict:
    """The binned-epoch-cache gate (doc/binned_cache.md): repeat (cache-hit)
    epochs must beat the text-parse path by >=4x on epoch wall-clock (the
    zero-copy hit path serves mmap-borrowed views, so a repeat epoch is
    pure memory bandwidth + repack), host-side copies on the hit path must
    stay under 10% of bytes served (cache.bytes_copied / cache.hit_bytes
    < 0.1 -> copy_ok), the cache-building first epoch must cost <=10% over
    a plain text epoch, and a small forest trained from the cache must be
    bit-identical to the text-path forest.  The sketch pass that fits the binner is timed
    separately and kept OUT of the build gate: fit_streamed needs fitted
    cuts on the text path too, so both workflows pay it — the gate watches
    the marginal cost of writing the cache.  repeat_ok / build_ok are soft
    asserts (red in the round artifact, not a crash): on a 1-core box the
    bin+write pass can't overlap idle cores, so build_ok is expected red
    there and meaningful on real hosts; forest_identical is exact.  The
    codec object on the DETAIL line A/Bs the same cache built under lz4:
    on-disk ratio, decode seconds absorbed inside the repack stage, and
    the compressed build/repeat epochs (doc/binned_cache.md "Block
    codec")."""
    jax, platform = pick_backend()
    import numpy as np
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.data import BinnedStagingIter, DeviceStagingIter
    from dmlc_core_tpu.data.binned_cache import _drain_host
    from dmlc_core_tpu.models import GBDT, QuantileBinner

    uri = str(data)
    cache_path = CACHE / (data.name + ".bincache")
    if cache_path.exists():
        cache_path.unlink()
    kw = dict(batch_size=131072, nnz_bucket=1 << 18)

    def epoch_secs(it) -> float:
        t0 = time.monotonic()
        last = None
        for batch in it:
            last = batch
        jax.block_until_ready((last.label, last.index))
        return time.monotonic() - t0

    out: dict = {"platform": platform}
    text_it = DeviceStagingIter(uri, autotune=False, **kw)
    epoch_secs(text_it)  # warmup: device_put compile + page cache
    text = min(epoch_secs(text_it) for _ in range(2))
    out["text_epoch_s"] = round(text, 3)

    # the sketch pass both workflows pay before epoch 1 can train
    binner = QuantileBinner(num_bins=16, missing_aware=True,
                            sketch_size=64, sketch_seed=3)
    t0 = time.monotonic()
    sk = DeviceStagingIter(uri, autotune=False, **kw)
    for wb in _drain_host(sk):
        nr = wb["num_rows"]
        nnz = int(wb["row_ptr"][nr])
        idx = np.asarray(wb["index"][:nnz], np.int64)
        val = np.asarray(wb["value"][:nnz], np.float32)
        binner.partial_fit_sparse(idx, val, int(idx.max(initial=-1)) + 1)
    sk.close()
    binner.finalize()
    out["sketch_s"] = round(time.monotonic() - t0, 3)

    binned = BinnedStagingIter(uri, binner, cache=str(cache_path), **kw)
    build = epoch_secs(binned)  # parse + native bin + cache write + stream
    rebuilds0 = telemetry.counter_get("cache.rebuilds")
    hit0 = telemetry.counter_get("cache.hit_bytes")
    copied0 = telemetry.counter_get("cache.bytes_copied")
    mmap0 = telemetry.counter_get("cache.mmap_opens")
    repeat = min(epoch_secs(binned) for _ in range(2))
    out["build_epoch_s"] = round(build, 3)
    out["repeat_epoch_s"] = round(repeat, 3)
    out["cache_mb"] = cache_path.stat().st_size >> 20 if cache_path.exists() \
        else None
    hit_bytes = telemetry.counter_get("cache.hit_bytes") - hit0
    copied_bytes = telemetry.counter_get("cache.bytes_copied") - copied0
    out["cache_hit_mb"] = round(hit_bytes / (1 << 20), 1)
    out["cache_rebuilds"] = telemetry.counter_get("cache.rebuilds") - rebuilds0
    out["zero_copy_opens"] = telemetry.counter_get("cache.mmap_opens") - mmap0
    out["bytes_copied_per_byte_served"] = round(
        copied_bytes / max(hit_bytes, 1), 4)
    out["copy_ok"] = out["bytes_copied_per_byte_served"] < 0.1
    if not out["copy_ok"]:
        log(f"[bench] WARNING: cache hit path copied "
            f"{out['bytes_copied_per_byte_served']:.3f} bytes per byte "
            f"served (want < 0.1) — zero-copy backend not engaged?")

    speedup = text / max(repeat, 1e-9)
    overhead_pct = (build - text) / max(text, 1e-9) * 100.0
    out["repeat_speedup_vs_text"] = round(speedup, 2)
    out["repeat_ok"] = speedup >= 4.0
    if not out["repeat_ok"]:
        log(f"[bench] WARNING: binned repeat epoch only {speedup:.2f}x the "
            f"text path (want >=4x): {repeat:.2f}s vs {text:.2f}s")
    out["build_overhead_pct"] = round(overhead_pct, 1)
    out["build_ok"] = overhead_pct <= 10.0
    if not out["build_ok"]:
        log(f"[bench] WARNING: cache-build epoch {overhead_pct:.1f}% over "
            f"the text epoch (want <=10%): {build:.2f}s vs {text:.2f}s")

    # forest A/B on a small slice: same binner cuts, text batches vs cached
    # uint8 blocks must grow the exact same trees (the bit-identity contract
    # that makes the cache a pure perf knob)
    ab = CACHE / "bincache_ab.libsvm"
    with open(data) as src, open(ab, "w") as dst:
        for _ in range(4096):
            line = src.readline()
            if not line:
                break
            dst.write(line)
    ab_cache = CACHE / "bincache_ab.libsvm.bincache"
    if ab_cache.exists():
        ab_cache.unlink()
    ab_binner = QuantileBinner(num_bins=16, missing_aware=True,
                               sketch_size=64, sketch_seed=3)
    ab_binned = BinnedStagingIter(str(ab), ab_binner, cache=str(ab_cache),
                                  batch_size=1024, nnz_bucket=1 << 15)
    ab_binned.ensure_cache()  # fits the binner via the sketch pass
    fkw = dict(num_features=128, num_bins=16, num_trees=2, max_depth=3,
               missing_aware=True)
    text_src = lambda: iter(DeviceStagingIter(  # noqa: E731
        str(ab), batch_size=1024, nnz_bucket=1 << 15, autotune=False))
    f_text = GBDT(**fkw).fit_streamed(text_src, ab_binner)
    f_bin = GBDT(**fkw).fit_streamed(lambda: iter(ab_binned), ab_binner)
    out["forest_identical"] = all(
        np.array_equal(np.asarray(f_text[k]), np.asarray(f_bin[k]))
        for k in f_text)
    if not out["forest_identical"]:
        log("[bench] WARNING: forest trained from the binned cache is NOT "
            "bit-identical to the text-path forest")

    # compressed tier (doc/binned_cache.md "Block codec"): rebuild the same
    # cache under bitshuffle+LZ4 and re-serve it — bit-identity raw-vs-lz4
    # is the test suite's contract (tests/test_binned_cache.py); the bench
    # reports the on-disk ratio and the decode time the hit path absorbed
    # inside the repack stage (decode_s is part of repeat_epoch_s, not an
    # extra stage).  Local disk is fast, so no local speed gate here — the
    # >=2x soft gate lives in run_dataservice, on a bandwidth-capped wire.
    from dmlc_core_tpu.data.binned_cache import resolve_codec
    if resolve_codec("lz4") != "lz4":
        out["codec"] = {"skipped": "libdmlctpu built with -DDMLCTPU_CODEC=0"}
        return out
    lz4_path = CACHE / (data.name + ".lz4.bincache")
    if lz4_path.exists():
        lz4_path.unlink()
    lz4_it = BinnedStagingIter(uri, binner, cache=str(lz4_path),
                               codec="lz4", **kw)
    lz4_build = epoch_secs(lz4_it)
    dus0 = telemetry.counter_get("cache.codec.decode_us")
    bin0 = telemetry.counter_get("cache.codec.bytes_in")
    bout0 = telemetry.counter_get("cache.codec.bytes_out")
    lz4_repeat = min(epoch_secs(lz4_it) for _ in range(2))
    raw_b = cache_path.stat().st_size
    lz4_b = lz4_path.stat().st_size
    bytes_in = telemetry.counter_get("cache.codec.bytes_in") - bin0
    bytes_out = telemetry.counter_get("cache.codec.bytes_out") - bout0
    out["codec"] = {
        "name": "lz4",
        "build_epoch_s": round(lz4_build, 3),
        "repeat_epoch_s": round(lz4_repeat, 3),
        "raw_cache_mb": round(raw_b / (1 << 20), 1),
        "lz4_cache_mb": round(lz4_b / (1 << 20), 1),
        "disk_ratio": round(raw_b / max(lz4_b, 1), 2),
        "expansion": round(bytes_out / max(bytes_in, 1), 2),
        "decode_s": round(
            (telemetry.counter_get("cache.codec.decode_us") - dus0) / 1e6, 3),
    }
    if out["codec"]["disk_ratio"] < 1.0:
        log(f"[bench] WARNING: lz4 bincache is LARGER than raw "
            f"({lz4_b} vs {raw_b} bytes) — codec not engaging?")
    return out


def run_dataservice(data: Path) -> dict:
    """The staging-service gate (doc/dataservice.md): a loopback-served
    pre-binned epoch (in-process lease board + one StagingWorker, the
    client pulling raw cache blocks over the 0xff9a channel) must reach
    >=0.7x the wall-clock of a local cache-hit epoch with the same
    geometry.  Soft assert (served_ok in the round artifact): loopback
    TCP on a 1-core box serializes the worker's reads against the
    client's repack, so the ratio is a floor, not a target — on real
    hosts the fetch overlaps training and the remote stream is the same
    bytes (bit-identity is the test suite's job, tests/test_dataservice.py).
    A second A/B pins the worker's outbound stream behind the
    DMLCTPU_DATASERVICE_THROTTLE_MBPS token bucket and serves the epoch
    raw vs lz4-compressed (codec object on the DETAIL line): with the
    socket as the bottleneck the compressed wire must reach >=2x the raw
    wire (codec_wire_ok, soft)."""
    jax, platform = pick_backend()
    import os
    import shutil

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.data import BinnedStagingIter
    from dmlc_core_tpu.dataservice import DataServiceIter, StagingWorker
    from dmlc_core_tpu.models import QuantileBinner
    from dmlc_core_tpu.tracker import metrics as tm

    uri = str(data)
    kw = dict(batch_size=131072, nnz_bucket=1 << 18)
    bkw = dict(num_bins=16, missing_aware=True, sketch_size=64, sketch_seed=3)

    def epoch_secs(it) -> float:
        t0 = time.monotonic()
        last = None
        for batch in it:
            last = batch
        jax.block_until_ready((last.label, last.index))
        return time.monotonic() - t0

    out: dict = {"platform": platform}

    # local reference: a cache-hit epoch with the same geometry
    ref_cache = CACHE / (data.name + ".dataservice_ref.bincache")
    if ref_cache.exists():
        ref_cache.unlink()
    local_it = BinnedStagingIter(uri, QuantileBinner(**bkw),
                                 cache=str(ref_cache), **kw)
    epoch_secs(local_it)  # build + device_put warmup
    local = min(epoch_secs(local_it) for _ in range(2))
    out["local_hit_epoch_s"] = round(local, 3)

    # the service: in-process lease board + one worker, client on loopback
    agg = tm.MetricsAggregator()
    old = {k: os.environ.get(k) for k in ("DMLC_TRACKER_URI",
                                          tm.METRICS_PORT_ENV,
                                          "DMLCTPU_DATASERVICE_THROTTLE_MBPS")}
    os.environ["DMLC_TRACKER_URI"] = "127.0.0.1"
    os.environ[tm.METRICS_PORT_ENV] = str(agg.port)
    svc_dir = CACHE / "dataservice_worker"
    shutil.rmtree(svc_dir, ignore_errors=True)
    worker = None
    try:
        worker = StagingWorker(cache_dir=str(svc_dir))
        it = DataServiceIter(uri, QuantileBinner(**bkw), **kw)
        fetch0 = telemetry.counter_get("dataservice.fetch_bytes")
        epoch_secs(it)  # worker-side cache build + client warmup
        served = min(epoch_secs(it) for _ in range(2))
        out["served_epoch_s"] = round(served, 3)
        out["fetched_mb"] = round(
            (telemetry.counter_get("dataservice.fetch_bytes") - fetch0)
            / (1 << 20), 1)

        # compressed-wire A/B (doc/binned_cache.md "Block codec"): cap the
        # worker's outbound stream with the token-bucket throttle so the
        # socket — not the parse or the repack — is the bottleneck, then
        # serve the same epoch raw vs lz4.  Frames cross the wire in the
        # cache's stored (compressed) form and the client decodes, so the
        # throttled epoch should speed up by ~the compression ratio.  Soft
        # gate codec_wire_ok: >=2x, red in the round artifact if the codec
        # stops paying for itself on a capped link.
        from dmlc_core_tpu.data.binned_cache import resolve_codec
        if resolve_codec("lz4") != "lz4":
            out["codec"] = {
                "skipped": "libdmlctpu built with -DDMLCTPU_CODEC=0"}
        else:
            lz4_it = DataServiceIter(uri, QuantileBinner(**bkw),
                                     codec="lz4", **kw)
            epoch_secs(lz4_it)  # worker-side lz4 cache build, unthrottled
            lz4_plain = min(epoch_secs(lz4_it) for _ in range(2))
            per_epoch_mb = out["fetched_mb"] / 3.0  # warmup + 2 timed
            cap_mbps = max(6.0, per_epoch_mb / 2.5)
            os.environ["DMLCTPU_DATASERVICE_THROTTLE_MBPS"] = (
                f"{cap_mbps:.1f}")
            throttled_raw = min(epoch_secs(it) for _ in range(2))
            throttled_lz4 = min(epoch_secs(lz4_it) for _ in range(2))
            os.environ.pop("DMLCTPU_DATASERVICE_THROTTLE_MBPS", None)
            # wall ratio vs net ratio: on a 1-core loopback the epoch wall
            # includes a serialized repack floor the cap never touches, so
            # the wall ratio understates the socket win.  The net ratio
            # divides the time the cap ADDED to each side (throttled minus
            # the unthrottled epoch) — that is the wire itself, and the
            # quantity the >=2x soft gate watches.
            wire_speedup = throttled_raw / max(throttled_lz4, 1e-9)
            net_raw = max(throttled_raw - served, 0.0)
            net_lz4 = max(throttled_lz4 - lz4_plain, 1e-9)
            wire_net = net_raw / net_lz4
            ok = wire_net >= 2.0 or wire_speedup >= 2.0
            out["codec"] = {
                "name": "lz4",
                "throttle_mbps": round(cap_mbps, 1),
                "lz4_epoch_s": round(lz4_plain, 3),
                "throttled_raw_epoch_s": round(throttled_raw, 3),
                "throttled_lz4_epoch_s": round(throttled_lz4, 3),
                "wire_speedup": round(wire_speedup, 2),
                "wire_net_speedup": round(wire_net, 2),
                "codec_wire_ok": ok,
            }
            if not ok:
                log(f"[bench] WARNING: lz4 wire only {wire_net:.2f}x raw "
                    f"net of the repack floor ({wire_speedup:.2f}x wall) "
                    f"under a {cap_mbps:.1f} MB/s cap (want >=2x): "
                    f"{throttled_lz4:.2f}s vs {throttled_raw:.2f}s")
    finally:
        if worker is not None:
            worker.close()
        agg.close()
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    ratio = local / max(served, 1e-9)
    out["served_vs_local_hit"] = round(ratio, 2)
    out["served_ok"] = ratio >= 0.7
    if not out["served_ok"]:
        log(f"[bench] WARNING: served epoch only {ratio:.2f}x the local "
            f"cache-hit epoch (want >=0.7x): {served:.2f}s vs {local:.2f}s")
    return out


def run_serving() -> dict:
    """The online-scoring gate (doc/serving.md): micro-batched concurrent
    scoring vs naive one-request-at-a-time sequential scoring, per request
    size 1/8/64 rows.  Headline = the batch-1 high-fan-in case (the auction
    shape micro-batching exists for): 16 closed-loop client threads
    submitting single-row requests must reach >=3x the naive QPS with
    p99 <= 5x p50 (serving_ok, soft).  A second gate is exact: after one
    warmup sweep over every bucket geometry the timed runs touch, the
    steady-state ``models.predict_retrace`` delta must be ZERO — the
    bucketed-padding contract means no live request ever recompiles."""
    jax, platform = pick_backend()
    import threading

    import numpy as np

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.models import SparseLinearModel
    from dmlc_core_tpu.serving import (MicroBatchQueue, ScoringEngine,
                                       ScoringIterator, pack_snapshot)

    F, NNZ = 1000, 16
    model = SparseLinearModel(num_features=F)
    snap = pack_snapshot("linear", {"num_features": F}, model.init())
    engine = ScoringEngine.from_snapshot_bytes(snap)
    rng = np.random.default_rng(11)

    def make_req(rows):
        return [(rng.integers(0, F, NNZ).astype(np.int32).tolist(),
                 (rng.random(NNZ) + 0.1).astype(np.float32).tolist())
                for _ in range(rows)]

    def naive(req_rows, n_requests):
        """Sequential round trips, no coalescing: pack one request, score
        it, block for the host result, repeat."""
        it = ScoringIterator(max_batch=128)
        reqs = [make_req(req_rows) for _ in range(n_requests)]
        t0 = time.monotonic()
        for r in reqs:
            batch, _ = it.pack(r)
            engine.score(batch)
        return n_requests * req_rows / (time.monotonic() - t0)

    def micro(req_rows, n_requests, threads=4, window=None):
        """Pipelined closed-loop fan-in: each client thread keeps up to
        ``window`` requests in flight (submit, then wait the oldest), so
        the queue sees a standing backlog to coalesce into full
        micro-batches — the auction fan-in shape.  The window scales
        inversely with request size (~max_batch rows in flight per
        thread), so big requests don't pile up a latency-inflating
        backlog micro-batching can't drain."""
        from collections import deque as _dq
        if window is None:
            window = max(2, min(64, 256 // req_rows))
        q = MicroBatchQueue(lambda: engine, max_batch=256, max_delay_us=200)
        lat_us: list = []
        lock = threading.Lock()
        per = max(window, n_requests // threads)

        def client():
            inflight: _dq = _dq()
            mine = []

            def harvest():
                t_sub, fut = inflight.popleft()
                fut.result(timeout=60)
                mine.append((time.monotonic_ns() - t_sub) // 1000)

            for _ in range(per):
                inflight.append((time.monotonic_ns(),
                                 q.submit(make_req(req_rows))))
                if len(inflight) >= window:
                    harvest()
            while inflight:
                harvest()
            with lock:
                lat_us.extend(mine)

        ts = [threading.Thread(target=client) for _ in range(threads)]
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        wall = time.monotonic() - t0
        q.close()
        lat = np.asarray(lat_us)
        return (len(lat_us) * req_rows / wall,
                float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))

    # warmup sweep: compile every reachable bucket geometry, then pin the
    # retrace counter — the timed sweep must not move it.  Every request
    # row carries exactly NNZ entries, so a micro-batch of r rows packs to
    # the (pow2(r), NNZ*pow2(r)) bucket: sweeping pow-2 row counts up to
    # the queue's max_batch covers everything coalescing can produce.
    it = ScoringIterator(max_batch=256)
    r = 1
    while r <= 256:
        batch, _ = it.pack(make_req(r))
        engine.score(batch)
        r *= 2
    sizes = (1, 8, 64)
    before = telemetry.snapshot()

    out: dict = {"platform": platform, "sizes": {}}
    for s in sizes:
        n_req = max(64, 2048 // s)
        nv = naive(s, n_req)
        mq, p50, p99 = micro(s, n_req)
        out["sizes"][str(s)] = {
            "naive_rows_s": round(nv), "micro_rows_s": round(mq),
            "qps_speedup": round(mq / max(nv, 1e-9), 2),
            "p50_us": round(p50), "p99_us": round(p99)}

    delta = telemetry.counters_delta(before, telemetry.snapshot())
    head = out["sizes"]["1"]
    out["qps_speedup"] = head["qps_speedup"]
    out["p50_us"], out["p99_us"] = head["p50_us"], head["p99_us"]
    out["p99_over_p50"] = round(head["p99_us"] / max(head["p50_us"], 1), 2)
    out["retrace_steady_delta"] = int(delta.get("models.predict_retrace", 0))
    out["serving_ok"] = (out["qps_speedup"] >= 3.0
                         and out["p99_over_p50"] <= 5.0
                         and out["retrace_steady_delta"] == 0)
    if not out["serving_ok"]:
        log(f"[bench] WARNING: serving gate missed (want >=3x naive QPS, "
            f"p99 <= 5x p50, zero retraces): speedup "
            f"{out['qps_speedup']}x, p99/p50 {out['p99_over_p50']}, "
            f"retraces {out['retrace_steady_delta']}")
    return out


# ---- the one device process ---------------------------------------------------
# A chip belongs to one process, so every device-touching phase runs in ONE
# child and the parent never opens a backend.  The child prints one
# "PHASE <name> <json>" line per phase as it completes; the first phase that
# raises ends the child with a traceback and a non-zero exit, and the parent
# exits non-zero with it.

_DEVICE_CHILD = r"""
import json, sys, time
import jax
import bench
from dmlc_core_tpu import compile_cache
compile_cache.configure()
_d = jax.devices()
print("DEVICE " + json.dumps({"platform": _d[0].platform,
                              "kind": _d[0].device_kind, "count": len(_d)}),
      flush=True)

def phase(name, fn):
    print("PHASE " + name + " " + json.dumps(fn()), flush=True)

data = bench.make_dataset()
csv = bench.make_csv_dataset()
rec = bench.make_recordio_dataset()
phase("staging", lambda: bench.run_staging(data))
phase("csv_staging", lambda: bench.run_staging(csv, fmt="csv"))
phase("recordio_staging", lambda: bench.run_recordio_staging(rec))
phase("autotune", lambda: bench.run_autotune_convergence(data))
phase("bincache", lambda: bench.run_bincache(bench.make_float_libsvm_dataset()))
phase("dataservice",
      lambda: bench.run_dataservice(bench.make_float_libsvm_dataset()))
phase("serving", bench.run_serving)
# NOTE gbdt runs LAST (after h2d/pallas/allreduce): it is the compile-
# heaviest phase on TPU (several full forest compiles for the histogram
# A/Bs), so the cheap headline phases print before it

def h2d():
    import numpy as np
    platform = jax.devices()[0].platform
    buf = np.ones((32 << 20) // 4, np.float32)
    jax.device_put(buf).block_until_ready()
    t0 = time.monotonic()
    for _ in range(3):
        jax.device_put(buf).block_until_ready()
    return {"gbps": round(3 * buf.nbytes / (time.monotonic() - t0) / 1e9, 3),
            "platform": platform}
phase("h2d", h2d)

def pallas_seg():
    # the tiled one-hot segment-sum kernel, natively compiled on TPU
    # (interpret mode elsewhere — tiny sizes, correctness + a timing note)
    import numpy as np
    import jax.numpy as jnp
    from dmlc_core_tpu.ops.pallas_segment import segment_sum
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    nnz, rows = (1 << 20, 4096) if on_tpu else (1 << 12, 256)
    rng = np.random.default_rng(0)
    row_id = jnp.asarray(np.sort(rng.integers(0, rows, nnz)).astype(np.int32))
    contrib = jnp.asarray(rng.standard_normal(nnz).astype(np.float32))
    want = segment_sum(contrib, row_id, rows)
    got = segment_sum(contrib, row_id, rows, force="pallas")
    err = float(jnp.max(jnp.abs(got - want)))
    out = {"platform": platform, "max_abs_err": round(err, 7), "nnz": nnz}
    if on_tpu:
        for name, force in (("pallas", "pallas"), ("xla", None)):
            f = lambda: segment_sum(contrib, row_id, rows, force=force)  # noqa: E731
            f().block_until_ready()
            t0 = time.monotonic()
            for _ in range(20):
                r = f()
            r.block_until_ready()
            out[f"{name}_us_per_call"] = round(
                (time.monotonic() - t0) / 20 * 1e6, 1)
    return out
phase("pallas_segment", pallas_seg)

def real_allreduce():
    # only meaningful with >=2 real devices (a multi-chip TPU VM); on one
    # chip the phase says so and the parent adds the labelled virtual-CPU-
    # mesh psum bench
    import numpy as np
    devices = jax.devices()
    if len(devices) < 2 or devices[0].platform == "cpu":
        return {"skipped": f"{len(devices)} {devices[0].platform} device(s)",
                "platform": devices[0].platform}
    from jax.sharding import Mesh
    from dmlc_core_tpu.parallel.collective import allreduce_bench
    mesh = Mesh(np.asarray(devices), ("data",))
    out = allreduce_bench(mesh, mib_per_device=16.0, iters=5)
    out["platform"] = devices[0].platform
    return out
phase("allreduce", real_allreduce)
phase("models", bench.run_models)

def gbdt_mesh():
    # plan-routed scale-out: 1->N trees/s via MeshPlan (each chip builds
    # its row shard's histogram with the Pallas kernel under the plan's
    # shard_map, plan.allreduce over ICI) plus the chunked-overlap A/B at
    # full count.  Only meaningful with >=2 real TPU devices; skips on
    # this one-chip rig (the parent falls back to the virtual host mesh).
    # Parity is pinned off-hardware by tests/test_meshplan.py.
    devices = jax.devices()
    if len(devices) < 2 or devices[0].platform != "tpu":
        return {"skipped": f"{len(devices)} {devices[0].platform} device(s)",
                "platform": devices[0].platform}
    return bench.mesh_gbdt_scaling(devices, histogram="pallas",
                                   rows=100_000 // len(devices) * len(devices),
                                   num_features=28, num_bins=256,
                                   trees=5, depth=6)
phase("gbdt_mesh", gbdt_mesh)

def mesh_scaleout():
    # 1->N bus-GB/s curves, flat psum vs hierarchical RS->tree->AG, small
    # and large payloads — the hier >= 1.5x gate is only meaningful here,
    # on a real multi-chip fabric
    devices = jax.devices()
    if len(devices) < 2 or devices[0].platform != "tpu":
        return {"skipped": f"{len(devices)} {devices[0].platform} device(s)",
                "platform": devices[0].platform}
    return bench.mesh_collective_scaling(devices)
phase("mesh_scaleout", mesh_scaleout)
phase("gbdt", bench.run_gbdt)
"""


def run_device_phases() -> dict:
    """All device phases in the one child that may open the backend.  Its
    failure — any phase raising, or the time limit — is this script's."""
    # generous: a cold run compiles every GBDT level and A/B forest
    timeout = 3000
    try:
        proc = subprocess.run([sys.executable, "-c", _DEVICE_CHILD],
                              stdout=subprocess.PIPE, text=True,
                              timeout=timeout, cwd=str(REPO))
    except subprocess.TimeoutExpired:
        raise SystemExit(f"[bench] device child exceeded {timeout}s")
    phases: dict = {}
    for line in proc.stdout.splitlines():
        if line.startswith("PHASE "):
            _, name, payload = line.split(" ", 2)
            phases[name] = json.loads(payload)
        elif line.startswith("DEVICE "):
            phases["device"] = json.loads(line[len("DEVICE "):])
    if proc.returncode != 0:
        raise SystemExit(
            f"[bench] device child failed (rc={proc.returncode}) after "
            f"phases {sorted(phases)}; its traceback is above")
    return phases


def main() -> None:
    data = make_dataset()
    log(f"[bench] dataset {data} ({data.stat().st_size >> 20} MB)")

    ref_rate = None
    exe = ensure_reference_binary()
    if exe is not None:
        run_reference(exe, data)  # warmup (page cache parity)
        ref_rate = run_reference(exe, data)
        log(f"[bench] reference libsvm_parser_test: {ref_rate} MB/s (parse only)")

    parse = run_parse(data)
    log(f"[bench] ours parse->RowBlock: {parse['mb_s']:.1f} MB/s")
    overhead = run_telemetry_overhead(data)
    log(f"[bench] telemetry overhead: {overhead}")
    faults_overhead = run_faults_overhead(data)
    log(f"[bench] fault-point overhead: {faults_overhead}")
    trace_overhead = run_trace_overhead(data)
    log(f"[bench] tracing overhead: {trace_overhead}")
    timeseries_overhead = run_timeseries_overhead(data)
    log(f"[bench] sampler overhead: {timeseries_overhead}")
    csv_data = make_csv_dataset()
    csv_ref_rate = None
    csv_exe = ensure_reference_csv_binary()
    if csv_exe is not None:
        run_rate([str(csv_exe), str(csv_data), "0", "1"])  # page-cache warmup
        csv_ref_rate = run_rate([str(csv_exe), str(csv_data), "0", "1"])
        log(f"[bench] reference csv (float) parse: {csv_ref_rate} MB/s")
    csv_parse = run_parse(csv_data, fmt="csv")
    log(f"[bench] ours csv parse: {csv_parse['mb_s']:.1f} MB/s")
    make_recordio_dataset()
    phases = run_device_phases()
    staging = phases["staging"]
    csv_staging = phases["csv_staging"]
    rec_staging = phases["recordio_staging"]
    log(f"[bench] ours parse->pad->HBM: {staging['mb_s']:.1f} MB/s "
        f"-> {staging['platform']}")
    log(f"[bench] ours csv->HBM prefetch: {csv_staging['mb_s']:.1f} MB/s")
    log(f"[bench] recordio->HBM: {rec_staging['mb_s']:.1f} MB/s, "
        f"{rec_staging['records_s']:.0f} records/s -> {rec_staging['platform']}")
    allreduce = phases["allreduce"]
    if "bus_gbps" not in allreduce:  # one device: the labelled virtual mesh
        allreduce = run_allreduce()
    log(f"[bench] allreduce: {allreduce}")
    # mesh scale-out: where the device child had one device, the virtual
    # 8-device CPU host mesh, labelled as such
    gbdt_mesh = phases["gbdt_mesh"]
    mesh_scaleout = phases["mesh_scaleout"]
    if "scaling" not in gbdt_mesh or "rows" not in mesh_scaleout:
        virt = run_mesh_virtual()
        if "scaling" not in gbdt_mesh:
            gbdt_mesh = virt["gbdt_mesh"]
        if "rows" not in mesh_scaleout:
            mesh_scaleout = virt["mesh_scaleout"]
    log(f"[bench] gbdt mesh scaling: {gbdt_mesh}")
    log(f"[bench] collective scale-out: {mesh_scaleout}")
    if mesh_scaleout.get("hier_gate_ok") is False:
        log("[bench] WARN " + mesh_scaleout.get("hier_gate_note", ""))
    vs = (parse["mb_s"] / ref_rate) if ref_rate else None
    full = {
        "metric": "libsvm_parse_mb_s",
        "value": round(parse["mb_s"], 2),
        "unit": "MB/s",
        "vs_baseline": round(vs, 3) if vs is not None else None,
        "baseline_mb_s": ref_rate,
        "staging_to_hbm_mb_s": round(staging["mb_s"], 2),
        "staging_rows_per_sec": round(staging.get("rows_s", 0)),
        "staging_platform": staging["platform"],
        "staging_vs_parse": round(staging["mb_s"] / parse["mb_s"], 3),
        "device": phases["device"],
        "csv_parse_mb_s": round(csv_parse["mb_s"], 2),
        "csv_baseline_mb_s": csv_ref_rate,
        "csv_vs_baseline": (round(csv_parse["mb_s"] / csv_ref_rate, 3)
                            if csv_ref_rate else None),
        "csv_staging_to_hbm_mb_s": round(csv_staging["mb_s"], 2),
        "recordio_staging_mb_s": round(rec_staging["mb_s"], 2),
        "recordio_records_per_sec": round(rec_staging["records_s"]),
        "allreduce_bus_gbps": (round(allreduce["bus_gbps"], 2)
                               if "bus_gbps" in allreduce else None),
        "allreduce_platform": allreduce.get("platform"),
        "allreduce_devices": allreduce.get("devices"),
        "allreduce_note": allreduce.get("note") or allreduce.get("error"),
        "collectives_bus_gbps": allreduce.get("others"),
        "model_family_rows_s": {
            k: v for k, v in phases.get("models", {}).items()
            if k.endswith("_rows_s") or k.endswith("_error")
            or k == "platform"} or None,
        "gbdt_row_trees_per_sec": phases.get("gbdt", {}).get("row_trees_s"),
        "gbdt_sparse_row_trees_per_sec": phases.get("gbdt", {}).get(
            "sparse_row_trees_s"),
        "gbdt_sparse_hist_ab": phases.get("gbdt", {}).get("sparse_hist_ab"),
        "gbdt_platform": phases.get("gbdt", {}).get("platform"),
        "gbdt_mesh": gbdt_mesh,
        "mesh_scaleout": mesh_scaleout,
        "h2d_gbps_single_chip": phases.get("h2d", {}).get("gbps"),
        "h2d_platform": phases.get("h2d", {}).get("platform"),
        "pallas_segment": phases.get("pallas_segment"),
        "stall_attribution": staging.get("parallel", {}).get(
            "stall_attribution"),
        "staging_job_table": staging.get("parallel", {}).get("job_table"),
        "autotune": phases.get("autotune"),
        "bincache": phases.get("bincache"),
        "dataservice": phases.get("dataservice"),
        "serving": phases.get("serving"),
        "telemetry_overhead": overhead,
        "faults_overhead": faults_overhead,
        "trace": trace_overhead,
        "timeseries": timeseries_overhead,
        "data_mb": data.stat().st_size >> 20,
    }
    # Full dump on its own prefixed line; the LAST line is a compact (<1 KB)
    # headline summary so a tail-capturing driver always gets parseable JSON
    # (round 4's single huge line arrived truncated mid-word -> parsed:null).
    print("DETAIL " + json.dumps(full), flush=True)
    gbdt = phases.get("gbdt", {})
    compact = {
        "metric": "libsvm_parse_mb_s",
        "value": full["value"],
        "unit": "MB/s",
        "vs_baseline": full["vs_baseline"],
        "csv_parse_mb_s": full["csv_parse_mb_s"],
        "csv_vs_baseline": full["csv_vs_baseline"],
        "staging_to_hbm_mb_s": full["staging_to_hbm_mb_s"],
        "recordio_staging_mb_s": full["recordio_staging_mb_s"],
        "gbdt_row_trees_per_sec": full["gbdt_row_trees_per_sec"],
        "model_family_rows_s": full["model_family_rows_s"],
        "gbdt_hist_ab": gbdt.get("hist_ab"),
        # headline only (full A/B dict rides the DETAIL line): the compact
        # line's 1 KB tail-capture contract can't afford both dicts
        "gbdt_sparse_hist_speedup": (gbdt.get("sparse_hist_ab") or {}).get(
            "sparse_hist_speedup"),
        "gbdt_sparse_hist_max_abs_err": (
            gbdt.get("sparse_hist_ab") or {}).get("max_abs_err"),
        "allreduce_bus_gbps": full["allreduce_bus_gbps"],
        "mesh_hier_vs_flat": mesh_scaleout.get("hier_vs_flat_large"),
        "gbdt_mesh_trees_s": [r.get("row_trees_s") for r in
                              gbdt_mesh.get("scaling", [])] or None,
        "gbdt_mesh_overlap_identical": gbdt_mesh.get(
            "overlap_forest_identical"),
        "h2d_gbps": full["h2d_gbps_single_chip"],
        "staging_platform": full["staging_platform"],
        "stall": (full["stall_attribution"] or {}).get("table"),
        "telemetry_overhead_pct": overhead.get("telemetry_overhead_pct"),
        "faults_overhead_pct": faults_overhead.get("faults_overhead_pct"),
        "timeseries_overhead_pct": timeseries_overhead.get(
            "timeseries_overhead_pct"),
        "autotune_convergence_ratio": (phases.get("autotune") or {}).get(
            "convergence_ratio"),
        "autotune_armed_overhead_pct": (phases.get("autotune") or {}).get(
            "armed_overhead_pct"),
        "bincache_repeat_speedup": (phases.get("bincache") or {}).get(
            "repeat_speedup_vs_text"),
        "bincache_forest_identical": (phases.get("bincache") or {}).get(
            "forest_identical"),
        "bincache_copy_ratio": (phases.get("bincache") or {}).get(
            "bytes_copied_per_byte_served"),
        "dataservice_served_vs_local": (phases.get("dataservice") or {}).get(
            "served_vs_local_hit"),
        "serving_qps_speedup": (phases.get("serving") or {}).get(
            "qps_speedup"),
        "serving_p99_over_p50": (phases.get("serving") or {}).get(
            "p99_over_p50"),
        "serving_retrace_delta": (phases.get("serving") or {}).get(
            "retrace_steady_delta"),
        "device": phases["device"],
        "detail": "full numbers on the DETAIL line above",
    }
    line = json.dumps(compact)
    if len(line) > 1000:  # keep the tail-capture contract by construction
        line = json.dumps({k: compact[k] for k in
                           ("metric", "value", "unit", "vs_baseline",
                            "device")})
    print(line, flush=True)


if __name__ == "__main__":
    main()
