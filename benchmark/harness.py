"""What every cell shares: the cell's files, the clock, the compile meter,
the benchmark's own spans, and the record a per-layer reader reads from."""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Any

_T0 = time.monotonic()


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def log_memory(tag: str, device=None) -> None:
    """The device's own memory books at this point of the run, so that the
    log shows which program brought the reserved scratch."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    log(f"device memory, {tag}: " + ", ".join(
        f"{k} {stats[k]}" for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "largest_free_block_bytes", "bytes_limit")
        if k in stats))


def process_age_s() -> float:
    """Seconds since this process was started, interpreter start-up and
    imports included (``/proc``: start time in clock ticks since boot)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def seed31(seed: int) -> int:
    """Any ``--seed`` (they run past 2**31) folded into a non-negative int32
    for ``jax.random.PRNGKey``; numpy generators take the seed whole."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    generator: str
    reference: str
    config: dict        # the configuration file, rehearsal overrides applied
    params: dict        # the traffic generator's parameters
    seed: int
    rehearse: bool
    cache_dir: Path     # generated data of this (cell, seed)

    @property
    def sizes(self) -> dict:
        return self.config["sizes"]


def load_cell(here: Path, name: str, seed: int, rehearse: bool) -> Cell:
    path = here / "workloads" / f"{name}.json"
    if not path.is_file():
        raise BenchFailure(f"no cell file {path}")
    spec = json.loads(path.read_text())
    config = json.loads(
        (here / "configs" / f"{spec['config']}.json").read_text())
    params = copy.deepcopy(spec["params"])
    if rehearse:
        over = spec.get("rehearse", {})
        params.update(over.get("params", {}))
        config["sizes"].update(over.get("sizes", {}))
        config["assumed"].update(over.get("assumed", {}))
        config["tolerance"]["limits"].update(over.get("limits", {}))
    if seed < 0:
        raise BenchFailure("--seed must not be negative")
    cache_dir = here / ".cache" / name / str(seed)
    cache_dir.mkdir(parents=True, exist_ok=True)
    return Cell(name=name, chips=int(spec["chips"]),
                generator=spec["generator"], reference=spec["reference"],
                config=config, params=params, seed=seed, rehearse=rehearse,
                cache_dir=cache_dir)


class CompileMeter:
    """What JAX itself reports about getting executables (copied from
    ``chip_smoke.py``): seconds tracing, lowering and compiling or fetching
    from the persistent cache, how many programs, how many cache hits."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += seconds
            self.programs += event.endswith("backend_compile_duration")

    def _event(self, event: str, **_) -> None:
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def snapshot(self) -> tuple:
        return self.seconds, self.programs, self.cache_hits

    def since(self, mark: tuple) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "programs": self.programs - mark[1],
                "cache_hits": self.cache_hits - mark[2]}


class Spans:
    """The benchmark's own spans around its calls into the program: total
    seconds and count per name on the host clock, and the same span as a
    ``TraceAnnotation`` so a traced run can name what the host was doing in
    a device gap."""

    def __init__(self):
        self._totals: dict = {}

    def reset(self) -> None:
        self._totals = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        slot = self._totals.setdefault(name, [0.0, 0])
        slot[0] += time.perf_counter() - t0
        slot[1] += 1

    def totals(self) -> dict:
        return {k: {"s": v[0], "n": v[1]} for k, v in self._totals.items()}


@dataclasses.dataclass
class RunRecord:
    """One traced run, as a per-layer reader sees it."""
    cell: Cell
    peaks: dict | None      # this device's row of peaks.json
    counters: dict          # native telemetry counters, delta over the window
    spans: dict             # Spans.totals() over the window
    trace: Any              # trace_reduce.Trace
    setup: dict             # CompileMeter over set-up
    native: dict            # native_build_info()
    window_s: float
    counts: dict            # what the generator counted (rows, steps, ...)
