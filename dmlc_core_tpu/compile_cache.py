"""Where compiled XLA programs are kept between runs.

A GBDT fit compiles one program per tree level and serving one per request
bucket; without a persistent cache every process start pays all of them
again.  ``configure()`` is called by each entry point that compiles
(``chip_smoke.py``, the examples, ``benchmark/run.py``, the scoring
server's ``main``) before its first jit — never at package import, so a
library user's own cache settings are left alone.

The same call starts the program's compile meter: what getting executables
costs, by stage and by program, in the telemetry registry (``compile.*``), in
the ring of spans and in ``programs()``.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import jax
import jax.monitoring

from . import telemetry

_CHECKOUT = Path(__file__).resolve().parent.parent


def configure() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX reads it itself and no directory is set in code; otherwise
    the cache lives at ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what a run has to find again.  Every program is kept,
    however quick its compile: with JAX's one-second floor a program near
    the floor is cached on one run and not on the next, and a warm start
    could not be told from a cold one by counting entries.

    A program's ``jax.named_scope`` paths are part of the key
    (``jax_compilation_cache_include_metadata_in_key``).  JAX leaves them
    out by default, and an executable fetched from a directory that an older
    checkout filled then carries that checkout's metadata: a profile of it
    shows no scope the program has gained since, and the per-scope device
    times (doc/observability.md, "Device scope contract") read nothing on
    exactly the warm runs.  So the key holds a program's operations and the
    scope path of each; a changed operation or scope compiles once.

    It does not hold where the source lines stand.  By default that metadata
    also names every instruction's file, line and callers' lines, and an edit
    that shifts a file's lines, or a checkout under another path, compiles
    every program of that file again.  ``jax_traceback_in_locations_limit =
    0`` makes JAX emit ``loc("jit(f)/gbdt.hist/sin")`` alone: file, line,
    callers and the checkout's path leave the program's text and with it the
    key.  This is the one place that knows; no other module arranges its
    code for it.

    A process held to the CPU platform (``JAX_PLATFORMS=cpu``: the tests, a
    rehearsal) caches nothing.  An XLA:CPU executable is tied to the CPU
    that compiled it — its loader logs the whole feature list on every hit
    and warns of SIGILL — and this directory travels with the checkout.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(_CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    if jax.config.jax_platforms == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
    _meter.register()   # once a process, however often this is called
    return jax.config.jax_compilation_cache_dir


# ---- the compile meter ------------------------------------------------------
#
# JAX tells ``jax.monitoring`` listeners when it starts and ends each stage of
# getting an executable, with the program's name (its ``log_elapsed_time``:
# a scalar at the start, a duration at the end, both on the compiling thread).
# The meter makes each stage a span with its total, so a compile is in the
# registry, in the ring and, as ``dmlctpu.compile.*``, in a profiler's file
# like any other host work, and keeps the stages' seconds by program name.
# The listeners run on trace and compile only: a cached dispatch never
# reaches them.

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_STAGE_KEYS = {_TRACE: "trace_s", _LOWER: "lower_s", _BACKEND: "backend_s"}

#: program names the table keeps apart; the rest share the row ``"other"``
PROGRAMS_MAX = 256


def _stage_span(event: str):
    if event == _TRACE:
        return telemetry.span("compile.trace", total="compile.trace_us")
    if event == _LOWER:
        return telemetry.span("compile.lower", total="compile.lower_us")
    return telemetry.span("compile.backend", total="compile.backend_us")


class _PerThread(threading.local):
    """A compiling thread's stages still open, innermost last (tracing a
    program traces the programs it calls), and what the persistent cache
    said since its last backend stage ended: JAX names no program on those
    events, the stage around them does."""

    def __init__(self):
        self.open, self.cache = [], {}


class _CompileMeter:
    def __init__(self):
        self._registered = False
        self._lock = threading.Lock()
        self._programs: dict = {}
        self._local = _PerThread()

    def register(self) -> None:
        with self._lock:
            if self._registered:
                return
            self._registered = True
        jax.monitoring.register_scalar_listener(self._start)
        jax.monitoring.register_event_duration_secs_listener(self._end)
        jax.monitoring.register_event_listener(self._event)

    def _start(self, event: str, _value, **_) -> None:
        if event in _STAGE_KEYS:
            stage = _stage_span(event)
            stage.__enter__()
            self._local.open.append((event, stage))

    def _event(self, event: str, **_) -> None:
        if event == _HIT:
            telemetry.counter_add("compile.cache_hits", 1)
            self._local.cache["hits"] = 1
        elif event == _MISS:
            telemetry.counter_add("compile.cache_misses", 1)
            self._local.cache["misses"] = 1

    def _end(self, event: str, seconds: float, fun_name: str = "",
             **_) -> None:
        local = self._local
        if event == _FETCH:
            telemetry.counter_add("compile.fetch_us", int(seconds * 1e6))
            local.cache["fetch_s"] = seconds
            return
        key = _STAGE_KEYS.get(event)
        if key is None:
            return
        # a stage that began before the listeners did has no span to end
        if local.open and local.open[-1][0] == event:
            local.open.pop()[1].__exit__(None, None, None)
        row = {key: seconds}
        if event == _BACKEND:
            telemetry.counter_add("compile.programs", 1)
            row["programs"] = 1
            row.update(local.cache)
            local.cache = {}
        # tracing names the function, lowering and the backend its module
        name = str(fun_name)
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        with self._lock:
            if name not in self._programs \
                    and len(self._programs) >= PROGRAMS_MAX:
                name = "other"
            into = self._programs.setdefault(name, {})
            for k, v in row.items():
                into[k] = into.get(k, 0) + v

    def programs(self) -> dict:
        with self._lock:
            return {name: dict(row) for name, row in self._programs.items()}


_meter = _CompileMeter()


def programs() -> dict:
    """What getting each program cost this process, by the name JAX gives it
    (``fun_name``): ``{name: {"trace_s", "lower_s", "backend_s", "fetch_s",
    "programs", "hits", "misses"}}``, a key only where it is not 0.
    ``backend_s`` is the compile, or on a hit of the persistent cache the
    fetch and load (``fetch_s`` of it the read).  `PROGRAMS_MAX` names are
    kept apart, later ones add to ``"other"``.  Empty before `configure`.
    A row that moves in steady state names the program that recompiled."""
    return _meter.programs()
