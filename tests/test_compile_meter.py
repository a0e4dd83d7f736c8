"""The program's own compile meter (``compile_cache.py``): counters, spans and
the table by program name, from JAX's monitoring events.  On the CPU, where
the persistent cache is off: hits, misses and the fetch stand still here."""
import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from dmlc_core_tpu import compile_cache, telemetry

STAGES = ("compile.trace_us", "compile.lower_us", "compile.backend_us",
          "compile.programs")
CACHE = ("compile.fetch_us", "compile.cache_hits", "compile.cache_misses")

pytestmark = pytest.mark.skipif(not telemetry.enabled(),
                                reason="counters are compiled out")


def read(names=STAGES + CACHE):
    return {k: telemetry.counter_get(k) for k in names}


def listeners():
    from jax._src import monitoring
    return (len(monitoring._scalar_listeners),
            len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners()))


def test_configure_twice_registers_once():
    compile_cache.configure()
    once = listeners()
    compile_cache.configure()
    assert listeners() == once


def test_a_fresh_jit_moves_every_stage_and_is_named_and_a_second_call_none():
    compile_cache.configure()

    @jax.jit
    def meter_probe_program(x):
        return jnp.sin(x) * 2 + 1

    x8, x16 = jax.block_until_ready((jnp.ones(8), jnp.ones(16)))
    before, main = read(), telemetry.counter_get("main.span_us")
    jax.block_until_ready(meter_probe_program(x8))
    first = read()
    assert all(first[k] > before[k] for k in STAGES)
    assert all(first[k] == before[k] for k in CACHE)    # the cache is off here
    # at the top level on the main thread the stages are the outermost spans
    assert telemetry.counter_get("main.span_us") > main
    row = compile_cache.programs()["meter_probe_program"]
    assert row["programs"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert "hits" not in row and "misses" not in row

    jax.block_until_ready(meter_probe_program(x8))      # cached
    assert read() == first
    assert compile_cache.programs()["meter_probe_program"] == row

    jax.block_until_ready(meter_probe_program(x16))     # a new shape
    again = read()
    assert again["compile.programs"] == first["compile.programs"] + 1
    assert compile_cache.programs()["meter_probe_program"]["programs"] == 2


def test_the_stages_are_spans_of_the_ring_inside_the_span_that_compiled():
    compile_cache.configure()

    @jax.jit
    def meter_ring_program(x):
        return jnp.cos(x) - 3

    x = jax.block_until_ready(jnp.ones(4))
    main = telemetry.counter_get("main.span_us")
    telemetry.trace_start()
    try:
        with telemetry.span("test.compiles", total="test.compiles_us"):
            jax.block_until_ready(meter_ring_program(x))
    finally:
        telemetry.trace_stop()
    events = telemetry.trace_dump()["traceEvents"]
    outer = next(e for e in events if e["name"] == "test.compiles")
    for name in ("compile.trace", "compile.lower", "compile.backend"):
        inside = [e for e in events if e["name"] == name]
        assert inside, name
        assert all(outer["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]
                   for e in inside)
    # nested under a program span they add nothing of their own to the main
    # thread's time: the outer span counts once
    assert telemetry.counter_get("main.span_us") - main == outer["dur"]


def test_the_table_stops_at_its_bound(monkeypatch):
    compile_cache.configure()
    meter = compile_cache._meter
    monkeypatch.setattr(meter, "_programs", {})
    monkeypatch.setattr(compile_cache, "PROGRAMS_MAX", 3)
    for i in range(6):
        jax.monitoring.record_event_duration_secs(
            compile_cache._BACKEND, 0.5, fun_name=f"jit(bound_probe_{i})")
    table = compile_cache.programs()
    assert sorted(table) == ["bound_probe_0", "bound_probe_1",
                             "bound_probe_2", "other"]
    assert table["other"] == {"backend_s": 1.5, "programs": 3}
    # a name already kept still has its own row
    jax.monitoring.record_event_duration_secs(
        compile_cache._TRACE, 0.25, fun_name="bound_probe_1")
    assert compile_cache.programs()["bound_probe_1"] == {
        "backend_s": 0.5, "programs": 1, "trace_s": 0.25}


def test_a_hit_and_its_fetch_go_to_the_program_whose_backend_stage_holds_them(
        monkeypatch):
    """JAX names no program on the persistent cache's events; they fall
    inside a backend stage on the same thread, which does."""
    compile_cache.configure()
    monkeypatch.setattr(compile_cache._meter, "_programs", {})
    before = read()
    jax.monitoring.record_scalar(compile_cache._BACKEND, 0.0,
                                 fun_name="jit(fetched_probe)")
    jax.monitoring.record_event(compile_cache._HIT)
    jax.monitoring.record_event_duration_secs(compile_cache._FETCH, 0.125)
    jax.monitoring.record_event_duration_secs(
        compile_cache._BACKEND, 0.25, fun_name="jit(fetched_probe)")
    jax.monitoring.record_scalar(compile_cache._BACKEND, 0.0,
                                 fun_name="jit(compiled_probe)")
    jax.monitoring.record_event(compile_cache._MISS)
    jax.monitoring.record_event_duration_secs(
        compile_cache._BACKEND, 2.0, fun_name="jit(compiled_probe)")
    got = {k: v - before[k] for k, v in read().items()}
    assert got["compile.cache_hits"] == 1 and got["compile.cache_misses"] == 1
    assert got["compile.fetch_us"] == 125000 and got["compile.programs"] == 2
    assert compile_cache.programs() == {
        "fetched_probe": {"backend_s": 0.25, "programs": 1, "hits": 1,
                          "fetch_s": 0.125},
        "compiled_probe": {"backend_s": 2.0, "programs": 1, "misses": 1}}
