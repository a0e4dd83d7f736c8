"""Reader ``setup_phase``: what the program's counters read when the trace
began, that is, over set-up.

The program keeps the registry's snapshot of the moment its ring of spans
starts beside the profiler's session (the first program span of the window)
and hands it out as ``otherData.registry_at_start`` of
``telemetry.trace_dump()``: every counter, among them the totals of the spans
that closed during set-up (``total=``, doc/observability.md "Trace spans").
The process's counters start at 0, so the snapshot is set-up's own delta and
this reader subtracts nothing.

args: ``num`` (counter names, summed); optionally ``den`` (counter names,
summed; the value is then ``num / den``); ``scale`` (default 1: a span's
total is microseconds, so seconds take 1e-6, and bytes over microseconds are
MB/s as they stand).
Returns nothing where the program keeps no such snapshot (a program before
PR 50) and where the denominator is 0.
"""


def registry_at_start(run) -> dict | None:
    """The snapshot, fetched once and kept on the record."""
    if not hasattr(run, "_registry_at_start"):
        from dmlc_core_tpu import telemetry
        run._registry_at_start = telemetry.trace_dump().get(
            "otherData", {}).get("registry_at_start")
    return run._registry_at_start


def value(args: dict, counters: dict | None):
    if counters is None:
        return None
    out = sum(counters.get(k, 0) for k in args["num"])
    if "den" in args:
        den = sum(counters.get(k, 0) for k in args["den"])
        if not den:
            return None
        out /= den
    return args.get("scale", 1.0) * out


def read(args: dict, run):
    return value(args, registry_at_start(run))
