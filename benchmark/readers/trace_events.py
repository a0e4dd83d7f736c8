"""Reader ``trace_events``: device time from the profiler's trace.

args: ``what`` =
  ``idle_pct``       100 x (1 - busy union / traced window);
  ``busy_per``       device busy seconds / counts[``per``], times ``scale``;
  ``pattern_per``    device seconds of events matching ``pattern`` /
                     counts[``per``], times ``scale``;
  ``roofline``       100 x least seconds for the work the function ``opcount``
                     (``"<module under benchmark/>:<function>"``) computes
                     from the counts / device seconds of events matching
                     ``pattern``.
Returns nothing when the trace holds no device event (or none matching).
"""
import importlib

from benchmark import opcount


def read(args: dict, run):
    trace = run.trace
    if trace is None or not trace.chips:
        return None
    what = args["what"]
    if what == "idle_pct":
        return trace.idle_pct
    if what == "busy_per":
        per = run.counts.get(args["per"], 0)
        return args.get("scale", 1.0) * trace.busy_s / per if per else None
    seconds = trace.pattern_s(args["pattern"])
    if not seconds:
        return None
    if what == "pattern_per":
        per = run.counts.get(args["per"], 0)
        return args.get("scale", 1.0) * seconds / per if per else None
    if what == "roofline":
        module, function = args["opcount"].split(":")
        work = getattr(importlib.import_module(f"benchmark.{module}"),
                       function)(run.counts)
        least, _bound = opcount.least_seconds(work, run.peaks)
        return 100.0 * least / seconds
    raise ValueError(f"trace_events: unknown what={what!r}")
