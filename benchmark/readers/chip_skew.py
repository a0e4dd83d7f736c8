"""Reader ``chip_skew``: how unevenly the chips of one run were busy.

No args.  From ``Trace.chips`` (per chip, the instruction events inside the
traced window): each chip's busy union; the largest less the smallest, over
their mean, in percent.  Returns nothing for a trace of fewer than two chips
that ran anything.
"""
from benchmark.trace_reduce import union_ns


def read(args: dict, run):
    trace = run.trace
    if trace is None or len(trace.chips) < 2:
        return None
    busy = [union_ns([(s, e) for _, s, e in chip]) for chip in trace.chips]
    mean = sum(busy) / len(busy)
    if not mean:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
