"""Reader ``counter_delta``: native telemetry counters over the window.

args: ``num`` (counter names, summed), and one of ``den`` (counter names,
summed) or ``den_count`` (a key of the generator's counts); ``scale``.
Returns nothing when the denominator did not move.
"""


def read(args: dict, run):
    num = sum(run.counters.get(k, 0) for k in args["num"])
    if "den" in args:
        den = sum(run.counters.get(k, 0) for k in args["den"])
    elif "den_count" in args:
        den = run.counts.get(args["den_count"], 0)
    else:
        den = 1
    if not den:
        return None
    return args.get("scale", 1.0) * num / den
