"""Reader ``bench_span``: one of the benchmark's own host-clock spans.

args: ``span``; ``over`` = ``"window"`` (share of the window, times
``scale``) or ``"count"`` (seconds a call, times ``scale``).
"""


def read(args: dict, run):
    slot = run.spans.get(args["span"])
    if not slot or not slot["n"]:
        return None
    if args["over"] == "window":
        return args.get("scale", 1.0) * slot["s"] / run.window_s
    return args.get("scale", 1.0) * slot["s"] / slot["n"]
