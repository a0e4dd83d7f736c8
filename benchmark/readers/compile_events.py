"""Reader ``compile_events``: what ``jax.monitoring`` reported while the run
was set up.  args: ``field`` = ``compile_s`` | ``programs`` | ``cache_hits``.
"""


def read(args: dict, run):
    return float(run.setup[args["field"]])
