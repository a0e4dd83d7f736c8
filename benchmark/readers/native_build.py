"""Reader ``native_build``: seconds this process spent building the native
library (0 when ``build/libdmlctpu.so`` was already up to date)."""


def read(args: dict, run):
    return float(run.native.get("build_seconds", 0.0))
