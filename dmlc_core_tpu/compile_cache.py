"""Where compiled XLA programs are kept between runs.

A GBDT fit compiles one program per tree level and serving one per request
bucket; without a persistent cache every process start pays all of them
again.  ``configure()`` is called by each entry point that compiles
(``chip_smoke.py``, the examples, ``benchmark/run.py``, the scoring
server's ``main``) before its first jit — never at package import, so a
library user's own cache settings are left alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

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
    return jax.config.jax_compilation_cache_dir
