"""Bytes the touched-rows step's scatter ALGORITHM needs, from the generator's
counts (see ``opcount.py`` for the rule: not what an implementation happens
to do)."""
from __future__ import annotations


def difacto_rows(counts: dict) -> dict:
    """The tables the in-place rows kernel visits in the DiFacto cell: the
    embedding rows and their AdaGrad sums, K floats each, read and written
    once at each distinct key of a minibatch whatever implements it.  (The
    count and ``(w, z, n)``, 16 B a key, go through XLA's scatters at the
    cell's 131,072 lanes of a ``[2^26]`` table, ``pallas_rows.engages``: they
    are in neither the bytes nor the kernel's time; ``benchmark/tests/
    test_stream_difacto.py`` holds that split.)  Whole tiles moved for one
    key, rows written back unchanged because their gate is shut, and the
    keys themselves do not count, so the share cannot pass 100%.  No flops."""
    row_bytes = 2 * 4 * counts["num_factors"]
    return {"flops": 0.0, "bytes": 2.0 * counts["distinct_keys"] * row_bytes}
