"""Bytes the sharded touched-rows step's scatter ALGORITHM needs on ONE chip,
from the generator's counts (see ``opcount.py`` for the rule: not what an
implementation happens to do)."""
from __future__ import annotations


def difacto_rows_shard(counts: dict) -> dict:
    """``opcount_rows_scatter.difacto_rows`` for a table sharded by key: the
    embedding rows and their AdaGrad sums, K floats each, are read and
    written once at each distinct key of a GLOBAL minibatch, on the chip that
    owns the key, whatever implements it.  ``distinct_keys`` is counted by
    the generator on the host from the seed's rows (every worker's rows of a
    step together: a key two workers name is one key), summed over the
    window's steps; the kernel's time in a trace is a chip's mean, so the
    work is a chip's too: a ``chips``-th of the keys.  Whole tiles moved for
    one key, rows written back unchanged because their gate is shut, the keys
    themselves and the exchanges that brought them do not count, so the share
    cannot pass 100%.  No flops."""
    row_bytes = 2 * 4 * counts["num_factors"]
    keys = counts["distinct_keys"] / counts["chips"]
    return {"flops": 0.0, "bytes": 2.0 * keys * row_bytes}
