"""Operations and bytes the dense GBDT histogram's ALGORITHM needs on ONE chip
of a row-sharded fit, from the generator's counts (see ``opcount.py`` for the
rule: not what an implementation happens to do)."""
from __future__ import annotations


def dense_histogram_shard(counts: dict) -> dict:
    """``opcount.dense_histogram`` for a shard: a level reads each of the
    chip's own rows once — ``F`` one-byte bin codes, the node id (4 B), the
    gradient and hessian (8 B) — and adds two numbers into a bucket for
    each (row, feature).  The kernel's time in a trace is a chip's mean, so
    the work is a chip's too: all shards' rows against one chip's time
    would read as many times too high as there are chips.  The level's
    histogram, written once and then reduced over the chips, is negligible
    beside the rows.  Bytes bound on every chip in peaks.json."""
    rows, features, levels = (counts["rows_per_chip"], counts["features"],
                              counts["levels"])
    return {"flops": 2.0 * rows * features * levels,
            "bytes": float(rows) * (features + 12) * levels}
