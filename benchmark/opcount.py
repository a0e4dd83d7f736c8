"""Operations and bytes each kernel's ALGORITHM needs, from shapes — what the
roofline share is taken against.  Not what an implementation happens to do:
padding, one-hot contractions and recomputation do not count.

Every function takes the generator's ``counts`` (what the run counted) and
returns ``{"flops": ..., "bytes": ...}`` for all the kernel's calls of the
traced window together.
"""
from __future__ import annotations


def least_seconds(work: dict, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    by_flops = work["flops"] / peaks["flops_bf16"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops > by_bytes else (by_bytes, "bytes")


def dense_histogram(counts: dict) -> dict:
    """A level of the dense GBDT histogram: every row's ``F`` one-byte bin
    codes, its node id (4 B) and its gradient and hessian (8 B) are read
    once; each (row, feature) adds two numbers into a bucket.  The
    histogram written back is negligible beside the rows.  Bytes bound on
    every chip in peaks.json: 2 flops against ``(F + 12) / F`` bytes."""
    rows, features, levels = (counts["data_rows"], counts["features"],
                              counts["levels"])
    return {"flops": 2.0 * rows * features * levels,
            "bytes": float(rows) * (features + 12) * levels}
