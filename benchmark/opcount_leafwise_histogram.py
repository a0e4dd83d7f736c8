"""Bytes the leaf-wise tree's histograms ALGORITHM needs, from the
generator's counts (see ``opcount.py`` for the rule: not what an
implementation happens to do)."""
from __future__ import annotations


def leafwise_histogram(counts: dict) -> dict:
    """Best-first growth by histogram subtraction visits every row once a
    tree and then the smaller child's rows of every expansion, whatever
    implements it (``rows_visited``: the generator's count off the forest,
    not a count of kernel launches).  A visited row is read once: its ``F``
    one-byte bin codes, its gradient and hessian (8 B) and its row index
    (4 B: the rows of a leaf are reached through the row order).  Every built
    histogram (``histograms_built``: the root's and one an expansion) is
    written once, ``F x num_bins`` buckets of two float32.  Padding to a
    static size, the int32 relayout of the bins, the one-hot contraction and
    the derived sibling do not count, so the share cannot pass 100%.  Bytes
    bound on every chip in peaks.json: 2 flops a (row, feature) against more
    than a byte."""
    rows, features = counts["rows_visited"], counts["features"]
    written = counts["histograms_built"] * features * counts["num_bins"] * 8
    return {"flops": 2.0 * rows * features,
            "bytes": float(rows) * (features + 12) + float(written)}
