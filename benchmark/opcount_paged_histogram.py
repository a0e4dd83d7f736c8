"""Operations and bytes the dense GBDT histogram's ALGORITHM needs in a PAGED
fit, from the generator's counts (see ``opcount.py`` for the rule: not what
an implementation happens to do)."""
from __future__ import annotations


def paged_dense_histogram(counts: dict) -> dict:
    """``opcount.dense_histogram`` whatever the pages: a level reads each of
    the data set's rows once — ``F`` one-byte bin codes, the node id (4 B),
    the gradient and hessian (8 B) — and adds two numbers into a bucket for
    each (row, feature).  That the rows arrive a page at a time, that a page
    is cast and transposed for every visit and that the level's histogram is
    read and written once a page belong to the implementation, not to the
    algorithm: they lower the share, they do not enter the work.  The ninth
    pass, which routes to the leaves, builds no histogram and is not counted.
    Bytes bound on every chip in peaks.json."""
    rows, features, levels = (counts["data_rows"], counts["features"],
                              counts["levels"])
    return {"flops": 2.0 * rows * features * levels,
            "bytes": float(rows) * (features + 12) * levels}
