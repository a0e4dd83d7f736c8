"""Operations and bytes the sparse GBDT histogram's ALGORITHM needs, from the
generator's counts (see ``opcount.py`` for the rule: not what an
implementation happens to do)."""
from __future__ import annotations


def sparse_histogram(counts: dict) -> dict:
    """A level of the sparse histogram reads every present entry's key
    (4 B), its row's node (4 B) and its row's gradient and hessian (8 B)
    once, and adds two numbers into a bucket for it; the level's histogram
    (nodes x features x bins x 2 float32) is written once.  Over a round of
    depth ``d`` the histograms are ``(2^d - 1)`` nodes' worth.  Skipped grid
    steps, boundary blocks read twice and the one-hot contraction do not
    count.  Bytes bound on every chip in peaks.json: 2 flops against 16 B."""
    entries, levels, rounds = (counts["entries"], counts["levels"],
                               counts["rounds"])
    nodes_a_round = 2 ** counts["max_depth"] - 1
    hist_bytes = 8.0 * counts["features"] * counts["bins"] * nodes_a_round
    return {"flops": 2.0 * entries * levels,
            "bytes": 16.0 * entries * levels + hist_bytes * rounds}
