"""Plain reference of the ``bosch-gbdt`` configuration: a numpy float64
histogram GBDT with XGBoost's sparsity-aware split, read densely.  Imports
nothing of the program.

The program under test never sees an absent cell: it sums the present
entries of a (node, feature) and takes the absent rows' mass as the node
total less that sum.  This reference does it the other way round.  It bins
the drawn entries itself under the cuts it is handed (the count of cuts at or
below the value, plus one; code 0 is kept for *absent*), lays them out as a
dense ``uint8[rows, features]`` matrix in which 0 marks an absent cell, and
from there on every sum is a ``bincount`` over **rows**: the absent rows of a
feature are the rows whose code is 0, summed like any other bin.

It is handed the forest a timed ``fit_batch`` returned and follows the
boosting rounds itself in float64 along the forest's splits, absent rows
going where the forest's stored ``default_right`` sends them.  Before each
tree the margins are the base plus the forest's own earlier leaf weights, so
its gradients and hessians are the ones the fit had; from them it works out,
at every split node, its own left and right sums, hence its own gain and
cover, and at every leaf its own weight.  Each is compared with what the
forest stores.  On the levels named in ``regret_levels`` it also builds the
full (node, feature, bin) histogram and asks how much gain the program's
choice gives away against the best split there is over every feature, every
threshold and **both directions** for the absent rows: a default direction
stored the wrong way round gives gain away and fails here (and in the gain
itself, which the reference works out along the stored direction).

Every error is taken against the mass that was summed, not against the
result, so that cancellation between positive and negative gradients does
not blow a rounding error up: with ``A = sum |g|`` of a side, a node's scale
is ``M = AL^2/(HL+l) + AR^2/(HR+l)`` and a leaf's is ``lr * A/(H+l)``.

Numbers compared (each has its limit in the configuration file):

- ``base_abs_err``        the base margin (log-odds of the label mean);
- ``gain_rel_err``        worst split node: ``|gain - gain_ref| / M``;
- ``cover_rel_err``       worst split node: hessian mass, relative;
- ``leaf_rel_err``        worst leaf: ``|leaf - leaf_ref| / (lr * A/(H+l))``;
- ``split_regret``        worst node of the regret levels: ``(best - chosen)
                          / M`` of the best split, all in float64;
- ``trees_missing``       trees the fit did not grow;
- ``cuts_rank_err``       worst cut of the table the fit binned under: how
                          many ranks its value lies from the nearest-rank
                          position of its quantile among its feature's values
                          in the binner's sample, which this file sorts
                          itself (0 to 0.5 for a nearest-rank cut, whichever
                          way a tie at .5 is rounded; a cut that is no value
                          of the sample counts one rank more).

The control is this reference one precision down, put in the forest's place:
every gradient and hessian rounded through bfloat16 before it is summed (what
a DEFAULT-precision contraction on the MXU does to them; the one-hot operand
is exact in bfloat16), sums kept in float32, its own leaves advancing its
margins.  It is judged as a forest is: against a float64 pass that follows
the control's leaves.  Two numbers no precision moves have controls of their
own: ``control.split_regret`` is the regret of the forest under test with
the default direction of its first tree's root stored the other way round,
and ``control.cuts_rank_err`` is that of the cuts rounded through bfloat16.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8     # bincount and fancy indexing release the interpreter lock


def _sigmoid(m):
    return 1.0 / (1.0 + np.exp(-m))


def _round_bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return a.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def _gain(gl, hl, g, h, lam):
    gr, hr = g - gl, h - hl
    return (gl ** 2 / (hl + lam) + gr ** 2 / (hr + lam)
            - g ** 2 / (h + lam))


def _scale(al, hl, a, h, lam):
    """The split's scale from absolute gradient sums (no cancellation)."""
    return al ** 2 / (hl + lam) + (a - al) ** 2 / (h - hl + lam)


def bin_dense(row_ptr, findex, value, cuts, rows: int, features: int):
    """``uint8[features, rows]``: each present cell's code (1 + the count of
    its feature's cuts at or below the value), 0 where the cell is absent."""
    cuts = np.asarray(cuts, np.float32)
    n_cuts = cuts.shape[1]
    flat = cuts.reshape(-1)
    dense_t = np.zeros((features, rows), np.uint8)
    counts = np.diff(row_ptr)
    edges = np.linspace(0, rows, THREADS * 16 + 1).astype(np.int64)

    def part(k):
        r0, r1 = int(edges[k]), int(edges[k + 1])
        e0, e1 = int(row_ptr[r0]), int(row_ptr[r1])
        fi = findex[e0:e1].astype(np.int64)
        v = np.asarray(value[e0:e1], np.float32)
        lo = np.zeros(e1 - e0, np.int64)
        hi = np.full(e1 - e0, n_cuts, np.int64)
        for _ in range(max(n_cuts, 1).bit_length()):    # cuts <= v
            open_ = lo < hi
            mid = (lo + hi) // 2
            up = open_ & (flat[fi * n_cuts + np.minimum(mid, n_cuts - 1)]
                          <= v)
            lo = np.where(up, mid + 1, lo)
            hi = np.where(open_ & ~up, mid, hi)
        rid = np.repeat(np.arange(r0, r1), counts[r0:r1])
        dense_t[fi, rid] = lo + 1

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(part, range(len(edges) - 1)))
    return dense_t


def cuts_rank_err(findex, value, cuts) -> float:
    """``findex``/``value``: the entries of the binner's sample; ``cuts``
    ``[features, bins - 2]``.  A feature the sample never saw has no
    quantiles and is passed over."""
    cuts = np.asarray(cuts, np.float32)
    features, n_cuts = cuts.shape
    value = np.asarray(value, np.float32)
    order = np.lexsort((value, findex))
    f_s, v_s = np.asarray(findex)[order], value[order]
    starts = np.searchsorted(f_s, np.arange(features + 1))
    q = np.arange(1, n_cuts + 1) / (n_cuts + 1)
    worst = 0.0
    for f in range(features):
        v = v_s[starts[f]:starts[f + 1]]
        if not v.size:
            continue
        want = q * (v.size - 1)
        below = np.searchsorted(v, cuts[f], side="left")
        upto = np.searchsorted(v, cuts[f], side="right")
        held = upto > below         # the cut is a value of the sample
        first, last = below, np.where(held, upto - 1, below)
        off = np.maximum(np.maximum(first - want, want - last), 0.0)
        worst = max(worst, float(np.max(off + ~held)))
    return worst


def _level_regret(bins_t, rel, n_nodes, g, h, chosen, sizes):
    """Full float64 histogram of one level; for each node the gain the
    chosen split gives away against the best over every feature, threshold
    and direction of the absent rows, over the best split's scale."""
    B, lam = sizes["num_bins"], sizes["lambda"]
    mcw = sizes["min_child_weight"]
    key0 = rel.astype(np.int32) * B
    absg = np.abs(g)
    nodes = np.arange(n_nodes)

    def feature(f):
        keys = key0 + bins_t[f]
        hg, hh, ha = (np.bincount(keys, weights=w, minlength=n_nodes * B)
                      .reshape(n_nodes, B) for w in (g, h, absg))
        gl, hl, al = (np.cumsum(x, axis=1) for x in (hg, hh, ha))
        gt, ht, at = gl[:, -1:], hl[:, -1:], al[:, -1:]
        # code 0 is the absent rows: the running sums hold them on the
        # left; taking them off sends them right
        sides = [(gl, hl, al),
                 (gl - hg[:, :1], hl - hh[:, :1], al - ha[:, :1])]
        best, scale = np.full(n_nodes, -np.inf), np.ones(n_nodes)
        for a, b, c in sides:
            gain = _gain(a, b, gt, ht, lam)
            gain = np.where((b >= mcw) & (ht - b >= mcw), gain, -np.inf)
            j = np.argmax(gain, axis=1)
            top = gain[nodes, j]
            better = top > best
            best = np.where(better, top, best)
            scale = np.where(better, _scale(c, b, at, ht, lam)[nodes, j],
                             scale)
        return best, scale

    with ThreadPoolExecutor(THREADS) as pool:
        found = list(pool.map(feature, range(bins_t.shape[0])))
    gains, scales = (np.array(x) for x in zip(*found))     # [F, nodes]
    which = np.argmax(gains, axis=0)
    best, scale = gains[which, nodes], scales[which, nodes]
    live = np.isfinite(best) & (best > 0)
    regret = np.where(live, (best - chosen) / np.maximum(scale, 1e-300), 0.0)
    return float(np.max(regret)) if regret.size else 0.0


def follow(bins_t: np.ndarray, label: np.ndarray, forest: dict, sizes: dict,
           num_trees: int, regret_levels, lower: bool = False,
           leaves: np.ndarray | None = None) -> dict:
    """The boosting rounds along ``forest``'s splits over the dense codes
    ``bins_t`` ([features, rows], 0 = absent).  ``lower`` rounds gradients
    and hessians through bfloat16 and sums in float32.  With ``leaves``
    ([trees, leaves]) the margins advance by those leaf weights and not by
    the pass's own, so that each tree is judged on the gradients the forest
    under test really had before it."""
    B, lam, lr = sizes["num_bins"], sizes["lambda"], sizes["learning_rate"]
    depth = sizes["max_depth"]
    rows = bins_t.shape[1]
    y = (label > 0.5).astype(np.float64)
    p = float(np.clip(y.mean(), 1e-6, 1 - 1e-6))
    base = np.log(p / (1 - p))
    margin = np.full(rows, base)
    n_internal, n_leaves = 2 ** depth - 1, 2 ** depth
    out = {"base": base,
           "gain": np.zeros((num_trees, n_internal)),
           "scale": np.ones((num_trees, n_internal)),
           "cover": np.zeros((num_trees, n_internal)),
           "valid": np.zeros((num_trees, n_internal), bool),
           "leaf": np.zeros((num_trees, n_leaves)),
           "leaf_scale": np.ones((num_trees, n_leaves)),
           "regret": 0.0}
    wanted = {(int(t), int(d)) for t, d in regret_levels}
    row_ids = np.arange(rows)
    for t in range(num_trees):
        prob = _sigmoid(margin)
        g, h = prob - y, np.maximum(prob * (1 - prob), 1e-16)
        if lower:
            g, h = _round_bf16(g), _round_bf16(h)
        absg = np.abs(g)

        def sums(keys, n):
            got = [np.bincount(keys, weights=w, minlength=n)
                   for w in (g, h, absg)]
            return [x.astype(np.float32) for x in got] if lower else got

        node = np.zeros(rows, np.int32)
        for d in range(depth):
            first, n_nodes = 2 ** d - 1, 2 ** d
            rel = node - first
            ids = slice(first, first + n_nodes)
            feat = forest["feature"][t, ids].astype(np.int64)
            thr = forest["threshold"][t, ids].astype(np.int32)
            dr = forest["default_right"][t, ids].astype(np.int32)
            row_bin = bins_t[feat[rel], row_ids]
            right = np.where(row_bin == 0, dr[rel] == 1, row_bin > thr[rel])
            sg, sh, sa = sums(rel * 2 + right, n_nodes * 2)
            gl, hl, al = sg[0::2], sh[0::2], sa[0::2]
            gt, ht, at = gl + sg[1::2], hl + sh[1::2], al + sa[1::2]
            gain = _gain(gl, hl, gt, ht, lam)
            valid = thr < B
            out["gain"][t, ids] = np.where(valid, gain, 0.0)
            out["scale"][t, ids] = np.where(
                valid, np.maximum(_scale(al, hl, at, ht, lam), 1e-300), 1.0)
            out["cover"][t, ids] = ht
            out["valid"][t, ids] = valid
            if (t, d) in wanted and not lower:
                out["regret"] = max(out["regret"], _level_regret(
                    bins_t, rel, n_nodes, g, h,
                    np.where(valid, gain, 0.0), sizes))
            node = 2 * node + 1 + right
        lg, lh, la = sums(node - (n_leaves - 1), n_leaves)
        out["leaf"][t] = lr * -lg / (lh + lam)
        out["leaf_scale"][t] = np.maximum(lr * la / (lh + lam), 1e-300)
        step = out["leaf"][t] if leaves is None else leaves[t]
        margin = margin + np.asarray(step, np.float64)[node - (n_leaves - 1)]
    return out


def _errors(got: dict, ref: dict) -> dict:
    """``got``: base, gain, cover, leaf as the program (or the control)
    gives them; ``ref``: the float64 pass."""
    valid = ref["valid"]
    gain_err = np.where(valid, np.abs(got["gain"] - ref["gain"])
                        / ref["scale"], 0.0)
    cover_err = np.where(valid, np.abs(got["cover"] - ref["cover"])
                         / np.maximum(np.abs(ref["cover"]), 1e-300), 0.0)
    leaf_err = np.abs(got["leaf"] - ref["leaf"]) / ref["leaf_scale"]
    return {"base_abs_err": float(abs(got["base"] - ref["base"])),
            "gain_rel_err": float(np.max(gain_err)),
            "cover_rel_err": float(np.max(cover_err)),
            "leaf_rel_err": float(np.max(leaf_err))}


def _stored(forest: dict, n: int) -> dict:
    return {"base": float(forest["base"]),
            "gain": np.asarray(forest["split_gain"][:n], np.float64),
            "cover": np.asarray(forest["split_cover"][:n], np.float64),
            "leaf": np.asarray(forest["leaf"][:n], np.float64)}


def compare(row_ptr, findex, value, cuts, label: np.ndarray, forest: dict,
            sizes: dict, num_trees: int, regret_levels, sample_rows: int,
            control: bool = False) -> list:
    """``row_ptr [rows + 1]``, ``findex``/``value`` ``[entries]``: the drawn
    rows in CSR form; ``cuts [features, bins - 2]``: the cuts the fit binned
    under, made from the first ``sample_rows`` rows' entries; ``forest``:
    what the timed ``fit_batch`` returned."""
    n = num_trees
    sample = int(row_ptr[min(sample_rows, len(label))])
    rows = len(label)
    bins_t = bin_dense(np.asarray(row_ptr, np.int64), findex, value, cuts,
                       rows, sizes["num_features"])
    got = _stored(forest, n)
    ref = follow(bins_t, label, forest, sizes, n, regret_levels,
                 leaves=got["leaf"])
    numbers = _errors(got, ref)
    numbers["split_regret"] = float(ref["regret"])
    grown = min(int(forest["trees_used"]), n,
                int(np.sum(np.any(forest["leaf"][:n] != 0, axis=1))))
    numbers["trees_missing"] = n - grown
    numbers["cuts_rank_err"] = cuts_rank_err(findex[:sample], value[:sample],
                                             cuts)
    out = [{"name": k, "value": v} for k, v in numbers.items()]
    if control:
        low = follow(bins_t, label, forest, sizes, n, (), lower=True)
        low_ref = follow(bins_t, label, forest, sizes, n, (),
                         leaves=low["leaf"])
        controls = _errors(low, low_ref)
        turned = np.array(forest["default_right"])
        turned[0, 0] = 1 - turned[0, 0]
        controls["split_regret"] = float(follow(
            bins_t, label, dict(forest, default_right=turned), sizes, 1,
            [(0, 0)])["regret"])
        controls["cuts_rank_err"] = cuts_rank_err(
            findex[:sample], value[:sample], _round_bf16(np.asarray(cuts)))
        out += [{"name": f"control.{k}", "value": v}
                for k, v in controls.items()]
    return out
