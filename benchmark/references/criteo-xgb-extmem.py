"""Plain reference of the ``criteo-xgb-extmem`` configuration: a numpy float64
histogram GBDT (``bincount`` sums, the textbook gain and leaf formulas) taken
a block of a page at a time, so that 2^28 rows never stand as one array.
Imports nothing of the program.

The arithmetic is ``references/airline-gbdt.py``'s: handed the forest the
window's last ``fit_paged`` returned, the generator's pages (host ``uint8
[<= page_rows, F]`` arrays, code 0 an absent cell) and labels, it follows
the boosting rounds in float64 along the forest's own splits and default
directions.  Before each tree the margins are the base plus the forest's own
earlier leaf weights; at every split node it works out its own left and
right (grad, hess) sums, hence its own gain and cover, at every leaf its own
weight, and on the levels named in ``regret_levels`` the full (node,
feature, bin) histogram, to ask how much gain the program's choice gives
away against the best over features x thresholds x both directions of the
absent cells.  Because the splits are the forest's, a row's path needs no
other row: blocks of ``BLOCK_ROWS`` rows of a page each go to a thread, and
the blocks' float64 sums are added in page order.

Numbers compared (each has its limit in the configuration file):

- ``base_abs_err``        the base margin (log-odds of the label mean);
- ``gain_rel_err``        worst split node: ``|gain - gain_ref| / M``;
- ``cover_rel_err``       worst split node: hessian mass, relative;
- ``leaf_rel_err``        worst leaf: ``|leaf - leaf_ref| / (lr * A/(H+l))``;
- ``root_cover_rel_err``  the first root's stored cover against the float64
                          hessian sum over ALL rows of ALL pages: a fit that
                          leaves one of 64 pages out reads 1/64 here, one
                          that visits a page twice the same;
- ``split_regret``        worst node of the regret levels: ``(best - chosen)
                          / M`` of the best split, all in float64;
- ``trees_missing``       trees the fit did not grow;

with ``A = sum |g|`` of a side, ``M = AL^2/(HL+l) + AR^2/(HR+l)`` — and three
that are exact, of what the program counted in that fit (``observed``):

- ``rows_streamed_mismatch``  ``gbdt.rows_streamed`` against rows x
                              (``max_depth`` + 1) x trees;
- ``page_bytes_mismatch``     ``page.h2d_bytes`` against pages x a whole
                              page's bytes x passes (a short last page is
                              put padded);
- ``pages_resident_max``      the most pages on the device at once, as the
                              prefetcher kept it; its limit is the
                              configuration's.

The control is this reference one precision down, put in the forest's place:
every gradient and hessian rounded through bfloat16 before it is summed, a
block's sums kept in float32, its own leaves advancing its margins; it is
judged as a forest is, against a float64 pass that follows the control's
leaves.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 20    # a page of 2^22 rows is 4 blocks; 2^28 rows are 256
THREADS = max(1, min(32, os.cpu_count() or 1))  # numpy drops the lock


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


def level_histogram(bins_t: np.ndarray, rel: np.ndarray, n_nodes: int,
                    num_bins: int, weights) -> np.ndarray:
    """``[len(weights), F, n_nodes, num_bins]`` float64: for every weight
    vector, its sum over the rows of a node that hold a bin code.
    ``bins_t``: ``[F, rows]`` codes; ``rel``: the rows' node of the level."""
    key0 = rel.astype(np.int64) * num_bins
    size = n_nodes * num_bins
    out = np.empty((len(weights), bins_t.shape[0], size))
    for f in range(bins_t.shape[0]):
        keys = key0 + bins_t[f]
        for i, w in enumerate(weights):
            out[i, f] = np.bincount(keys, weights=w, minlength=size)
    return out.reshape(len(weights), bins_t.shape[0], n_nodes, num_bins)


def _level_regret(hist: np.ndarray, chosen: np.ndarray, sizes: dict) -> float:
    """From a level's whole (grad, hess, |grad|) histogram: for each node
    the gain the chosen split gives away against the best over features x
    thresholds x both directions of the absent cells (bin 0), over the best
    split's scale."""
    lam, mcw = sizes["lambda"], sizes["min_child_weight"]
    hg, hh, ha = hist                              # [F, nodes, B]
    gl, hl, al = (np.cumsum(x, axis=2) for x in (hg, hh, ha))
    gt, ht, at = gl[..., -1:], hl[..., -1:], al[..., -1:]
    sides = [(gl, hl, al),              # absent cells left, then right
             (gl - hg[..., :1], hl - hh[..., :1], al - ha[..., :1])]
    best = np.full(hg.shape[:2], -np.inf)
    scale = np.ones(hg.shape[:2])
    for a, b, c in sides:
        gain = _gain(a, b, gt, ht, lam)
        gain = np.where((b >= mcw) & (ht - b >= mcw), gain, -np.inf)
        j = np.argmax(gain, axis=2)[..., None]
        top = np.take_along_axis(gain, j, axis=2)[..., 0]
        better = top > best
        best = np.where(better, top, best)
        scale = np.where(better, np.take_along_axis(
            _scale(c, b, at, ht, lam), j, axis=2)[..., 0], scale)
    which = np.argmax(best, axis=0)[None]           # over the features
    best = np.take_along_axis(best, which, axis=0)[0]
    scale = np.take_along_axis(scale, which, axis=0)[0]
    live = np.isfinite(best) & (best > 0)
    regret = np.where(live, (best - chosen) / np.maximum(scale, 1e-300), 0.0)
    return float(np.max(regret)) if regret.size else 0.0


def follow(pages, label: np.ndarray, forest: dict, sizes: dict,
           num_trees: int, regret_levels, lower: bool = False,
           leaves: np.ndarray | None = None,
           block_rows: int = BLOCK_ROWS) -> dict:
    """The boosting rounds along ``forest``'s splits, a block of a page at a
    time.  ``lower`` rounds gradients and hessians through bfloat16 and keeps
    a block's sums in float32.  With ``leaves`` ([trees, leaves]) the margins
    advance by those leaf weights and not by the pass's own, so that each
    tree is judged on the gradients the forest under test really had."""
    if not sizes["missing_aware"]:
        raise ValueError("this configuration's code 0 is an absent cell")
    B, lam, lr = sizes["num_bins"], sizes["lambda"], sizes["learning_rate"]
    depth = sizes["max_depth"]
    blocks, first = [], 0       # (page, lo, hi in the page, first row)
    for i, page in enumerate(pages):
        for lo in range(0, page.shape[0], block_rows):
            hi = min(lo + block_rows, page.shape[0])
            blocks.append((i, lo, hi, first + lo))
        first += page.shape[0]
    rows = first
    if label.shape[0] != rows:
        raise ValueError(f"{label.shape[0]} labels for {rows} rows of pages")
    positives = int(np.count_nonzero(label > 0.5))
    p = float(np.clip(positives / rows, 1e-6, 1 - 1e-6))
    base = np.log(p / (1 - p))
    margins = [np.full(hi - lo, base) for _, lo, hi, _ in blocks]
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

    def kept(sums):
        return [s.astype(np.float32) for s in sums] if lower else sums

    def block_pass(t: int, i: int):
        """One block through tree ``t``: per level the (g, h, |g|) sums of
        every node's two sides, the leaves' sums, the wanted levels' whole
        histograms, and each row's leaf."""
        page, lo, hi, at = blocks[i]
        n = hi - lo
        bins_t = np.ascontiguousarray(pages[page][lo:hi].T)
        y = (label[at:at + n] > 0.5).astype(np.float64)
        prob = _sigmoid(margins[i])
        g, h = prob - y, np.maximum(prob * (1 - prob), 1e-16)
        if lower:
            g, h = _round_bf16(g), _round_bf16(h)
        weights = (g, h, np.abs(g))
        row_ids = np.arange(n)
        node = np.zeros(n, np.int32)
        sides, hists = [], {}
        for d in range(depth):
            first, n_nodes = 2 ** d - 1, 2 ** d
            rel = node - first
            ids = slice(first, first + n_nodes)
            feat = forest["feature"][t, ids].astype(np.int64)
            thr = forest["threshold"][t, ids].astype(np.int32)
            dr = forest["default_right"][t, ids].astype(np.int32)
            row_bin = bins_t[feat[rel], row_ids]
            right = np.where(row_bin == 0, dr[rel] == 1, row_bin > thr[rel])
            sides.append(kept([np.bincount(rel * 2 + right, weights=w,
                                           minlength=n_nodes * 2)
                               for w in weights]))
            if (t, d) in wanted and not lower:
                hists[d] = level_histogram(bins_t, rel, n_nodes, B, weights)
            node = 2 * node + 1 + right
        leaf_of = node - (n_leaves - 1)
        leaf_sums = kept([np.bincount(leaf_of, weights=w, minlength=n_leaves)
                          for w in weights])
        return sides, leaf_sums, hists, leaf_of

    with ThreadPoolExecutor(THREADS) as pool:
        for t in range(num_trees):
            sides, leaf_sums, hists, leaf_of = None, None, {}, []
            for got in pool.map(lambda i: block_pass(t, i),
                                range(len(blocks))):
                leaf_of.append(got[3])
                if sides is None:
                    sides, leaf_sums, hists = got[:3]
                    continue
                for d in range(depth):
                    for k in range(3):
                        sides[d][k] += got[0][d][k]
                for k in range(3):
                    leaf_sums[k] += got[1][k]
                for d, hist in got[2].items():
                    hists[d] += hist
            for d in range(depth):
                first, n_nodes = 2 ** d - 1, 2 ** d
                ids = slice(first, first + n_nodes)
                sg, sh, sa = sides[d]
                gl, hl, al = sg[0::2], sh[0::2], sa[0::2]
                gt, ht, at = gl + sg[1::2], hl + sh[1::2], al + sa[1::2]
                gain = _gain(gl, hl, gt, ht, lam)
                valid = forest["threshold"][t, ids].astype(np.int32) < B
                out["gain"][t, ids] = np.where(valid, gain, 0.0)
                out["scale"][t, ids] = np.where(
                    valid, np.maximum(_scale(al, hl, at, ht, lam), 1e-300),
                    1.0)
                out["cover"][t, ids] = ht
                out["valid"][t, ids] = valid
                if d in hists:
                    out["regret"] = max(out["regret"], _level_regret(
                        hists[d], np.where(valid, gain, 0.0), sizes))
            lg, lh, la = leaf_sums
            out["leaf"][t] = lr * -lg / (lh + lam)
            out["leaf_scale"][t] = np.maximum(lr * la / (lh + lam), 1e-300)
            step = np.asarray(out["leaf"][t] if leaves is None else leaves[t],
                              np.float64)
            for i, leaf_i in enumerate(leaf_of):
                margins[i] += step[leaf_i]
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
    root = abs(got["cover"][0, 0] - ref["cover"][0, 0]) / max(
        abs(ref["cover"][0, 0]), 1e-300)
    return {"base_abs_err": float(abs(got["base"] - ref["base"])),
            "gain_rel_err": float(np.max(gain_err)),
            "cover_rel_err": float(np.max(cover_err)),
            "leaf_rel_err": float(np.max(leaf_err)),
            "root_cover_rel_err": float(root)}


def _stored(forest: dict, n: int) -> dict:
    return {"base": float(forest["base"]),
            "gain": np.asarray(forest["split_gain"][:n], np.float64),
            "cover": np.asarray(forest["split_cover"][:n], np.float64),
            "leaf": np.asarray(forest["leaf"][:n], np.float64)}


def counted(pages, sizes: dict, num_trees: int, observed: dict,
            page_rows: int) -> dict:
    """The three exact numbers: what one fit of ``num_trees`` trees over
    ``pages`` streams and puts, against what the program counted."""
    rows = sum(int(page.shape[0]) for page in pages)
    passes = num_trees * (sizes["max_depth"] + 1)
    page_bytes = page_rows * int(pages[0].shape[1]) * pages[0].itemsize
    return {
        "rows_streamed_mismatch": abs(
            int(observed["gbdt.rows_streamed"]) - rows * passes),
        "page_bytes_mismatch": abs(
            int(observed["page.h2d_bytes"])
            - len(pages) * page_bytes * passes),
        "pages_resident_max": int(observed["page.resident_max"])}


def compare(pages, label: np.ndarray, forest: dict, sizes: dict,
            num_trees: int, regret_levels, observed: dict, page_rows: int,
            control: bool = False, block_rows: int = BLOCK_ROWS) -> list:
    """``pages``: the host pages, uint8 ``[<= page_rows, F]``; ``label``:
    ``[rows]``; ``forest``: what the timed ``fit_paged`` returned;
    ``observed``: that fit's counters."""
    n = num_trees
    got = _stored(forest, n)
    ref = follow(pages, label, forest, sizes, n, regret_levels,
                 leaves=got["leaf"], block_rows=block_rows)
    numbers = _errors(got, ref)
    numbers["split_regret"] = float(ref["regret"])
    grown = min(int(forest["trees_used"]), n,
                int(np.sum(np.any(forest["leaf"][:n] != 0, axis=1))))
    numbers["trees_missing"] = n - grown
    numbers.update(counted(pages, sizes, n, observed, page_rows))
    out = [{"name": k, "value": v} for k, v in numbers.items()]
    if control:
        low = follow(pages, label, forest, sizes, n, (), lower=True,
                     block_rows=block_rows)
        low_ref = follow(pages, label, forest, sizes, n, (),
                         leaves=low["leaf"], block_rows=block_rows)
        out += [{"name": f"control.{k}", "value": v}
                for k, v in _errors(low, low_ref).items()]
    return out
