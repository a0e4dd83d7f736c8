"""Plain reference of the ``epsilon-lgbm`` configuration: a histogram GBDT
grown leaf by leaf (best-first), numpy float64, sequential.  Imports nothing
of the program.

The rule (the program's ``models/gbdt_leafwise.py`` states the same one):

* logistic objective: ``p = sigmoid(m)``, ``g = p - y``, ``h = p (1 - p)``;
  the first margin is ``log(ybar / (1 - ybar))``;
* a leaf with sums ``(G, H)`` and a cut ``(f, b)`` ("right if bin > b") that
  gives ``(G_L, H_L)``, ``(G_R, H_R)``: gain ``G_L^2 / (H_L + l) + G_R^2 /
  (H_R + l) - G^2 / (H + l)``; valid only if ``H_L >= min_child_weight``,
  ``H_R >= min_child_weight`` and gain > 0; of equal gains the lowest
  feature, then the lowest bin (then missing mass to the left) wins;
* the frontier is every leaf with a valid best split; the leaf of largest
  gain is expanded next, ties to the lower node id; its children take the
  next two node ids, left then right; growth stops at ``max_leaves`` leaves
  or on an empty frontier (``max_depth`` > 0 keeps a leaf at that depth from
  splitting); a leaf's value is ``-eta G / (H + l)``.

`grow_tree` and `fit` are that builder (the CPU tests hold the program to
them at small shapes).  `compare` is handed the forest a timed ``fit``
returned, the rows it saw and what its counter of histogram rows read, and
follows the boosting rounds in float64 along the forest's own pointers:
before each tree the margins are the base plus the forest's own earlier leaf
values, so its gradients are the ones the fit had.  Every error is taken
against the mass that was summed (``A = sum |g|`` of a side; a node's scale is
``M = A_L^2 / (H_L + l) + A_R^2 / (H_R + l)``, a leaf's ``eta A / (H + l)``),
so that cancellation does not blow a rounding error up.  Numbers compared
(each has its limit in the configuration file):

- ``base_abs_err``          the base margin;
- ``gain_rel_err``          worst split node: ``|gain - gain_ref| / M``;
- ``cover_rel_err``         worst node: stored hessian mass, relative;
- ``leaf_rel_err``          worst leaf: ``|leaf - leaf_ref| / (eta A / (H + l))``;
- ``split_regret``          at the listed ``[tree, expansion]``: how much gain
                            the chosen cut gives away against the expanded
                            leaf's best, over that best's ``M``, in float64;
- ``order_regret``          at the same expansions: how far the best gain of
                            any leaf of the frontier lies above the expanded
                            leaf's best, over its ``M``; 0 when the order is
                            right, a near-tie when float32 swapped two;
- ``constraint_violations`` children whose hessian mass lies more than
                            `_MASS_SLACK` under ``min_child_weight``, leaves
                            past ``max_leaves``, nodes past ``max_depth``,
                            expansions whose gain is not > 0;
- ``stopped_early``         trees that hold fewer than ``max_leaves`` leaves
                            while a leaf still has a valid split;
- ``pointer_errors``        child ids out of range or not in creation order,
                            nodes whose stored row count is not the count of
                            rows the pointers bring there;
- ``rows_visited_mismatch`` the program's counter of rows its histograms
                            visited against ``rows + sum of the smaller
                            child's rows``, read off the forest's own counts;
- ``trees_missing``         trees the fit did not grow.

The control is this reference one precision down, put in the forest's place:
gradients and hessians rounded through bfloat16 before they are summed, sums
kept in float32, its own leaves advancing its margins, judged against a
float64 pass that follows the control's leaves.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8     # bincount releases the interpreter lock
# a child's float64 hessian mass may lie this far (relative) under the
# minimum before it counts as a violation: the program holds the minimum
# against its float32 sums
_MASS_SLACK = 1e-3


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
    """A split's scale from absolute gradient sums (no cancellation)."""
    return al ** 2 / (hl + lam) + (a - al) ** 2 / (h - hl + lam)


def grad_hess(margin: np.ndarray, y: np.ndarray):
    p = _sigmoid(margin)
    return p - y, np.maximum(p * (1 - p), 1e-16)


def base_margin(label: np.ndarray) -> float:
    p = float(np.clip((label > 0.5).mean(), 1e-6, 1 - 1e-6))
    return float(np.log(p / (1 - p)))


def best_splits(bins_t: np.ndarray, rows: np.ndarray, leaf: np.ndarray,
                n_leaves: int, g: np.ndarray, h: np.ndarray,
                sizes: dict) -> dict:
    """The best valid cut of each of ``n_leaves`` leaves from their full
    float64 histograms.  bins_t: ``[F, all rows]``; rows: the row ids that
    stand in the leaves; leaf: each one's leaf in ``[0, n_leaves)``.  Returns
    ``gain`` (-inf where no cut is valid), ``feature``, ``bin``,
    ``default_right`` and the best cut's ``scale``, ``[n_leaves]`` each."""
    B, lam = sizes["num_bins"], sizes["lambda"]
    mcw = sizes["min_child_weight"]
    key0 = leaf.astype(np.int64) * B
    gs, hs = g[rows], h[rows]
    absg = np.abs(gs)
    ids = np.arange(n_leaves)

    def feature(f):
        keys = key0 + bins_t[f, rows]
        hg, hh, ha = (np.bincount(keys, weights=w, minlength=n_leaves * B)
                      .reshape(n_leaves, B) for w in (gs, hs, absg))
        gl, hl, al = (np.cumsum(x, axis=1) for x in (hg, hh, ha))
        gt, ht, at = gl[:, -1:], hl[:, -1:], al[:, -1:]
        sides = [(gl, hl, al)]
        if sizes["missing_aware"]:      # missing mass (bin 0) sent right
            sides.append((gl - hg[:, :1], hl - hh[:, :1], al - ha[:, :1]))
        best = np.full(n_leaves, -np.inf)
        at_bin, at_dir = np.zeros(n_leaves, int), np.zeros(n_leaves, int)
        scale = np.ones(n_leaves)
        with np.errstate(divide="ignore", invalid="ignore"):
            for d, (a, b, c) in enumerate(sides):
                gain = _gain(a, b, gt, ht, lam)
                gain = np.where((b >= mcw) & (ht - b >= mcw) & (gain > 0),
                                gain, -np.inf)
                j = np.argmax(gain, axis=1)
                top = gain[ids, j]
                # of equal gains the lower bin, then the left direction
                better = (top > best) | ((top == best) & (j < at_bin))
                better &= np.isfinite(top)
                best = np.where(better, top, best)
                at_bin = np.where(better, j, at_bin)
                at_dir = np.where(better, d, at_dir)
                scale = np.where(better, _scale(c, b, at, ht, lam)[ids, j],
                                 scale)
        return best, at_bin, at_dir, scale

    F = bins_t.shape[0]
    if F * rows.size > 1 << 22:
        with ThreadPoolExecutor(THREADS) as pool:
            found = list(pool.map(feature, range(F)))
    else:
        found = [feature(f) for f in range(F)]
    gains, cuts, dirs, scales = (np.array(x) for x in zip(*found))  # [F, n]
    which = np.argmax(gains, axis=0)        # the first: the lowest feature
    return {"gain": gains[which, ids], "feature": which,
            "bin": cuts[which, ids], "default_right": dirs[which, ids],
            "scale": np.maximum(scales[which, ids], 1e-300)}


def goes_right(bins_t, rows, f, b, d, missing_aware: bool) -> np.ndarray:
    code = bins_t[f, rows]
    right = code > b
    if missing_aware:
        right = np.where(code == 0, d == 1, right)
    return right


def grow_tree(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray,
              sizes: dict) -> dict:
    """One tree, best-first, float64.  Returns the program's per-node tables
    (``feature``, ``threshold``, ``default_right``, ``left``, ``right``,
    ``node_rows``, ``split_gain``, ``split_cover``, ``leaf``), ``order`` (the
    node expanded at each expansion) and every row's ``leaf_of_row``."""
    B, lam, lr = sizes["num_bins"], sizes["lambda"], sizes["learning_rate"]
    L, depth_cap = sizes["max_leaves"], sizes.get("max_depth", 0)
    n_nodes = 2 * L - 1
    rows = bins_t.shape[1]
    ids = np.arange(n_nodes)
    tree = {"feature": np.zeros(n_nodes, int),
            "threshold": np.full(n_nodes, B), "left": ids.copy(),
            "right": ids.copy(), "default_right": np.zeros(n_nodes, int),
            "node_rows": np.zeros(n_nodes, int),
            "split_gain": np.zeros(n_nodes), "split_cover": np.zeros(n_nodes),
            "leaf": np.zeros(n_nodes), "order": []}
    members = {0: np.arange(rows)}
    frontier = {}       # node -> its best split
    depth = {0: 0}

    def settle(node):
        idx = members[node]
        G, H = g[idx].sum(), h[idx].sum()
        tree["node_rows"][node] = idx.size
        tree["split_cover"][node] = H
        tree["leaf"][node] = -lr * G / (H + lam)
        if depth_cap and depth[node] >= depth_cap:
            return
        best = best_splits(bins_t, idx, np.zeros(idx.size, int), 1, g, h,
                           sizes)
        if np.isfinite(best["gain"][0]):
            frontier[node] = {k: v[0] for k, v in best.items()}

    settle(0)
    used = 1
    while frontier and (used + 1) // 2 < L:
        top = max(s["gain"] for s in frontier.values())
        node = min(n for n, s in frontier.items() if s["gain"] == top)
        s = frontier.pop(node)
        idx = members.pop(node)
        right = goes_right(bins_t, idx, s["feature"], s["bin"],
                           s["default_right"], sizes["missing_aware"])
        tree["feature"][node], tree["threshold"][node] = s["feature"], s["bin"]
        tree["default_right"][node] = s["default_right"]
        tree["left"][node], tree["right"][node] = used, used + 1
        tree["split_gain"][node] = s["gain"]
        tree["order"].append(node)
        for child, part in ((used, idx[~right]), (used + 1, idx[right])):
            members[child], depth[child] = part, depth[node] + 1
            settle(child)
        used += 2
    leaf_of_row = np.zeros(rows, int)
    for node, idx in members.items():
        leaf_of_row[idx] = node
    tree["leaf_of_row"] = leaf_of_row
    return tree


def fit(bins: np.ndarray, label: np.ndarray, sizes: dict,
        num_trees: int) -> dict:
    """The whole fit in float64: ``base`` and a list of `grow_tree` trees."""
    bins_t = np.ascontiguousarray(bins.T)
    y = (label > 0.5).astype(np.float64)
    base = base_margin(label)
    margin = np.full(bins.shape[0], base)
    trees = []
    for _ in range(num_trees):
        g, h = grad_hess(margin, y)
        tree = grow_tree(bins_t, g, h, sizes)
        margin = margin + tree["leaf"][tree["leaf_of_row"]]
        trees.append(tree)
    return {"base": base, "trees": trees}


def route(bins_t: np.ndarray, tree: dict, missing_aware: bool):
    """Every node's rows as the tree's own pointers bring them there
    (``{node: row ids}``, the root's all rows), every row's leaf, and how
    many child ids are out of range or not above their parent's."""
    n_nodes = tree["left"].shape[0]
    members = {0: np.arange(bins_t.shape[1])}
    leaf_of_row = np.zeros(bins_t.shape[1], int)
    bad = 0
    for node in range(n_nodes):
        idx = members.get(node)
        if idx is None:
            continue
        lo, hi = int(tree["left"][node]), int(tree["right"][node])
        if lo == node and hi == node:
            leaf_of_row[idx] = node
            continue
        if not (node < lo < n_nodes and node < hi < n_nodes and lo != hi
                and lo not in members and hi not in members):
            bad += 1
            leaf_of_row[idx] = node
            continue
        right = goes_right(bins_t, idx, int(tree["feature"][node]),
                           int(tree["threshold"][node]),
                           int(tree["default_right"][node]), missing_aware)
        members[lo], members[hi] = idx[~right], idx[right]
    return members, leaf_of_row, bad


def _tree_of(forest: dict, t: int) -> dict:
    return {k: np.asarray(forest[k][t]) for k in (
        "feature", "threshold", "default_right", "left", "right",
        "node_rows", "split_gain", "split_cover", "leaf")}


def _frontier_regret(bins_t, tree, members, depth, expansion, g, h, sizes):
    """At one expansion: (gain the chosen cut gives away against the
    expanded leaf's best, how far the frontier's best leaf lies above the
    expanded leaf's best), each over the better split's scale."""
    first_new = 2 * expansion + 1               # ids of this expansion's kids
    parent_of = {}
    for node in members:
        if tree["left"][node] != node:
            parent_of[int(tree["left"][node])] = node
            parent_of[int(tree["right"][node])] = node
    if first_new not in parent_of:
        return 0.0, 0.0
    expanded = parent_of[first_new]
    # the frontier before it: nodes made so far that had not been split yet
    front = [n for n in members if n < first_new and (
        tree["left"][n] == n or tree["left"][n] >= first_new)]
    cap = sizes.get("max_depth", 0)
    front = [n for n in front if not cap or depth[n] < cap]
    rows = np.concatenate([members[n] for n in front])
    leaf = np.concatenate([np.full(members[n].size, i)
                           for i, n in enumerate(front)])
    best = best_splits(bins_t, rows, leaf, len(front), g, h, sizes)
    at = front.index(expanded)
    lam = sizes["lambda"]
    idx = members[expanded]
    right = goes_right(bins_t, idx, int(tree["feature"][expanded]),
                       int(tree["threshold"][expanded]),
                       int(tree["default_right"][expanded]),
                       sizes["missing_aware"])
    chosen = _gain(g[idx[~right]].sum(), h[idx[~right]].sum(), g[idx].sum(),
                   h[idx].sum(), lam)
    own = best["gain"][at]
    split_regret = (max(own - chosen, 0.0) / best["scale"][at]
                    if np.isfinite(own) else 0.0)
    top = int(np.argmax(best["gain"]))
    order_regret = (max(best["gain"][top] - own, 0.0) / best["scale"][top]
                    if np.isfinite(best["gain"][top]) else 0.0)
    return float(split_regret), float(order_regret)


def follow(bins_t: np.ndarray, label: np.ndarray, forest: dict, sizes: dict,
           num_trees: int, regret_expansions=(), lower: bool = False,
           leaves=None) -> dict:
    """The boosting rounds along ``forest``'s pointers.  ``lower`` rounds
    gradients and hessians through bfloat16 and keeps sums in float32.  With
    ``leaves`` (a list of per-node value arrays) the margins advance by those
    and not by the pass's own, so that each tree is judged on the gradients
    the forest under test really had before it."""
    lam, lr = sizes["lambda"], sizes["learning_rate"]
    mcw, L = sizes["min_child_weight"], sizes["max_leaves"]
    cap = sizes.get("max_depth", 0)
    y = (label > 0.5).astype(np.float64)
    base = base_margin(label)
    margin = np.full(bins_t.shape[1], base)
    wanted = {}
    for t, e in regret_expansions:
        wanted.setdefault(int(t), []).append(int(e))
    out = {"base": base, "trees": [], "split_regret": 0.0,
           "order_regret": 0.0, "constraint_violations": 0,
           "stopped_early": 0, "pointer_errors": 0, "rows_visited": 0}
    for t in range(num_trees):
        tree = _tree_of(forest, t)
        g, h = grad_hess(margin, y)
        if lower:
            g, h = _round_bf16(g), _round_bf16(h)
        members, leaf_of_row, bad = route(bins_t, tree,
                                          sizes["missing_aware"])
        n_nodes = tree["left"].shape[0]
        sums = np.zeros((n_nodes, 3))
        for node, idx in members.items():
            s = (g[idx].sum(), h[idx].sum(), np.abs(g[idx]).sum())
            sums[node] = np.float32(s) if lower else s
        G, H, A = sums.T
        split = np.zeros(n_nodes, bool)
        depth = {0: 0}
        gain, scale = np.zeros(n_nodes), np.ones(n_nodes)
        visited = bins_t.shape[1]
        for node in sorted(members):
            lo, hi = int(tree["left"][node]), int(tree["right"][node])
            if lo == node or lo not in members:
                continue
            split[node] = True
            depth[lo] = depth[hi] = depth[node] + 1
            gain[node] = _gain(G[lo], H[lo], G[node], H[node], lam)
            scale[node] = max(_scale(A[lo], H[lo], A[node], H[node], lam),
                              1e-300)
            visited += min(int(tree["node_rows"][lo]),
                           int(tree["node_rows"][hi]))
            out["constraint_violations"] += int(
                min(H[lo], H[hi]) < mcw * (1 - _MASS_SLACK))
            out["constraint_violations"] += int(
                not tree["split_gain"][node] > 0)
            out["constraint_violations"] += int(
                bool(cap) and depth[node] >= cap)
        reached = np.zeros(n_nodes, bool)
        reached[list(members)] = True
        counts = np.zeros(n_nodes, int)
        for node, idx in members.items():
            counts[node] = idx.size
        n_leaves = int(reached.sum() - split.sum())
        out["constraint_violations"] += max(n_leaves - L, 0)
        out["pointer_errors"] += bad + int(np.sum(
            counts != np.asarray(tree["node_rows"])))
        out["rows_visited"] += visited
        if not lower:
            for e in wanted.get(t, ()):
                s, o = _frontier_regret(bins_t, tree, members, depth, e, g,
                                        h, sizes)
                out["split_regret"] = max(out["split_regret"], s)
                out["order_regret"] = max(out["order_regret"], o)
            if n_leaves < L and not bad:
                final = [n for n in members if not split[n]
                         and (not cap or depth[n] < cap)]
                if final:
                    best = best_splits(
                        bins_t, np.concatenate([members[n] for n in final]),
                        np.concatenate([np.full(members[n].size, i)
                                        for i, n in enumerate(final)]),
                        len(final), g, h, sizes)
                    out["stopped_early"] += int(
                        np.isfinite(best["gain"]).any())
        mass = np.where(reached, H + lam, 1.0)      # an unused node: 0 / 1
        value = -lr * G / mass
        out["trees"].append({
            "gain": np.where(split, gain, 0.0), "scale": scale,
            "split": split, "reached": reached, "cover": H, "leaf": value,
            "leaf_scale": np.maximum(lr * A / mass, 1e-300)})
        step = value if leaves is None else np.asarray(leaves[t], np.float64)
        margin = margin + step[leaf_of_row]
    return out


def _errors(got: dict, ref: dict) -> dict:
    """``got``: base and per-tree gain, cover, leaf as the program (or the
    control) gives them; ``ref``: the float64 pass."""
    worst = {"gain_rel_err": 0.0, "cover_rel_err": 0.0, "leaf_rel_err": 0.0}
    for mine, theirs in zip(got["trees"], ref["trees"]):
        split, reached = theirs["split"], theirs["reached"]
        leaf = reached & ~split
        if split.any():
            worst["gain_rel_err"] = max(worst["gain_rel_err"], float(np.max(
                np.abs(mine["gain"] - theirs["gain"])[split]
                / theirs["scale"][split])))
        worst["cover_rel_err"] = max(worst["cover_rel_err"], float(np.max(
            np.abs(mine["cover"] - theirs["cover"])[reached]
            / np.maximum(np.abs(theirs["cover"][reached]), 1e-300))))
        worst["leaf_rel_err"] = max(worst["leaf_rel_err"], float(np.max(
            np.abs(mine["leaf"] - theirs["leaf"])[leaf]
            / theirs["leaf_scale"][leaf])))
    return {"base_abs_err": float(abs(got["base"] - ref["base"])), **worst}


def _stored(forest: dict, n: int) -> dict:
    return {"base": float(forest["base"]),
            "trees": [{"gain": np.asarray(forest["split_gain"][t], np.float64),
                       "cover": np.asarray(forest["split_cover"][t],
                                           np.float64),
                       "leaf": np.asarray(forest["leaf"][t], np.float64)}
                      for t in range(n)]}


def compare(bins: np.ndarray, label: np.ndarray, forest: dict, sizes: dict,
            num_trees: int, regret_expansions, rows_visited: int,
            control: bool = False) -> list:
    """``rows_visited``: what the program's counter ``gbdt.hist_rows_visited``
    read over the fit that returned ``forest``."""
    n = num_trees
    bins_t = np.ascontiguousarray(bins.T)
    got = _stored(forest, n)
    leaves = [tree["leaf"] for tree in got["trees"]]
    ref = follow(bins_t, label, forest, sizes, n, regret_expansions,
                 leaves=leaves)
    numbers = _errors(got, ref)
    for key in ("split_regret", "order_regret", "constraint_violations",
                "stopped_early", "pointer_errors"):
        numbers[key] = float(ref[key])
    numbers["rows_visited_mismatch"] = float(
        abs(int(rows_visited) - ref["rows_visited"]))
    grown = min(int(forest["trees_used"]), n, int(np.sum(
        np.asarray(forest["left"][:n])[:, 0] != 0)))
    numbers["trees_missing"] = n - grown
    out = [{"name": k, "value": v} for k, v in numbers.items()]
    if control:
        low = follow(bins_t, label, forest, sizes, n, (), lower=True)
        low_ref = follow(bins_t, label, forest, sizes, n, (),
                         leaves=[tree["leaf"] for tree in low["trees"]])
        out += [{"name": f"control.{k}", "value": v}
                for k, v in _errors(low, low_ref).items()]
    return out
