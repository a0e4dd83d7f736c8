"""Plain reference of the ``criteo-tb-ftrl`` configuration: hashed logistic
regression trained by per-coordinate FTRL-Proximal, Algorithm 1 of McMahan et
al., "Ad Click Prediction: a View from the Trenches" (KDD 2013), one update a
minibatch with the minibatch's SUMMED gradient, as dmlc/wormhole's
``learn/linear`` pushes it (``algo = ftrl``).  For a minibatch with live
entries ``(r, i, x)`` and U the distinct ids among them:

    w_i  = 0                                                if |z_i| <= l1
         = -(z_i - sign(z_i) l1) / ((beta + sqrt(n_i)) / alpha + l2)    else
    m_r  = b + sum_(r,i,x) w_i x          p_r = sigmoid(m_r)
    g_i  = sum_(r,i,x) weight_r (p_r - y_r) x
    s_i  = (sqrt(n_i + g_i^2) - sqrt(n_i)) / alpha
    z_i += g_i - s_i w_i                  n_i += g_i^2          (i in U, once)

The bias is one more coordinate that every row holds with x = 1.  The loss
reported for a minibatch is its weighted mean logistic loss before the update.

numpy float64 over the distinct keys of each minibatch (``np.unique`` +
``np.add.at``); state is kept only for keys ever touched, never a table of
the key space.  Imports nothing of the program and is handed nothing it made
but the numbers to compare.

``compare`` follows the first steps the program took, from zero state, and
one step it took after the window from the state the window left (``live``),
and returns

- ``z_rel_err``, ``n_rel_err``  ``z`` and ``n`` after the compared steps at a
                         fixed sample of touched ids and at the bias: the
                         largest ``|got - ref| / max(|ref|, 1)``;
- ``w_abs_err``          the same sample's ``w``, largest absolute error;
- ``zero_set_mismatch``  sampled ids whose ``w`` is zero on one side only, ids
                         whose ``|z|`` lies within 1e-3 of ``l1`` (the width
                         of ``z``'s own limit) left out;
- ``live_z_rel_err``, ``live_n_rel_err``, ``live_w_abs_err``,
  ``live_zero_set_mismatch``  the same four after the live step, at the bias
                         and at EVERY distinct id of its minibatch, against
                         one step of this file from the ``(z, n)`` the
                         program held there before it;
- ``untouched_changed``  entries of a fixed sample of ids that no row of the
                         file names which are not exactly ``(0, 0, 0)`` when
                         the window closes;
- ``loss_rel_err``, ``live_loss_rel_err``  each compared step's loss, the
                         largest relative error: a reading, held to no limit
                         (the control moves it no more than the chip's own
                         ``log1p(exp)`` does).

The control runs the same update with ``z`` and ``n`` stored in bfloat16 and
is put in the program's place.
"""
from __future__ import annotations

import numpy as np

# a sound z may differ from the reference's by z_rel_err's limit, so an id
# whose |z| lies that near l1 may be zero on one side only
THRESHOLD_BAND = 1e-3
BIAS = -1           # the bias's key in the reference's own books
# the same numbers' names after the step taken from the window's state
LIVE = {"loss_rel_err": "live_loss_rel_err", "z_rel_err": "live_z_rel_err",
        "n_rel_err": "live_n_rel_err", "w_abs_err": "live_w_abs_err",
        "zero_set_mismatch": "live_zero_set_mismatch"}


def weights(z, n, sizes: dict):
    """Algorithm 1's closed form of ``(z, n)``."""
    a, b, l1, l2 = (sizes[k] for k in ("alpha", "beta", "l1", "l2"))
    shrunk = -(z - np.sign(z) * l1) / ((b + np.sqrt(n)) / a + l2)
    return np.where(np.abs(z) <= l1, 0.0, shrunk)


def _stored(x, store):
    return x if store is None else x.astype(store).astype(np.float64)


def ftrl_steps(batches, sizes: dict, store=None, start=None) -> dict:
    """Follow ``batches`` (each a dict of ``row``, ``index``, ``value`` an
    entry and ``label``, ``weight`` a row) from zero state, or from ``start``:
    sorted ``keys`` (``BIAS`` first) and their ``z`` and ``n``.  ``store`` is
    the dtype ``z`` and ``n`` are rounded through after every update (the
    control's bfloat16).  Returns the losses and the state of every key ever
    touched: sorted ``keys`` (``BIAS`` first), ``z``, ``n``, ``w``."""
    if sizes["objective"] != "logistic":
        raise ValueError("the reference follows the logistic objective")
    alpha = sizes["alpha"]
    keys, z, n = (np.array([BIAS]), np.zeros(1), np.zeros(1)) \
        if start is None else start
    keys, z, n = (np.array(keys, np.int64), np.array(z, np.float64),
                  np.array(n, np.float64))
    losses = []
    for batch in batches:
        value = np.asarray(batch["value"], np.float64)
        live = value != 0
        row = np.asarray(batch["row"], np.int64)[live]
        index = np.asarray(batch["index"], np.int64)[live]
        value = value[live]
        label = (np.asarray(batch["label"]) > 0.5).astype(np.float64)
        weight = np.asarray(batch["weight"], np.float64)
        # this minibatch's keys join the books with zero state
        merged = np.union1d(keys, index)
        at = np.searchsorted(merged, keys)
        z_all, n_all = np.zeros(len(merged)), np.zeros(len(merged))
        z_all[at], n_all[at] = z, n
        keys, z, n = merged, z_all, n_all

        u, inverse = np.unique(index, return_inverse=True)
        slot = np.searchsorted(keys, u)
        w_u = weights(z[slot], n[slot], sizes)
        w_b = weights(z[:1], n[:1], sizes)[0]
        margin = np.full(len(label), w_b)
        np.add.at(margin, row, w_u[inverse] * value)
        nll = (np.maximum(margin, 0) - margin * label
               + np.log1p(np.exp(-np.abs(margin))))
        losses.append(float(np.sum(nll * weight) / max(np.sum(weight), 1.0)))
        dm = weight * (1.0 / (1.0 + np.exp(-margin)) - label)
        g = np.zeros(len(u))
        np.add.at(g, inverse, dm[row] * value)
        for where, grad, w_old in ((slot, g, w_u),
                                   (np.zeros(1, np.int64),
                                    np.array([dm.sum()]), np.array([w_b]))):
            sigma = (np.sqrt(n[where] + grad ** 2) - np.sqrt(n[where])) / alpha
            z[where] = _stored(z[where] + grad - sigma * w_old, store)
            n[where] = _stored(n[where] + grad ** 2, store)
    return {"losses": losses, "keys": keys, "z": z, "n": n,
            "w": weights(z, n, sizes)}


def _rel(got, ref) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref)
                        / np.maximum(np.abs(ref), 1.0)))


def _errors(got: dict, ref: dict, sizes: dict) -> dict:
    gw, rw = np.asarray(got["w"], np.float64), ref["w"]
    near = np.abs(np.abs(ref["z"]) - sizes["l1"]) < THRESHOLD_BAND
    return {"loss_rel_err": max(abs(g - r) / abs(r) for g, r in
                                zip(got["losses"], ref["losses"])),
            "z_rel_err": _rel(got["z"], ref["z"]),
            "n_rel_err": _rel(got["n"], ref["n"]),
            "w_abs_err": float(np.max(np.abs(gw - rw))),
            "zero_set_mismatch": int(np.sum(((gw == 0) != (rw == 0)) & ~near))}


def sampled(state: dict, sample_ids) -> dict:
    """``state`` of :func:`ftrl_steps` at the bias and then at ``sample_ids``
    (ids never touched read zero)."""
    ids = np.concatenate([[BIAS], np.asarray(sample_ids, np.int64)])
    at = np.minimum(np.searchsorted(state["keys"], ids), len(state["keys"]) - 1)
    found = state["keys"][at] == ids
    out = {k: np.where(found, state[k][at], 0.0) for k in ("z", "n", "w")}
    out["losses"] = state["losses"]
    return out


def dense_batches(label, index, batch_size: int):
    """Minibatches of ``batch_size`` rows from ``label [rows]`` and
    ``index [rows, entries]``: one id a column, every value 1, weight 1."""
    entries = index.shape[1]
    for at in range(0, len(label), batch_size):
        rows = len(label[at:at + batch_size])
        yield {"row": np.repeat(np.arange(rows), entries),
               "index": index[at:at + rows].reshape(-1),
               "value": np.ones(rows * entries),
               "label": label[at:at + rows], "weight": np.ones(rows)}


def _compared(got: dict, batches: list, sizes: dict, control: bool,
              names=None, start=None, at=None) -> list:
    """``got`` against this file's steps over ``batches`` from ``start``,
    and the control's steps against them, each at the ids ``at``; the
    numbers under ``names`` (their own without)."""
    def follow(store=None):
        state = ftrl_steps(batches, sizes, store=store, start=start)
        return state if at is None else sampled(state, at)
    ref = follow()
    if len(got["losses"]) != len(ref["losses"]):
        raise ValueError("the program took another number of steps")
    names = names or {k: k for k in LIVE}
    out = [{"name": names[k], "value": v}
           for k, v in _errors(got, ref, sizes).items()]
    if control:
        import ml_dtypes
        out += [{"name": f"control.{names[k]}", "value": v} for k, v in
                _errors(follow(ml_dtypes.bfloat16), ref, sizes).items()]
    return out


def compare(got: dict, label, index, sample_ids, sizes: dict,
            control: bool = False, live: dict | None = None) -> list:
    """``got``: ``losses`` of the compared steps; ``z``, ``n``, ``w`` after
    them at the bias and then at ``sample_ids``; ``untouched``, the
    ``[ids, 3]`` entries ``(w, z, n)`` at ids no row names, read when the
    window closed.  ``label`` and ``index`` are the compared steps' rows.

    ``live``: one step taken from the state the window left — its rows
    (``label``, ``index``), its ``loss``, and ``before`` and ``after``, the
    ``[1 + keys, 3]`` entries ``(w, z, n)`` at the bias and then at ``keys``,
    the sorted distinct ids of those rows."""
    batches = list(dense_batches(label, index, sizes["batch_size"]))
    out = _compared(got, batches, sizes, control, at=sample_ids)
    out.append({"name": "untouched_changed",
                "value": int(np.sum(np.asarray(got["untouched"]) != 0))})
    if live is not None:
        before, after = (np.asarray(live[k], np.float64)
                         for k in ("before", "after"))
        keys = np.concatenate([[BIAS], np.asarray(live["keys"], np.int64)])
        out += _compared(
            {"losses": [live["loss"]], "w": after[:, 0], "z": after[:, 1],
             "n": after[:, 2]},
            list(dense_batches(live["label"], live["index"],
                               len(live["label"]))),
            sizes, control, names=LIVE,
            start=(keys, before[:, 1], before[:, 2]))
    return out
