"""Plain reference of the ``criteo-tb-difacto-ps4`` configuration: the
``criteo-tb-difacto`` reference's step (its own copy: a later change to either
leaves the other as it is), taken on the GLOBAL minibatch — the rows of all
``workers`` together, ``batch_size`` each — over ONE unsharded view of the
table.  It knows nothing of chips, owners or exchanges: a parameter server's
synchronous step is, by its semantics, one step on the workers' rows
together (counts first, then the gate, then the step), and that is all that
is written here.

A factorization machine trained as DiFacto trains it (Li, Wang, Liu, Smola,
"DiFacto — Distributed Factorization Machines", WSDM 2016, Algorithms 2-3;
dmlc/wormhole ``learn/difacto``: ``async_sgd.h``, ``sgd_server_handle.h``;
over ps-lite, Li et al., OSDI 2014): FTRL-Proximal
(McMahan et al., KDD 2013, Algorithm 1) on the weights and the bias, AdaGrad
on embedding rows of K floats that exist only for a key seen more than
``threshold`` times and only while l1 has not zeroed the key's weight.  For a
minibatch with live entries ``(r, i, x)`` and U the distinct ids among them:

    c_i += #{entries of i}                                   (i in U, first)
    w_i  = Algorithm 1's closed form of (z_i, n_i)
    a_i  = [c_i > threshold] [w_i != 0]
    P_rk = sum_(r,i,x) a_i v_ik x        q_r = sum_(r,i,x) sum_k (a_i v_ik x)^2
    m_r  = b + sum_(r,i,x) w_i x + (sum_k P_rk^2 - q_r) / 2
    s_r  = weight_r (sigmoid(m_r) - y_r)
    g_i  = sum_(r,i,x) s_r x                                 FTRL, as in
    G_ik = a_i sum_(r,i,x) s_r x (P_rk - v_ik x)             criteo-tb-ftrl.py
    h    = G_ik + l2_V v_ik;  N_ik += h^2;  v_ik -= alpha_V h / (beta_V + sqrt(N_ik))
                                                             (a_i = 1 only)

Departures from the paper and from wormhole, the program's own: ``v`` is drawn
for every key before the first step and the gate unmasks a row (wormhole
draws a row when it allocates it), so the reference is handed the program's
initial rows; the counts are added before the gate is read, in the same
minibatch (wormhole's workers push counts ahead of the weights they pull);
the bias is one more FTRL coordinate; gradients are the SUM over the
minibatch; the workers' minibatches are applied as one summed update
(wormhole's pushes are asynchronous with a bounded delay); no dropout,
clipping or normalisation.

numpy float64 over the distinct keys of each minibatch; state only for keys
ever touched.  Imports nothing of the program and is handed nothing it made
but the numbers to compare and its initial embedding rows.

``compare`` follows the first steps from zero state and one step after the
window from the state the window left (``live``, names prefixed ``live_``):

- ``z_rel_err``, ``n_rel_err``, ``nv_rel_err``  largest ``|got - ref| /
  max(|ref|, 1)`` of ``z``, ``n`` and the embedding's ``N`` at the sampled ids
  (and the bias);
- ``w_abs_err``, ``v_abs_err``  largest absolute error of ``w`` and ``v``;
- ``zero_set_mismatch``  ids whose ``w`` is zero on one side only, ids within
  ``THRESHOLD_BAND`` of ``|z| = l1`` left out;
- ``count_mismatch``  ids whose count differs (integers, exact);
- ``active_set_mismatch``  ids whose embedding row has moved (``N != 0``, or
  in the live step ``N`` changed) on one side only; ids whose ``|z|`` came
  within the band of ``l1`` while their gate was read are left out (the
  live step reads its gate off the program's own ``(z, n)`` and counts, so
  both sides decide alike there);
- ``gate_unexercised``  how many of these fail: some sampled id crossed the
  threshold inside the compared steps; the live step updated some embedding
  rows; the live step left some shut;
- ``untouched_changed``  entries of ids no row names that are not as drawn;
- ``loss_rel_err``, ``live_loss_rel_err``  logged, held to no limit.

The control keeps ``z``, ``n`` and ``N`` in bfloat16 and is put in the
program's place.
"""
from __future__ import annotations

import numpy as np

THRESHOLD_BAND = 1e-3
BIAS = -1
# the same numbers' names after the step taken from the window's state
LIVE = {"loss_rel_err": "live_loss_rel_err", "z_rel_err": "live_z_rel_err",
        "n_rel_err": "live_n_rel_err", "w_abs_err": "live_w_abs_err",
        "zero_set_mismatch": "live_zero_set_mismatch",
        "nv_rel_err": "live_nv_rel_err", "v_abs_err": "live_v_abs_err",
        "count_mismatch": "live_count_mismatch",
        "active_set_mismatch": "live_active_set_mismatch"}


def weights(z, n, sizes: dict):
    """Algorithm 1's closed form of ``(z, n)``."""
    a, b, l1, l2 = (sizes[k] for k in ("alpha", "beta", "l1", "l2"))
    shrunk = -(z - np.sign(z) * l1) / ((b + np.sqrt(n)) / a + l2)
    return np.where(np.abs(z) <= l1, 0.0, shrunk)


def _stored(x, store):
    return x if store is None else x.astype(store).astype(np.float64)


def difacto_steps(batches, sizes: dict, rows_of, store=None,
                  start=None) -> dict:
    """Follow ``batches`` (dicts of ``row``, ``index``, ``value`` an entry and
    ``label``, ``weight`` a row) from zero state, or from ``start``: a dict of
    sorted ``keys`` (``BIAS`` first), ``z``, ``n``, ``c``, ``v``, ``nv``.
    ``rows_of(ids)`` gives the initial embedding rows of ids that join the
    books.  ``store``: the dtype ``z``, ``n`` and ``nv`` are rounded through
    after every update.  Returns the losses and every key ever touched:
    ``keys``, ``z``, ``n``, ``w``, ``c``, ``v``, ``nv``; ``near``, whether a
    key's ``|z|`` came within the band of ``l1`` while its gate was read;
    ``opened``, how many keys each step updated an embedding row of."""
    if sizes["objective"] != "logistic":
        raise ValueError("the reference follows the logistic objective")
    alpha, width = sizes["alpha"], int(sizes["num_factors"])
    va, vb, vl2 = sizes["alpha_v"], sizes["beta_v"], sizes["l2_v"]
    threshold = sizes["threshold"]
    if start is None:
        start = {"keys": [BIAS], "z": [0.0], "n": [0.0], "c": [0],
                 "v": np.zeros((1, width)), "nv": np.zeros((1, width))}
    keys = np.array(start["keys"], np.int64)
    z, n, v, nv = (np.array(start[k], np.float64) for k in
                   ("z", "n", "v", "nv"))
    c = np.array(start["c"], np.int64)
    near = np.zeros(len(keys), bool)
    losses, opened = [], []
    for batch in batches:
        value = np.asarray(batch["value"], np.float64)
        live = value != 0
        row = np.asarray(batch["row"], np.int64)[live]
        index = np.asarray(batch["index"], np.int64)[live]
        value = value[live]
        label = (np.asarray(batch["label"]) > 0.5).astype(np.float64)
        weight = np.asarray(batch["weight"], np.float64)
        # this minibatch's keys join the books: zero state, their drawn rows
        fresh = np.setdiff1d(index, keys)
        if len(fresh):
            merged = np.union1d(keys, fresh)
            at, new = np.searchsorted(merged, keys), np.searchsorted(
                merged, fresh)
            grown = []
            for old, fill in ((z, 0.0), (n, 0.0), (c, 0), (near, False),
                              (v, None), (nv, 0.0)):
                wider = np.zeros((len(merged),) + old.shape[1:], old.dtype)
                wider[at] = old
                if fill is None:
                    wider[new] = rows_of(fresh)
                grown.append(wider)
            keys, (z, n, c, near, v, nv) = merged, grown

        u, inverse, times = np.unique(index, return_inverse=True,
                                      return_counts=True)
        slot = np.searchsorted(keys, u)
        c[slot] += times
        w_u = weights(z[slot], n[slot], sizes)
        w_b = weights(z[:1], n[:1], sizes)[0]
        counted = c[slot] > threshold
        gate = counted & (w_u != 0)
        near[slot] |= counted & (
            np.abs(np.abs(z[slot]) - sizes["l1"]) < THRESHOLD_BAND)
        ax = gate[inverse] * value
        vx = v[slot][inverse] * ax[:, None]
        pooled = np.zeros((len(label), width))
        np.add.at(pooled, row, vx)
        squares = np.zeros(len(label))
        np.add.at(squares, row, np.sum(vx * vx, axis=1))
        margin = np.full(len(label), w_b)
        np.add.at(margin, row, w_u[inverse] * value)
        margin += 0.5 * (np.sum(pooled * pooled, axis=1) - squares)
        nll = (np.maximum(margin, 0) - margin * label
               + np.log1p(np.exp(-np.abs(margin))))
        losses.append(float(np.sum(nll * weight) / max(np.sum(weight), 1.0)))
        dm = weight * (1.0 / (1.0 + np.exp(-margin)) - label)
        g = np.zeros(len(u))
        np.add.at(g, inverse, dm[row] * value)
        g_v = np.zeros((len(u), width))
        np.add.at(g_v, inverse, (dm[row] * ax)[:, None] * (pooled[row] - vx))
        for where, grad, w_old in ((slot, g, w_u),
                                   (np.zeros(1, np.int64),
                                    np.array([dm.sum()]), np.array([w_b]))):
            sigma = (np.sqrt(n[where] + grad ** 2) - np.sqrt(n[where])) / alpha
            z[where] = _stored(z[where] + grad - sigma * w_old, store)
            n[where] = _stored(n[where] + grad ** 2, store)
        on = slot[gate]
        h = g_v[gate] + vl2 * v[on]
        nv[on] = _stored(nv[on] + h * h, store)
        v[on] = v[on] - va * h / (vb + np.sqrt(nv[on]))
        opened.append(int(gate.sum()))
    return {"losses": losses, "keys": keys, "z": z, "n": n, "c": c, "v": v,
            "nv": nv, "w": weights(z, n, sizes), "near": near,
            "opened": opened}


def _rel(got, ref) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref)
                        / np.maximum(np.abs(ref), 1.0)))


def _errors(got: dict, ref: dict, sizes: dict, moved) -> dict:
    """``moved(state)``: which ids' embedding rows a side has updated."""
    gw, rw = np.asarray(got["w"], np.float64), ref["w"]
    band = np.abs(np.abs(ref["z"]) - sizes["l1"]) < THRESHOLD_BAND
    return {"loss_rel_err": max(abs(g - r) / abs(r) for g, r in
                                zip(got["losses"], ref["losses"])),
            "z_rel_err": _rel(got["z"], ref["z"]),
            "n_rel_err": _rel(got["n"], ref["n"]),
            "w_abs_err": float(np.max(np.abs(gw - rw))),
            "zero_set_mismatch": int(np.sum(((gw == 0) != (rw == 0)) & ~band)),
            "nv_rel_err": _rel(got["nv"], ref["nv"]),
            "v_abs_err": float(np.max(np.abs(
                np.asarray(got["v"], np.float64) - ref["v"]))),
            "count_mismatch": int(np.sum(np.asarray(got["c"], np.int64)
                                         != ref["c"])),
            "active_set_mismatch": int(np.sum(
                (moved(got) != moved(ref)) & ~ref["near"]))}


def sampled(state: dict, sample_ids, rows_of) -> dict:
    """``state`` of :func:`difacto_steps` at the bias and then at
    ``sample_ids`` (an id never touched reads zero state and its drawn
    row)."""
    ids = np.concatenate([[BIAS], np.asarray(sample_ids, np.int64)])
    at = np.minimum(np.searchsorted(state["keys"], ids), len(state["keys"]) - 1)
    found = state["keys"][at] == ids
    out = {k: np.where(found, state[k][at], 0) for k in ("z", "n", "w", "c")}
    out["near"] = found & state["near"][at]
    drawn = np.concatenate([np.zeros((1, state["v"].shape[1])),
                            rows_of(ids[1:])])
    out["v"] = np.where(found[:, None], state["v"][at], drawn)
    out["nv"] = np.where(found[:, None], state["nv"][at], 0.0)
    out["losses"], out["opened"] = state["losses"], state["opened"]
    return out


def global_batch(sizes: dict) -> int:
    """Rows of one step: every worker's minibatch together."""
    return int(sizes["batch_size"]) * int(sizes.get("workers", 1))


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


def _lookup(ids, rows):
    """``rows_of`` over a sorted list of ids and their rows."""
    ids, rows = np.asarray(ids, np.int64), np.asarray(rows, np.float64)

    def rows_of(wanted):
        at = np.searchsorted(ids, wanted)
        if np.any(ids[np.minimum(at, len(ids) - 1)] != wanted):
            raise ValueError("an id's drawn embedding row was not handed in")
        return rows[at]
    return rows_of


def _compared(got: dict, batches: list, sizes: dict, control: bool, rows_of,
              moved, names=None, start=None, at=None) -> tuple:
    def follow(store=None):
        state = difacto_steps(batches, sizes, rows_of, store=store,
                              start=start)
        return state if at is None else sampled(state, at, rows_of)
    ref = follow()
    if len(got["losses"]) != len(ref["losses"]):
        raise ValueError("the program took another number of steps")
    names = names or {k: k for k in LIVE}
    out = [{"name": names[k], "value": v}
           for k, v in _errors(got, ref, sizes, moved).items()]
    if control:
        import ml_dtypes
        out += [{"name": f"control.{names[k]}", "value": v} for k, v in
                _errors(follow(ml_dtypes.bfloat16), ref, sizes, moved).items()]
    return out, ref


def compare(got: dict, label, index, sample_ids, sizes: dict,
            control: bool = False, live: dict | None = None) -> list:
    """``got``: ``losses`` of the compared steps; ``z``, ``n``, ``w``, ``c``
    (``[1 + ids]``) and ``v``, ``nv`` (``[1 + ids, K]``, the bias's row
    zeros) after them at the bias and then at ``sample_ids``;
    ``drawn_ids``, ``drawn_rows``: the program's initial embedding rows at
    every id of the compared rows (sorted); ``untouched_changed``: what the
    generator counted of the ids no row names.  ``label`` and ``index`` are
    the compared steps' rows.

    ``live``: one step from the state the window left — its rows (``label``,
    ``index``), its ``loss``, and ``before`` and ``after``, dicts of ``w``,
    ``z``, ``n``, ``c`` (``[1 + keys]``) and ``v``, ``nv`` (``[1 + keys,
    K]``) at the bias and then at ``keys``, the sorted distinct ids."""
    batches = list(dense_batches(label, index, global_batch(sizes)))
    rows_of = _lookup(got["drawn_ids"], got["drawn_rows"])
    out, ref = _compared(
        got, batches, sizes, control, rows_of, at=sample_ids,
        moved=lambda s: np.any(np.asarray(s["nv"]) != 0, axis=1))
    crossed = bool(np.any(ref["c"][1:] > sizes["threshold"]))
    out.append({"name": "untouched_changed",
                "value": int(got["untouched_changed"])})
    unexercised = int(not crossed)
    if live is not None:
        before, after = live["before"], live["after"]
        keys = np.concatenate([[BIAS], np.asarray(live["keys"], np.int64)])
        held = np.asarray(before["nv"], np.float64)
        more, ref_live = _compared(
            dict(after, losses=[live["loss"]]),
            list(dense_batches(live["label"], live["index"],
                               len(live["label"]))),
            sizes, control, _lookup([], np.zeros((0, held.shape[1]))),
            names=LIVE, start=dict(before, keys=keys),
            moved=lambda s: np.any(np.asarray(s["nv"]) != held, axis=1))
        out += more
        opened = ref_live["opened"][0]
        unexercised += int(opened == 0) + int(opened == len(live["keys"]))
    out.append({"name": "gate_unexercised", "value": unexercised})
    return out
