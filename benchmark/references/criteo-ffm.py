"""Plain reference of the ``criteo-ffm`` configuration: the classic per-row
pairwise field-aware factorization machine,

    margin(x) = b + sum_i w[f_i] x_i
                  + sum_{i<j} <v[f_i, field_j], v[f_j, field_i]> x_i x_j

(Juan et al. 2016; not the field-grouped identity the program uses), logistic
loss averaged over the batch, plain autodiff, plain SGD — ``jax.numpy`` in
float32 on the dense ``[rows, entries]`` layout, in blocks of rows so that it
never holds more than the program did.  Imports nothing of the program and is
handed nothing it made: the initial table is drawn here from the seed by the
same published rule (``init_scale * normal(PRNGKey(seed))``, zeros for ``w``
and ``b``), the rows are the generator's own.

It follows the first steps the program took and compares

- ``loss_rel_err``      each step's loss;
- ``grad_norm_gap``     the first gradient as the optimizer got it, worked
                        out from the parameters after one step: the gap
                        between the program's norm and the reference's, by
                        the worst leaf, against the larger of that leaf's
                        reference norm and the median leaf's;
- ``delta_norm_gap``    the same for the parameters' change after all steps;
- ``delta_sample_diff`` the change of ``w`` and ``v`` at the features of the
                        sampled rows, element by element: norm of the
                        difference over the reference's norm, worst leaf.

The control runs the same steps with the table read, multiplied and summed in
bfloat16 (parameters and update stay float32) and is put in the program's
place.
"""
from __future__ import annotations

import numpy as np

BLOCK_ROWS = 1024


def _steps(label, index, sample_ids, sizes, seed: int, dtype) -> dict:
    import jax
    import jax.numpy as jnp
    F, A, K = sizes["num_features"], sizes["num_fields"], sizes["num_factors"]
    B, lr = sizes["batch_size"], sizes["learning_rate"]
    n = index.shape[1]
    fld = jnp.arange(n, dtype=jnp.int32) % A       # one entry a field, in order
    upper = jnp.triu(jnp.ones((n, n), dtype), k=1)

    def block_loss(params, idx, y):
        v = params["v"].astype(dtype)[idx]                   # [r, n, A, K]
        toward = jnp.take(v, fld, axis=2)                    # [r, i, j, K]
        pair = jnp.sum(toward * jnp.swapaxes(toward, 1, 2), axis=-1)
        margin = (params["b"] + jnp.sum(params["w"].astype(dtype)[idx], axis=1)
                  + jnp.sum(pair * upper, axis=(1, 2))).astype(jnp.float32)
        nll = (jnp.maximum(margin, 0) - margin * y
               + jnp.log1p(jnp.exp(-jnp.abs(margin))))
        return jnp.sum(nll) / B

    grad_block = jax.jit(jax.value_and_grad(block_loss))

    @jax.jit
    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    @jax.jit
    def sgd(p, g):
        return jax.tree.map(lambda x, d: x - lr * d, p, g)

    @jax.jit
    def moved(a, b):
        return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum((y - x) ** 2)), a, b)

    @jax.jit
    def sample_change(a, b, ids):
        return {k: b[k][ids] - a[k][ids] for k in ("w", "v")}

    with jax.default_matmul_precision("highest"):
        start = {"w": jnp.zeros(F, jnp.float32),
                 "v": sizes["init_scale"] * jax.random.normal(
                     jax.random.PRNGKey(seed), (F, A, K), jnp.float32),
                 "b": jnp.zeros((), jnp.float32)}
        params, out = start, {"losses": []}
        for t in range(len(label) // B):
            loss, grads = 0.0, None
            for r in range(t * B, (t + 1) * B, BLOCK_ROWS):
                end = min(r + BLOCK_ROWS, (t + 1) * B)
                idx = jnp.asarray(index[r:end])
                y = jnp.asarray(label[r:end], jnp.float32)
                bl, bg = grad_block(params, idx, y)
                loss += float(bl)
                grads = bg if grads is None else add(grads, bg)
            params = sgd(params, grads)
            out["losses"].append(loss)
            if t == 0:
                out["first_grad"] = jax.device_get(moved(start, params))
        out["change"] = jax.device_get(moved(start, params))
        out["sample_change"] = jax.device_get(
            sample_change(start, params, jnp.asarray(sample_ids)))
    return out


def _norm_gap(got: dict, ref: dict) -> float:
    floor = float(np.median([float(ref[k]) for k in ref]))
    return max(abs(float(got[k]) - float(ref[k]))
               / max(float(ref[k]), floor, 1e-30) for k in ref)


def _errors(got: dict, ref: dict) -> dict:
    sample = max(
        float(np.linalg.norm(np.asarray(got["sample_change"][k], np.float64)
                             - np.asarray(ref["sample_change"][k], np.float64))
              / max(np.linalg.norm(np.asarray(ref["sample_change"][k],
                                              np.float64)), 1e-30))
        for k in ref["sample_change"])
    return {"loss_rel_err": max(abs(g - r) / abs(r) for g, r in
                                zip(got["losses"], ref["losses"])),
            "grad_norm_gap": _norm_gap(got["first_grad"], ref["first_grad"]),
            "delta_norm_gap": _norm_gap(got["change"], ref["change"]),
            "delta_sample_diff": sample}


def compare(got: dict, label, index, sample_ids, sizes: dict, seed: int,
            control: bool = False) -> list:
    import jax.numpy as jnp
    ref = _steps(label, index, sample_ids, sizes, seed, jnp.float32)
    if len(got["losses"]) != len(ref["losses"]):
        raise ValueError("the program took another number of steps")
    out = [{"name": k, "value": v} for k, v in _errors(got, ref).items()]
    if control:
        low = _steps(label, index, sample_ids, sizes, seed, jnp.bfloat16)
        out += [{"name": f"control.{k}", "value": v}
                for k, v in _errors(low, ref).items()]
    return out
