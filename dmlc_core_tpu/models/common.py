"""Shared loss pieces for the model families (one stable implementation,
used by linear / FM / GBDT alike)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import telemetry


def count_predict_retrace() -> None:
    """Bump ``models.predict_retrace`` as a TRACE-TIME side effect.

    Call this from inside a jitted predict body: the Python statement runs
    only while jax traces the function (once per new input geometry), never
    on cached-executable calls — so the counter is an exact census of
    predict recompiles.  Steady-state serving must hold it at zero; see
    doc/serving.md.
    """
    try:
        telemetry.counter_add("models.predict_retrace", 1)
    except Exception:  # counting must never break tracing
        pass


def logistic_nll(margin: jax.Array, label: jax.Array) -> jax.Array:
    """Per-row binary-cross-entropy from margins, overflow-stable.

    Accepts labels in {0,1} or {-1,1} (anything > 0.5 is positive).
    """
    y = jnp.where(label > 0.5, 1.0, 0.0)
    return (jnp.maximum(margin, 0) - margin * y
            + jnp.log1p(jnp.exp(-jnp.abs(margin))))


class SGDModelMixin:
    """loss / predict / train_step shared by the margins-based families
    (linear, FM, field-aware FM) — ONE implementation of the objective
    dispatch, weighted padding-inert mean, l2 penalty, and jitted SGD
    step, so the three models cannot drift apart.

    Subclasses provide ``margins(params, batch)`` plus attributes
    ``objective`` ("logistic"/"squared"), ``l2``, ``learning_rate``, and
    may override ``_l2_terms(params)`` (default: just ``params["w"]``)
    to widen the penalty set.
    """

    #: parallel.MeshPlan (or None): owns device placement and row layout
    #: for the data-parallel path.  Set through the model ctor's
    #: ``mesh_plan=`` (legacy ``(mesh, axis)`` tuples adapt).
    mesh_plan = None

    def _set_mesh_plan(self, mesh_plan) -> None:
        from ..parallel.meshplan import MeshPlan
        self.mesh_plan = MeshPlan.from_spec(mesh_plan)

    def place_params(self, params: dict) -> dict:
        """Replicate params over the plan's mesh — the layout under
        which ``train_step``'s gradient reduction lowers to the psum
        over the plan axes (the rabit-allreduce path).  No plan: pass
        through."""
        if self.mesh_plan is None:
            return params
        return jax.device_put(params, self.mesh_plan.replicated_sharding())

    def batch_sharding(self):
        """Sharding for staged batches under the plan: rows over the
        plan axes, host-major (None without a plan)."""
        return (None if self.mesh_plan is None
                else self.mesh_plan.data_sharding())

    def grad_allreduce(self, grads: dict, op: str = "sum") -> dict:
        """Reduce a grad pytree through the plan's collective strategy —
        for custom shard_map/pmap training loops that compute per-shard
        grads themselves (``train_step`` under GSPMD doesn't need it:
        the compiler inserts the psum).  Call inside traced code."""
        if self.mesh_plan is None:
            return grads
        return jax.tree.map(lambda g: self.mesh_plan.allreduce(g, op),
                            grads)

    def _l2_terms(self, params: dict) -> tuple:
        return (params["w"],)

    def loss(self, params: dict, batch) -> jax.Array:
        from ..ops.sparse import padded_row_mean
        # the model's own scopes (``ffm.gather`` ...) nest inside this one;
        # under ``value_and_grad`` the backward ops keep the forward path,
        # as ``transpose(jvp(sgd.loss))/...``
        with jax.named_scope("sgd.loss"):
            m = self.margins(params, batch)
            if self.objective == "logistic":
                per_row = logistic_nll(m, batch.label)  # {-1,1} or {0,1}
            else:
                per_row = 0.5 * (m - batch.label) ** 2
            data_loss = padded_row_mean(per_row, batch.weight)
            if self.l2 > 0.0:
                data_loss = data_loss + 0.5 * self.l2 * sum(
                    jnp.sum(t ** 2) for t in self._l2_terms(params))
            return data_loss

    def predict(self, params: dict, batch) -> jax.Array:
        m = self.margins(params, batch)
        return jax.nn.sigmoid(m) if self.objective == "logistic" else m

    @functools.partial(jax.jit, static_argnums=0)
    def _predict_padded(self, params: dict, batch) -> jax.Array:
        count_predict_retrace()
        return self.predict(params, batch)

    def predict_bucketed(self, params: dict, batch,
                         row_bucket=None, nnz_bucket=None) -> jax.Array:
        """Geometry-stable predict: pad the batch up to its pow-2
        (rows, nnz) bucket, score under ONE jit cache entry per bucket,
        slice back to the real rows.  An ad-hoc request stream then costs
        O(log(size range)) compiles total instead of one per distinct
        geometry; ``models.predict_retrace`` counts the traces that do
        happen.  Real-row outputs are bit-identical to ``predict`` (pad
        rows have weight 0 / value-0 lanes, inert in the margins)."""
        from ..data.staging import pad_batch_to_bucket
        padded = pad_batch_to_bucket(batch, row_bucket, nnz_bucket)
        return self._predict_padded(params, padded)[:batch.batch_size]

    def train_step(self, params: dict, batch) -> Tuple[dict, jax.Array]:
        """One SGD step; returns (new_params, loss).  ``params`` is donated.

        Under jit with replicated params and a data-sharded batch, the
        grad reduction lowers to a psum over the mesh — the
        rabit-allreduce path.  With a ``mesh_plan`` set, that layout is
        exactly ``place_params`` + ``batch_sharding`` — the plan owns
        placement; GSPMD still owns the reduction (explicit routes use
        ``grad_allreduce``).

        The host's part of a step (the dispatch of the jitted
        ``_train_step``, or the wait for a full device queue) is the span
        ``sgd.step``.
        """
        with telemetry.span("sgd.step"):
            return self._train_step(params, batch)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _train_step(self, params: dict, batch) -> Tuple[dict, jax.Array]:
        loss, grads = jax.value_and_grad(self.loss)(params, batch)
        with jax.named_scope("sgd.update"):
            new_params = jax.tree.map(
                lambda p, g: p - self.learning_rate * g, params, grads)
        return new_params, loss
