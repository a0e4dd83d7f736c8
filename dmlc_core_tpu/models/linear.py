"""Sparse linear model (logistic / squared loss) — the end-to-end slice of
SURVEY.md §7: LibSVM shards → DeviceStagingIter → SGD with data-parallel
gradient psum; the Row::SDot analogue vectorized through csr_matvec.

Pure-functional: params is a pytree {"w": f32[dim], "b": f32[]}; all steps
are jittable; under a mesh, replicated params + data-sharded batches make
XLA insert the gradient all-reduce automatically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..data.staging import PaddedBatch
from ..ops.pallas_segment import check_force
from ..ops.sparse import csr_matvec, csr_row_sums
from .. import telemetry
from .common import TouchedRowsMixin


class SparseLinearModel(TouchedRowsMixin):
    """Logistic regression / linear regression over sparse batches.

    objective: "logistic" (labels in {0,1} or {-1,1}) or "squared".
    optimizer: None (plain minibatch SGD over the whole table) or
        ``common.FTRL(alpha, beta, l1, l2)``: per-coordinate FTRL-Proximal
        over the rows a batch names (``TouchedRowsMixin``); ``init`` then
        returns the state ``(z, n)`` beside ``w`` and ``b``.
    mesh: a ``parallel.MeshPlan`` (with an optimizer): ``(w, z, n)``
        sharded by key over the plan's chips, trained by
        ``TouchedRowsMixin._sharded_rows_step`` on a batch laid over them.
    """

    def __init__(self, num_features: int, objective: str = "logistic",
                 l2: float = 0.0, learning_rate: float = 0.1,
                 sdot_backend: str | None = None, optimizer=None,
                 mesh=None):
        if objective not in ("logistic", "squared"):
            raise ValueError(f"unknown objective '{objective}'")
        check_force(sdot_backend, "sdot_backend")
        self.num_features = num_features
        self.objective = objective
        self.l2 = l2
        self.learning_rate = learning_rate
        # Row::SDot reduction backend (ops.sparse force=): None/"xla" =
        # GSPMD-safe scatter-add; "pallas" = scatter-free kernel,
        # single-device TPU only (no pallas partitioning rule)
        self.sdot_backend = sdot_backend
        self._set_optimizer(optimizer, mesh)

    def init(self, seed: int = 0) -> dict:
        # to the tables' end, not their dispatch
        with telemetry.span("model.init", total="model.init_us"):
            return jax.block_until_ready(self.init_tables(self._fresh, seed))

    def _fresh(self, seed) -> dict:
        del seed  # linear model: zero init is canonical
        return {"w": self.table_zeros((self.num_features,), jnp.float32),
                "b": self.table_zeros((), jnp.float32, by_key=False)}

    # ---- pure functions (jit-friendly) --------------------------------------
    def margins(self, params: dict, batch: PaddedBatch) -> jax.Array:
        """Per-row scores w·x + b."""
        if self.mesh is not None:
            return self.margins_of_rows(self.rows_of_entries(
                params, ("w",), batch.index), params, batch)
        return csr_matvec(params["w"], batch.index, batch.value,
                          batch.row_ids(), batch.batch_size,
                          force=self.sdot_backend) + params["b"]

    def evaluate(self, params: dict, batches) -> dict:
        """Accuracy/loss over an iterable of batches (host-side reduce)."""
        total_w = 0.0
        total_loss = 0.0
        correct = 0.0
        for batch in batches:
            m = self.margins(params, batch)
            w = batch.weight
            total_w += float(jnp.sum(w))
            total_loss += float(self.loss(params, batch)) * float(jnp.sum(w))
            if self.objective == "logistic":
                y = jnp.where(batch.label > 0.5, 1.0, 0.0)
                pred = (m > 0).astype(jnp.float32)
                correct += float(jnp.sum((pred == y) * w))
        out = {"loss": total_loss / max(total_w, 1.0)}
        if self.objective == "logistic":
            out["accuracy"] = correct / max(total_w, 1.0)
        return out

    def margins_of_rows(self, rows: dict, dense: dict,
                        batch: PaddedBatch) -> jax.Array:
        """Per-row scores from the weights gathered an entry,
        ``rows["w"] = w[batch.index]`` (the touched-rows step)."""
        with jax.named_scope("linear.margins"):
            return csr_row_sums(rows["w"] * batch.value, batch.row_ids(),
                                batch.row_ptr) + dense["b"]
