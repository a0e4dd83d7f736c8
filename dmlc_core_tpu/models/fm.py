"""Factorization Machine — the model family the libfm parser feeds.

score(x) = b + w·x + ½ Σ_k [(Σ_i v_ik x_i)² − Σ_i v_ik² x_i²]

The second-order term is two sparse×dense products into [batch, K] (MXU-side
work once K is wide), so the whole step jits to gathers + segment-sums + a
couple of dense reductions.

With an ``optimizer`` it trains as dmlc/wormhole's DiFacto does (Li et al.,
WSDM 2016): FTRL-Proximal on ``w`` and the bias, AdaGrad on the embedding
rows ``v``, over the rows a batch names (``common.TouchedRowsMixin``), and a
key's embedding row exists only once the key has been seen more than
``threshold`` times and while l1 has not zeroed its weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..data.staging import PaddedBatch
from ..ops.pallas_segment import check_force
from ..ops.sparse import (csr_matmul, csr_matvec, csr_row_sumsq_matmul,
                          csr_row_sums)
from .. import telemetry
from .common import TouchedRowsMixin


class FactorizationMachine(TouchedRowsMixin):
    """optimizer: None (plain minibatch SGD over the whole table) or a rule
        a table, ``{"w": common.FTRL(...), "v": common.AdaGrad(...)}``: the
        touched-rows step; ``init`` then returns each rule's state and the
        count table beside ``w``, ``v`` and ``b``.
    threshold: the occurrences a key must have passed before its embedding
        row enters a margin and is updated (counted over the live entries
        delivered, the step's own included); an optimizer needs one, and a
        scorer rebuilt from a snapshot takes it to read the gate off the
        ``count`` table that rides with the weights.  The row is also off
        while the key's ``w`` is 0: the paper's memory-adaptive constraint
        (wormhole's ``l1_shrk``), which couples it to FTRL's l1.
    mesh: a ``parallel.MeshPlan`` (with an optimizer): ``w``, ``v``, the
        count and the rules' state sharded by key over the plan's chips,
        each made on its own chip by ``init``; ``train_step`` then runs
        ``TouchedRowsMixin._sharded_rows_step`` on a batch laid over the
        same chips, and ``predict`` reads the shards under the gate."""

    row_tables = ("w", "v")
    gated_tables = ("v",)

    def __init__(self, num_features: int, num_factors: int = 16,
                 objective: str = "logistic", l2: float = 0.0,
                 learning_rate: float = 0.05, init_scale: float = 0.01,
                 sdot_backend: str | None = None, optimizer=None,
                 threshold: int | None = None, mesh=None):
        if objective not in ("logistic", "squared"):
            raise ValueError(f"unknown objective '{objective}'")
        check_force(sdot_backend, "sdot_backend")
        self.num_features = num_features
        self.num_factors = num_factors
        self.objective = objective
        self.l2 = l2
        self.learning_rate = learning_rate
        self.init_scale = init_scale
        # reduction backend for the three Row::SDot ops (ops.sparse force=):
        # None/"xla" = scatter-add (GSPMD-partitionable — required for
        # sharded batches); "pallas" = the scatter-free kernel, a
        # SINGLE-device TPU knob (pallas_call has no partitioning rule)
        self.sdot_backend = sdot_backend
        if (threshold is None and optimizer is not None) or (threshold or 0) < 0:
            raise ValueError("an optimizer's embedding rows are gated by a "
                             f"count threshold >= 0, got {threshold!r}")
        self.count_threshold = threshold
        self._set_optimizer(optimizer, mesh)

    @functools.partial(jax.jit, static_argnums=0)
    def _draw(self, key: jax.Array) -> jax.Array:
        # one program: drawn eagerly, the bits and the unscaled normals of a
        # table of 4.3 GB are two tables more for a moment
        drawn = self.init_scale * jax.random.normal(
            key, (self.num_features, self.num_factors), jnp.float32)
        if self.mesh is None:
            return drawn
        # each chip draws the rows it owns (the counter-based generator
        # splits by element): the same rows as without a plan
        return jax.lax.with_sharding_constraint(
            drawn, self.mesh.data_sharding())

    def init(self, seed: int = 0) -> dict:
        # to the tables' end, not their dispatch
        with telemetry.span("model.init", total="model.init_us"):
            return jax.block_until_ready(self.init_tables(self._fresh, seed))

    def _fresh(self, seed) -> dict:
        return {
            "w": self.table_zeros((self.num_features,), jnp.float32),
            "v": self._draw(jax.random.PRNGKey(seed)),
            "b": self.table_zeros((), jnp.float32, by_key=False),
        }

    def margins(self, params: dict, batch: PaddedBatch) -> jax.Array:
        if "count" in params:
            # a gated model scores what its training saw: the same rows
            # through the same gate and the same sums
            rows = self.rows_of_entries(
                params, self.row_tables + ("count",), batch.index)
            on = (batch.value != 0) & self.active(rows.pop("count"),
                                                  rows["w"])
            return self.margins_of_rows(rows, params, batch, on)
        B = batch.batch_size
        rid = batch.row_ids()  # derived on device; CSE'd across the three uses
        fb = self.sdot_backend
        linear = csr_matvec(params["w"], batch.index, batch.value, rid, B,
                            force=fb)
        vx = csr_matmul(params["v"], batch.index, batch.value, rid, B,
                        force=fb)  # [B,K]
        v2x2 = csr_row_sumsq_matmul(params["v"], batch.index, batch.value,
                                    rid, B, force=fb)  # [B,K]
        second = 0.5 * jnp.sum(vx ** 2 - v2x2, axis=-1)
        return linear + second + params["b"]

    def margins_of_rows(self, rows: dict, dense: dict, batch: PaddedBatch,
                        on: jax.Array) -> jax.Array:
        """Per-row scores from ``w`` and ``v`` gathered an entry (the
        touched-rows step); ``on``: the entries whose embedding row is
        switched on."""
        rid, row_ptr = batch.row_ids(), batch.row_ptr
        with jax.named_scope("linear.margins"):
            linear = csr_row_sums(rows["w"] * batch.value, rid, row_ptr)
        with jax.named_scope("fm.margins"):
            vx = rows["v"] * jnp.where(on, batch.value, 0.0)[:, None]
            pooled = csr_row_sums(vx, rid, row_ptr)             # [B, K]
            squares = csr_row_sums(jnp.sum(vx * vx, axis=1), rid, row_ptr)
            second = 0.5 * (jnp.sum(pooled * pooled, axis=1) - squares)
        return linear + second + dense["b"]

    def _l2_terms(self, params: dict) -> tuple:
        return (params["w"], params["v"])
