"""Field-aware Factorization Machine — the consumer of the libfm
parser's field lane (reference src/data/libfm_parser.h parses
"label field:idx:val" triples; `DeviceStagingIter(with_field=True)`
stages the field ids to HBM, and this model is what they are FOR).

score(x) = b + w·x + ½ Σ_{i≠j} <v[f_i, fl_j], v[f_j, fl_i]> x_i x_j

where fl_i is entry i's field.  The classic formulation is a per-row
O(nnz²) pairwise loop — hostile to XLA (dynamic row extents, scalar
loops).  This implementation uses the field-grouped identity instead:

    S[r, a, b, :] = Σ_{k in row r, fl_k = a} x_k · v[f_k, b, :]
    Σ_{i≠j} <v[f_i, fl_j], v[f_j, fl_i]> x_i x_j
        = Σ_{a,b} <S[r, a, b], S[r, b, a]>  −  Σ_k x_k²·|v[f_k, fl_k]|²

— static shapes, O(nnz · fields · K) work, padding entries (value 0) inert
by construction.  Factors live as v[num_features, num_fields, K].

Scoring (``margins``) gathers each entry's ``[fields, K]`` block from the
table and sums S by segment.  Training without a penalty (``l2 == 0``) runs
``common.TouchedRowsMixin._wide_rows_step`` under the rule ``common.SGD``:
plain SGD moves no row the batch does not name, so the table is read and
written a DISTINCT key and no gradient is shaped like it.  That step lays the
entries on the lanes by (field, row) (``lay_entries``), so that S is the rows
the entries hold, as they lie, and the exchange of ``a`` and ``b`` a
permutation of whole blocks of rows (``margins_of_rows``).  With a penalty
every row moves a step, and the dense ``_train_step`` runs.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.staging import PaddedBatch
from ..ops.pallas_segment import check_force
from ..ops.sparse import csr_matvec, slot_sums
from .. import telemetry
from .common import SGD, TouchedRowsMixin


class FieldLanes(NamedTuple):
    """A batch's entries on the lanes by (field, row): what
    ``lay_entries`` makes of a ``PaddedBatch`` and ``margins_of_rows``
    reads.  ``slot`` is ``field * batch_size + row``, ascending, and
    ``num_fields * batch_size`` on the dead lanes (``value == 0``) after
    the live ones."""
    label: jax.Array
    weight: jax.Array
    index: jax.Array
    value: jax.Array
    slot: jax.Array


class FieldAwareFactorizationMachine(TouchedRowsMixin):
    row_tables = ("w", "v")

    def __init__(self, num_features: int, num_fields: int,
                 num_factors: int = 4, objective: str = "logistic",
                 l2: float = 0.0, learning_rate: float = 0.05,
                 init_scale: float = 0.01,
                 sdot_backend: str | None = None):
        if objective not in ("logistic", "squared"):
            raise ValueError(f"unknown objective '{objective}'")
        if num_fields < 1:
            raise ValueError("num_fields must be >= 1")
        check_force(sdot_backend, "sdot_backend")
        self.num_features = num_features
        self.num_fields = num_fields
        self.num_factors = num_factors
        self.objective = objective
        self.l2 = l2
        self.learning_rate = learning_rate
        self.init_scale = init_scale
        self.sdot_backend = sdot_backend
        # read off the model: without a penalty the gradient is zero off the
        # batch's rows and the touched-rows step is the same mathematics
        self._set_optimizer(SGD(learning_rate) if l2 == 0.0 else None)

    def init(self, seed: int = 0) -> dict:
        # to the tables' end, not their dispatch
        with telemetry.span("model.init", total="model.init_us"):
            key = jax.random.PRNGKey(seed)
            return jax.block_until_ready({
                "w": jnp.zeros(self.num_features, jnp.float32),
                "v": self.init_scale * jax.random.normal(
                    key,
                    (self.num_features, self.num_fields, self.num_factors),
                    jnp.float32),
                "b": jnp.zeros((), jnp.float32),
            })

    def margins(self, params: dict, batch: PaddedBatch) -> jax.Array:
        if batch.field is None:
            raise ValueError(
                "FFM needs field ids: stage with "
                "DeviceStagingIter(..., with_field=True) (libfm format)")
        B = batch.batch_size
        A = self.num_fields
        rid = batch.row_ids()
        idx, val = batch.index, batch.value
        # out-of-range field ids clamp (padding lanes carry value 0, so
        # their clamped target contributes nothing anyway)
        fld = jnp.clip(batch.field, 0, A - 1)

        with jax.named_scope("ffm.linear"):
            linear = csr_matvec(params["w"], idx, val, rid, B,
                                force=self.sdot_backend)
        with jax.named_scope("ffm.gather"):
            # [nnz, A, K]: entry k's factor rows toward EVERY target field
            ve = params["v"][idx] * val[:, None, None]
        with jax.named_scope("ffm.reduce"):
            # accumulate by (row, source field) -> S[r, a, b, :]
            S = jax.ops.segment_sum(
                ve, rid * A + fld, num_segments=B * A
            ).reshape(B, A, A, self.num_factors)
            cross = jnp.einsum("rabk,rbak->r", S, S)
        with jax.named_scope("ffm.diag"):
            # self-pair diagonal (i == j): x_k^2 * |v[f_k, fl_k]|^2
            v_self = params["v"][idx, fld]                       # [nnz, K]
            diag = jax.ops.segment_sum(
                (val ** 2) * jnp.sum(v_self ** 2, axis=-1), rid,
                num_segments=B)
        return linear + 0.5 * (cross - diag) + params["b"]

    def lay_entries(self, batch: PaddedBatch) -> FieldLanes:
        """The entries sorted by (field, row), the dead lanes last: one
        sort of the entry lanes.  A batch with one entry a field in every
        row then holds slot ``q`` on lane ``q``."""
        if batch.field is None:
            raise ValueError(
                "FFM needs field ids: stage with "
                "DeviceStagingIter(..., with_field=True) (libfm format)")
        A, B = self.num_fields, batch.batch_size
        fld = jnp.clip(batch.field, 0, A - 1)
        rid = batch.row_ids()
        with jax.named_scope("sgd.unique"):
            slot, index, value = jax.lax.sort(
                (jnp.where(batch.value != 0, fld * B + rid, A * B),
                 batch.index, batch.value), num_keys=1, is_stable=False)
        return FieldLanes(batch.label, batch.weight, index, value, slot)

    def margins_of_rows(self, rows: dict, dense: dict,
                        lanes: FieldLanes) -> jax.Array:
        """Per-row scores from ``w`` and ``v`` as the entries hold them
        (the touched-rows step), the entries laid by ``lay_entries``.

        Everything is summed on the grid of slots ``[fields, rows]``: where
        every slot holds one entry (lane ``q`` is slot ``q``: a batch of one
        entry a field) the entries ARE the grid; any other batch (a field
        twice in a row, a field absent) is summed onto it along the runs of
        equal slots, one row read a slot at its run's end.  ``S[a, r, b]``
        against ``S[b, r, a]`` is then a product of whole ``[K, rows]``
        blocks, and a row's sums are sums over the grid's first axis."""
        A, K, B = self.num_fields, self.num_factors, lanes.label.shape[0]
        slots, x, slot = A * B, lanes.value, lanes.slot
        fits = slot.shape[0] >= slots

        def onto_slots(per_lane: tuple) -> tuple:
            def summed(per_lane):
                ptr = jnp.searchsorted(
                    slot, jnp.arange(slots + 1, dtype=slot.dtype)
                ).astype(jnp.int32)
                # side by side, one walk along the runs for all three
                wide = [c.reshape(c.shape[0], -1) for c in per_lane]
                sums = slot_sums(jnp.concatenate(wide, axis=1), slot, ptr)
                cuts = np.cumsum([c.shape[1] for c in wide])[:-1]
                return tuple(c.reshape((slots,) + like.shape[1:]) for c, like
                             in zip(jnp.split(sums, cuts, axis=1), per_lane))
            if not fits:
                return summed(per_lane)
            as_laid = (jnp.all(slot[:slots] == jnp.arange(
                slots, dtype=slot.dtype)) & jnp.all(slot[slots:] == slots))
            return jax.lax.cond(
                as_laid, lambda per_lane: tuple(c[:slots] for c in per_lane),
                summed, per_lane)

        with jax.named_scope("ffm.linear"):
            wx = rows["w"] * x
        with jax.named_scope("ffm.diag"):
            # entry k's factor rows toward every field, ``[n, A * K]``, and
            # toward its own (by select: the K floats at its own field)
            ve = rows["v"] * x[:, None]
            fld = jnp.minimum(slot // B, A - 1)
            sq = jnp.sum(jnp.where(
                fld[:, None] == jnp.arange(A * K) // K, ve * ve, 0), axis=1)
        with jax.named_scope("ffm.reduce"):
            S, sq, wx = onto_slots((ve, sq, wx))
            # T[b, k, a, r] = S[a, r, b, k]: rows on the lanes, so that
            # S[b, r, a, k] is T with its first and third axes exchanged
            T = S.T.reshape(A, K, A, B)
            cross = jnp.sum(T * T.transpose(2, 1, 0, 3), axis=(0, 1, 2))
        with jax.named_scope("ffm.diag"):
            diag = jnp.sum(sq.reshape(A, B), axis=0)
        with jax.named_scope("ffm.linear"):
            linear = jnp.sum(wx.reshape(A, B), axis=0)
        return linear + 0.5 * (cross - diag) + dense["b"]

    def _l2_terms(self, params: dict) -> tuple:
        return (params["w"], params["v"])
