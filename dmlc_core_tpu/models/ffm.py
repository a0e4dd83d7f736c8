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

so the whole interaction term is ONE gather ([nnz, fields, K] factor
rows), ONE segment-sum keyed by (row, source-field), and ONE einsum —
static shapes, O(nnz · fields · K) work, padding entries (value 0)
inert by construction.  Factors live as v[num_features, num_fields, K].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..data.staging import PaddedBatch
from ..ops.pallas_segment import check_force
from ..ops.sparse import csr_matvec
from .common import SGDModelMixin


class FieldAwareFactorizationMachine(SGDModelMixin):
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

    def init(self, seed: int = 0) -> dict:
        key = jax.random.PRNGKey(seed)
        return {
            "w": jnp.zeros(self.num_features, jnp.float32),
            "v": self.init_scale * jax.random.normal(
                key, (self.num_features, self.num_fields, self.num_factors),
                jnp.float32),
            "b": jnp.zeros((), jnp.float32),
        }

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

    def _l2_terms(self, params: dict) -> tuple:
        return (params["w"], params["v"])
