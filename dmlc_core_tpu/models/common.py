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


# ---- the touched-rows step --------------------------------------------------
# A step that reads and writes only the rows its batch names, with optimizer
# state beside the parameters.  Everything here stays BELOW ``_train_step``,
# late imports included: a program's compile-cache key holds its source
# lines, and the plain SGD step keeps the lines it had (PERF.md section 6).
import collections  # noqa: E402
import dataclasses  # noqa: E402

#: lanes of sorted distinct keys the step's visit of its tables may take,
#: and the batch's own lanes after these.  The step's cost does not follow
#: the batch alone: XLA's scatter passes over its whole operand (6.25 ms a
#: 2 GiB table on a v5e whatever the keys, three tables a visit), so the keys
#: are visited ONCE, and what follows their number is the lanes the gathers
#: and scatters carry (PERF.md section 5 has the step's time by distinct keys)
TOUCHED_ROWS_VISITS = (1 << 16, 1 << 17, 1 << 18, 1 << 19)


@dataclasses.dataclass(frozen=True)
class FTRL:
    """Per-coordinate FTRL-Proximal: Algorithm 1 of McMahan et al., "Ad
    Click Prediction: a View from the Trenches", KDD 2013.  A coordinate
    keeps ``(z, n)``; its weight is their closed form."""
    alpha: float = 0.1
    beta: float = 1.0
    l1: float = 1.0
    l2: float = 0.0

    def __post_init__(self):
        if self.alpha <= 0 or min(self.beta, self.l1, self.l2) < 0:
            raise ValueError(f"FTRL needs alpha > 0 and beta, l1, l2 >= 0, "
                             f"got {self}")

    def weights(self, z: jax.Array, n: jax.Array) -> jax.Array:
        shrunk = -(z - jnp.sign(z) * self.l1) / (
            (self.beta + jnp.sqrt(n)) / self.alpha + self.l2)
        return jnp.where(jnp.abs(z) <= self.l1, 0.0, shrunk)

    def apply(self, z: jax.Array, n: jax.Array, g: jax.Array) -> tuple:
        """One update of coordinates holding ``(z, n)`` with gradient ``g``:
        the new ``(w, z, n)``."""
        n_new = n + g * g
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / self.alpha
        z_new = z + g - sigma * self.weights(z, n)
        return self.weights(z_new, n_new), z_new, n_new


class TouchedRowsMixin(SGDModelMixin):
    """``train_step`` for a model that names an ``optimizer``: distinct keys
    of the batch, their rows gathered, the loss differentiated with respect
    to the *gathered* rows, one optimizer update a distinct key, rows
    scattered back in place into the donated tables.  No table-sized
    temporary and no table-sized gradient; a coordinate no live entry names
    is neither gathered nor set (the scatters' pass over their operand is
    XLA's, see ``TOUCHED_ROWS_VISITS``).  Without an optimizer the plain SGD
    step above runs.

    A model provides ``row_tables`` (the parameters that ``batch.index``
    addresses, one float a key today: a table of rows ``[F, ...]`` would
    carry a column a trailing element through the same sorts; every other
    parameter is one more coordinate that every row holds) and
    ``margins_of_rows(rows, dense, batch)``, its margins from the rows
    gathered an entry (``rows[k]`` is ``params[k][batch.index]``).

    ``params["ftrl"]`` holds the state, ``{"z": {...}, "n": {...}}`` shaped
    like the parameters; ``params[k]`` itself stays the weight, so
    ``predict``, checkpoints and the scoring server see what they saw.
    """

    optimizer = None
    row_tables = ("w",)

    def _set_optimizer(self, optimizer) -> None:
        if optimizer is not None and not isinstance(optimizer, FTRL):
            raise ValueError(f"unknown optimizer {optimizer!r}")
        if optimizer is not None and self.l2 > 0.0:
            raise ValueError("with an optimizer the penalty is the "
                             "optimizer's (FTRL(l2=...)), not the model's")
        self.optimizer = optimizer
        # (distinct keys, tiles written) of the steps in flight: two device
        # scalars a step
        self._touched = collections.deque()

    def init_optimizer(self, params: dict) -> dict:
        """``params`` with the optimizer's zero state beside them."""
        if self.optimizer is None:
            return params
        zeros = functools.partial(jax.tree.map, jnp.zeros_like)
        return dict(params, ftrl={"z": zeros(params), "n": zeros(params)})

    def train_step(self, params: dict, batch) -> Tuple[dict, jax.Array]:
        """One step; returns (new_params, loss), the weighted mean loss of
        the batch before its update.  ``params`` is donated."""
        if self.optimizer is None:
            return super().train_step(params, batch)
        with telemetry.span("sgd.step"):
            new_params, loss, counts = self._touched_rows_step(params, batch)
        self._touched.append(counts)
        self.flush_step_counters(wait=False)
        return new_params, loss

    def flush_step_counters(self, wait: bool = True) -> None:
        """Add the finished steps to the counters ``sgd.steps``,
        ``sgd.touched_rows`` and ``sgd.scatter_tiles`` (tiles the in-place
        kernel wrote; 0 where XLA's scatter ran).  ``wait=False`` (every
        ``train_step``) takes only the steps the device has finished and
        never waits for it."""
        while self._touched and (wait or self._touched[0][0].is_ready()):
            touched, tiles = self._touched.popleft()
            telemetry.counter_add("sgd.touched_rows", int(touched))
            telemetry.counter_add("sgd.scatter_tiles", int(tiles))
            telemetry.counter_add("sgd.steps", 1)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _touched_rows_step(self, params: dict, batch) -> tuple:
        from ..ops.pallas_rows import scatter_rows
        from ..ops.sparse import padded_row_mean, reduce_by_key
        opt, names = self.optimizer, self.row_tables
        state = params["ftrl"]
        dense = {k: v for k, v in params.items()
                 if k not in names and k != "ftrl"}
        with jax.named_scope("sgd.gather_rows"):
            rows = {k: params[k][batch.index] for k in names}

        # the loss differentiated with respect to the gathered rows: the
        # gradient of the SUM over the minibatch, d(loss_r)/d(margin_r)
        # written out (``logistic_nll``'s own derivative is off by a half
        # where a margin is exactly 0, as every margin of a first step is)
        with jax.named_scope("sgd.loss"):
            m, pull = jax.vjp(
                lambda r, d: self.margins_of_rows(r, d, batch), rows, dense)
            if self.objective == "logistic":
                per_row = logistic_nll(m, batch.label)
                slope = jax.nn.sigmoid(m) - jnp.where(batch.label > 0.5, 1., 0.)
            else:
                per_row, slope = 0.5 * (m - batch.label) ** 2, m - batch.label
            loss = padded_row_mean(per_row, batch.weight)
            g_rows, g_dense = pull(slope * batch.weight)
        with jax.named_scope("sgd.unique"):
            entries = batch.index.shape[0]
            keys, sums, touched = reduce_by_key(
                batch.index, batch.value != 0,
                tuple(g_rows[k] for k in names), self.num_features)
        # the distinct keys lie first and ascending: ONE visit of the tables,
        # over the shortest run of lanes that holds them all.  Each candidate
        # is a loop of one trip or none (a ``cond`` would copy the tables
        # into its branches; a loop's carry stays in place)
        sizes = [c for c in TOUCHED_ROWS_VISITS if c < entries] + [entries]
        sorted_distinct = dict(unique_indices=True, indices_are_sorted=True)
        tables = {k: (params[k], state["z"][k], state["n"][k]) for k in names}
        tiles = jnp.zeros((), jnp.int32)
        for fewer, lanes in zip([0] + sizes, sizes):
            def visit(_, carry, lanes=lanes):
                tables, tiles = carry
                out = {}
                for name, column in zip(names, sums):
                    with jax.named_scope("sgd.gather_rows"):
                        z, n = (t.at[keys[:lanes]].get(
                            mode="fill", fill_value=0.0, **sorted_distinct)
                            for t in tables[name][1:])
                    with jax.named_scope("sgd.ftrl"):
                        updated = opt.apply(z, n, column[:lanes])
                    # in place either way: the tiles the keys name where the
                    # table is long against ``lanes``, XLA's scatters elsewhere
                    with jax.named_scope("sgd.scatter_rows"):
                        out[name], wrote = scatter_rows(
                            tables[name], keys[:lanes], updated, touched)
                    tiles = tiles + wrote
                return out, tiles

            mine = (touched > fewer) & (touched <= lanes)
            tables, tiles = jax.lax.fori_loop(0, mine.astype(jnp.int32), visit,
                                              (tables, tiles))
        with jax.named_scope("sgd.ftrl"):
            tables.update({k: opt.apply(state["z"][k], state["n"][k],
                                        g_dense[k]) for k in dense})
        w, z, n = ({k: t[i] for k, t in tables.items()} for i in range(3))
        return dict(w, ftrl={"z": z, "n": n}), loss, (touched, tiles)
