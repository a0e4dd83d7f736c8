"""Shared loss pieces for the model families (one stable implementation,
used by linear / FM / GBDT alike)."""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
        rabit-allreduce path.

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
# state beside the parameters.

#: lanes of sorted distinct keys the step's visit of its tables may take,
#: and the batch's own lanes after these.  The step's cost does not follow
#: the batch alone: XLA's scatter passes over its whole operand (6.25 ms a
#: 2 GiB table on a v5e whatever the keys, three tables a visit), so the keys
#: are visited ONCE, and what follows their number is the lanes the gathers
#: and scatters carry (PERF.md section 5 has the step's time by distinct keys)
TOUCHED_ROWS_VISITS = (1 << 16, 1 << 17, 1 << 18, 1 << 19)
#: distinct keys ``_wide_rows_step`` reads, updates and writes at a time (a
#: loop of as many trips as the batch's keys need, one at Criteo's 50,800),
#: and the lanes of the windows it sums a row's gradients along inside a
#: key's run before the windows themselves (``ops.sparse.window_totals``)
WIDE_ROWS_CHUNK = 1 << 16
RUN_WINDOW = 16
#: distinct keys a worker may hand ONE owner in the sharded step's exchanges
#: (``_sharded_rows_step``), and the worker's own entry lanes after these: the
#: capacity is static, so a step whose fullest (worker, owner) pair passes a
#: candidate runs the next one, and the last one holds whatever a minibatch
#: names.  At Criteo Terabyte's draw over 2^28 buckets a worker's 16,384 rows
#: name 85,200 distinct keys and the fullest (worker, owner) pair of four
#: chips holds 21,600 of them
EXCHANGE_LANES = (1 << 15,)


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


@dataclasses.dataclass(frozen=True)
class AdaGrad:
    """Per-coordinate AdaGrad as DiFacto's servers keep an embedding row
    (Li et al., WSDM 2016, Algorithm 3; dmlc/wormhole ``learn/difacto``
    ``sgd_server_handle.h``): ``g += l2 v``, ``n += g^2``,
    ``v -= alpha g / (beta + sqrt(n))``.  A coordinate keeps ``n``."""
    alpha: float = 0.01
    beta: float = 1.0
    l2: float = 0.0

    def __post_init__(self):
        if self.alpha <= 0 or min(self.beta, self.l2) < 0:
            raise ValueError(f"AdaGrad needs alpha > 0 and beta, l2 >= 0, "
                             f"got {self}")

    def apply(self, v: jax.Array, n: jax.Array, g: jax.Array) -> tuple:
        """One update of coordinates holding ``(v, n)`` with gradient ``g``:
        the new ``(v, n)``."""
        g = g + self.l2 * v
        n_new = n + g * g
        return v - self.alpha * g / (self.beta + jnp.sqrt(n_new)), n_new


@dataclasses.dataclass(frozen=True)
class SGD:
    """Plain minibatch SGD, ``p -= learning_rate * g``, with ``g`` the
    gradient of the weighted MEAN loss (``SGDModelMixin._train_step``'s; the
    other rules take the minibatch's sum).  A coordinate keeps nothing, and
    one no live entry names has ``g = 0``: the rule is the dense step without
    a penalty, on the rows a batch names."""
    learning_rate: float = 0.05

    def apply(self, p: jax.Array, g: jax.Array) -> tuple:
        """One update of coordinates ``p`` with gradient ``g``: the new
        ``(p,)``."""
        return (p - self.learning_rate * g,)


#: where each rule's state lives in the parameters, its slots in the order
#: ``apply`` takes and returns them after the weight
_RULE_STATE = {FTRL: ("ftrl", ("z", "n")), AdaGrad: ("adagrad", ("n",)),
               SGD: ("sgd", ())}
#: what rides beside the parameters: the rules' state (training's alone: a
#: snapshot leaves it out) and the count table (which scoring's gate reads)
RULE_STATE_KEYS = tuple(held for held, slots in _RULE_STATE.values() if slots)
STATE_KEYS = RULE_STATE_KEYS + ("count",)


def _with_state(params: dict, rule, name: str) -> tuple:
    """Parameter ``name`` and its state under ``rule``: ``(weight,
    *state)``, the order ``apply`` returns them in."""
    held, slots = _RULE_STATE[type(rule)]
    return (params[name],) + tuple(params[held][slot][name] for slot in slots)


def _params_of(tables: dict, rules: dict) -> dict:
    """The parameters from each one's ``(weight, *state)``: the weights at
    the top, each rule's state where ``_RULE_STATE`` keeps it."""
    out = {k: t[0] for k, t in tables.items()}
    for k, t in tables.items():
        under, slots = _RULE_STATE[type(rules[k])]
        for slot, value in zip(slots, t[1:]):
            out.setdefault(under, {}).setdefault(slot, {})[k] = value
    return out


def _read_by(rule, table: tuple) -> tuple:
    """Which of a parameter's ``(weight, *state)`` the rule reads: FTRL's
    weight is the closed form of its state and is never read."""
    return table[1:] if isinstance(rule, FTRL) else table


def _apply(rule, held: tuple, g: jax.Array) -> tuple:
    """``rule.apply`` under the rule's own scope: the new ``(weight,
    *state)``."""
    if isinstance(rule, AdaGrad):
        with jax.named_scope("sgd.adagrad"):
            return rule.apply(*held, g)
    if isinstance(rule, SGD):
        with jax.named_scope("sgd.update"):
            return rule.apply(*held, g)
    with jax.named_scope("sgd.ftrl"):
        return rule.apply(*held, g)


def visit_distinct(touched, entries: int, carry, body):
    """ONE visit of the distinct keys, over the shortest run of lanes of
    ``TOUCHED_ROWS_VISITS`` (and the entry lanes after them) that holds all
    ``touched`` of them: ``body(lanes, trip, carry)`` in a loop of one trip
    or none a candidate (a ``cond`` would copy the tables into its branches;
    a loop's carry stays in place); ``trip`` is the loop's own index, 0."""
    sizes = [c for c in TOUCHED_ROWS_VISITS if c < entries] + [entries]
    for fewer, lanes in zip([0] + sizes, sizes):
        mine = (touched > fewer) & (touched <= lanes)
        carry = jax.lax.fori_loop(
            0, mine.astype(jnp.int32),
            lambda trip, c, lanes=lanes: body(lanes, trip, c), carry)
    return carry


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _zeros_on(shape: tuple, dtype, sharding) -> jax.Array:
    """Zeros made where ``sharding`` lays them: each shard on its own chip,
    so that a table no one chip holds never passes through one."""
    return jax.lax.with_sharding_constraint(jnp.zeros(shape, dtype), sharding)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _tables_on_plan(model, fresh, seed) -> dict:
    """``TouchedRowsMixin.init_tables`` under a plan: one program."""
    return model.init_optimizer(fresh(seed))


class TouchedRowsMixin(SGDModelMixin):
    """``train_step`` for a model that names an ``optimizer``: distinct keys
    of the batch, their rows gathered once a key and carried to the entries
    by sorts, the loss differentiated with respect to the rows an *entry*
    holds, one optimizer update a distinct key, rows scattered back in place
    into the donated tables.  No table-sized temporary and no table-sized
    gradient; a coordinate no live entry names is neither gathered nor set
    (the scatters' pass over their operand is XLA's, see
    ``TOUCHED_ROWS_VISITS``).  Without an optimizer the plain SGD step above
    runs.

    A model provides ``row_tables`` (the parameters that ``batch.index``
    addresses, ``[F]`` or ``[F, K]``; every other parameter is one more
    coordinate that every row holds) and ``margins_of_rows(rows, dense,
    batch)``, its margins from the rows an entry (``rows[k]`` is
    ``params[k][batch.index]`` on every live lane, 0 on a dead one).
    ``optimizer`` is one ``FTRL`` for a model
    of one table, or a rule a table: an ``FTRL`` for the first (the other
    parameters share it) and an ``AdaGrad`` for each of the rest; or one
    ``SGD`` for every parameter, which keeps no state: the parameters stay
    as ``init`` made them.

    A table whose rows have a shape of their own (``[F, A, K]``: the
    field-aware machine's) takes the same walk in ``_wide_rows_step``,
    which is laid out for rows that no fast memory holds an entry.

    ``gated_tables`` and ``count_threshold`` are DiFacto's gate: a gated
    table's row exists only for a key seen more than ``count_threshold``
    times and while l1 has not zeroed the key's weight in the first table.
    ``params["count"]`` then holds a key's occurrences, int32, and
    ``margins_of_rows`` takes a fourth argument, the entries whose gate is
    open.

    The rules' state rides beside the parameters (``params["ftrl"] = {"z":
    {...}, "n": {...}}``, ``params["adagrad"] = {"n": {...}}``, each shaped
    like the parameters under that rule); ``params[k]`` itself stays the
    weight, so ``predict``, checkpoints and the scoring server see what they
    saw.

    ``mesh`` (a ``parallel.MeshPlan``) shards every row table and its state
    by key over the plan's chips, each chip a worker and a server as
    ps-lite's nodes are: ``_sharded_rows_step``, a program of its own made
    of this step's pieces.
    """

    optimizer = None
    mesh = None
    row_tables = ("w",)
    gated_tables = ()
    count_threshold = None

    def _set_optimizer(self, optimizer, mesh=None) -> None:
        if mesh is not None:
            from ..parallel import MeshPlan
            if not isinstance(mesh, MeshPlan):
                raise TypeError("mesh= takes a parallel.MeshPlan, got "
                                f"{type(mesh).__name__}")
            if optimizer is None or isinstance(optimizer, SGD):
                raise ValueError(
                    "a plan shards the tables for the touched-rows step "
                    "under FTRL / AdaGrad; without an optimizer, or under "
                    "SGD (whose mean takes the global weight), there is no "
                    "sharded step")
            mesh.rows_per_shard(self.num_features)
        self.mesh = mesh
        names = self.row_tables
        fits = optimizer is None or isinstance(optimizer, SGD) or (
            len(names) == 1 if isinstance(optimizer, FTRL) else
            isinstance(optimizer, dict) and set(optimizer) == set(names)
            and isinstance(optimizer[names[0]], FTRL)
            and all(isinstance(optimizer[k], AdaGrad) for k in names[1:]))
        if not fits:
            raise ValueError(f"unknown optimizer {optimizer!r}")
        if optimizer is not None and self.l2 > 0.0:
            raise ValueError("with an optimizer the penalty is the "
                             "optimizer's (FTRL(l2=...)), not the model's")
        self.optimizer = optimizer
        # the counts of the steps in flight: device scalars
        self._touched = collections.deque()

    def rule_of(self, name: str):
        """The rule of table ``name``; of every other parameter, the rule
        of the first table."""
        if isinstance(self.optimizer, dict):
            return self.optimizer.get(name, self.optimizer[self.row_tables[0]])
        return self.optimizer

    def init_tables(self, fresh, seed: int) -> dict:
        """What ``init`` returns: ``fresh(seed)``, the model's bare
        parameters, with each rule's zero state.

        Under a plan ONE program makes them all, handed nothing but the
        seed: every chip then lays its shards out alike, in the program's
        order, whatever the first chip alone held a moment before (a key,
        a scalar).  Where a table lies moves what its reads a distinct key
        cost (the same gather 2.7 or 3.5 ms on two chips of one step), and
        in the sharded step every chip waits at each exchange for the
        slowest (PERF.md, PR 45)."""
        if self.mesh is None:
            return self.init_optimizer(fresh(seed))
        if not 0 <= seed < 1 << 32:
            raise ValueError("under a plan a seed is 0 .. 2**32 - 1, got "
                             f"{seed!r}")
        return _tables_on_plan(self, fresh, np.uint32(seed))

    def init_optimizer(self, params: dict) -> dict:
        """``params`` with each rule's zero state and, under a gate, the
        count table beside them (no optimizer: as given)."""
        if self.optimizer is None:
            return params
        out = dict(params)
        for name, p in params.items():
            held, slots = _RULE_STATE[type(self.rule_of(name))]
            for slot in slots:
                out.setdefault(held, {}).setdefault(slot, {})[name] = (
                    self.table_zeros(p.shape, p.dtype, name in self.row_tables))
        if self.count_threshold is not None:
            out["count"] = self.table_zeros((self.num_features,), jnp.int32)
        return out

    def table_zeros(self, shape: tuple, dtype, by_key: bool = True):
        """Zeros for a parameter or its state: under a plan a row table's
        are made shard by shard, each on the chip that owns those keys, and
        every other parameter's on every chip."""
        if self.mesh is None:
            return jnp.zeros(shape, dtype)
        return _zeros_on(tuple(shape), jnp.dtype(dtype), (
            self.mesh.data_sharding() if by_key
            else self.mesh.replicated_sharding()))

    def rows_of_entries(self, params: dict, names, index: jax.Array) -> dict:
        """``{name: params[name][index]}``, and ``params["count"][index]``
        if ``"count"`` is asked for: a gather a table, or under a plan
        ``MeshPlan.take_rows`` of tables sharded by key (scoring's read;
        the sharded step routes its keys instead)."""
        if self.mesh is None:
            return {k: params[k][index] for k in names}
        return {k: self.mesh.take_rows(params[k], index) for k in names}

    def lay_entries(self, batch):
        """The batch as ``_wide_rows_step`` walks it: anything that holds
        ``index``, ``value`` (0 on a dead lane), ``label`` and ``weight``;
        it is what ``margins_of_rows`` is handed there.  As staged, unless a
        model's margins want the entries in another order than the rows'."""
        return batch

    def _loss_and_slope(self, m: jax.Array, batch) -> tuple:
        """``(loss, cotangent)`` from the margins: the weighted mean loss
        of the batch, and what the rows' gradients are pulled from —
        ``d(loss_r)/d(margin_r)`` written out (``logistic_nll``'s own
        derivative is off by a half where a margin is exactly 0, as every
        margin of a first step is) times the rows' weights: the gradient of
        the SUM over the minibatch, which is FTRL's and DiFacto's
        convention; under ``SGD`` of the weighted MEAN, as
        ``_train_step``'s autodiff has it."""
        from ..ops.sparse import padded_row_mean
        mean = isinstance(self.optimizer, SGD)
        if self.objective == "logistic":
            per_row = logistic_nll(m, batch.label)
            if mean:
                # sigmoid(m) as a quotient of two vectors: a v5e takes
                # ``1 / x`` (``jax.nn.sigmoid`` is one) by an estimate that
                # reads 1e-6 high, and a gradient summed from such slopes
                # comes out 2e-6 long on every leaf, where the dense step's
                # and the plain reference's lie 1e-7 apart
                e = jnp.exp(-jnp.abs(m))
                slope = (jnp.where(m >= 0, 1.0, e) / (1.0 + e)
                         - jnp.where(batch.label > 0.5, 1., 0.))
            else:
                slope = jax.nn.sigmoid(m) - jnp.where(batch.label > 0.5, 1., 0.)
        else:
            per_row, slope = 0.5 * (m - batch.label) ** 2, m - batch.label
        loss = padded_row_mean(per_row, batch.weight)
        if mean:
            return loss, slope * (batch.weight * (
                1.0 / jnp.maximum(jnp.sum(batch.weight), 1.0)))
        return loss, slope * batch.weight

    def active(self, count: jax.Array, weight: jax.Array) -> jax.Array:
        """The gate from a key's count and its first table's weight, each
        gathered wherever the caller needs the gate (an entry, a key)."""
        return (count > self.count_threshold) & (weight != 0)

    def train_step(self, params: dict, batch) -> Tuple[dict, jax.Array]:
        """One step; returns (new_params, loss), the weighted mean loss of
        the batch before its update.  ``params`` is donated."""
        if self.optimizer is None:
            return super().train_step(params, batch)
        wide = any(params[k].ndim > 2 for k in self.row_tables)
        exchange = None
        if self.mesh is not None:
            if wide:
                raise ValueError("rows with a shape of their own ([F, A, K]) "
                                 "have no sharded step")
            # the plan counts the step's exchanges and reductions once a
            # call; what they carried is noted when the program is traced
            with self.mesh.counting("sgd.sharded_step",
                                    around=telemetry.span("sgd.step")):
                new_params, loss, counts, exchange = (
                    self._sharded_rows_step(params, batch))
        else:
            with telemetry.span("sgd.step"):
                new_params, loss, counts = (
                    self._wide_rows_step if wide else
                    self._touched_rows_step)(params, batch)
        self._touched.append((counts, exchange))
        self.flush_step_counters(wait=False)
        return new_params, loss

    def flush_step_counters(self, wait: bool = True) -> None:
        """Add the finished steps to the counters ``sgd.steps``,
        ``sgd.touched_rows``, ``sgd.scatter_tiles`` (tiles the in-place
        kernel wrote; 0 where XLA's scatter ran), ``sgd.spread_entries``
        (live entries whose rows reached them from the distinct keys
        through ``spread_by_key``) and, under a gate, ``sgd.active_rows``
        (distinct keys whose gate was open) and ``sgd.activated_rows``
        (those whose count crossed the threshold in the step).  Under a
        plan all of these are the GLOBAL step's, and beside them go
        ``sgd.owner_rows`` (distinct keys the fullest owner updated),
        ``sgd.exchange_lanes`` (the capacity a destination of the candidate
        that ran), ``sgd.exchange_overflow`` (steps that had to take a
        wider candidate than the first) and the step's three exchanges in
        ``mesh.alltoall_calls`` and ``mesh.alltoall_bytes`` (what a chip
        handed them at the capacity that ran; the reductions go through
        ``plan.counting``).
        ``wait=False`` (every ``train_step``) takes only the steps the
        device has finished and never waits for it."""
        while self._touched and (wait or self._touched[0][0][0].is_ready()):
            (touched, tiles, spread, *gate), exchange = self._touched.popleft()
            if exchange is not None:
                owner_rows, lanes, overflow = exchange
                telemetry.counter_add("mesh.alltoall_calls", 3)
                telemetry.counter_add("mesh.alltoall_bytes",
                                      self._exchange_bytes[int(lanes)])
                telemetry.counter_add("sgd.owner_rows", int(owner_rows))
                telemetry.counter_add("sgd.exchange_lanes", int(lanes))
                telemetry.counter_add("sgd.exchange_overflow", int(overflow))
            telemetry.counter_add("sgd.touched_rows", int(touched))
            telemetry.counter_add("sgd.scatter_tiles", int(tiles))
            telemetry.counter_add("sgd.spread_entries", int(spread))
            telemetry.counter_add("sgd.steps", 1)
            if gate:
                telemetry.counter_add("sgd.active_rows", int(gate[0]))
                telemetry.counter_add("sgd.activated_rows", int(gate[1]))

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _touched_rows_step(self, params: dict, batch) -> tuple:
        """Every table is read ONCE a step and once a distinct key: a first
        visit of the distinct keys gathers each table's row and its state,
        ``spread_by_key`` carries what the margins take an entry to the
        entries (bit for bit what ``params[k][index]`` holds there), and the
        second visit applies the rules to what the first read and scatters.

        Under a gate the step runs in DiFacto's order: a key's occurrences
        are added to ``params["count"]`` first; the gate is read off the
        counts and weights as the step then finds them, a distinct key; a
        gated table's row enters a margin and is updated only where the gate
        is open, and is written back bit for bit where it is shut."""
        from ..ops.pallas_rows import scatter_rows
        from ..ops.sparse import reduce_by_key, spread_by_key
        names, first = self.row_tables, self.row_tables[0]
        rules = {k: self.rule_of(k) for k in params if k not in STATE_KEYS}
        gate = self.count_threshold is not None
        dense = [k for k in rules if k not in names]
        # tables of one float a key ride the sorts beside the key; rows of K
        # floats are gathered by rank and summed in key order
        flat = [k for k in names if params[k].ndim == 1]
        wide = [k for k in names if params[k].ndim > 1]
        index, live = batch.index, batch.value != 0
        entries = index.shape[0]
        lane = jnp.arange(entries, dtype=jnp.int32)
        sorted_distinct = dict(unique_indices=True, indices_are_sorted=True)
        tiles = zero = jnp.zeros((), jnp.int32)
        gated = ()

        def padded(x):
            """A visit's lanes with zeros up to the entry lanes after them:
            one shape whichever candidate ran."""
            return jnp.concatenate([x, jnp.zeros(
                (entries - x.shape[0],) + x.shape[1:], x.dtype)])

        def lanes_of(x, lanes, trip):
            """The first ``lanes`` of what another visit ``padded``.
            ``trip`` is 0: with it what a visit computes from arrays its
            loop does not carry stays in its loop; without it XLA hoists
            that out of every candidate's loop and runs all five every step
            (the embedding gradients' gathers so: 46 ms on a v5e)."""
            return jax.lax.dynamic_slice_in_dim(x, trip, lanes)

        # the distinct keys, how the entries lie on them and, under a gate,
        # each key's occurrences
        with jax.named_scope("sgd.unique"):
            keys, seen, touched, runs = reduce_by_key(
                index, live, (live.astype(jnp.int32),) if gate else (),
                self.num_features, runs=True)

        if gate:
            def count_visit(lanes, _trip, carry):
                count, tiles, _, _ = carry
                with jax.named_scope("sgd.gather_rows"):
                    old = count.at[keys[:lanes]].get(
                        mode="fill", fill_value=0, **sorted_distinct)
                with jax.named_scope("sgd.count"):
                    new = old + seen[0][:lanes]
                    crossed = jnp.sum(
                        (lane[:lanes] < touched)
                        & (old <= self.count_threshold)
                        & (new > self.count_threshold), dtype=jnp.int32)
                with jax.named_scope("sgd.scatter_rows"):
                    (count,), wrote = scatter_rows(
                        (count,), keys[:lanes], (new,), touched)
                return count, tiles + wrote, crossed, padded(new)

            count, tiles, crossed, new_count = visit_distinct(
                touched, entries, (params["count"], tiles, zero,
                                   jnp.zeros(entries, jnp.int32)), count_visit)

        tables = {k: _with_state(params, rules[k], k) for k in names}

        def read(lanes, trip, _held):
            at = keys[:lanes] + trip
            with jax.named_scope("sgd.gather_rows"):
                return jax.tree.map(lambda t: padded(t.at[at].get(
                    mode="fill", fill_value=0, **sorted_distinct)), tables)

        # ``w`` is gathered, not taken as the closed form of ``(z, n)``: a
        # table restored without its state holds a weight that is not
        held = visit_distinct(touched, entries, jax.tree.map(
            lambda t: jnp.zeros((entries,) + t.shape[1:], t.dtype), tables),
            read)
        if gate:
            with jax.named_scope("sgd.count"):
                open_ = (lane < touched) & self.active(
                    new_count, held[first][0])
                opened = jnp.sum(open_, dtype=jnp.int32)
        with jax.named_scope("sgd.unique"):
            spread = spread_by_key(
                runs, tuple(held[k][0] for k in flat)
                + ((open_.astype(jnp.int32),) if gate else ())
                + ((lane,) if wide else ()))
        rows = dict(zip(flat, spread))
        if wide:
            # an entry takes its key's row by the key's rank out of the
            # distinct keys' rows (a dead entry's lane reads past them and
            # takes 0).  Out of a copy made HERE: what a loop hands on
            # lives in HBM, and this gather out of HBM costs what the
            # table's did (13.9 ms on a v5e); a fresh array XLA keeps in
            # fast memory, as it does the gradient rows (3 ms)
            with jax.named_scope("sgd.gather_rows"):
                rank = jnp.where(live, spread[-1], entries)
                rows.update({k: jnp.where(
                    (lane < touched)[:, None], held[k][0], 0).at[rank].get(
                        mode="fill", fill_value=0) for k in wide})
        if gate:
            with jax.named_scope("sgd.count"):
                gated = (live & (spread[len(flat)] > 0),)
        # the loss differentiated with respect to the rows an entry
        with jax.named_scope("sgd.loss"):
            m, pull = jax.vjp(
                lambda r, d: self.margins_of_rows(r, d, batch, *gated),
                rows, {k: params[k] for k in dense})
            loss, slope = self._loss_and_slope(m, batch)
            g_rows, g_dense = pull(slope)
        with jax.named_scope("sgd.unique"):
            _keys, sums, _touched, *wide_sums = reduce_by_key(
                index, live, tuple(g_rows[k] for k in flat),
                self.num_features, tuple(g_rows[k] for k in wide))

        def visit(lanes, trip, carry):
            tables, tiles = carry
            at, out = keys[:lanes], {}
            for name in names:
                with jax.named_scope("sgd.gather_rows"):
                    was = tuple(lanes_of(h, lanes, trip) for h in held[name])
                    if name in flat:
                        g = sums[flat.index(name)][:lanes]
                    else:
                        running, ends = wide_sums[0]
                        g = running[wide.index(name)][ends[:lanes] + trip]
                # the rule takes arrays that are made, whichever candidate
                # runs: fused into what brings them, its products and sums
                # would contract differently from one candidate to the next
                was, g = jax.lax.optimization_barrier((was, g))
                updated = _apply(rules[name], _read_by(rules[name], was), g)
                if name in self.gated_tables:
                    with jax.named_scope("sgd.count"):
                        on = lanes_of(open_, lanes, trip)
                        updated = tuple(
                            jnp.where(on.reshape((-1,) + (1,) * (
                                new.ndim - 1)), new, old)
                            for new, old in zip(updated, was))
                # in place either way: the tiles the keys name where the
                # table is long against ``lanes``, XLA's scatters elsewhere
                with jax.named_scope("sgd.scatter_rows"):
                    out[name], wrote = scatter_rows(
                        tables[name], at, updated, touched)
                tiles = tiles + wrote
            return out, tiles

        tables, tiles = visit_distinct(
            touched, entries, (tables, tiles), visit)
        tables.update({k: _apply(rules[k], _read_by(rules[k], _with_state(
            params, rules[k], k)), g_dense[k]) for k in dense})
        out = _params_of(tables, rules)
        counts = (touched, tiles, jnp.sum(live, dtype=jnp.int32))
        if gate:
            out["count"] = count
            counts += (opened, crossed)
        return out, loss, counts

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _sharded_rows_step(self, params: dict, batch) -> tuple:
        """``_touched_rows_step`` over tables sharded by key (``self.mesh``):
        ONE program under ``plan.shard_map`` in which every chip is a worker
        (its own rows of the global minibatch) and a server (the keys
        ``[c F/S, (c+1) F/S)`` of every table), as DiFacto's nodes are over
        ps-lite.  It gives what one ``_touched_rows_step`` gives on the
        global minibatch (counts first, then the gate, then the step), a
        key's gradient summed in another order:

        (a) worker: ``reduce_by_key`` over its own rows' entries;
        (b) its distinct keys and their occurrences to their owners
            (``plan.alltoall``): sorted keys lie owner by owner, so what goes
            to owner ``d`` is a slice of them, at a static capacity a
            destination, ``EXCHANGE_LANES`` (a step whose fullest pair
            passes a candidate runs the next; the last holds every lane:
            no key is ever dropped);
        (c) owner: the senders' lists merged into the shard's distinct keys,
            the counts added, every table read once a distinct key, the
            gate read there;
        (d) ``w``, the gated rows and the gate back through the inverse
            exchange and spread to the entries;
        (e) worker: margins, loss (a ``psum``), gradients summed a key;
        (f) gradient sums to the owners, summed a key over the senders, a
            rule a table, rows whose gate is shut written back as they
            were, ``scatter_rows`` into the local shard.

        The batch is the global one laid over the chips on its leading axes
        (``DeviceStagingIter(sharding=plan.data_sharding())``; ``row_ptr``
        and ``num_rows`` on every chip): chip ``c`` works the rows ``[c B/S,
        (c+1) B/S)``, whose entries must lie on its own lanes ``[c E/S,
        (c+1) E/S)`` — rows of a fixed number of entries staged with
        ``nnz_bucket = batch_size * entries``.  A live entry on another
        chip's lanes poisons the loss (NaN): it is never silently dropped.

        Returns ``(params, loss, counts, (owner_rows, lanes, overflow))``."""
        plan, names = self.mesh, self.row_tables
        rules = {k: self.rule_of(k) for k in params if k not in STATE_KEYS}
        by_key, everywhere = plan.row_spec, jax.sharding.PartitionSpec()

        def spec_of(name):
            return by_key if name in names else everywhere

        specs = {k: spec_of(k) for k in rules}
        for k in RULE_STATE_KEYS:
            if k in params:
                specs[k] = {slot: {n: spec_of(n) for n in held}
                            for slot, held in params[k].items()}
        if "count" in params:
            specs["count"] = by_key
        batch_specs = dataclasses.replace(
            batch, row_ptr=everywhere, num_rows=everywhere,
            **{f: by_key for f in ("label", "weight", "index", "value",
                                   "field", "qid")
               if getattr(batch, f) is not None})
        return plan.shard_map(
            functools.partial(self._sharded_rows_body, rules=rules),
            (specs, batch_specs),
            (specs, everywhere, everywhere, everywhere),
            check_replication=False)(params, batch)

    def _sharded_rows_body(self, params: dict, batch, rules: dict) -> tuple:
        """One chip's part of ``_sharded_rows_step``: ``params`` its shard
        of every table, ``batch`` its lanes of the global batch.  The
        exchanges and the owner's work run in a loop of one trip or none a
        candidate capacity (``visit_distinct``'s idiom: the tables stay in
        place in the loop's carry) on either side of the worker's margins,
        which are compiled once: what the owner's first half hands its
        second (the keys, the gate, each table's weight a key) rides in
        arrays of the widest candidate's lanes."""
        from ..ops.pallas_rows import scatter_rows
        from ..ops.sparse import reduce_by_key, spread_by_key
        plan, names, first = self.mesh, self.row_tables, self.row_tables[0]
        shards, bound = plan.num_shards, self.num_features
        owned = plan.rows_per_shard(bound)
        gate = self.count_threshold is not None
        dense = [k for k in rules if k not in names]
        flat = [k for k in names if params[k].ndim == 1]
        wide = [k for k in names if params[k].ndim > 1]
        widths = [params[k].shape[1] for k in wide]
        me = plan.shard_index()
        rows_here, entries = batch.label.shape[0], batch.index.shape[0]
        lane = jnp.arange(entries, dtype=jnp.int32)
        sorted_distinct = dict(mode="fill", unique_indices=True,
                               indices_are_sorted=True)
        zero = jnp.zeros((), jnp.int32)
        # this chip's rows of the global batch, as a batch of its own
        ptr = jax.lax.dynamic_slice_in_dim(
            batch.row_ptr, me * rows_here, rows_here + 1) - me * entries
        index, live = batch.index, batch.value != 0
        stray = jnp.sum(live & ((lane < ptr[0]) | (lane >= ptr[-1])),
                        dtype=jnp.int32)
        batch = dataclasses.replace(
            batch, row_ptr=jnp.clip(ptr, 0, entries),
            num_rows=jnp.clip(batch.num_rows - me * rows_here, 0, rows_here))
        tables = {k: _with_state(params, rules[k], k) for k in names}
        sizes = [c for c in EXCHANGE_LANES if c < entries] + [entries]
        widest = shards * sizes[-1]
        # what a chip hands the three exchanges at each capacity, 4 B an
        # element: the keys (and counts), what the margins take, the
        # gradient sums.  Only one candidate runs, so the step counts its
        # own exchanges once it knows which (``flush_step_counters``)
        self._exchange_bytes = {c: 4 * shards * c * (
            1 + gate + 2 * (len(flat) + sum(widths)) + gate) for c in sizes}

        # (a) the worker's distinct keys, how its entries lie on them and,
        # under a gate, each key's occurrences
        with jax.named_scope("sgd.unique"):
            keys, seen, _touched, runs = reduce_by_key(
                index, live, (live.astype(jnp.int32),) if gate else (),
                bound, runs=True)
            # sorted keys lie owner by owner: where each owner's begin
            starts = jnp.sum(
                keys[None, :] < (jnp.arange(shards + 1, dtype=jnp.int32)
                                 * owned)[:, None], axis=1, dtype=jnp.int32)
            most = plan.allreduce(jnp.max(starts[1:] - starts[:-1]), "max")

        def candidates(body, carry):
            """``body(capacity, trip, carry)`` for the one candidate that
            holds ``most``; ``trip`` is the loop's own index, 0."""
            for fewer, capacity in zip([-1] + sizes, sizes):
                here = (most > fewer) & (most <= capacity)
                carry = jax.lax.fori_loop(
                    0, here.astype(jnp.int32),
                    lambda trip, c, capacity=capacity: body(capacity, trip, c),
                    carry)
            return carry

        def lay(capacity, trip):
            """How the compact lanes go out to the owners and come back at
            ``capacity`` a destination.  ``trip`` is 0: with it what follows
            stays in its candidate's loop (``_touched_rows_step.lanes_of``)."""
            begins = starts + trip
            fits = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                    < (begins[1:] - begins[:-1])[:, None])

            def outbound(x, fill):
                """What the compact lanes ``x`` hold for each owner:
                ``[S, capacity]``."""
                x = jnp.concatenate([x, jnp.full(capacity, fill, x.dtype)])
                return jnp.where(fits, jnp.stack([
                    jax.lax.dynamic_slice_in_dim(x, begins[d], capacity)
                    for d in range(shards)]), fill)

            def inbound(x):
                """The inverse: ``[S, R, capacity]`` back from the owners
                onto the compact lanes, ``[R, entries]``; each owner's
                slice is written where it was taken, the next one over
                whatever lay past its keys."""
                out = jnp.zeros((x.shape[1], entries + capacity), x.dtype)
                x = jnp.where(fits[:, None, :], x, 0)
                for d in range(shards):
                    out = jax.lax.dynamic_update_slice(
                        out, x[d], (zero, begins[d]))
                return out[:, :entries]

            return fits, outbound, inbound

        def handed(x, fill=0):
            """The owner's lanes of one candidate up to the widest one's:
            one shape whichever ran."""
            return jnp.concatenate([x, jnp.full(
                (widest - x.shape[0],) + x.shape[1:], fill, x.dtype)])

        def pull(capacity, trip, carry):
            count = carry[0]
            held_lanes = shards * capacity
            # a candidate past the first hardly ever runs: its passes along
            # the runs are a loop, not 22 fusions each
            looped = capacity != sizes[0]
            owner_lane = jnp.arange(held_lanes, dtype=jnp.int32)
            _fits, outbound, inbound = lay(capacity, trip)
            # (b) keys and occurrences to their owners
            with jax.named_scope("sgd.unique"):
                sent = jnp.stack(
                    [outbound(keys, bound)]
                    + ([outbound(seen[0], 0)] if gate else []), axis=1)
            got = plan.alltoall(sent, noted=False)
            # (c) the owner's distinct keys, the senders' counts added
            with jax.named_scope("sgd.owner_merge"):
                theirs = got[:, 0].reshape(held_lanes)
                mine, added, holds, sender_runs = reduce_by_key(
                    theirs, theirs < bound,
                    (got[:, 1].reshape(held_lanes),) if gate else (),
                    bound, runs=True, looped=looped)
                # rows of the local shard: the spare ids stay past it
                at = mine - me * owned
            tiles = crossed = opened = zero
            if gate:
                with jax.named_scope("sgd.gather_rows"):
                    old = count.at[at].get(fill_value=0, **sorted_distinct)
                with jax.named_scope("sgd.count"):
                    new_count = old + added[0]
                    crossed = jnp.sum(
                        (owner_lane < holds)
                        & (old <= self.count_threshold)
                        & (new_count > self.count_threshold), dtype=jnp.int32)
                with jax.named_scope("sgd.scatter_rows"):
                    (count,), tiles = scatter_rows(
                        (count,), at, (new_count,), holds)
            # each table's weight a distinct key (``w`` is gathered, not
            # taken as the closed form of ``(z, n)``: a table restored
            # without its state holds a weight that is not)
            with jax.named_scope("sgd.gather_rows"):
                weights = {k: tables[k][0].at[at].get(
                    fill_value=0, **sorted_distinct) for k in names}
            open_ = owner_lane < holds
            if gate:
                with jax.named_scope("sgd.count"):
                    open_ = open_ & self.active(new_count, weights[first])
                    opened = jnp.sum(open_, dtype=jnp.int32)
            # (d) what the margins take, back to the senders' lanes
            with jax.named_scope("sgd.owner_merge"):
                back = spread_by_key(
                    sender_runs, tuple(weights[k] for k in flat)
                    + ((open_.astype(jnp.int32),) if gate else ())
                    + ((owner_lane,) if wide else ()), looped=looped)
                pulled = [b.astype(jnp.float32)[:, None]
                          for b in back[:len(flat) + gate]]
                if wide:
                    rank = jnp.where(theirs < bound, back[-1], held_lanes)
                    pulled += [jnp.where(open_[:, None], weights[k], 0).at[
                        rank].get(mode="fill", fill_value=0) for k in wide]
                # keys on the minor axis: a row of K floats there would be
                # padded to a tile's 128
                pulled = jnp.concatenate(pulled, axis=1).reshape(
                    shards, capacity, -1).transpose(0, 2, 1)
            pulled = inbound(plan.alltoall(pulled, noted=False))
            return (count, pulled, handed(theirs, bound), handed(mine, bound),
                    handed(open_), {k: handed(w) for k, w in weights.items()},
                    jnp.stack([holds, tiles, opened, crossed]))

        rows_to_pull = len(flat) + gate + sum(widths)
        (count, pulled, theirs_, mine_, open_, weights_,
         owner_counts) = candidates(pull, (
             params.get("count"),
             jnp.zeros((rows_to_pull, entries), jnp.float32),
             jnp.zeros(widest, jnp.int32), jnp.zeros(widest, jnp.int32),
             jnp.zeros(widest, bool),
             {k: jnp.zeros((widest,) + tables[k][0].shape[1:],
                           tables[k][0].dtype) for k in names},
             jnp.zeros(4, jnp.int32)))

        # ... and to the entries
        with jax.named_scope("sgd.unique"):
            spread = spread_by_key(
                runs, tuple(pulled[i] for i in range(len(flat)))
                + ((pulled[len(flat)].astype(jnp.int32),) if gate else ())
                + ((lane,) if wide else ()))
        rows = dict(zip(flat, spread))
        if wide:
            with jax.named_scope("sgd.gather_rows"):
                rank = jnp.where(live, spread[-1], entries)
                cut = len(flat) + gate
                for k, width in zip(wide, widths):
                    rows[k] = pulled[cut:cut + width].T.at[rank].get(
                        mode="fill", fill_value=0)
                    cut += width
        gated = ()
        if gate:
            with jax.named_scope("sgd.count"):
                gated = (live & (spread[len(flat)] > 0),)
        # (e) the loss differentiated with respect to the rows an entry
        with jax.named_scope("sgd.loss"):
            m, pull_back = jax.vjp(
                lambda r, d: self.margins_of_rows(r, d, batch, *gated),
                rows, {k: params[k] for k in dense})
            loss, slope = self._loss_and_slope(m, batch)
            g_rows, g_dense = pull_back(slope)
            # the mean's two halves, for the sum over the workers
            weight = jnp.sum(batch.weight)
            loss = loss * jnp.maximum(weight, 1.0)
        with jax.named_scope("sgd.unique"):
            _keys, sums, _touched, *wide_sums = reduce_by_key(
                index, live, tuple(g_rows[k] for k in flat), bound,
                tuple(g_rows[k] for k in wide))

        def push(capacity, trip, carry):
            tables, tiles = carry
            held_lanes = shards * capacity
            fits, outbound, _inbound = lay(capacity, trip)

            def lanes_of(x):
                return jax.lax.dynamic_slice_in_dim(x, trip, held_lanes)

            # (f) the gradient sums to the owners
            with jax.named_scope("sgd.unique"):
                pushed = [outbound(g, 0)[:, None, :] for g in sums]
                if wide:
                    running, ends = wide_sums[0]
                    ends = outbound(ends, entries)
                    pushed += [jnp.where(
                        fits[:, :, None], r.at[ends].get(
                            mode="fill", fill_value=0), 0).transpose(0, 2, 1)
                               for r in running]
                pushed = jnp.concatenate(pushed, axis=1)
            pushed = plan.alltoall(pushed, noted=False)
            theirs, at = lanes_of(theirs_), lanes_of(mine_) - me * owned
            holds, on = owner_counts[0], lanes_of(open_)
            with jax.named_scope("sgd.owner_merge"):
                # a key's sum over its senders, on the owner's compact lanes
                cut = len(flat)
                _mine, flat_sums, _holds, *row_sums = reduce_by_key(
                    theirs, theirs < bound,
                    tuple(pushed[:, i].reshape(held_lanes)
                          for i in range(cut)), bound,
                    tuple(pushed[:, cut + sum(widths[:j]):
                                 cut + sum(widths[:j + 1])].transpose(
                                     0, 2, 1).reshape(held_lanes, width)
                          for j, width in enumerate(widths)),
                    looped=capacity != sizes[0])
            out = {}
            for name in names:
                with jax.named_scope("sgd.gather_rows"):
                    # the rule's state a distinct key, beside the weight
                    # the owner's first half read
                    was = (lanes_of(weights_[name]),) + tuple(
                        t.at[at].get(fill_value=0, **sorted_distinct)
                        for t in tables[name][1:])
                    if name in flat:
                        g = flat_sums[flat.index(name)]
                    else:
                        running, ends = row_sums[0]
                        g = running[wide.index(name)].at[ends].get(
                            mode="fill", fill_value=0)
                was, g = jax.lax.optimization_barrier((was, g))
                updated = _apply(rules[name], _read_by(rules[name], was), g)
                if name in self.gated_tables:
                    with jax.named_scope("sgd.count"):
                        updated = tuple(
                            jnp.where(on.reshape((-1,) + (1,) * (
                                new.ndim - 1)), new, old)
                            for new, old in zip(updated, was))
                with jax.named_scope("sgd.scatter_rows"):
                    out[name], wrote = scatter_rows(
                        tables[name], at, updated, holds)
                tiles = tiles + wrote
            return out, tiles

        tables, tiles = candidates(push, (tables, owner_counts[1]))
        # every other parameter lives on every chip and takes the sum of the
        # workers' gradients
        total = plan.allreduce(jnp.concatenate(
            [g_dense[k].reshape(-1) for k in dense]
            + [jnp.stack([loss, weight])]))
        cut = 0
        for k in dense:
            g = total[cut:cut + params[k].size].reshape(params[k].shape)
            cut += params[k].size
            tables[k] = _apply(rules[k], _read_by(rules[k], _with_state(
                params, rules[k], k)), g)
        holds, _tiles, opened, crossed = owner_counts
        counts = plan.allreduce(jnp.stack(
            [holds, tiles, jnp.sum(live, dtype=jnp.int32), opened, crossed,
             stray]))
        # a live entry that lay on another chip's lanes poisons the loss
        loss = jnp.where(counts[5] > 0, jnp.nan,
                         total[-2] / jnp.maximum(total[-1], 1.0))
        out = _params_of(tables, rules)
        if gate:
            out["count"] = count
        ran = zero + sum((most > c).astype(jnp.int32) for c in sizes[:-1])
        lanes = jnp.asarray(sizes, jnp.int32)[ran]
        return (out, loss, tuple(counts[i] for i in range(5 if gate else 3)),
                (plan.allreduce(holds, "max"), lanes,
                 (ran > 0).astype(jnp.int32)))

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _wide_rows_step(self, params: dict, batch) -> tuple:
        """``_touched_rows_step``'s walk for tables whose rows have a shape
        of their own (``[F, A, K]``; a row is its ``A * K`` floats of a 2-D
        array wherever it is off its table) and are so wide that nothing an
        entry holds of them stays in fast memory (156 floats at Criteo's
        field-aware shapes: 409 MB an array).  The same steps — the distinct
        keys, every table read once a key, rows carried to the entries by
        rank, the loss differentiated with respect to the rows an entry, the
        gradients summed along the keys' runs, one rule a key, rows scattered
        in place — laid out for such rows and for a program that is fetched
        in a second (a sort of the entry lanes is 2-3 MB of it):

        - five sorts of the entry lanes, not eleven: the model's own order
          (``lay_entries``), the keys with their lanes, the distinct keys
          with the lanes their runs start on, each entry's rank brought back
          (the rank a running count of run starts), and the windows' lanes.
          Nothing is gathered one float an entry lane (5 ms each on a v5e,
          where a sort of the lanes takes 0.8);
        - every table's row lies side by side in ONE array, ``w``'s float
          beside the 156 of ``v``: one gather by rank takes it to the
          entries, one by the keys' sort brings its gradient back;
        - the keys are visited ``WIDE_ROWS_CHUNK`` at a time in ONE loop body
          of as many trips as they need (a trip reads the chunk's rows, hands
          them to its entries out of its own lanes, which fast memory holds,
          and keeps them for the update), where ``visit_distinct`` compiles a
          body a candidate size;
        - the gradient rows are summed at two levels
          (``ops.sparse.window_totals``): four passes over every entry lane,
          then the windows of a chunk's runs.

        No gate: ``count_threshold`` is for ``_touched_rows_step``."""
        from ..ops.pallas_rows import scatter_rows
        from ..ops.sparse import run_sums, run_windows, window_totals
        if self.count_threshold is not None:
            raise ValueError("a count gate runs on _touched_rows_step")
        batch = self.lay_entries(batch)
        names = self.row_tables
        rules = {k: self.rule_of(k) for k in params if k not in STATE_KEYS}
        dense = [k for k in rules if k not in names]
        index, live = batch.index, batch.value != 0
        entries, bound = index.shape[0], self.num_features
        chunk = min(WIDE_ROWS_CHUNK, entries)
        padded = -(-entries // chunk) * chunk
        windows = min(entries, chunk + entries // RUN_WINDOW)
        lane = jnp.arange(entries, dtype=jnp.int32)
        sorted_distinct = dict(mode="fill", unique_indices=True,
                               indices_are_sorted=True)
        tables = {k: _with_state(params, rules[k], k) for k in names}

        def row_shape(t):
            return (math.prod(t.shape[1:]),) * (t.ndim > 1)

        with jax.named_scope("sgd.unique"):
            sk, order = jax.lax.sort((jnp.where(live, index, bound), lane),
                                     num_keys=1, is_stable=False)
            alive = sk < bound
            start = alive & jnp.concatenate(
                [jnp.ones(1, bool), sk[1:] != sk[:-1]])
            touched = jnp.sum(start, dtype=jnp.int32)
            spread = jnp.sum(alive, dtype=jnp.int32)
            # a sorted lane's key's rank among the distinct keys
            run = jnp.cumsum(start.astype(jnp.int32)) - 1
            # the distinct keys, ascending, with the lane each one's run
            # starts on; ids past the table after them, ascending too
            keys, first = jax.lax.sort(
                (jnp.where(start, sk, bound + lane), lane), num_keys=1,
                is_stable=False)
            keys = jnp.concatenate([keys, bound + entries + jnp.arange(
                padded - entries, dtype=jnp.int32)])
            first = jnp.concatenate([
                jnp.where(lane < touched, first, spread),
                jnp.full(padded + 1 - entries, spread, jnp.int32)])
            # each entry's rank on its own lane (a dead entry's: past every
            # chunk)
            _, rank = jax.lax.sort((order, jnp.where(alive, run, padded)),
                                   num_keys=1, is_stable=False)

        # every table's row side by side: ONE array goes to the entries and
        # one comes back (``w``'s float rides beside the 156 of ``v``)
        floats = [math.prod(params[k].shape[1:]) for k in names]
        cuts = [sum(floats[:i]) for i in range(1, len(names))]

        def unpack(packed):
            return {k: part.reshape(part.shape[:1] + row_shape(params[k]))
                    for k, part in zip(names, jnp.split(packed, cuts, axis=1))}

        def read(c, carry):
            held, rows = carry
            with jax.named_scope("sgd.gather_rows"):
                at = jax.lax.dynamic_slice_in_dim(keys, c * chunk, chunk)
                got = {k: tuple(
                    t.at[at].get(fill_value=0, **sorted_distinct).reshape(
                        (chunk,) + row_shape(t)) for t in tables[k])
                       for k in names}
                held = jax.tree.map(
                    lambda h, g: jax.lax.dynamic_update_slice_in_dim(
                        h, g, c * chunk, 0), held, got)
                # out of the chunk's own lanes: a fresh array that fast
                # memory holds (6 ms for 655,360 rows of 156 floats on a
                # v5e; 101 out of an array of as many lanes as entries)
                local = rank - c * chunk
                mine = (local >= 0) & (local < chunk)
                taken = jnp.concatenate(
                    [got[k][0].reshape(chunk, -1) for k in names],
                    axis=1).at[jnp.where(mine, local, chunk)].get(
                        mode="fill", fill_value=0)
            return held, jnp.where(mine[:, None], taken, rows)

        chunks = (touched + chunk - 1) // chunk
        held, rows = jax.lax.fori_loop(0, chunks, read, (
            {k: tuple(jnp.zeros((padded,) + row_shape(t), t.dtype)
                      for t in tables[k]) for k in names},
            jnp.zeros((entries, sum(floats)), params[names[0]].dtype)))

        with jax.named_scope("sgd.loss"):
            m, pull = jax.vjp(
                lambda r, d: self.margins_of_rows(unpack(r), d, batch),
                rows, {k: params[k] for k in dense})
            loss, slope = self._loss_and_slope(m, batch)
            g_rows, g_dense = pull(slope)

        with jax.named_scope("sgd.unique"):
            ids, anchors = run_windows(sk, alive, RUN_WINDOW)
            sums = run_sums(ids, g_rows[order], below=RUN_WINDOW)

        def update(c, carry):
            tables, tiles = carry
            at = jax.lax.dynamic_slice_in_dim(keys, c * chunk, chunk)
            with jax.named_scope("sgd.unique"):
                totals = unpack(window_totals(
                    sums, sk, anchors, first[c * chunk],
                    first[(c + 1) * chunk], windows, chunk, bound)[0])
            out = {}
            for name in names:
                with jax.named_scope("sgd.gather_rows"):
                    was = tuple(jax.lax.dynamic_slice_in_dim(
                        h, c * chunk, chunk) for h in held[name])
                updated = _apply(rules[name], _read_by(rules[name], was),
                                 totals[name])
                with jax.named_scope("sgd.scatter_rows"):
                    out[name], wrote = scatter_rows(
                        tables[name], at, tuple(
                            u.reshape((chunk,) + params[name].shape[1:])
                            for u in updated),
                        jnp.clip(touched - c * chunk, 0, chunk))
                tiles = tiles + wrote
            return out, tiles

        tables, tiles = jax.lax.fori_loop(
            0, chunks, update, (tables, jnp.zeros((), jnp.int32)))
        tables.update({k: _apply(rules[k], _read_by(rules[k], _with_state(
            params, rules[k], k)), g_dense[k]) for k in dense})
        out = _params_of(tables, rules)
        return out, loss, (touched, tiles, spread)
