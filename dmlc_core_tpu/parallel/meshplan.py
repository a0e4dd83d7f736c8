"""Mesh plan — one object owning topology, layout, and the reduction.

The reference scatters its scaling decisions across the tracker (TCP
tree+ring construction, tracker/dmlc_tracker/tracker.py:185-252) and
per-callsite allreduce calls.  Here the same decisions — how many hosts
× chips exist, how rows are laid out over them, and how a payload is
reduced — live in a single ``MeshPlan``:

* **Topology discovery**: hosts are distinct ``process_index`` values
  (the DMLC_* bootstrap maps workers onto processes); chips are the
  per-host local devices.  Multi-host topologies get a 2-D
  ``(host, chip)`` mesh; single-host gets the classic 1-D ``data`` mesh.
  ``build(hosts=)`` forces a synthetic host factor on a flat device set
  (how the virtual 8-device CPU mesh exercises the 2-D paths).
* **Reduction**: XLA's own ``psum`` / ``pmax`` / ``pmin`` / ``pmean`` over
  the plan's axes, which is topology-aware on ICI (a hand-built ring is
  rabit's answer to TCP): 0.117 of a 1,081 ms Airline round on four
  chips (PERF.md, PR 43).
* **Exchange and key layout** (the parameter server's half, PR 45): a table
  sharded on axis 0 over the plan's axes is range-partitioned by key as
  ps-lite's servers partition theirs, shard ``c`` of ``S`` owning rows
  ``[c F/S, (c+1) F/S)`` (:meth:`MeshPlan.rows_per_shard`,
  :meth:`MeshPlan.owner_of`); :meth:`MeshPlan.alltoall` carries what every
  shard has for every other (a minibatch's keys to their owners, the rows
  back, the gradients out) as XLA's ``all_to_all``;
  :meth:`MeshPlan.take_rows` reads such a table at ids every shard holds.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collective import _OPS, shard_map_compat
from .mesh import make_mesh


class MeshPlan:
    """Topology + layout + the reduction over them.

    Parameters
    ----------
    mesh:
        The jax Mesh to plan over.
    axes:
        Row-sharding axis names in major→minor order.  2-D plans are
        ``("host", "chip")``.  Defaults to all mesh axes.
    collective, overlap_chunks:
        Take one value each, ``"flat"`` and ``1``: the benchmark's
        generator passes them.  The ``ppermute`` ring (``"hier"``,
        ``"auto"``) and the chunked level loop (``overlap_chunks > 1``)
        were removed in PR 44, unmeasured on a chip; asking for them
        raises.
    """

    def __init__(self, mesh: Mesh, axes: Optional[Sequence[str]] = None,
                 collective: str = "flat", overlap_chunks: int = 1):
        axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(
                    f"plan axis {a!r} not in mesh axes {mesh.axis_names}")
        if not axes or len(axes) > 2:
            raise ValueError(f"MeshPlan wants 1 or 2 axes, got {axes!r}")
        if collective != "flat":
            raise ValueError(
                f"collective={collective!r}: the hierarchical ppermute ring "
                "and its 'auto' routing were removed in PR 44 (no chip cell "
                "ever ran them; XLA's psum is topology-aware on ICI): the "
                "one route is 'flat'")
        if int(overlap_chunks) != 1:
            raise ValueError(
                f"overlap_chunks={overlap_chunks!r}: the chunked level loop "
                "was removed in PR 44 (the reductions are 0.01% of a "
                "four-chip round: nothing to hide): the one value is 1")
        self.mesh = mesh
        self.axes = axes
        self.collective = collective
        self.overlap_chunks = 1
        # calls and bytes allreduce and alltoall have been traced with; what
        # counting() saw of them
        self._traced, self._census = [0, 0, 0, 0], {}
        platforms = {d.platform for d in np.asarray(mesh.devices).ravel()}
        self.fabric = "ici" if platforms == {"tpu"} else "host"

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def build(cls, devices=None, hosts: Optional[int] = None,
              **kwargs) -> "MeshPlan":
        """Discover topology and build the mesh.

        hosts × local devices come from the devices' ``process_index``
        (populated by the DMLC_* → jax.distributed bootstrap); a flat
        single-process device set stays 1-D unless ``hosts`` forces a
        synthetic host factor — the virtual-CPU stand-in for a
        multi-host pod.
        """
        devices = list(jax.devices() if devices is None else devices)
        n = len(devices)
        if hosts is None:
            hosts = len({d.process_index for d in devices})
        devices.sort(key=lambda d: (d.process_index, d.id))
        if hosts > 1:
            if n % hosts:
                raise ValueError(
                    f"{n} device(s) do not split over {hosts} host(s)")
            mesh = make_mesh((hosts, n // hosts), ("host", "chip"), devices)
            return cls(mesh, ("host", "chip"), **kwargs)
        return cls(make_mesh((n,), ("data",), devices), ("data",), **kwargs)

    # ------------------------------------------------------------------
    # layout
    # ------------------------------------------------------------------
    @property
    def num_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    @property
    def row_spec(self) -> P:
        """Leading (row) dim sharded over every plan axis, host-major."""
        return P(self.axes)

    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.row_spec)

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def _over(self):
        """The axis name a collective over the whole plan takes."""
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def shard_index(self) -> jax.Array:
        """This shard's place along the plan's axes, host-major: the ``c``
        of the rows ``row_spec`` gives it.  Call inside traced code."""
        at = 0
        for a in self.axes:
            at = at * self.mesh.shape[a] + jax.lax.axis_index(a)
        return at

    def rows_per_shard(self, length: int) -> int:
        """Rows of a table of ``length`` sharded on axis 0 that one shard
        holds: shard ``c`` owns keys ``[c * rows, (c + 1) * rows)``."""
        if length % self.num_shards:
            raise ValueError(f"a table of {length} rows does not split over "
                             f"{self.num_shards} shard(s)")
        return length // self.num_shards

    def owner_of(self, keys: jax.Array, length: int) -> jax.Array:
        """The shard that owns each of ``keys`` of a table of ``length``
        rows (ps-lite's range partition)."""
        return keys // self.rows_per_shard(length)

    def take_rows(self, table: jax.Array, ids: jax.Array) -> jax.Array:
        """``table[ids]`` for a table sharded on axis 0 over the plan and
        ids every shard holds: each shard reads the rows it owns, zeros
        elsewhere, and a sum brings them together (a row plus zeros: every
        bit but a ``-0.0``'s sign).  Call inside jit; an id past the table
        reads 0."""
        rows = self.rows_per_shard(table.shape[0])

        def local(shard, ids):
            at = ids - self.shard_index() * rows
            mine = (at >= 0) & (at < rows)
            got = shard[jnp.where(mine, at, 0)]
            got = jnp.where(
                mine.reshape(mine.shape + (1,) * (got.ndim - 1)), got, 0)
            return _OPS["sum"](got, self._over)

        return self.shard_map(local, (self.row_spec, P()), P())(table, ids)

    def shard_map(self, fn, in_specs, out_specs,
                  check_replication: bool = True):
        return shard_map_compat(fn, self.mesh, in_specs, out_specs,
                                check_replication=check_replication)

    def describe(self) -> dict:
        c = self.mesh.shape[self.axes[-1]]   # the chips of one host
        return {"devices": self.num_shards,
                "hosts": self.num_shards // c, "chips_per_host": c,
                "axes": list(self.axes), "fabric": self.fabric,
                "collective": self.collective,
                "overlap_chunks": self.overlap_chunks}

    # ------------------------------------------------------------------
    # the reduction (call inside plan.shard_map-traced code)
    # ------------------------------------------------------------------
    def allreduce(self, x: jax.Array, op: str = "sum") -> jax.Array:
        """All-reduce over the plan axes; call inside traced code.

        Runs when a program is TRACED, once a compile: :meth:`counting`
        counts what it notes once a call.
        """
        if op not in _OPS:
            raise ValueError(
                f"unknown allreduce op '{op}' (have {sorted(_OPS)})")
        # noted, not counted: this line runs once a compile, however often
        # the compiled reduction runs (counting())
        self._traced[0] += 1
        self._traced[1] += int(x.size) * x.dtype.itemsize
        # every plan-routed reduction under one scope: collective time is
        # read off a device trace by this name
        with jax.named_scope("mesh.allreduce"):
            return _OPS[op](x, self._over)

    def alltoall(self, x: jax.Array, noted: bool = True) -> jax.Array:
        """The exchange: ``x`` is ``[S, ...]`` on every shard, ``x[d]`` what
        this shard has for shard ``d``; returns ``[S, ...]`` whose row ``s``
        is what shard ``s`` had for this one (shards counted host-major, as
        :meth:`shard_index` counts them).  Call inside traced code; on a
        plan of one shard it is the identity.  Noted for :meth:`counting`
        as :meth:`allreduce` is, unless ``noted=False``: an exchange on a
        path that only some executions of its program take (one candidate
        capacity of several) is counted by its caller, who learns which
        ran."""
        if x.shape[0] != self.num_shards:
            raise ValueError(f"alltoall wants one row a shard "
                             f"({self.num_shards}), got {x.shape}")
        if noted:
            self._traced[2] += 1
            self._traced[3] += int(x.size) * x.dtype.itemsize
        # every plan-routed exchange under one scope, as the reductions are
        with jax.named_scope("mesh.alltoall"):
            return jax.lax.all_to_all(x, self._over, 0, 0)

    def counting(self, program: str, executions: int = 1, around=None):
        """Context manager around ``executions`` calls of ONE jitted program
        whose trace calls :meth:`allreduce` or :meth:`alltoall`: adds what
        they reduce to the counters ``mesh.allreduce_calls`` and
        ``mesh.collective_bytes`` and what they exchange to
        ``mesh.alltoall_calls`` and ``mesh.alltoall_bytes`` (the payload a
        collective is handed, as every chip holds it; not what moves over
        the wires: of an exchange's payload the row a chip keeps for itself
        never leaves it).

        Only the caller of a compiled program knows how often it runs.
        What one execution reduces is noted when a call inside the block
        traces the program, under the caller's name for it, ``program``,
        and counted for these calls and for every later block of that
        name, which finds the program compiled.  ``around``: a context
        manager to hold open around the block (a span)."""
        return _Counting(self, program, executions, around)


class _Counting:
    """:meth:`MeshPlan.counting`'s block."""

    def __init__(self, plan, program, executions, around):
        self.plan, self.program = plan, program
        self.executions, self.around = executions, around

    def __enter__(self):
        if self.around is not None:
            self.around.__enter__()
        self.mark = tuple(self.plan._traced)

    def __exit__(self, *exc):
        plan = self.plan
        seen = tuple(now - then for now, then in zip(plan._traced, self.mark))
        if any(seen):
            plan._census[self.program] = seen
        seen = plan._census.get(self.program, (0, 0, 0, 0))
        try:
            from .. import telemetry
            telemetry.counter_add("mesh.allreduce_calls",
                                  seen[0] * self.executions)
            telemetry.counter_add("mesh.collective_bytes",
                                  seen[1] * self.executions)
            telemetry.counter_add("mesh.alltoall_calls",
                                  seen[2] * self.executions)
            telemetry.counter_add("mesh.alltoall_bytes",
                                  seen[3] * self.executions)
        except Exception:
            pass
        if self.around is not None:
            return self.around.__exit__(*exc)
