"""Mesh plan — one object owning topology, layout, and collective choice.

The reference scatters its scaling decisions across the tracker (TCP
tree+ring construction, tracker/dmlc_tracker/tracker.py:185-252) and
per-callsite allreduce calls.  Here the same decisions — how many hosts
× chips exist, how rows are laid out over them, and *which* reduction
algorithm a given payload should use — live in a single ``MeshPlan``:

* **Topology discovery**: hosts are distinct ``process_index`` values
  (the DMLC_* bootstrap maps workers onto processes); chips are the
  per-host local devices.  Multi-host topologies get a 2-D
  ``(host, chip)`` mesh; single-host gets the classic 1-D ``data`` mesh.
  ``DMLCTPU_MESH_HOSTS`` forces a synthetic host factor on a flat device
  set (how the virtual 8-device CPU mesh exercises the 2-D paths).
* **Collective strategy**: small payloads take the flat ``psum`` (XLA
  picks its latency-optimal algorithm); large histogram/gradient
  reductions take the hierarchical route of the MLPerf TPU-v3 pod paper
  — in-host ring reduce-scatter, cross-host recursive-doubling tree,
  in-host ring allgather — built from ``ppermute`` on the named axes.
  ``DMLCTPU_MESH_COLLECTIVE`` overrides the per-payload choice.
* **Back-compat**: every API that used to take the raw ``(mesh, axis)``
  tuple adapts it via :meth:`MeshPlan.from_spec`.

Within one hierarchical reduction each block's contributions are
combined in ring order, so results are deterministic run-to-run on a
fixed plan (they may differ from the flat route by float rounding —
why ``strategy_for`` is trace-time static, never data-dependent).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collective import _OPS, shard_map_compat
from .mesh import make_mesh
from ..timer import Stopwatch

_COMBINE = {"sum": jnp.add, "mean": jnp.add,
            "max": jnp.maximum, "min": jnp.minimum}
_STRATEGIES = ("auto", "flat", "hier")


def _env_collective() -> str:
    mode = os.environ.get("DMLCTPU_MESH_COLLECTIVE", "auto").strip().lower()
    if mode not in _STRATEGIES:
        raise ValueError(
            f"DMLCTPU_MESH_COLLECTIVE={mode!r} not in {_STRATEGIES}")
    return mode


def _env_threshold_bytes() -> int:
    return int(os.environ.get("DMLCTPU_MESH_HIER_THRESHOLD_KB", "256")) << 10


def _env_overlap_chunks() -> int:
    return max(1, int(os.environ.get("DMLCTPU_MESH_OVERLAP_CHUNKS", "1")))


class MeshPlan:
    """Topology + layout + per-payload collective strategy.

    Parameters
    ----------
    mesh:
        The jax Mesh to plan over.
    axes:
        Row-sharding axis names in major→minor order.  2-D plans are
        ``("host", "chip")``; a 1-D plan's single axis doubles as the
        chip (ring) axis.  Defaults to all mesh axes.
    collective:
        "auto" (payload-routed), "flat", or "hier".  Default: the
        ``DMLCTPU_MESH_COLLECTIVE`` env knob, else "auto".
    hier_threshold_bytes:
        auto-mode payload size at which hierarchical takes over.
        Default: ``DMLCTPU_MESH_HIER_THRESHOLD_KB`` (256 KiB).
    overlap_chunks:
        feature-chunk count for the GBDT level-loop collective/compute
        overlap (1 = unchunked).  Default:
        ``DMLCTPU_MESH_OVERLAP_CHUNKS``.
    """

    def __init__(self, mesh: Mesh, axes: Optional[Sequence[str]] = None,
                 collective: Optional[str] = None,
                 hier_threshold_bytes: Optional[int] = None,
                 overlap_chunks: Optional[int] = None,
                 prefer_gspmd: bool = False):
        axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(
                    f"plan axis {a!r} not in mesh axes {mesh.axis_names}")
        if not axes or len(axes) > 2:
            raise ValueError(f"MeshPlan wants 1 or 2 axes, got {axes!r}")
        collective = collective if collective is not None else _env_collective()
        if collective not in _STRATEGIES:
            raise ValueError(
                f"collective {collective!r} not in {_STRATEGIES}")
        self.mesh = mesh
        self.axes = axes
        self.collective = collective
        self.hier_threshold_bytes = (
            hier_threshold_bytes if hier_threshold_bytes is not None
            else _env_threshold_bytes())
        self.overlap_chunks = (
            max(1, int(overlap_chunks)) if overlap_chunks is not None
            else _env_overlap_chunks())
        # legacy (mesh, axis)-tuple adapters set this: XLA consumers keep
        # GSPMD auto-partitioning (uneven rows) instead of shard_map
        self.prefer_gspmd = bool(prefer_gspmd)
        # what allreduce has been traced with; what counting() saw of it
        self._traced, self._census = [0, 0], {}
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
        single-process device set stays 1-D unless ``hosts`` (or the
        ``DMLCTPU_MESH_HOSTS`` knob) forces a synthetic host factor —
        the virtual-CPU stand-in for a multi-host pod.
        """
        devices = list(jax.devices() if devices is None else devices)
        n = len(devices)
        if hosts is None:
            hosts = int(os.environ.get("DMLCTPU_MESH_HOSTS", "0")) or None
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

    @classmethod
    def from_spec(cls, spec, **kwargs) -> Optional["MeshPlan"]:
        """Adapt any accepted mesh spec to a plan.

        ``None`` → ``None``; a ``MeshPlan`` passes through; a bare
        ``Mesh`` plans over all its axes; the legacy ``(mesh, axis)``
        tuple (axis a name or name-tuple) is validated and wrapped —
        the shape every pre-plan ``histogram_mesh=`` caller passed.
        """
        if spec is None:
            return None
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, Mesh):
            return cls(spec, **kwargs)
        mesh, axis = spec
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(
                    f"histogram_mesh axis {a!r} not in mesh axes "
                    f"{mesh.axis_names}")
        kwargs.setdefault("prefer_gspmd", True)
        return cls(mesh, axes, **kwargs)

    # ------------------------------------------------------------------
    # layout
    # ------------------------------------------------------------------
    @property
    def chip_axis(self) -> str:
        return self.axes[-1]

    @property
    def host_axis(self) -> Optional[str]:
        return self.axes[0] if len(self.axes) > 1 else None

    @property
    def num_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    @property
    def legacy_spec(self):
        """The old ``(mesh, axis)`` tuple for callers not yet converted."""
        return (self.mesh,
                self.axes[0] if len(self.axes) == 1 else self.axes)

    @property
    def row_spec(self) -> P:
        """Leading (row) dim sharded over every plan axis, host-major."""
        return P(self.axes)

    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.row_spec)

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_map(self, fn, in_specs, out_specs,
                  check_replication: bool = True):
        return shard_map_compat(fn, self.mesh, in_specs, out_specs,
                                check_replication=check_replication)

    def describe(self) -> dict:
        c = self.mesh.shape[self.chip_axis]
        return {"devices": self.num_shards,
                "hosts": self.num_shards // c, "chips_per_host": c,
                "axes": list(self.axes), "fabric": self.fabric,
                "collective": self.collective,
                "hier_threshold_bytes": self.hier_threshold_bytes,
                "overlap_chunks": self.overlap_chunks}

    # ------------------------------------------------------------------
    # collectives (call inside plan.shard_map-traced code)
    # ------------------------------------------------------------------
    def strategy_for(self, nbytes: int) -> str:
        """flat | hier for a payload of ``nbytes`` — trace-time static."""
        if self.num_shards <= 1:
            return "flat"
        if self.collective != "auto":
            return self.collective
        return "hier" if nbytes >= self.hier_threshold_bytes else "flat"

    def allreduce(self, x: jax.Array, op: str = "sum",
                  strategy: Optional[str] = None) -> jax.Array:
        """All-reduce over the plan axes; call inside traced code.

        Strategy defaults to :meth:`strategy_for` on the payload size
        (static under trace).  Runs when a program is TRACED, once a
        compile: :meth:`counting` counts what it notes once a call.
        """
        if op not in _OPS:
            raise ValueError(
                f"unknown allreduce op '{op}' (have {sorted(_OPS)})")
        nbytes = int(x.size) * x.dtype.itemsize
        strat = strategy or self.strategy_for(nbytes)
        # noted, not counted: this line runs once a compile, however often
        # the compiled reduction runs (counting(), at the end of the class;
        # no line above it may move: a program's lines are in its cache key)
        self._traced[0] += 1
        self._traced[1] += nbytes
        # every plan-routed reduction under one scope, flat or hierarchical:
        # collective time is read off a device trace by this name
        with jax.named_scope("mesh.allreduce"):
            if strat == "flat" or self.num_shards <= 1:
                return _OPS[op](
                    x, self.axes if len(self.axes) > 1 else self.axes[0])
            return self._hier_allreduce(x, op)

    def _hier_allreduce(self, x: jax.Array, op: str) -> jax.Array:
        """Ring reduce-scatter (chip) → tree (host) → ring allgather.

        Built from ``ppermute`` so every hop is an explicit neighbor
        transfer: in-host hops ride the fast fabric, and the cross-host
        stage moves only 1/c of the payload per host.  Contributions to
        each block combine in ring order — deterministic per plan.
        """
        combine = _COMBINE[op]
        chip, host = self.chip_axis, self.host_axis
        c = int(self.mesh.shape[chip])
        h = int(self.mesh.shape[host]) if host is not None else 1
        shape, dtype = x.shape, x.dtype
        flat = x.reshape(-1)
        size = int(flat.size)
        m = -(-size // c)
        if m * c != size:  # pad to c blocks; the tail never leaks back
            flat = jnp.concatenate(
                [flat, jnp.zeros((m * c - size,), dtype)])
        blocks = flat.reshape(c, m)
        idx = jax.lax.axis_index(chip)
        ring = [(i, (i + 1) % c) for i in range(c)]

        # in-host ring reduce-scatter: after c-1 hops, this device holds
        # the fully in-host-reduced block (idx+1) % c
        acc = jax.lax.dynamic_index_in_dim(blocks, idx, 0, keepdims=False)
        for s in range(c - 1):
            acc = jax.lax.ppermute(acc, chip, ring)
            blk = jax.lax.dynamic_index_in_dim(
                blocks, (idx - s - 1) % c, 0, keepdims=False)
            acc = combine(acc, blk)

        # cross-host stage on the scattered block: recursive-doubling
        # tree for power-of-two host counts (log2(h) hops, operand order
        # commutes so every host lands on the same bits), flat XLA
        # reduction otherwise
        if h > 1:
            if h & (h - 1) == 0:
                step = 1
                while step < h:
                    other = jax.lax.ppermute(
                        acc, host, [(i, i ^ step) for i in range(h)])
                    acc = combine(acc, other)
                    step *= 2
            else:
                acc = _OPS["sum" if op in ("sum", "mean") else op](acc, host)

        # in-host ring allgather back to the replicated full payload
        out = jnp.zeros((c, m), dtype)
        out = jax.lax.dynamic_update_index_in_dim(
            out, acc, (idx + 1) % c, 0)
        cur = acc
        for s in range(c - 1):
            cur = jax.lax.ppermute(cur, chip, ring)
            out = jax.lax.dynamic_update_index_in_dim(
                out, cur, (idx - s) % c, 0)
        res = out.reshape(-1)[:size].reshape(shape)
        if op == "mean":
            res = res / self.num_shards
        return res


    def counting(self, program: str, executions: int = 1, around=None):
        """Context manager around ``executions`` calls of ONE jitted program
        whose trace calls :meth:`allreduce`: adds what they reduce to the
        counters ``mesh.allreduce_calls`` and ``mesh.collective_bytes`` (the
        payload a reduction is handed, as every chip holds it; not what a
        route moves over the wires).

        Only the caller of a compiled program knows how often it runs.
        What one execution reduces is noted when a call inside the block
        traces the program, under the caller's name for it, ``program``,
        and counted for these calls and for every later block of that
        name, which finds the program compiled.  A context manager and not
        a wrapper, so that no frame of it stands between the caller and the
        program while that is traced (source lines and callers are part of
        a program's compile-cache key).  ``around``: a context manager to
        hold open around the block (a span)."""
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
        calls, nbytes = (plan._traced[0] - self.mark[0],
                         plan._traced[1] - self.mark[1])
        if calls:
            plan._census[self.program] = (calls, nbytes)
        calls, nbytes = plan._census.get(self.program, (0, 0))
        try:
            from .. import telemetry
            telemetry.counter_add("mesh.allreduce_calls",
                                  calls * self.executions)
            telemetry.counter_add("mesh.collective_bytes",
                                  nbytes * self.executions)
        except Exception:
            pass
        if self.around is not None:
            return self.around.__exit__(*exc)


def plan_allreduce_bench(plan: MeshPlan, strategy: str = "auto",
                         mib_per_device: float = 8.0, iters: int = 10,
                         warmup: int = 2) -> dict:
    """Bus bandwidth of the plan's allreduce at a forced strategy.

    Compile happens in the explicit warmup calls, never in the timed
    loop.  Bus GB/s uses the NCCL-tests 2(n-1)/n allreduce factor so
    flat-vs-hier rows compare directly with ``collective_bench``.
    """
    n = plan.num_shards
    nfloats = max(n, int(mib_per_device * (1 << 20) // 4))
    nfloats -= nfloats % n
    forced = None if strategy == "auto" else strategy

    def body(x):
        return plan.allreduce(x, "sum", strategy=forced)

    step = jax.jit(plan.shard_map(body, in_specs=plan.row_spec,
                                  out_specs=P(), check_replication=False))
    x = jax.device_put(
        np.random.default_rng(0).standard_normal((nfloats,),
                                                 dtype=np.float32),
        plan.data_sharding())
    with plan.counting("plan_allreduce_bench", max(1, warmup)):
        for _ in range(max(1, warmup)):
            step(x).block_until_ready()
    watch = Stopwatch()
    with plan.counting("plan_allreduce_bench", iters):
        for _ in range(iters):
            out = step(x)
        out.block_until_ready()
    secs = watch.elapsed() / iters
    nbytes = nfloats * 4 // n  # per-device payload, NCCL-tests convention
    algo = nbytes / secs / 1e9
    return {"devices": n, "bytes": nbytes, "seconds_per_iter": secs,
            "algo_gbps": algo, "bus_gbps": algo * 2.0 * (n - 1) / n,
            "strategy": strategy if forced else plan.strategy_for(nbytes),
            "op": "allreduce"}
