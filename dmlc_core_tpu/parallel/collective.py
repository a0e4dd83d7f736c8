"""Collectives — the rabit-allreduce equivalent, on XLA/ICI.

The reference builds a TCP tree+ring and brokers peer links through the
tracker (tracker/dmlc_tracker/tracker.py:185-252).  On TPU the topology is
the hardware: a jitted psum over a mesh axis lowers to an ICI all-reduce.
``allreduce`` is the drop-in API; ``allreduce_bench`` measures achieved
bus bandwidth (BASELINE config 4).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..timer import Stopwatch

_OPS: dict[str, Callable] = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
    "mean": jax.lax.pmean,
}


def shard_map_compat(fn, mesh, in_specs, out_specs,
                     check_replication: bool = True):
    """``jax.shard_map`` with the static replication check switchable:
    ``check_replication=False`` is needed when an op's output replication
    is real but not statically inferable (all_gather, pallas_call)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


def allreduce(x: jax.Array, op: str = "sum", axis_name: str = "data") -> jax.Array:
    """All-reduce across a mesh axis; call inside shard_map/pmap-traced code."""
    try:
        fn = _OPS[op]
    except KeyError:
        raise ValueError(f"unknown allreduce op '{op}' (have {sorted(_OPS)})") from None
    return fn(x, axis_name)


def allreduce_bench(mesh: Mesh, mib_per_device: float = 64.0,
                    iters: int = 10, warmup: int = 2) -> dict:
    """Measure all-reduce bus bandwidth over the mesh's ``data`` axis.

    Returns {bytes, seconds_per_iter, algo_gbps, bus_gbps}.  Bus bandwidth
    uses the standard 2(n-1)/n ring factor.
    """
    return collective_bench(mesh, "allreduce", mib_per_device, iters,
                            warmup=warmup)


def collective_sweep(mesh: Mesh, op: str = "allreduce",
                     payloads_mib: Sequence[float] = (0.25, 16.0),
                     iters: int = 10, warmup: int = 2) -> list:
    """``collective_bench`` at several payload sizes — the small/large
    sweep that makes the latency-vs-bandwidth regimes visible in one
    DETAIL row."""
    return [collective_bench(mesh, op, mib, iters, warmup=warmup)
            for mib in payloads_mib]


# per-op (kernel builder, out spec, algbw size base as a function of the
# per-device bytes, bus-bandwidth ring factor).  Conventions follow
# NCCL-tests so numbers compare across stacks: the algbw base is the
# TOTAL data size of the op (allgather: n * sendcount; the others equal
# the per-device buffer), bus factors allreduce 2(n-1)/n,
# allgather/reducescatter (n-1)/n, ppermute 1 (pure point-to-point).
def _kernels(n):
    def allreduce_fn(x):
        return jax.lax.psum(x, "data")

    def allgather_fn(x):
        return jax.lax.all_gather(x, "data")

    def reducescatter_fn(x):
        return jax.lax.psum_scatter(x, "data", tiled=True)

    def ppermute_fn(x):
        # the permutation pairs must be static, so the axis size comes from
        # the mesh (jax.lax.axis_size only exists in newer jax releases)
        return jax.lax.ppermute(x, "data",
                                [(i, (i + 1) % n) for i in range(n)])

    one = lambda n: 1.0  # noqa: E731
    return {
        "allreduce": (allreduce_fn, P(), one, lambda n: 2.0 * (n - 1) / n),
        # all_gather returns the FULL [n, shard] array on every device, so
        # the global result is replicated — P(), not P(None, "data"),
        # which would mislabel it as an n-fold-duplicated sharded array
        "allgather": (allgather_fn, P(), lambda n: float(n),
                      lambda n: (n - 1) / n),
        "reducescatter": (reducescatter_fn, P("data"), one,
                          lambda n: (n - 1) / n),
        "ppermute": (ppermute_fn, P("data"), one, lambda n: 1.0),
    }


def collective_bench(mesh: Mesh, op: str = "allreduce",
                     mib_per_device: float = 64.0, iters: int = 10,
                     warmup: int = 2) -> dict:
    """Bandwidth of one XLA collective over the mesh's ``data`` axis — the
    ICI/DCN data plane the reference's TCP tree+ring bootstrap hands off
    to (SURVEY §5 'distributed communication backend').

    op: "allreduce" | "allgather" | "reducescatter" | "ppermute".
    Compile lands in the explicit ``warmup`` calls, never the timed loop.
    Returns {devices, bytes, seconds_per_iter, algo_gbps, bus_gbps, op}.
    """
    kernels = _kernels(mesh.devices.size)
    try:
        fn, out_spec, size_base, bus_factor = kernels[op]
    except KeyError:
        raise ValueError(
            f"unknown collective '{op}' (have {sorted(kernels)})") from None
    n = mesh.devices.size
    nfloats = int(mib_per_device * (1 << 20) // 4)
    # reducescatter (tiled psum_scatter) needs the per-device count
    # divisible by the axis size; rounding down keeps every op valid on
    # non-power-of-two meshes
    nfloats = max(n, nfloats - nfloats % n)
    # allgather: every device holds the FULL gathered array, so the global
    # result is replicated (out_specs P()); jax's static replication check
    # cannot infer all_gather output replication, so it is disabled for
    # that op only (the other ops keep the check)
    step = jax.jit(shard_map_compat(fn, mesh, in_specs=P("data"),
                                    out_specs=out_spec,
                                    check_replication=(op != "allgather")))
    x = jax.device_put(
        np.random.default_rng(0).standard_normal((n * nfloats,),
                                                 dtype=np.float32),
        NamedSharding(mesh, P("data")))
    for _ in range(max(1, warmup)):  # compile + steady-state warmup
        step(x).block_until_ready()
    watch = Stopwatch()
    for _ in range(iters):
        out = step(x)
    out.block_until_ready()
    secs = watch.elapsed() / iters
    nbytes = int(nfloats * 4 * size_base(n))  # NCCL-tests size convention
    algo = nbytes / secs / 1e9
    return {"devices": n, "bytes": nbytes, "seconds_per_iter": secs,
            "algo_gbps": algo, "bus_gbps": algo * bus_factor(n), "op": op}
