"""--cluster=tpu: launch ranks onto a TPU VM slice (the BASELINE north star).

One rank per TPU VM host, never more: a host's chips belong to one process
(JAX opens every local chip at its first backend call, and a second process
on the host then fails or hangs), so ``-n`` above the host count is refused.
The one process drives all local chips through a ``MeshPlan``.  Hosts come
from --host-file (the slice's worker hostnames, e.g. from `gcloud compute
tpus tpu-vm list-...`) or, absent that, from TPU_WORKER_HOSTNAMES in the
environment.  Rank assignment is
topology-aware: the host list is kept in slice order (worker-0 …
worker-N-1 matches the physical ICI layout), so DMLC_TASK_ID == TPU worker
id and jax.distributed's process ids line up with ICI neighbours.

Each rank gets the verbatim DMLC_* contract plus:
  DMLC_JAX_COORDINATOR  host:port of the JAX coordination service
  TPU_WORKER_ID         its slice worker id
Worker code calls dmlc_core_tpu.parallel.init_from_env() (or plain
jax.distributed.initialize()) and the data plane is XLA collectives over
ICI — no rabit ring ever forms unless a legacy client asks the tracker for
one (the tracker still serves the full rabit protocol for those).
"""
from __future__ import annotations

import os
import subprocess

from ..submit import submit
from ._threads import RankThreads


def slice_hosts(args) -> list:
    if args.host_file:
        from .ssh import parse_host_file
        return parse_host_file(args.host_file)
    names = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if names:
        return [(h.strip(), 22) for h in names.split(",") if h.strip()]
    # single-host slice (e.g. v5e-8 single VM): run everything locally
    return [("localhost", 22)]


def run(args) -> None:
    hosts = slice_hosts(args)
    if args.num_workers > len(hosts):
        raise SystemExit(
            f"dmlc-submit --cluster=tpu: {args.num_workers} workers on "
            f"{len(hosts)} host(s) — one rank per TPU host: its one process "
            "owns every local chip (use MeshPlan to drive them); list more "
            "hosts with --host-file or TPU_WORKER_HOSTNAMES")
    ranks = RankThreads()

    def spawn_all(num_workers: int, num_servers: int, envs: dict) -> None:
        assert num_servers == 0, "--cluster=tpu is rabit/collective mode only"

        def one(task_id: int, host: str, port: int) -> None:
            env_pairs = dict(envs)
            env_pairs.update(args.extra_env)
            env_pairs.update({
                "DMLC_ROLE": "worker",
                "DMLC_TASK_ID": task_id,
                "DMLC_JOB_CLUSTER": "tpu",
                "DMLC_NODE_HOST": host,
                "TPU_WORKER_ID": task_id,
            })
            if host in ("localhost", "127.0.0.1"):
                env = os.environ.copy()
                env.update({k: str(v) for k, v in env_pairs.items()})
                proc = subprocess.run(args.command, env=env)
            else:
                exports = "; ".join(f"export {k}={v!s}" for k, v in env_pairs.items())
                remote = f"{exports}; cd {os.getcwd()}; " + " ".join(args.command)
                proc = subprocess.run(["ssh", "-o", "StrictHostKeyChecking=no",
                                       "-p", str(port), host, remote])
            if proc.returncode != 0:
                raise RuntimeError(f"tpu worker {task_id} on {host} exited {proc.returncode}")

        for task_id in range(num_workers):
            host, port = hosts[task_id]
            ranks.spawn(one, task_id, host, port)

    tracker = submit(args.num_workers, 0, spawn_all, host_ip=args.host_ip,
                     extra_envs=args.extra_env)
    ranks.join_tracker(tracker)
