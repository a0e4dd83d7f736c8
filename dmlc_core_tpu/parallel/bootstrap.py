"""Multi-host bootstrap from the DMLC_* env contract.

The reference tracker hands every worker its coordinates through env vars
(tracker/dmlc_tracker/tracker.py:177-183): DMLC_TRACKER_URI/PORT, DMLC_ROLE,
DMLC_TASK_ID, DMLC_NUM_WORKER.  This module keeps that contract verbatim and
maps it onto ``jax.distributed.initialize`` — the JAX coordination service
plays the tracker role; the data plane is XLA collectives, not rabit TCP.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional


@dataclass
class DmlcEnvInfo:
    role: str
    task_id: int
    num_workers: int
    tracker_uri: Optional[str]
    tracker_port: Optional[int]

    @property
    def coordinator_address(self) -> Optional[str]:
        if self.tracker_uri is None:
            return None
        return f"{self.tracker_uri}:{self.tracker_port}"


def dmlc_env_info() -> DmlcEnvInfo:
    """Read the worker-side DMLC_* contract (absent vars → single-process)."""
    return DmlcEnvInfo(
        role=os.environ.get("DMLC_ROLE", "worker"),
        task_id=int(os.environ.get("DMLC_TASK_ID", "0")),
        num_workers=int(os.environ.get("DMLC_NUM_WORKER", "1")),
        tracker_uri=os.environ.get("DMLC_TRACKER_URI"),
        tracker_port=int(os.environ["DMLC_TRACKER_PORT"])
        if "DMLC_TRACKER_PORT" in os.environ else None,
    )


def init_from_env(coordinator_port_offset: int = 1) -> DmlcEnvInfo:
    """Initialize jax.distributed from the DMLC_* contract if multi-worker.

    The coordination service binds on the tracker host at
    ``DMLC_TRACKER_PORT + coordinator_port_offset`` (the tracker itself owns
    DMLC_TRACKER_PORT for legacy rabit clients).  Single-worker env: no-op.
    """
    import jax

    info = dmlc_env_info()
    if info.num_workers <= 1 or info.tracker_uri is None:
        return info
    coordinator = f"{info.tracker_uri}:{info.tracker_port + coordinator_port_offset}"
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=info.num_workers,
        process_id=info.task_id,
    )
    return info


def pin_host_only() -> None:
    """Declare this process host-only: a data-service staging worker, the
    tracker, the ``dmlc-submit`` parent.  None of them ever needs an
    accelerator, but each imports JAX (a binner's cuts are a ``jnp`` array,
    the resource sampler asks for ``jax.devices()``), and on a TPU host the
    first backend call would open the chips the trainer next to it owns —
    one process per chip.  Pinned through ``jax.config`` rather than the
    environment, which the ranks and workers started from here inherit.
    Call at the top of ``main``, before anything can touch a backend."""
    import jax
    jax.config.update("jax_platforms", "cpu")
