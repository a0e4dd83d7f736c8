"""Distributed execution: device meshes, collectives, multi-host bootstrap."""
from .mesh import make_mesh, data_sharding, replicated_sharding
from .collective import (allreduce, allreduce_bench, collective_bench,
                         collective_sweep)
from .meshplan import MeshPlan
from .bootstrap import dmlc_env_info, init_from_env, pin_host_only

__all__ = [
    "make_mesh", "data_sharding", "replicated_sharding",
    "allreduce", "allreduce_bench", "collective_bench", "collective_sweep",
    "MeshPlan",
    "init_from_env", "dmlc_env_info", "pin_host_only",
]
