#!/usr/bin/env python
"""Multi-process (multi-host) training example.

Each process parses ITS shard of the dataset (part=process_index) and the
multi-host staging path assembles global batches across all processes:

    # via the launcher (one rank per TPU VM host; DMLC_* env provides the
    # coordinator address and task ids):
    dmlc-submit --cluster=tpu -n 2 -- python examples/distributed_train.py

    # or standalone on one machine, two processes:
    python examples/distributed_train.py --coord 127.0.0.1:9355 --nprocs 2 --pid 0 &
    python examples/distributed_train.py --coord 127.0.0.1:9355 --nprocs 2 --pid 1

The training step is the same single-host code: replicated params,
data-sharded global batches, XLA inserts the gradient all-reduce.
nnz_max pins every process's shard to identical shapes (required for
multi-host global arrays).
"""
from __future__ import annotations

import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))
sys.path.insert(0, _here)  # for `from train_linear import synth_dataset`

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default="/tmp/train_linear_synth.libsvm")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=4096,
                    help="rows per PROCESS per global batch")
    ap.add_argument("--nnz-max", type=int, default=1 << 17,
                    help="hard per-process nonzero cap (fixed shapes)")
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--coord", default=None,
                    help="host:port of the jax coordinator (defaults to "
                         "DMLC_JAX_COORDINATOR from the launcher)")
    ap.add_argument("--nprocs", type=int, default=None)
    ap.add_argument("--pid", type=int, default=None)
    args = ap.parse_args()

    import jax

    # generate the demo dataset BEFORE joining the cluster: every process
    # writes its own copy when missing (deterministic seed -> identical
    # bytes), which also covers multi-host rigs where /tmp is per-host
    if not os.path.exists(args.data):
        from train_linear import synth_dataset
        synth_dataset(args.data)

    # under dmlc-submit the DMLC_* contract carries everything (the library
    # bootstrap derives the coordinator from it); standalone runs pass
    # --coord/--nprocs/--pid explicitly
    if args.coord:
        jax.distributed.initialize(coordinator_address=args.coord,
                                   num_processes=args.nprocs,
                                   process_id=args.pid)
    else:
        from dmlc_core_tpu.parallel.bootstrap import init_from_env
        init_from_env()

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dmlc_core_tpu import compile_cache
    from dmlc_core_tpu.data import DeviceStagingIter
    from dmlc_core_tpu.models import SparseLinearModel

    compile_cache.configure()
    pid, nprocs = jax.process_index(), jax.process_count()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    it = DeviceStagingIter(args.data, batch_size=args.batch_size,
                           nnz_bucket=1 << 14, nnz_max=args.nnz_max,
                           part=pid, num_parts=nprocs, sharding=sharding)
    for _ in it:  # size the feature space; max_index folds across processes
        pass
    num_features = it.max_index + 1

    model = SparseLinearModel(num_features=num_features, learning_rate=args.lr)
    params = model.init()
    for epoch in range(args.epochs):
        loss = None
        for batch in it:
            params, loss = model.train_step(params, batch)
        if pid == 0:
            print(f"epoch {epoch}: loss {float(loss):.4f} "
                  f"({nprocs} processes, {len(jax.devices())} devices)",
                  flush=True)
    if pid == 0:
        print("done", flush=True)


if __name__ == "__main__":
    main()
