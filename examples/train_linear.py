#!/usr/bin/env python
"""End-to-end training example: libsvm shards -> native parse/pack ->
device-staged batches -> sparse logistic regression with SGD.

This is the SURVEY §7 slice as a user would run it:

    python examples/train_linear.py [--data file.libsvm] [--epochs 3]
                                    [--batch-size 8192] [--shard]

With no --data a synthetic dataset is generated.  --shard lays the batch
over all local devices (data parallelism on one host: the gradient psum
rides ICI on a TPU slice, and works identically on the virtual CPU mesh:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8).

For MULTI-PROCESS training (one process per TPU VM host) see
examples/distributed_train.py.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def synth_dataset(path: str, rows: int = 200_000, dim: int = 1000) -> None:
    """Sparse binary problem with a planted weight vector."""
    import numpy as np
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=dim)
    with open(path, "w") as f:
        for _ in range(rows):
            nnz = rng.integers(5, 30)
            idx = rng.choice(dim, size=nnz, replace=False)
            val = rng.random(nnz).astype(np.float32)
            y = int(val @ w_true[idx] > 0)
            f.write(f"{y} " + " ".join(f"{i}:{v:.4f}" for i, v in
                                       sorted(zip(idx, val))) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=8192)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--shard", action="store_true",
                    help="shard batches over all local devices (DP)")
    args = ap.parse_args()

    import jax
    import numpy as np

    from dmlc_core_tpu import compile_cache
    from dmlc_core_tpu.data import DeviceStagingIter
    from dmlc_core_tpu.models import SparseLinearModel

    compile_cache.configure()

    data = args.data
    if data is None:
        data = "/tmp/train_linear_synth.libsvm"
        if not os.path.exists(data):
            print("generating synthetic dataset ...")
            synth_dataset(data)

    sharding = None
    if args.shard:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        sharding = NamedSharding(mesh, P("data"))
        print(f"sharding batches over {len(jax.devices())} "
              f"{jax.devices()[0].platform} devices")

    # host-only pass to size the feature space (no device transfers)
    from dmlc_core_tpu.data import Parser
    num_features = 0
    with Parser(data) as sizing:
        for block in sizing:
            if len(block.index):
                num_features = max(num_features, int(block.index.max()) + 1)
    print(f"{num_features} features")

    it = DeviceStagingIter(data, batch_size=args.batch_size,
                           nnz_bucket=1 << 16, sharding=sharding)

    model = SparseLinearModel(num_features=num_features,
                              learning_rate=args.lr)
    params = model.init()
    for epoch in range(args.epochs):
        t0 = time.monotonic()
        loss = None
        n = 0
        for batch in it:
            params, loss = model.train_step(params, batch)
            n += 1
        secs = time.monotonic() - t0
        print(f"epoch {epoch}: loss {float(loss):.4f}  "
              f"({n} batches, {secs:.1f}s)")

    metrics = model.evaluate(params, it)
    print(f"final: {metrics}")


if __name__ == "__main__":
    main()
