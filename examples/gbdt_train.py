#!/usr/bin/env python
"""End-to-end histogram-GBDT example: libsvm shards -> native parse/pack ->
device-staged batches -> densify -> quantile bins -> boosted trees.

The XGBoost-hist workflow (BASELINE target 5) on this stack::

    python examples/gbdt_train.py [--data file.libsvm] [--trees 10]
                                  [--depth 5] [--bins 64] [--shard]

With no --data a synthetic nonlinear dataset is generated.  --shard lays
the binned rows over all local devices: every tree level's gradient
histogram then carries a compiler-inserted psum over the mesh — the rabit
histogram-allreduce (reference tracker/dmlc_tracker/tracker.py:185-252)
riding ICI on a TPU slice (identical on the virtual CPU mesh:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def synth_dataset(path: str, rows: int = 50_000, dim: int = 32) -> None:
    """Sparse rows whose label is a nonlinear (XOR-style) feature rule —
    unlearnable by the linear model, easy for trees."""
    import numpy as np
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for _ in range(rows):
            nnz = rng.integers(max(4, dim // 4), dim)
            idx = np.sort(rng.choice(dim, size=nnz, replace=False))
            val = rng.uniform(-1, 1, size=nnz)
            lut = dict(zip(idx.tolist(), val.tolist()))
            y = int((lut.get(0, 0.0) > 0) ^ (lut.get(1, 0.0) > 0.2))
            f.write(f"{y} " + " ".join(f"{i}:{v:.4f}" for i, v in lut.items())
                    + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None)
    ap.add_argument("--trees", type=int, default=10)
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument("--bins", type=int, default=64)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=16384)
    ap.add_argument("--num-workers", type=int, default=1,
                    help="parallel parse workers for the staging iterator "
                         "(deterministic: batches are identical for any "
                         "worker count)")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="staged batches buffered ahead of the consumer "
                         "(default: staging iterator's own default)")
    ap.add_argument("--shard", action="store_true",
                    help="row-shard over all local devices (data parallel)")
    ap.add_argument("--kernel-mesh", action="store_true",
                    help="with --shard: run the Pallas histogram kernel "
                         "per-device under shard_map + explicit psum "
                         "(histogram_mesh=MeshPlan) instead of the GSPMD "
                         "scatter-add route; interpret-mode (slow) off-TPU")
    ap.add_argument("--missing", action="store_true",
                    help="sparsity-aware mode: absent libsvm features are "
                         "MISSING (NaN -> reserved bin, learned per-node "
                         "default direction), not zeros")
    ap.add_argument("--native-sparse", action="store_true",
                    help="train straight on the staged CSR batch "
                         "(fit_batch: O(nnz) histograms, no densify; "
                         "implies --missing semantics)")
    ap.add_argument("--rank", action="store_true",
                    help="learning-to-rank demo: a qid-grouped libsvm "
                         "dataset staged with with_qid=True into "
                         "objective='rank:pairwise' (reports within-query "
                         "pairwise accuracy)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_core_tpu import compile_cache
    from dmlc_core_tpu.data import DeviceStagingIter
    from dmlc_core_tpu.models import GBDT, QuantileBinner
    from dmlc_core_tpu.ops.sparse import csr_to_dense, csr_to_dense_missing
    from dmlc_core_tpu.parallel import MeshPlan

    compile_cache.configure()

    stage_kw = dict(num_workers=args.num_workers)
    if args.prefetch_depth is not None:
        stage_kw["prefetch_depth"] = args.prefetch_depth

    def entry_mask(row_ptr, n_entries):
        """Structural mask of REAL CSR entries: the slots covered by some
        row's [row_ptr[r], row_ptr[r+1]) span.  Everything outside a span
        is nnz-bucket lane padding — unlike ``value != 0`` this keeps
        genuine zero-valued features and works on concatenated batches
        whose padding lanes sit between the per-batch segments."""
        edges = np.zeros(n_entries + 1, np.int64)
        np.add.at(edges, row_ptr[:-1], 1)
        np.add.at(edges, row_ptr[1:], -1)
        return np.cumsum(edges[:-1]) > 0

    def concat_staged(uri, with_qid=False, sketch=None):
        """Drain ALL staged batches of a dataset into one host PaddedBatch
        (hist-GBDT needs the full dataset per level); None if no rows.
        With ``sketch`` (a QuantileBinner), each batch's entries feed the
        streaming quantile sketch as it goes by — bounded-memory cuts over
        the whole stream, the XGBoost-sketch pattern for data that never
        fits in one sample (caller runs ``sketch.finalize()`` after)."""
        from dmlc_core_tpu.data.staging import PaddedBatch
        it = DeviceStagingIter(uri, batch_size=args.batch_size,
                               with_qid=with_qid, **stage_kw)
        parts = []
        for b in it:
            idxs, vals = np.asarray(b.index), np.asarray(b.value)
            if sketch is not None:
                m = entry_mask(np.asarray(b.row_ptr), vals.shape[0])
                sketch.partial_fit_sparse(idxs[m], vals[m], args.dim)
            parts.append((np.asarray(b.label), np.asarray(b.weight),
                          np.asarray(b.row_ptr), idxs, vals,
                          np.asarray(b.qid) if with_qid else None))
        if not parts:
            return None
        nnz_off = np.cumsum([0] + [p[4].shape[0] for p in parts])
        return PaddedBatch(
            label=jnp.asarray(np.concatenate([p[0] for p in parts])),
            weight=jnp.asarray(np.concatenate([p[1] for p in parts])),
            row_ptr=jnp.asarray(np.concatenate(
                [parts[0][2]] + [p[2][1:] + off for p, off
                                 in zip(parts[1:], nnz_off[1:-1])])),
            index=jnp.asarray(np.concatenate([p[3] for p in parts])),
            value=jnp.asarray(np.concatenate([p[4] for p in parts])),
            num_rows=jnp.asarray(np.int32(
                sum(int((p[1] > 0).sum()) for p in parts))),
            field=None,
            qid=(jnp.asarray(np.concatenate([p[5] for p in parts]))
                 if with_qid else None))

    if args.rank:
        data_rank = args.data or "/tmp/gbdt_rank_example.libsvm"
        if args.data is None and not os.path.exists(data_rank):
            print("generating synthetic ranking dataset...", flush=True)
            rng = np.random.default_rng(0)
            with open(data_rank, "w") as f:
                for q in range(1500):
                    for _ in range(int(rng.integers(6, 14))):
                        v = {int(i): float(rng.uniform(0.1, 2.0))
                             for i in np.sort(rng.choice(
                                 args.dim, size=max(3, args.dim // 4),
                                 replace=False))}
                        rel = round(2 * v.get(0, 0.0)
                                    + v.get(1, 0.0) ** 2
                                    + float(rng.normal(0, 0.05)), 4)
                        f.write(f"{rel} qid:{q} " + " ".join(
                            f"{i}:{val:.4f}" for i, val in v.items()) + "\n")
        batch = concat_staged(data_rank, with_qid=True)
        if batch is None:
            print(f"error: no rows staged from {data_rank}", file=sys.stderr)
            return 1
        mask = entry_mask(np.asarray(batch.row_ptr),
                          int(batch.value.shape[0]))
        binner = QuantileBinner(num_bins=args.bins, missing_aware=True)
        binner.fit_sparse(np.asarray(batch.index)[mask],
                          np.asarray(batch.value)[mask],
                          num_features=args.dim)
        model = GBDT(num_features=args.dim, num_trees=args.trees,
                     max_depth=args.depth, num_bins=args.bins,
                     learning_rate=0.3, objective="rank:pairwise",
                     missing_aware=True)
        t0 = time.monotonic()
        params = model.fit_batch(batch, binner)
        jax.block_until_ready(params["leaf"])
        t_fit = time.monotonic() - t0
        scores = np.asarray(model.margins_batch(params, batch, binner))
        w = np.asarray(batch.weight)
        y = np.asarray(batch.label)
        q = np.asarray(batch.qid)
        # within-query pairwise accuracy over a row sample
        rng2 = np.random.default_rng(1)
        real = np.flatnonzero(w > 0)
        good = total = 0
        for i in rng2.choice(real, size=min(4000, len(real)), replace=False):
            same = real[(q[real] == q[i]) & (real != i)]
            for j in same:
                if y[i] == y[j]:
                    continue
                total += 1
                good += (scores[i] > scores[j]) == (y[i] > y[j])
        acc = good / max(total, 1)
        print(f"fit {args.trees} rank trees in {t_fit:.2f}s; "
              f"pairwise accuracy={acc:.4f} over {total} sampled pairs",
              flush=True)
        print(f"final: pairwise_accuracy={acc:.4f}", flush=True)
        return 0 if acc > 0.8 else 1

    data = args.data
    if data is None:
        data = "/tmp/gbdt_example.libsvm"
        if not os.path.exists(data):
            print("generating synthetic dataset...", flush=True)
            synth_dataset(data, dim=args.dim)

    if args.native_sparse:
        # no densify: staged CSR batches concatenated into one host batch
        # for fit_batch (hist-GBDT needs the full dataset per level); bin
        # cuts come from the STREAMING sketch fed batch-by-batch during
        # the drain.  The reservoir is sized past this dataset's
        # per-feature counts so the streamed cuts stay EXACTLY the
        # one-shot fit_sparse cuts; at real Higgs scale you would let the
        # default (smaller) reservoir subsample — that bounded memory is
        # the point of the streaming path.
        binner = QuantileBinner(num_bins=args.bins, missing_aware=True,
                                sketch_size=1 << 16)
        t0 = time.monotonic()
        batch = concat_staged(data, sketch=binner)
        if batch is None:
            print(f"error: no rows staged from {data}", file=sys.stderr)
            return 1
        t_stage = time.monotonic() - t0
        binner.finalize()
        mask = entry_mask(np.asarray(batch.row_ptr),
                          int(batch.value.shape[0]))
        n_real = int(np.asarray(batch.weight).sum())
        print(f"staged {n_real} rows ({int(mask.sum())} nnz) "
              f"in {t_stage:.2f}s (bin cuts streamed per batch)", flush=True)
        model = GBDT(num_features=args.dim, num_trees=args.trees,
                     max_depth=args.depth, num_bins=args.bins,
                     learning_rate=0.4, missing_aware=True)
        t0 = time.monotonic()
        params = model.fit_batch(batch, binner)
        jax.block_until_ready(params["leaf"])
        t_fit = time.monotonic() - t0
        pred = np.asarray(model.predict_batch(params, batch, binner))
        w = np.asarray(batch.weight)
        y = np.asarray(batch.label)
        acc = float(((pred > 0.5) == (y > 0.5))[w > 0].mean())
        rate = args.trees * n_real / max(t_fit, 1e-9)
        print(f"fit {args.trees} trees (sparse-native, depth {args.depth}, "
              f"{args.bins} bins) in {t_fit:.2f}s = {rate:,.0f} "
              f"row-trees/s", flush=True)
        print(f"final: accuracy={acc:.4f}", flush=True)
        return 0 if acc > 0.8 else 1

    # stage sparse batches to device, densify each into [rows, dim]
    t0 = time.monotonic()
    it = DeviceStagingIter(data, batch_size=args.batch_size, **stage_kw)
    dense_parts, label_parts = [], []
    densify = jax.jit(csr_to_dense_missing if args.missing else csr_to_dense,
                      static_argnums=(3, 4))
    for batch in it:
        d = densify(batch.index, batch.value, batch.row_ids(),
                    batch.batch_size, args.dim)
        keep = np.asarray(batch.weight) > 0  # drop padding rows on host
        dense_parts.append(np.asarray(d)[keep])
        label_parts.append(np.asarray(batch.label)[keep])
    x = np.concatenate(dense_parts)
    y = np.concatenate(label_parts)
    t_stage = time.monotonic() - t0
    print(f"staged+densified {x.shape[0]} rows x {args.dim} features "
          f"in {t_stage:.2f}s", flush=True)

    binner = QuantileBinner(num_bins=args.bins, missing_aware=args.missing)
    bins_host = np.asarray(binner.fit_transform(x))

    if args.kernel_mesh and not args.shard:
        raise SystemExit("--kernel-mesh requires --shard")

    if args.shard:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devices = np.asarray(jax.devices())
        mesh = Mesh(devices, ("data",))
        rows = NamedSharding(mesh, P("data"))
        pad = (-len(y)) % len(devices)  # shardable row count; weight-0 pad
        bins_in = jax.device_put(
            np.pad(bins_host, ((0, pad), (0, 0))), rows)
        label_in = jax.device_put(np.pad(y, (0, pad)), rows)
        weight = jax.device_put(
            np.pad(np.ones_like(y), (0, pad)), rows)
        print(f"sharding {len(y)}(+{pad} pad) rows over "
              f"{len(devices)} devices", flush=True)
    else:
        bins_in, label_in, weight = (jnp.asarray(bins_host),
                                     jnp.asarray(y), None)

    mesh_kw = {}
    if args.kernel_mesh:
        # the sharded-kernel route: the row padding above already makes
        # rows divide by the device count (shard_map's even-sharding rule)
        mesh_kw = dict(histogram="pallas",
                       histogram_mesh=MeshPlan(mesh, ("data",)))
        print("histogram route: pallas kernel per-device under shard_map "
              "+ psum", flush=True)
    model = GBDT(num_features=args.dim, num_trees=args.trees,
                 max_depth=args.depth, num_bins=args.bins,
                 learning_rate=0.4, missing_aware=args.missing, **mesh_kw)

    t0 = time.monotonic()
    params = model.fit(bins_in, label_in, weight=weight)
    jax.block_until_ready(params["leaf"])
    t_fit = time.monotonic() - t0

    pred = np.asarray(model.predict(params, jnp.asarray(bins_host)))
    acc = float(np.mean((pred > 0.5) == (y > 0.5)))
    loss = float(model.loss(params, jnp.asarray(bins_host), jnp.asarray(y)))
    rate = args.trees * x.shape[0] / max(t_fit, 1e-9)
    print(f"fit {args.trees} trees (depth {args.depth}, {args.bins} bins) "
          f"in {t_fit:.2f}s = {rate:,.0f} row-trees/s", flush=True)
    print(f"final: loss={loss:.4f} accuracy={acc:.4f}", flush=True)
    return 0 if acc > 0.8 else 1


if __name__ == "__main__":
    sys.exit(main())
