#!/usr/bin/env python3
"""Record the small device trace that benchmark/tests check trace_reduce.py on.

Run on the chip: ``python benchmark/testdata/record_trace.py``.  One GBDT fit
at Higgs width on 65,536 rows is traced; the xplane file lands in
``chiprun_out/trace_probe/`` and, trimmed by hand to the file kept beside this
script, is what the tests read.  It also prints which planes, lines and event
names a TPU trace holds, which is what the patterns in
``benchmark/layer_metrics/*.json`` were written from.
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def describe(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            total = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
            for name, ns in total.most_common(12):
                print(f"    {ns / 1e6:10.3f} ms  {name[:150]}")
            if events and ("TPU" in plane.name or "XLA" in line.name):
                e = max(events, key=lambda e: e.duration_ns)
                print(f"    stats of longest: "
                      f"{[(k, str(v)[:80]) for k, v in e.stats][:12]}")


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dmlc_core_tpu import compile_cache
    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models import GBDT
    from dmlc_core_tpu.models.ffm import FieldAwareFactorizationMachine
    compile_cache.configure()
    print("devices", jax.devices(), flush=True)
    out = ROOT / "chiprun_out" / "trace_probe"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    rng = np.random.default_rng(0)
    rows = 65536
    bins = jnp.asarray(rng.integers(1, 256, (rows, 28)).astype(np.uint8))
    y = jnp.asarray((rng.random(rows) < 0.5).astype(np.float32))
    model = GBDT(num_features=28, num_trees=2, max_depth=6, num_bins=256,
                 missing_aware=True)
    print("levels", model.level_backends())
    jax.block_until_ready(model.fit(bins, y))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out / "gbdt"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            jax.block_until_ready(model.fit(bins, y))
    jax.profiler.stop_trace()
    path = glob.glob(str(out / "gbdt" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    print("GBDT trace", path, os.path.getsize(path), "bytes")
    describe(path)

    B, A, F = 4096, 39, 1 << 16
    ffm = FieldAwareFactorizationMachine(num_features=F, num_fields=A)
    nnz = B * A
    batch = PaddedBatch(
        label=jnp.asarray((rng.random(B) < 0.3).astype(np.float32)),
        weight=jnp.ones(B, jnp.float32),
        row_ptr=jnp.arange(B + 1, dtype=jnp.int32) * A,
        index=jnp.asarray(rng.integers(0, F, nnz).astype(np.int32)),
        value=jnp.ones(nnz, jnp.float32),
        num_rows=jnp.asarray(np.int32(B)),
        field=jnp.asarray(np.tile(np.arange(A, dtype=np.int32), B)))
    params = ffm.init(0)
    params, loss = ffm.train_step(params, batch)
    jax.block_until_ready(params)
    jax.profiler.start_trace(str(out / "ffm"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            params, loss = ffm.train_step(params, batch)
        jax.block_until_ready(params)
    jax.profiler.stop_trace()
    path = glob.glob(str(out / "ffm" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    print("FFM trace", path, os.path.getsize(path), "bytes")
    describe(path)
    print("memory", jax.devices()[0].memory_stats())
    return 0


if __name__ == "__main__":
    sys.exit(main())
