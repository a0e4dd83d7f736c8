#!/usr/bin/env python3
"""Record the two small device traces that benchmark/tests check
``readers/trace_scope.py`` on: a program whose phases carry scopes.

Run on the chip: ``python benchmark/testdata/record_scoped_trace.py``.  One
GBDT fit (2 trees, depth 6, Higgs width) on 65,536 rows and three FFM
``train_step``s (4,096 rows x 39 fields, 2^16 features) are traced, each
under a ``bench.window`` annotation as ``run.py`` holds one; the xplane files
land, xz-compressed and whole, in ``chiprun_out/scoped_trace/`` and are kept
beside this script as ``gbdt_fit_scoped.xplane.pb.xz`` and
``ffm_steps_scoped.xplane.pb.xz``.  It prints the device time per scope as
the reader sees it, and the busy union both ways.
"""
from __future__ import annotations

import collections
import glob
import lzma
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def keep(trace_dir: Path, out: Path) -> None:
    from benchmark import trace_reduce
    from benchmark.readers import trace_scope
    path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    with open(path, "rb") as f:
        raw = f.read()
    with lzma.open(out, "wb", preset=9 | lzma.PRESET_EXTREME) as f:
        f.write(raw)
    print(f"{out.name}: {len(raw)} bytes, {out.stat().st_size} compressed")
    trace = trace_reduce.reduce(str(out))
    chips = trace_scope.load(str(out))
    print(f"  busy {trace.busy_s:.9f}s by trace_reduce, "
          f"{trace_scope.scope_s(chips, trace.window_ns, ''):.9f}s by "
          f"trace_scope, window {trace.window_s:.9f}s")
    total = collections.Counter()
    for scope, _name, s, e in chips[0]:
        total[scope] += e - s
    for scope, ns in total.most_common(60):
        print(f"  {ns / 1e6:10.3f} ms  {scope!r}")
    print("  idle gaps:", trace.top_gaps(8))


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
    out = ROOT / "chiprun_out" / "scoped_trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    rng = np.random.default_rng(0)

    rows = 65536
    bins = jnp.asarray(rng.integers(1, 256, (rows, 28)).astype(np.uint8))
    y = jnp.asarray((rng.random(rows) < 0.5).astype(np.float32))
    model = GBDT(num_features=28, num_trees=2, max_depth=6, num_bins=256,
                 missing_aware=True)
    print("levels", model.level_backends())
    jax.block_until_ready(model.fit(bins, y))
    jax.profiler.start_trace(str(out / "gbdt"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            jax.block_until_ready(model.fit(bins, y))
    jax.profiler.stop_trace()
    keep(out / "gbdt", out / "gbdt_fit_scoped.xplane.pb.xz")

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
    keep(out / "ffm", out / "ffm_steps_scoped.xplane.pb.xz")
    shutil.rmtree(out / "gbdt")
    shutil.rmtree(out / "ffm")
    return 0


if __name__ == "__main__":
    sys.exit(main())
