"""Real 2-process jax.distributed staging through DeviceStagingIter's
multi-host path (make_array_from_process_local_data + the per-batch
(has_data, num_rows, row_ptr) host allgather).

Each process parses ITS OWN file with deliberately uneven row counts, so the
local batch counts differ and the exhausted process must keep contributing
all-padding batches — the exactly-once / no-deadlock contract this path
exists for (the process-level lift of the reference's multi-rank
exactly-once split, test/unittest/unittest_inputsplit.cc:116-158).

CPU cross-process collectives ride jaxlib's Gloo backend; each process hosts
4 virtual CPU devices (8 global).
"""
import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# 2-process jax.distributed children (~80 s): full tier only
pytestmark = pytest.mark.slow

_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, f0, f1 = int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu.data import DeviceStagingIter

B, NNZ_MAX = 16, 32
mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharding = NamedSharding(mesh, P("data"))

# missing nnz_max must fail loudly, not deadlock later
bad = DeviceStagingIter(f0, batch_size=B, nnz_bucket=8, sharding=sharding,
                        format="libsvm")
try:
    next(iter(bad))
    raise SystemExit("expected ValueError without nnz_max")
except ValueError:
    pass
bad.close()

it = DeviceStagingIter(f0 if pid == 0 else f1, batch_size=B, nnz_bucket=8,
                       nnz_max=NNZ_MAX, sharding=sharding, format="libsvm")

@jax.jit
def batch_sum(label, weight):
    return jnp.sum(label * weight)

total = 0.0
rows = None
batches = 0
for b in it:
    assert b.label.shape == (2 * B,), b.label.shape
    assert b.value.shape == (2 * NNZ_MAX,), b.value.shape
    assert b.index.shape == (2 * NNZ_MAX,)
    assert b.row_ptr.shape == (2 * B + 1,), b.row_ptr.shape
    rp = np.asarray(b.row_ptr)
    assert rp[0] == 0 and (np.diff(rp) >= 0).all(), "global CSR not monotone"
    assert rp[-1] == 2 * NNZ_MAX
    total += float(batch_sum(b.label, b.weight))
    rows = int(b.num_rows)  # replicated global real-row count of this batch
    batches += 1
print("RESULT " + json.dumps({"pid": pid, "batches": batches,
                              "label_sum": total}), flush=True)

# failure propagation: process 0's stream FATALs mid-epoch (feature id >=
# 2^31 trips the staged int32 check); process 1 must raise promptly via the
# status=-1 broadcast instead of wedging in its next collective
import pathlib
bad = pathlib.Path(f0).parent / f"bad{pid}.libsvm"
rows = ["1 1:1"] * 40 + (["1 3000000000:1"] if pid == 0 else ["1 2:1"] * 40)
bad.write_text("\n".join(rows) + "\n")
it_bad = DeviceStagingIter(str(bad), batch_size=B, nnz_bucket=8,
                           nnz_max=NNZ_MAX, sharding=sharding, format="libsvm")
try:
    for b in it_bad:
        pass
    raise SystemExit("expected staging failure to propagate")
except RuntimeError as e:
    if pid == 0:  # the original native parse error
        assert "feature id" in str(e), e
    else:  # the status=-1 broadcast from the failing peer
        assert "process(es) [0]" in str(e), e
print("ERRPROP_OK", flush=True)
"""


_RECORD_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, f0, f1 = int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu.data import RecordStagingIter

mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharding = NamedSharding(mesh, P("data"))
it = RecordStagingIter(f0 if pid == 0 else f1, records_cap=8,
                       bytes_cap=1024, sharding=sharding)

@jax.jit
def chk(b):
    starts, ends = b.spans()
    mask = b.record_mask()
    first = b.bytes[jnp.clip(starts, 0, b.bytes.shape[0] - 1)].astype(jnp.int32)
    return (jnp.sum(jnp.where(mask, first, 0)),
            jnp.sum(jnp.where(mask, ends - starts, 0)))

first_sum = size_sum = records = batches = 0
for b in it:
    assert b.blocks == 2 and b.bytes.shape == (2 * 1024,), (b.blocks, b.bytes.shape)
    assert b.offsets.shape == (2 * 9,)
    f, s = chk(b)
    first_sum += int(f); size_sum += int(s)
    records += int(b.num_records)
    batches += 1
print("RESULT " + json.dumps({"pid": pid, "batches": batches,
                              "first_sum": first_sum, "size_sum": size_sum,
                              "records": records}), flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_two(child_src: str, *argv: str, label: str = "process",
             timeout: int = 300):
    """Launch the given child source as BOTH jax.distributed processes
    (pid, coordinator port, then *argv as argv[3:]), fail fast on hangs or
    nonzero exits, and return ({pid: parsed RESULT json}, {pid: stdout})."""
    port = str(_free_port())
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [subprocess.Popen(
        [sys.executable, "-c", child_src, str(p), port, *map(str, argv)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=str(REPO)) for p in (0, 1)]
    results, outs = {}, {}
    for p, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"{label} {p} hung (multi-host deadlock?)")
        assert proc.returncode == 0, f"{label} {p} failed:\n{err[-2000:]}"
        outs[p] = out
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results[p] = json.loads(line[len("RESULT "):])
    return results, outs


def test_two_process_record_staging(tmp_path):
    """RecordStagingIter multi-host path: byte-exact record spans across
    per-process blocks (padding must never leak into a record's payload),
    uneven files exercising the padding-block tail."""
    import sys as _sys
    _sys.path.insert(0, str(REPO))
    from dmlc_core_tpu.io import RecordIOWriter

    files, first_sums, size_sums, counts = [], 0, 0, 0
    for p, n_rec in ((0, 37), (1, 11)):
        f = tmp_path / f"rec{p}.rec"
        with RecordIOWriter(str(f)) as w:
            for j in range(n_rec):
                body = bytes([(p * 100 + j) % 251]) + b"x" * (j % 17)
                w.write(body)
                first_sums += body[0]
                size_sums += len(body)
                counts += 1
        files.append(str(f))

    results, _ = _run_two(_RECORD_CHILD, files[0], files[1],
                          label="record process")
    # identical global stream on both processes (modulo the pid tag)
    assert ({k: v for k, v in results[0].items() if k != "pid"}
            == {k: v for k, v in results[1].items() if k != "pid"})
    assert results[0]["records"] == counts
    assert results[0]["first_sum"] == first_sums
    assert results[0]["size_sum"] == size_sums
    assert results[0]["batches"] >= 5  # 37 records / 8-cap blocks


def test_two_process_staging_uneven_parts(tmp_path):
    # uneven: 60 rows vs 25 rows -> process 1 exhausts first and must pad
    files, sums = [], []
    for p, n_rows in ((0, 60), (1, 25)):
        f = tmp_path / f"part{p}.libsvm"
        lines, s = [], 0
        for j in range(n_rows):
            label = p * 1000 + j
            nnz = (j % 5) + 1
            feats = " ".join(f"{(j * 7 + k) % 97}:{k + 1}" for k in range(nnz))
            lines.append(f"{label} {feats}")
            s += label
        f.write_text("\n".join(lines) + "\n")
        files.append(str(f))
        sums.append(s)

    results, outs = _run_two(_CHILD, files[0], files[1])
    for p in (0, 1):
        assert "ERRPROP_OK" in outs[p], f"process {p} missed error propagation"
    assert set(results) == {0, 1}
    # both processes observe the identical global stream
    assert results[0]["batches"] == results[1]["batches"]
    assert results[0]["label_sum"] == results[1]["label_sum"]
    # exactly-once: weighted label sum equals the sum over BOTH files
    # (padding rows carry weight 0, so they are inert)
    assert results[0]["label_sum"] == float(sums[0] + sums[1])
    # ragged tail really happened: 60 rows cannot fit the batches 25 rows
    # needs, so the global batch count exceeds process 1's local need
    assert results[0]["batches"] >= 4


_CKPT_CHILD = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, out = int(sys.argv[1]), sys.argv[2], sys.argv[3]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu import checkpoint

mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharded = NamedSharding(mesh, P("data"))
local = np.arange(8, dtype=np.float32) + 100 * pid
tree = {"w": jax.make_array_from_process_local_data(sharded, local),
        "b": jnp.float32(3.5)}
n = checkpoint.save(tree, out)
print(f"SAVED pid={pid} leaves={n}", flush=True)
if pid == 0:
    arrays, meta = checkpoint.load(out)
    by_shape = {a.shape: a for a in arrays}
    w = by_shape[(16,)]
    expect = np.concatenate([np.arange(8, dtype=np.float32),
                             np.arange(8, dtype=np.float32) + 100])
    np.testing.assert_array_equal(w, expect)
    print("CKPT_OK", flush=True)
"""


def test_two_process_checkpoint_save(tmp_path):
    """checkpoint.save of a multi-host global array: all processes join the
    allgather, only process 0 writes, and the file holds the GLOBAL data."""
    out = str(tmp_path / "ckpt.rec")
    _, outs = _run_two(_CKPT_CHILD, out, label="checkpoint process")
    assert "SAVED pid=0 leaves=2" in outs[0]
    assert "SAVED pid=1 leaves=0" in outs[1]  # non-zero rank writes nothing
    assert "CKPT_OK" in outs[0]


# ONE source of truth for the 2-process GBDT tests: the global-dataset
# recipe (exec'd by the in-parent reference, concatenated into both child
# scripts) and the model hyperparameters (eval'd by the parent, pasted
# into the children) — edits here reach all three fits, so the tests
# cannot silently stop pinning the same forest.
_GBDT_RECIPE = r"""
halves = [np.random.default_rng(100 + p).uniform(-1, 1, (256, 4))
          .astype(np.float32) for p in (0, 1)]
x_all = np.concatenate(halves)
y_all = ((x_all[:, 0] > 0) ^ (x_all[:, 1] * x_all[:, 2] > 0.1)).astype(np.float32)
bins_all = np.asarray(QuantileBinner(num_bins=16).fit_transform(x_all))
"""
_GBDT_KW_SRC = ("dict(num_features=4, num_trees=2, max_depth=3, "
                "num_bins=16, learning_rate=0.5)")

_GBDT_CHILD_PRELUDE = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port = int(sys.argv[1]), sys.argv[2]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu.models import GBDT, QuantileBinner
from dmlc_core_tpu.parallel import MeshPlan

# both processes deterministically regenerate the GLOBAL dataset, bin with
# shared global cuts, then contribute only their half of the rows
""" + _GBDT_RECIPE + r"""
mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharding = NamedSharding(mesh, P("data"))
lo, hi = pid * 256, (pid + 1) * 256
bins_g = jax.make_array_from_process_local_data(sharding, bins_all[lo:hi])
label_g = jax.make_array_from_process_local_data(sharding, y_all[lo:hi])
kw = """ + _GBDT_KW_SRC + "\n"

_GBDT_CHILD = _GBDT_CHILD_PRELUDE + r"""
forest = GBDT(**kw).fit(bins_g, label_g)
print("RESULT " + json.dumps({
    "pid": pid,
    "feature": np.asarray(forest["feature"]).tolist(),
    "threshold": np.asarray(forest["threshold"]).tolist(),
    "leaf": np.round(np.asarray(forest["leaf"]), 5).tolist(),
    "base": round(float(forest["base"]), 6)}), flush=True)
"""


def test_two_process_gbdt_histogram_allreduce():
    """GBDT fit over jax.distributed: each process contributes half the
    rows; the per-level histogram segment-sum crosses the process boundary
    (Gloo collectives standing in for ICI/DCN), and the forest must equal a
    single-process fit on the full data — the multi-host lift of the rabit
    histogram allreduce the reference's tracker brokers."""
    import sys as _sys
    _sys.path.insert(0, str(REPO))
    import numpy as np

    results, _ = _run_two(_GBDT_CHILD, label="gbdt process")
    assert set(results) == {0, 1}
    # both processes hold the identical replicated forest
    assert ({k: v for k, v in results[0].items() if k != "pid"}
            == {k: v for k, v in results[1].items() if k != "pid"})

    # single-process reference on the concatenated data — same recipe
    # string the children embed, exec'd here
    from dmlc_core_tpu.models import GBDT, QuantileBinner
    import jax.numpy as jnp
    ns = {"np": np, "QuantileBinner": QuantileBinner}
    exec(_GBDT_RECIPE, ns)  # noqa: S102 — shared single-source recipe
    ref = GBDT(**eval(_GBDT_KW_SRC)).fit(ns["bins_all"],
                                         jnp.asarray(ns["y_all"]))
    assert results[0]["feature"] == np.asarray(ref["feature"]).tolist()
    assert results[0]["threshold"] == np.asarray(ref["threshold"]).tolist()
    np.testing.assert_allclose(np.asarray(results[0]["leaf"]),
                               np.asarray(ref["leaf"]), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(results[0]["base"], float(ref["base"]),
                               atol=2e-6)


# same prelude (dataset + sharded global arrays) as _GBDT_CHILD; here the
# per-level histogram runs the Pallas kernel PER PROCESS-LOCAL DEVICE
# under shard_map and the explicit psum crosses the process boundary over
# Gloo — the sharded-kernel route (histogram_mesh) in a real multi-host
# setting
_GBDT_MESH_CHILD = _GBDT_CHILD_PRELUDE + r"""
forest_x = GBDT(histogram="xla", **kw).fit(bins_g, label_g)
forest_p = GBDT(histogram="pallas",
                histogram_mesh=MeshPlan(mesh, ("data",)),
                **kw).fit(bins_g, label_g)
match = (np.array_equal(np.asarray(forest_x["feature"]),
                        np.asarray(forest_p["feature"]))
         and np.array_equal(np.asarray(forest_x["threshold"]),
                            np.asarray(forest_p["threshold"]))
         and np.allclose(np.asarray(forest_x["leaf"]),
                         np.asarray(forest_p["leaf"]),
                         rtol=1e-3, atol=1e-4))
print("RESULT " + json.dumps({
    "pid": pid,
    "routes_match": bool(match),
    "feature": np.asarray(forest_p["feature"]).tolist(),
    "leaf": np.round(np.asarray(forest_p["leaf"]), 5).tolist()}), flush=True)
"""


def test_two_process_gbdt_histogram_mesh_kernel_route():
    """The sharded-kernel route across a REAL process boundary: two
    jax.distributed processes, each running the Pallas histogram kernel
    (interpret mode on CPU) on its local row shard under shard_map, the
    explicit psum riding Gloo — and the forest must equal the GSPMD/XLA
    route's fit of the same global data, in-child, on both processes."""
    results, _ = _run_two(_GBDT_MESH_CHILD, label="gbdt mesh process")
    assert set(results) == {0, 1}
    assert results[0]["routes_match"] and results[1]["routes_match"]
    # both processes hold the identical replicated kernel-route forest
    assert results[0]["feature"] == results[1]["feature"]
    assert results[0]["leaf"] == results[1]["leaf"]


_SPARSE_GBDT_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, f0, f1 = int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu.data import DeviceStagingIter
from dmlc_core_tpu.models import GBDT, QuantileBinner

mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharding = NamedSharding(mesh, P("data"))

# THE real path: each process stages ITS OWN shard; the multi-host staging
# layer assembles the global fixed-shape batch
it = DeviceStagingIter(f0 if pid == 0 else f1, batch_size=64,
                       nnz_bucket=64, nnz_max=512, sharding=sharding,
                       format="libsvm")
batches = list(it)
assert len(batches) == 1, len(batches)
batch = batches[0]

# shared binner: per-feature cuts sketched from the UNION of both shards
# (both processes read both tiny files, so the cuts are identical)
idx_all, val_all = [], []
for path in (f0, f1):
    for line in open(path):
        for tok in line.split()[1:]:
            i, v = tok.split(":")
            idx_all.append(int(i)); val_all.append(float(v))
binner = QuantileBinner(num_bins=16, missing_aware=True)
binner.fit_sparse(np.asarray(idx_all), np.asarray(val_all, np.float32),
                  num_features=6)

model = GBDT(num_features=6, num_trees=3, max_depth=3, num_bins=16,
             learning_rate=0.5, missing_aware=True)
forest = model.fit_batch(batch, binner)
print("RESULT " + json.dumps({
    "pid": pid,
    "feature": np.asarray(forest["feature"]).tolist(),
    "threshold": np.asarray(forest["threshold"]).tolist(),
    "default_right": np.asarray(forest["default_right"]).tolist(),
    "leaf": np.round(np.asarray(forest["leaf"]), 5).tolist(),
    "base": round(float(forest["base"]), 6)}), flush=True)
"""


def test_two_process_sparse_gbdt_end_to_end(tmp_path):
    """The whole stack, multi-host: per-process libsvm shards -> multi-host
    DeviceStagingIter (fixed-shape global batches over jax.distributed) ->
    sparse-native fit_batch (O(nnz) histograms with cross-process psum) ->
    forest equal to a single-process dense-reference fit on the union."""
    import sys as _sys
    _sys.path.insert(0, str(REPO))
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    files, all_rows = [], []
    for p, n_rows in ((0, 40), (1, 24)):
        f = tmp_path / f"gshard{p}.libsvm"
        lines = []
        for _ in range(n_rows):
            nnz = int(rng.integers(2, 6))
            idx = np.sort(rng.choice(6, size=nnz, replace=False))
            lut = {int(i): float(rng.uniform(0.2, 2.0)) for i in idx}
            y = int((0 in lut) ^ (lut.get(1, 0.0) > 1.0))
            lines.append((y, lut))
            all_rows.append((y, lut))
        f.write_text("\n".join(
            f"{y} " + " ".join(f"{i}:{v:.6f}" for i, v in lut.items())
            for y, lut in lines) + "\n")
        files.append(str(f))

    results, _ = _run_two(_SPARSE_GBDT_CHILD, files[0], files[1],
                          label="sparse gbdt process")
    assert set(results) == {0, 1}
    assert ({k: v for k, v in results[0].items() if k != "pid"}
            == {k: v for k, v in results[1].items() if k != "pid"})

    # single-process reference: dense missing-aware fit on the union
    from dmlc_core_tpu.models import GBDT, QuantileBinner
    dense = np.full((len(all_rows), 6), np.nan, np.float32)
    y = np.zeros(len(all_rows), np.float32)
    idx_all, val_all = [], []
    for r, (label, lut) in enumerate(all_rows):
        y[r] = label
        for i, v in lut.items():
            dense[r, i] = v
            idx_all.append(i)
            val_all.append(v)
    binner = QuantileBinner(num_bins=16, missing_aware=True)
    binner.fit_sparse(np.asarray(idx_all), np.asarray(val_all, np.float32),
                      num_features=6)
    model = GBDT(num_features=6, num_trees=3, max_depth=3, num_bins=16,
                 learning_rate=0.5, missing_aware=True)
    ref = model.fit(binner.transform(jnp.asarray(dense)), jnp.asarray(y))
    for k in ("feature", "threshold", "default_right"):
        assert results[0][k] == np.asarray(ref[k]).tolist(), k
    np.testing.assert_allclose(np.asarray(results[0]["leaf"]),
                               np.asarray(ref["leaf"]), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(results[0]["base"], float(ref["base"]),
                               atol=2e-6)


_FFM_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, f0, f1 = int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu.data import DeviceStagingIter
from dmlc_core_tpu.models import FieldAwareFactorizationMachine

mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharding = NamedSharding(mesh, P("data"))

# each process stages ITS OWN libfm shard WITH the field lane; the
# multi-host layer assembles one global fixed-shape batch
it = DeviceStagingIter(f0 if pid == 0 else f1, batch_size=64,
                       nnz_bucket=64, nnz_max=256, sharding=sharding,
                       with_field=True, format="libfm")
batches = list(it)
assert len(batches) == 1, len(batches)
batch = batches[0]
assert batch.field is not None

ffm = FieldAwareFactorizationMachine(num_features=16, num_fields=2,
                                     num_factors=8, learning_rate=0.5,
                                     init_scale=0.1)
params = ffm.init(seed=1)

import jax.numpy as jnp

# all 200 SGD steps in ONE jitted dispatch: per-step dispatches would pay
# a cross-process Gloo collective round-trip each, minutes on this rig.
# The global batch must be an ARGUMENT (closing over a multi-host array
# in jit is rejected), and per-row results must reduce to replicated
# scalars in-jit (non-addressable shards cannot be fetched to host).
@jax.jit
def train_200(p, b):
    def body(p, _):
        l, g = jax.value_and_grad(ffm.loss)(p, b)
        return jax.tree.map(
            lambda a, g_: a - ffm.learning_rate * g_, p, g), l
    return jax.lax.scan(body, p, None, length=200)

@jax.jit
def accuracy(p, b):
    pred = ffm.predict(p, b) > 0.5
    y = b.label > 0.5
    return jnp.sum((pred == y) * b.weight) / jnp.sum(b.weight)

params, losses = train_200(params, batch)
loss0, loss = float(losses[0]), float(losses[-1])
acc = float(accuracy(params, batch))
print("RESULT " + json.dumps({
    "pid": pid,
    "num_rows": int(batch.num_rows),
    "loss0": round(loss0, 6), "loss": round(float(loss), 6),
    "acc": round(acc, 4),
    "w_sum": round(float(np.abs(np.asarray(params["w"])).sum()), 5),
    "v_sum": round(float(np.abs(np.asarray(params["v"])).sum()), 5)}),
    flush=True)
"""


def test_two_process_ffm_field_lane_end_to_end(tmp_path):
    """The field lane, multi-host: per-process libfm shards (with_field
    staging) -> global batches over jax.distributed -> FFM SGD fitting a
    field-pairing signal; both processes converge to the SAME replicated
    params and the real (weight>0) rows classify correctly."""
    import numpy as np

    rng = np.random.default_rng(5)
    files = []
    for p, n_rows in ((0, 40), (1, 24)):
        f = tmp_path / f"fshard{p}.libfm"
        lines = []
        for _ in range(n_rows):
            u = int(rng.integers(0, 8))
            i = int(rng.integers(0, 8))
            y = 1 if (u + i) % 2 == 0 else 0
            lines.append(f"{y} 0:{u}:1 1:{8 + i}:1")
        f.write_text("\n".join(lines) + "\n")
        files.append(str(f))

    results, _ = _run_two(_FFM_CHILD, files[0], files[1],
                          label="ffm process")
    assert set(results) == {0, 1}
    r0, r1 = results[0], results[1]
    # replicated params identical across processes; field model fits the
    # pairing signal; the global batch carries exactly the union's rows
    assert {k: v for k, v in r0.items() if k != "pid"} \
        == {k: v for k, v in r1.items() if k != "pid"}
    assert r0["num_rows"] == 64
    assert r0["loss"] < 0.3 * r0["loss0"], (r0["loss0"], r0["loss"])
    assert r0["acc"] > 0.95, r0["acc"]


_PARALLEL_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, port, f0, f1 = int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=pid)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from dmlc_core_tpu.data import DeviceStagingIter

mesh = Mesh(np.asarray(jax.devices()), ("data",))
sharding = NamedSharding(mesh, P("data"))

@jax.jit
def wsum(label, weight):
    return jnp.sum(label * weight)

@jax.jit
def vsum(value):
    return jnp.sum(value)

def drain(nw):
    it = DeviceStagingIter(f0 if pid == 0 else f1, batch_size=16,
                           nnz_bucket=8, nnz_max=32, sharding=sharding,
                           format="libsvm", num_workers=nw)
    sig = []
    for b in it:
        sig.append((int(b.num_rows), round(float(wsum(b.label, b.weight)), 6),
                    round(float(vsum(b.value)), 6),
                    np.asarray(b.row_ptr).tolist()))
    return sig

ref = drain(1)
par = drain(2)
assert par == ref, "2-worker multi-host stream diverged from 1-worker"
print("RESULT " + json.dumps({"pid": pid, "batches": len(ref),
                              "label_sum": sum(s[1] for s in ref)}),
      flush=True)
"""


def test_two_process_staging_parallel_workers_lockstep(tmp_path):
    """Multi-host lockstep with the sharded worker pool: each process
    stages its (uneven) shard with num_workers=2 and must observe the
    SAME global batch stream as with num_workers=1 — the per-batch
    allgather rounds stay aligned because the pool is deterministic and
    the virtual-part count depends only on the dataset, never on the
    worker count."""
    files, sums = [], []
    for p, n_rows in ((0, 60), (1, 25)):
        f = tmp_path / f"wpart{p}.libsvm"
        lines, s = [], 0
        for j in range(n_rows):
            label = p * 1000 + j
            nnz = (j % 5) + 1
            feats = " ".join(f"{(j * 7 + k) % 97}:{k + 1}" for k in range(nnz))
            lines.append(f"{label} {feats}")
            s += label
        f.write_text("\n".join(lines) + "\n")
        files.append(str(f))
        sums.append(s)

    results, _ = _run_two(_PARALLEL_CHILD, files[0], files[1],
                          label="parallel staging process")
    assert set(results) == {0, 1}
    assert results[0]["batches"] == results[1]["batches"]
    assert results[0]["label_sum"] == results[1]["label_sum"]
    assert results[0]["label_sum"] == float(sums[0] + sums[1])


# -- job-wide observability plane over a real 2-process epoch ----------------

_TELEMETRY_CHILD = r"""
import json, os, sys, time
pid, port, mport, f0, f1 = (int(sys.argv[1]), sys.argv[2], sys.argv[3],
                            sys.argv[4], sys.argv[5])
# the env contract a tracker launcher ships (RabitTracker.worker_envs):
# set BEFORE the staging import path so _observability_scope arms the
# pusher automatically -- this child never touches the metrics API during
# the epoch, proving the zero-code-change wiring.  Each worker stages its
# OWN shard single-host (the tracker channel is the cross-process piece
# under test; it must work no matter how the data plane is sharded).
os.environ["DMLC_TRACKER_URI"] = "127.0.0.1"
os.environ["DMLC_TRACKER_METRICS_PORT"] = mport
os.environ["DMLC_WORKER_RANK"] = str(pid)
os.environ["DMLCTPU_METRICS_INTERVAL_S"] = "0.3"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from urllib.request import urlopen
from dmlc_core_tpu import telemetry, telemetry_http
from dmlc_core_tpu.data import DeviceStagingIter
from dmlc_core_tpu.tracker import metrics as tmetrics

@jax.jit
def wsum(label, weight):
    return jnp.sum(label * weight)

stalls0 = telemetry.watchdog_stall_count()
srv = telemetry_http.serve(port=0)
scraped = None
label_sum = 0.0
batches = 0
# watchdog false-positive check, two-process flavor: a slow-but-
# progressing consumer (sleep per batch) must never trip a 2 s deadline
# because every poll sees SOME counter move
with telemetry.watchdog(deadline_s=2.0, poll_s=0.1):
    it = DeviceStagingIter(f0 if pid == 0 else f1, batch_size=16,
                           nnz_bucket=8, nnz_max=32, format="libsvm")
    for b in it:
        if scraped is None:
            # live scrape DURING the epoch, not after it
            with urlopen(srv.url + "/metrics", timeout=10) as r:
                assert r.status == 200, r.status
                assert r.headers["Content-Type"].startswith("text/plain"), \
                    r.headers["Content-Type"]
                scraped = r.read().decode()
        label_sum += float(wsum(b.label, b.weight))
        batches += 1
        time.sleep(0.05)
stalls = telemetry.watchdog_stall_count() - stalls0
srv.close()
# the iterator armed the pusher from env (ensure_pusher gates on env only,
# so this holds even in stub builds); stop it WITH a final push so the
# tracker is guaranteed to hold this process's end-of-epoch counters
assert tmetrics._pusher is not None, "staging iterator never armed pusher"
tmetrics.stop_pusher(final_push=True)
snap = telemetry.snapshot()
counters = snap.get("counters", {})
print("RESULT " + json.dumps({
    "pid": pid, "batches": batches, "label_sum": label_sum,
    "stalls": stalls,
    "enabled": bool(snap.get("enabled", False)),
    "split_bytes": counters.get("split.bytes", 0),
    "parse_rows": counters.get("parse.rows", 0),
    "scrape_ok": scraped is not None,
    "scrape_has_registry": "dmlctpu_" in (scraped or "")}), flush=True)
"""


def test_two_process_tracker_metrics_aggregation(tmp_path):
    """The tracker-side aggregation acceptance: two worker processes stage
    their own shards while pushing snapshots to an in-parent
    MetricsAggregator over the env-negotiated side channel; the tracker's
    job_snapshot() per-host byte/row counters must sum exactly to the
    totals a single process staging both files would have seen.  Also
    covers the in-worker /metrics endpoint serving Prometheus text DURING
    the epoch and the no-false-positive watchdog contract under real
    two-process batch cadence."""
    import sys as _sys
    _sys.path.insert(0, str(REPO))
    from dmlc_core_tpu.tracker.metrics import MetricsAggregator
    from dmlc_core_tpu import telemetry_http

    files, sums, rows_total = [], [], 0
    for p, n_rows in ((0, 60), (1, 25)):
        f = tmp_path / f"tpart{p}.libsvm"
        lines, s = [], 0
        for j in range(n_rows):
            label = p * 1000 + j
            nnz = (j % 5) + 1
            feats = " ".join(f"{(j * 7 + k) % 97}:{k + 1}" for k in range(nnz))
            lines.append(f"{label} {feats}")
            s += label
        f.write_text("\n".join(lines) + "\n")
        files.append(str(f))
        sums.append(s)
        rows_total += n_rows

    agg = MetricsAggregator(host_ip="127.0.0.1", port=0)
    try:
        results, _ = _run_two(_TELEMETRY_CHILD, str(agg.port), files[0],
                              files[1], label="telemetry process")
        assert set(results) == {0, 1}
        for p in (0, 1):
            assert results[p]["stalls"] == 0, \
                f"watchdog false positive on process {p}"
            assert results[p]["scrape_ok"], f"process {p} never scraped"
            # each worker's epoch stayed correct under the observability
            # plane (padding rows carry weight 0, so they are inert)
            assert results[p]["label_sum"] == float(sums[p])

        view = agg.job_snapshot()
        assert view["num_hosts"] == 2 and set(view["hosts"]) == {0, 1}
        assert view["restarted"] is False
        fleet = view["fleet"]["counters"]
        if results[0]["enabled"]:
            # per-host counters sum EXACTLY to the single-process totals:
            # each worker parsed only its own file, so the fleet merge must
            # add the per-host values without loss — the same arithmetic a
            # single process staging both files would have accumulated.
            # (Each host's count is a whole multiple of its file's rows:
            # the batcher's eager prefetch + BeforeFirst rewind may parse a
            # small file twice, the record.bytes caveat in
            # doc/observability.md — a throughput metric, not exact-IO.)
            for rank, n_rows in ((0, 60), (1, 25)):
                host_c = view["hosts"][rank]["snapshot"]["counters"]
                assert host_c["parse.rows"] == results[rank]["parse_rows"]
                assert host_c["split.bytes"] == results[rank]["split_bytes"]
                assert host_c["parse.rows"] >= n_rows
                assert host_c["parse.rows"] % n_rows == 0
            assert fleet["parse.rows"] >= rows_total
            assert fleet["parse.rows"] == (results[0]["parse_rows"]
                                           + results[1]["parse_rows"])
            assert fleet["split.bytes"] == (results[0]["split_bytes"]
                                            + results[1]["split_bytes"])
            assert fleet["split.bytes"] >= sum(
                os.path.getsize(f) for f in files)
            assert results[0]["scrape_has_registry"]
            # per-host attribution made it into the job view
            for rank in (0, 1):
                attr = view["hosts"][rank]["attribution"]
                assert set(attr["stages"])
                assert attr["wall_s"] is None or attr["wall_s"] >= 0.0

        # the human-facing table renders both ranks, worst-bound first
        table = agg.format_job_table()
        assert "rank" in table.splitlines()[0]
        assert len(table.splitlines()) == 3, table

        # tracker-side live export: one exposition, host-labeled per rank
        with telemetry_http.serve(port=0, provider=agg.provider) as srv:
            from urllib.request import urlopen
            with urlopen(srv.url + "/metrics", timeout=10) as r:
                assert r.status == 200
                text = r.read().decode()
        if results[0]["enabled"]:
            assert 'rank="0"' in text and 'rank="1"' in text
            assert "dmlctpu_parse_rows_total" in text
    finally:
        agg.close()


_SHARD_HANDOFF_CHILD = r"""
import json, sys, time
pid, _coord, mport, recfile = (int(sys.argv[1]), sys.argv[2], sys.argv[3],
                               sys.argv[4])
import jax
jax.config.update("jax_platforms", "cpu")
from dmlc_core_tpu import telemetry
from dmlc_core_tpu.data import RecordStagingIter
from dmlc_core_tpu.tracker.metrics import ShardClient, push_once

client = ShardClient("127.0.0.1", int(mport), rank=pid)
it = RecordStagingIter(recfile, records_cap=4, bytes_cap=512,
                       part=pid, num_parts=2)
if pid == 0:
    # the straggler: report a restart (a persistent flag on the tracker,
    # one of the handoff drivers) and parse each claimed shard slowly
    push_once("127.0.0.1", int(mport), rank=0, restarted=True)
else:
    # let the straggler register its shard set before this worker can
    # finish its own and reach the steal loop
    time.sleep(0.5)

ids, nrec = [], 0
for w in it.host_batches_coordinated(epoch=7, client=client):
    offs, n = w["offsets"], int(w["num_records"])
    for k in range(n):
        o = int(offs[k])
        ids.append(int(w["bytes"][o]) * 256 + int(w["bytes"][o + 1]))
    nrec += n
    if pid == 0:
        time.sleep(0.25)
print("RESULT " + json.dumps({
    "pid": pid, "records": nrec, "ids": sorted(ids),
    "enabled": telemetry.enabled(),
    "steals": telemetry.counter_get("shard.steal_gained"),
    "denied": telemetry.counter_get("shard.claim_denied")}), flush=True)
"""


def test_two_process_straggler_shard_handoff(tmp_path):
    """The work-stealing acceptance: two workers split one recordio file
    via tracker-coordinated shard ownership; worker 0 is a flagged
    straggler (restart-reported, 0.25 s per batch), worker 1 drains its own
    shards and must steal >= 1 pending shard from worker 0 — and the UNION
    of records parsed by the two workers must be the file's record set
    exactly once (bit-identical total visitation through the handoff)."""
    import sys as _sys
    _sys.path.insert(0, str(REPO))
    from dmlc_core_tpu.io import RecordIOWriter
    from dmlc_core_tpu.tracker.metrics import MetricsAggregator

    n_records = 200
    f = tmp_path / "handoff.rec"
    with RecordIOWriter(str(f)) as w:
        for j in range(n_records):
            # 2-byte unique id prefix so visitation is checkable per record
            w.write(bytes([j // 256, j % 256]) + b"p" * (8 + j % 24))

    agg = MetricsAggregator(host_ip="127.0.0.1", port=0)
    try:
        results, _ = _run_two(_SHARD_HANDOFF_CHILD, str(agg.port), str(f),
                              label="handoff process")
        assert set(results) == {0, 1}
        r0, r1 = results[0], results[1]
        # exactly-once job-wide visitation, bit-identical record ids
        assert r0["records"] + r1["records"] == n_records
        assert sorted(r0["ids"] + r1["ids"]) == list(range(n_records))
        # the flagged straggler lost at least one shard to the healthy host
        view = agg.job_snapshot()
        board = view["shards"]["7"]
        assert board["pending"] == 0
        assert len(board["stolen"]) >= 1, (board, r0, r1)
        assert all(h["from"] == 0 and h["to"] == 1 for h in board["stolen"])
        if r1["enabled"]:  # worker-side counters mirror the board
            assert r1["steals"] == len(board["stolen"])
        assert 0 in agg.flagged_ranks()  # the restart flag is persistent
    finally:
        agg.close()
