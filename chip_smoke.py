#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the flagship model (histogram GBDT at Higgs
width: 28 features, 256 bins, depth 6; three trees, weights from a seed):

    device -> native build -> feed -> train -> kernels -> checkpoint
           -> serve -> mesh (two or more devices)

    libsvm bytes on disk -> DeviceStagingIter -> GBDT (fit_streamed: sparse
    Pallas kernel; fit: dense Pallas kernel) -> checkpoint -> ScoringServer

and checks at every step that what came out is right by the repo's own
means: staged content against an independent host parse, each forest
against the same fit on XLA scatter, each kernel against its XLA reference
(the entry lookup against XLA's gather, the entries' push against its
scatter-add and the layout's binning against the bisection, bit for bit,
at the benchmark's sparse cell's 1,183,747 rows and 2.18e8 entry lanes:
3 GB of the chip for a moment), /score against
predict_batch, sharded against single-device.

It claims no speed.  It exits non-zero at the first failure and prints
nothing on standard output then.  On success standard output is two lines,
each one JSON object: the full summary (phases, kernels, versions, cache;
also written to ``summary.json`` under ``--out``), and last the verdict with
exactly these keys, the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU it fails in the first phase.  ``--rehearse-cpu`` (which also
needs ``JAX_PLATFORMS=cpu`` in the environment) walks the same phases at
tiny sizes with interpreted kernels, to debug the script itself off the
chip; its summary says ``"rehearsal": true`` and proves nothing about a
device.

    python chip_smoke.py [--expect-devices N] [--out DIR]
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-cpu
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
import traceback
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

# Flagship width at both sizes; rows, depth and trees are what is cut.  Row
# counts leave the last batch short (padding is part of the path) and divide
# by eight, so a mesh fit sees every row the one-device fit saw.
FEATURES = 28
FULL = dict(rows=8 * 16384 + 1000, batch_size=16384, bins=256, depth=6,
            trees=3, kernel_rows=65536, score_rows=(1, 7, 64, 300),
            # the benchmark's Bosch configuration: what the entry lookup
            # kernel is sized for (its table in VMEM, 2.18e8 entry lanes)
            lookup=dict(rows=1183747, features=968, stations=52, share=0.19))
TINY = dict(rows=2 * 512 + 104, batch_size=512, bins=32, depth=3,
            trees=2, kernel_rows=1024, score_rows=(1, 7, 40),
            lookup=dict(rows=40000, features=9, stations=4, share=0.19))


T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


class Failure(Exception):
    """A check the smoke makes did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise Failure(msg)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def cache_entries(cache_dir: str) -> int:
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for n in os.listdir(cache_dir) if not n.endswith("-atime"))


# The program's own set-up counters (doc/observability.md, "Trace spans"): the
# compile meter that ``compile_cache.configure()`` registers and the totals of
# the spans set-up's work runs under.  The benchmark reads the same ones.
SETUP_TABLE = (
    ("program_seconds", "main.span_us"),
    ("trace_seconds", "compile.trace_us"),
    ("lower_seconds", "compile.lower_us"),
    ("backend_seconds", "compile.backend_us"),
    ("fetch_seconds", "compile.fetch_us"),
    ("binner_seconds", "binner.fit_us"),
    ("init_seconds", "model.init_us"),
    ("fit_seconds", "gbdt.fit_us"),
)
COMPILE_STAGES = ("trace_seconds", "lower_seconds", "backend_seconds")


def setup_counters() -> dict:
    """The table's seconds and the meter's counts as they stand; zeros until
    the package is imported (nothing compiles before that)."""
    telemetry = getattr(sys.modules.get("dmlc_core_tpu"), "telemetry", None)
    read = telemetry.counter_get if telemetry else (lambda name: 0)
    out = {key: read(name) / 1e6 for key, name in SETUP_TABLE}
    out.update(programs=read("compile.programs"),
               cache_hits=read("compile.cache_hits"),
               cache_misses=read("compile.cache_misses"))
    return out


def compile_since(mark: dict) -> dict:
    """A phase's share of getting executables: seconds tracing, lowering and
    compiling (or fetching from the persistent cache), how many programs,
    how many came from the cache.  The rest of its wall clock is it running."""
    now = setup_counters()
    return {"compile_seconds": round(sum(now[k] - mark[k]
                                         for k in COMPILE_STAGES), 2),
            "programs": now["programs"] - mark["programs"],
            "cache_hits": now["cache_hits"] - mark["cache_hits"]}


def slowest_programs(n: int = 5) -> list:
    """``compile_cache.programs()``'s ``n`` dearest rows: which programs the
    compile seconds went to."""
    from dmlc_core_tpu import compile_cache
    cost = lambda row: sum(row.get(k, 0.0)                  # noqa: E731
                           for k in ("trace_s", "lower_s", "backend_s"))
    rows = sorted(compile_cache.programs().items(),
                  key=lambda item: -cost(item[1]))[:n]
    return [{"program": name, "seconds": round(cost(row), 2),
             **{k: round(v, 2) if isinstance(v, float) else v
                for k, v in row.items()}} for name, row in rows]


# ---- phases -----------------------------------------------------------------

def phase_device(ctx: dict) -> dict:
    import jax
    import jaxlib
    args = ctx["args"]
    backend = jax.default_backend()
    if args.rehearse_cpu:
        require(os.environ.get("JAX_PLATFORMS") == "cpu" and backend == "cpu",
                "--rehearse-cpu needs JAX_PLATFORMS=cpu in the environment "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
                f"backend {backend!r})")
    else:
        require(backend == "tpu",
                f"no TPU: jax.default_backend() is {backend!r} "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
                "--rehearse-cpu walks the phases off the chip")
    devices = jax.devices()
    if args.expect_devices is not None:
        require(len(devices) == args.expect_devices,
                f"--expect-devices {args.expect_devices}: JAX found "
                f"{len(devices)} {backend} device(s)")
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    ctx["device"] = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices)}
    ctx["versions"] = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                       "libtpu": libtpu_version,
                       "python": sys.version.split()[0]}
    return dict(ctx["device"])


def phase_native(ctx: dict) -> dict:
    # importing the package builds cpp/ into build/libdmlctpu.so when the
    # library is missing or older than the sources (an incremental ninja
    # run under the build lock), so a fresh checkout compiles it right here
    import dmlc_core_tpu
    info = dmlc_core_tpu.native_build_info()
    want = HERE / "build" / "libdmlctpu.so"
    require(Path(info["library"]) == want,
            f"loaded {info['library']}, not this checkout's {want} "
            "(unset DMLCTPU_LIBRARY_PATH)")
    from dmlc_core_tpu import compile_cache, telemetry
    require(telemetry.enabled(), "native runtime built without telemetry: "
            "the smoke reads its counters")
    cache_dir = compile_cache.configure()
    ctx["cache"] = {"dir": cache_dir,
                    "from_env": bool(os.environ.get(
                        "JAX_COMPILATION_CACHE_DIR")),
                    "entries_before": cache_entries(cache_dir)}
    if ctx["device"]["platform"] == "tpu":
        hbm = telemetry.resource_sample().get("resource.hbm_bytes_limit")
        require(hbm, "the TPU reported no memory limit "
                "(telemetry.resource_sample)")
        info["hbm_bytes_limit"] = int(hbm)
    ctx["native"] = info
    return info


def write_dataset(path: Path, rows: int, seed: int = 12):
    """Higgs-shaped libsvm from a seed: 28 float features a row, a label
    trees can learn and a linear model cannot."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, FEATURES)).astype(np.float32)
    # the staging convention reads a stored 0 as an absent entry; keep every
    # value printable as non-zero at six decimals
    x = np.where(np.abs(x) < 1e-3, np.float32(1e-3), x)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0.3) ^ (x[:, 2] > 1.0)).astype(np.float32)
    fmt = "%d " + " ".join(f"{j}:%.6f" for j in range(FEATURES))
    with open(path, "w") as f:
        np.savetxt(f, np.column_stack([y, x]), fmt=fmt)


def host_parse(uri: str):
    """The reference the staged content is held against: one pass of the
    host Parser, no staging, no device."""
    from dmlc_core_tpu import Parser
    labels, index, value, counts = [], [], [], []
    with Parser(uri, format="libsvm") as parser:
        for block in parser:
            labels.append(block.label)
            index.append(block.index.astype(np.int64))
            value.append(block.values_or_ones())
            counts.append(np.diff(block.offset).astype(np.int64))
    return (np.concatenate(labels), np.concatenate(index),
            np.concatenate(value), np.concatenate(counts))


def content_sums(label, index, value, rows: int, nnz: int) -> dict:
    v = np.asarray(value, np.float64)
    return {"rows": int(rows), "nnz": int(nnz),
            "sum_label": float(np.sum(np.asarray(label, np.float64))),
            "sum_value": float(np.sum(v)),
            "sum_index_value": float(np.sum(np.asarray(index, np.float64) * v))}


def staged_sums(batches) -> dict:
    """The same sums over an epoch of staged batches, read back from the
    devices they sit on."""
    return content_sums(
        np.concatenate([np.asarray(b.label) for b in batches]),
        np.concatenate([np.asarray(b.index) for b in batches]),
        np.concatenate([np.asarray(b.value) for b in batches]),
        sum(int(b.num_rows) for b in batches),
        sum(int(np.asarray(b.row_ptr)[-1]) for b in batches))


def require_same_sums(got: dict, want: dict, what: str) -> None:
    for k, w in want.items():
        g = got[k]
        same = g == w if isinstance(w, int) else abs(g - w) <= 1e-9 * max(
            abs(w), 1.0)
        require(same, f"{what}: {k} staged {g!r} != host parse {w!r}")


def require_on_devices(batch, platform: str, what: str) -> None:
    import jax
    for leaf in jax.tree.leaves(batch):
        require(isinstance(leaf, jax.Array),
                f"{what}: a staged leaf is {type(leaf).__name__}, "
                "not a jax.Array")
        require(all(d.platform == platform for d in leaf.devices()),
                f"{what}: a staged leaf sits on "
                f"{sorted(d.platform for d in leaf.devices())}, not {platform}")


def phase_feed(ctx: dict) -> dict:

    from dmlc_core_tpu import DeviceStagingIter
    from dmlc_core_tpu.models import QuantileBinner
    size, platform = ctx["size"], ctx["device"]["platform"]
    uri = str(ctx["out"] / "higgs_shaped.libsvm")
    t0 = time.monotonic()
    write_dataset(Path(uri), size["rows"])
    label, index, value, counts = host_parse(uri)
    want = content_sums(label, index, value, len(label), len(index))
    require(want["rows"] == size["rows"], f"host parse saw {want['rows']} "
            f"rows, wrote {size['rows']}")
    write_parse_s = time.monotonic() - t0

    # Every batch is held until the epoch is over and only then read back:
    # the leaves were zero-copy views over native arenas that return to the
    # pool once JAX lets go of them, so a put that aliased host memory or a
    # DMA still in flight would show up here as another batch's bytes.
    stage_opts = dict(batch_size=size["batch_size"], num_workers=4)
    ctx["stage_opts"] = stage_opts
    t0 = time.monotonic()
    it = DeviceStagingIter(uri, **stage_opts)
    batches = list(it)
    it.close()
    stage_s = time.monotonic() - t0
    for b in batches:
        require_on_devices(b, platform, "text feed")
    require_same_sums(staged_sums(batches), want, "text feed")
    require(it.max_index == FEATURES - 1,
            f"max_index {it.max_index}, want {FEATURES - 1}")

    # The binned cache's hit path puts arena views with donate=True.  Same
    # check, against the text epoch just verified: bin codes lane for lane.
    binner = QuantileBinner(num_bins=size["bins"], missing_aware=True)
    binner.fit_sparse(index, value, FEATURES)
    cache = str(ctx["out"] / "higgs_shaped.bincache")
    binned_it = DeviceStagingIter(uri, bin_cache=cache, binner=binner,
                                  **stage_opts)
    list(binned_it)                      # builds the cache
    hit = list(binned_it)                # served from it
    require(len(hit) == len(batches), f"bincache epoch has {len(hit)} "
            f"batches, text epoch {len(batches)}")
    for i, (b, t) in enumerate(zip(hit, batches)):
        require_on_devices(b, platform, "bincache feed")
        want_bin = np.asarray(binner.transform_entries(t.index, t.value))
        live = np.asarray((t.value != 0))
        require(np.array_equal(np.asarray(b.emask), live)
                and np.array_equal(np.asarray(b.ebin)[live], want_bin[live])
                and np.array_equal(np.asarray(b.index), np.asarray(t.index))
                and np.array_equal(np.asarray(b.label), np.asarray(t.label)),
                f"bincache batch {i} differs from the text batch")

    ctx.update(uri=uri, binner=binner, host=(label, index, value, counts),
               first_batch=batches[0])
    return {"rows": want["rows"], "nnz": want["nnz"],
            "batches": len(batches),
            "write_and_host_parse_seconds": round(write_parse_s, 2),
            "first_epoch_seconds": round(stage_s, 2),
            "bincache_batches": len(hit)}


def logloss_accuracy(prob, label):
    p = np.clip(np.asarray(prob, np.float64), 1e-7, 1 - 1e-7)
    y = np.asarray(label) > 0.5
    loss = float(-np.mean(np.where(y, np.log(p), np.log1p(-p))))
    return loss, float(np.mean((p > 0.5) == y))


def same_structure(a: dict, b: dict) -> bool:
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in ("feature", "threshold", "default_right"))


def phase_train(ctx: dict) -> dict:
    import jax

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.models import GBDT, QuantileBinner
    size, uri, binner = ctx["size"], ctx["uri"], ctx["binner"]
    label, index, value, counts = ctx["host"]
    # On one chip the route is left to "auto" and must come out as the
    # kernel; the rehearsal has to ask for the (interpreted) kernel by name.
    # With several chips and no mesh declared "auto" keeps XLA scatter
    # (pallas_call has no partitioning rule): there the kernels run in the
    # mesh phase, under histogram_mesh.
    routed = "pallas" if ctx["args"].rehearse_cpu else "auto"
    expect = ("pallas" if ctx["args"].rehearse_cpu
              or ctx["device"]["count"] == 1 else "xla")
    config = dict(num_features=FEATURES, num_trees=size["trees"],
                  max_depth=size["depth"], num_bins=size["bins"],
                  missing_aware=True)
    levels = size["trees"] * size["depth"] if expect == "pallas" else 0
    out = {"histogram": routed, "expected_route": expect}

    # (a) disk to forest: fit_streamed re-stages the file every pass
    def streamed(histogram):
        model = GBDT(histogram=histogram, **config)
        t0 = time.monotonic()
        forest = model.fit_streamed(uri, binner,
                                    staging_options=ctx["stage_opts"])
        jax.block_until_ready(forest)
        return model, forest, time.monotonic() - t0

    before = telemetry.counter_get("gbdt.hist_sparse_pallas")
    model, forest, fit_s = streamed(routed)
    require(set(model.level_backends(sparse=True)) == {expect},
            f"fit_streamed: histogram={routed!r} resolved levels to "
            f"{model.level_backends(sparse=True)}, want {expect}")
    kernel_levels = telemetry.counter_get("gbdt.hist_sparse_pallas") - before
    require(kernel_levels == levels, f"fit_streamed ran the sparse kernel "
            f"on {kernel_levels} levels, want {levels}")
    log(f"  fit_streamed({routed}) {fit_s:.1f}s")
    forest_xla, xla_s = forest, 0.0
    if expect == "pallas":
        _, forest_xla, xla_s = streamed("xla")
        log(f"  fit_streamed(xla) {xla_s:.1f}s")
    scores = {}
    for name, f in (("kernel", forest), ("xla", forest_xla)):
        prob = model.predict_staged(f, uri, binner, **ctx["stage_opts"])
        require(prob.shape == (size["rows"],) and np.isfinite(prob).all(),
                f"predict_staged gave shape {prob.shape} / non-finite values")
        scores[name] = logloss_accuracy(prob, label)
    loss, acc = scores["kernel"]
    require(acc > 0.8, f"fit_streamed training accuracy {acc:.4f} <= 0.8")
    require(abs(loss - scores["xla"][0]) <= 1e-3,
            f"fit_streamed loss {loss:.6f} vs XLA-scatter fit "
            f"{scores['xla'][0]:.6f}")
    out["streamed"] = {
        "levels": model.level_backends(sparse=True), "loss": round(loss, 6),
        "loss_xla": round(scores["xla"][0], 6), "accuracy": round(acc, 4),
        "same_structure_as_xla": same_structure(forest, forest_xla),
        "fit_seconds": round(fit_s, 2), "xla_fit_seconds": round(xla_s, 2)}
    ctx.update(model=model, forest=forest, config=config)

    # (b) the binned dense matrix (every row of this file has all 28)
    require((counts == FEATURES).all(), "dataset rows are not all dense")
    dense_binner = QuantileBinner(num_bins=size["bins"], missing_aware=True)
    bins = dense_binner.fit_transform(value.reshape(-1, FEATURES))
    y = jax.numpy.asarray(label)

    def dense(histogram):
        m = GBDT(histogram=histogram, **config)
        t0 = time.monotonic()
        f = m.fit(bins, y)
        jax.block_until_ready(f)
        return m, f, time.monotonic() - t0

    dmodel, dforest, fit_s = dense(routed)
    require(set(dmodel.level_backends()) == {expect},
            f"fit: histogram={routed!r} resolved levels to "
            f"{dmodel.level_backends()}, want {expect}")
    log(f"  fit({routed}) {fit_s:.1f}s")
    dforest_xla, xla_s = dforest, 0.0
    if expect == "pallas":
        _, dforest_xla, xla_s = dense("xla")
        log(f"  fit(xla) {xla_s:.1f}s")
    dloss = float(dmodel.loss(dforest, bins, y))
    dloss_xla = float(dmodel.loss(dforest_xla, bins, y))
    _, dacc = logloss_accuracy(dmodel.predict(dforest, bins), label)
    require(dacc > 0.8, f"fit training accuracy {dacc:.4f} <= 0.8")
    require(abs(dloss - dloss_xla) <= 1e-3,
            f"fit loss {dloss:.6f} vs XLA-scatter fit {dloss_xla:.6f}")
    out["dense"] = {
        "levels": dmodel.level_backends(), "loss": round(dloss, 6),
        "loss_xla": round(dloss_xla, 6), "accuracy": round(dacc, 4),
        "same_structure_as_xla": same_structure(dforest, dforest_xla),
        "fit_seconds": round(fit_s, 2), "xla_fit_seconds": round(xla_s, 2)}
    ctx.update(dense_bins=np.asarray(bins), dense_loss=dloss)
    return out


def run_kernel(name: str, n_nodes, fn, args, reference, interpreted: bool,
               limit: float = 1e-4):
    """Lower, compile and run one jitted kernel call; hold it to its XLA
    reference (``limit``, of the reference's largest value) and time a
    second call.  ``tpu_custom_call`` in the lowered module is Mosaic: the
    interpreter lowers to plain XLA ops instead."""
    import jax
    lowered = fn.lower(*args)
    mosaic = "tpu_custom_call" in lowered.as_text()
    run = lowered.compile()
    got = jax.block_until_ready(run(*args))
    t0 = time.monotonic()
    jax.block_until_ready(run(*args))
    ms = (time.monotonic() - t0) * 1e3
    err = max(rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                            jax.tree.leaves(reference)))
    row = {"kernel": name, "n_nodes": n_nodes, "interpret": not mosaic,
           "ms": round(ms, 2), "rel_err": float(f"{err:.3g}")}
    log(f"  {row}")
    require(mosaic != interpreted,
            f"{name} n_nodes={n_nodes}: "
            + ("ran interpreted, not compiled by Mosaic" if not mosaic
               else "compiled by Mosaic in a CPU rehearsal"))
    require(err <= limit, f"{name} n_nodes={n_nodes}: relative error "
            f"{err:.3g} against the XLA reference")
    return row


def station_layout(rows: int, features: int, stations: int, share: float):
    """The row ids of a feature-sorted layout shaped like the benchmark's
    sparse cell: a station's features share the rows that visit it
    (`station_plan`'s probabilities), ascending within each feature's run,
    then `sparse_hist_layout`'s padding (row 0); with them the lane at which
    each feature's run begins (``[features + 1]``) and the first station's
    features."""
    from benchmark.traffic.sparse_fit import station_plan
    from dmlc_core_tpu.ops import pallas_segment as ps
    rng = np.random.default_rng(9)
    runs = []
    sizes, probs = station_plan(features, stations, share)
    for size, p in zip(sizes, probs):
        runs += [np.flatnonzero(rng.random(rows) < p).astype(np.int32)
                 ] * int(size)
    rid = np.concatenate(runs)
    fstart = np.concatenate([[0], np.cumsum([len(r) for r in runs])])
    lanes = ps._round_up_some(len(rid), ps._NNZ_TILE, 64)
    return (np.pad(rid, (0, lanes - len(rid))), fstart.astype(np.int32),
            np.arange(int(sizes[0]), dtype=np.int32))


def check_entry_lookup(shape: dict, interpreted: bool) -> list:
    """The lookup kernel against XLA's gather, bit for bit, for a level's
    slots (one plane) and a tree's (grad, hess) (six)."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import pallas_segment as ps
    rows = shape["rows"]
    require(interpreted or ps.entry_lookup_engages(True, 6 * rows),
            f"the entry lookup does not engage at {rows} rows on a chip")
    rng = np.random.default_rng(11)
    rid = jnp.asarray(station_layout(**shape)[0])
    cspan = jax.jit(ps._chunk_spans)(rid)
    visits = int(jnp.sum((cspan >> 16) - (cspan & 0xFFFF) + 1))
    slot = jnp.asarray(rng.integers(-1, 128, rows).astype(np.int32))
    gh = jnp.asarray(rng.standard_normal((rows, 2)).astype(np.float32))

    def bits(a):
        return jax.lax.bitcast_convert_type(a, jnp.uint32)

    out = []
    for name, table in (("slots", slot), ("grad_hess", gh)):
        args = (rid, cspan, table)
        # one program: a [lanes, 2] array of its own would be padded to tiles
        want = jax.jit(lambda t, r: t[r] if t.ndim == 1 else t[r].T)(
            table, rid)
        lowered = jax.jit(ps._lookup_values).lower(*args)
        mosaic = "tpu_custom_call" in lowered.as_text()
        require(mosaic != interpreted, f"entry_lookup {name}: "
                + ("interpreted on a chip" if not mosaic
                   else "compiled by Mosaic in a CPU rehearsal"))
        run = lowered.compile()
        got = jax.block_until_ready(run(*args))
        t0 = time.monotonic()
        jax.block_until_ready(run(*args))
        ms = (time.monotonic() - t0) * 1e3
        same = bool(jnp.all(bits(got) == bits(want)))
        row = {"kernel": f"entry_lookup({name})", "entries": int(rid.shape[0]),
               "chunk_visits": visits, "interpret": not mosaic,
               "ms": round(ms, 1), "exact": same}
        log(f"  {row}")
        require(same, f"entry_lookup {name}: not table[rid] bit for bit")
        out.append(row)
    return out


def check_entry_push(shape: dict, interpreted: bool) -> list:
    """The push kernel against XLA's scatter-add, exactly, with every
    sub-tile live (the layout's own spans) and with one station's runs live
    (`run_spans`: what a level that splits on that station's features
    visits)."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import pallas_segment as ps
    rows = shape["rows"]
    require(interpreted or ps.route_push_engages(True, rows),
            f"the entries' push does not engage at {rows} rows on a chip")
    rng = np.random.default_rng(13)
    rid_h, fstart, station = station_layout(**shape)
    rid = jnp.asarray(rid_h)
    cspan = jax.jit(ps._chunk_spans)(rid)
    carried = rng.integers(0, 3, len(rid_h)).astype(np.int32)
    carried[fstart[-1]:] = 0                        # the padding lanes
    by_station = np.zeros_like(carried)
    by_station[:fstart[len(station)]] = carried[:fstart[len(station)]]
    cases = (("all_live", cspan, carried),
             ("one_station", jax.jit(ps.run_spans)(
                 cspan, jnp.asarray(fstart), jnp.asarray(station)),
              by_station))
    scatter_add = jax.jit(lambda r, v: jnp.zeros(rows, jnp.float32).at[r].add(
        v.astype(jnp.float32)))
    lowered = jax.jit(ps.push_to_rows, static_argnums=3).lower(
        rid, cspan, rid, rows)
    mosaic = "tpu_custom_call" in lowered.as_text()
    require(mosaic != interpreted, "entry_push: "
            + ("interpreted on a chip" if not mosaic
               else "compiled by Mosaic in a CPU rehearsal"))
    run = lowered.compile()
    out = []
    for name, span, val in cases:
        val = jnp.asarray(val)
        want = scatter_add(rid, val)
        got = jax.block_until_ready(run(rid, span, val))
        t0 = time.monotonic()
        jax.block_until_ready(run(rid, span, val))
        ms = (time.monotonic() - t0) * 1e3
        same = bool(jnp.all(got == want))
        visited = span != ps._EMPTY_SPAN
        row = {"kernel": f"entry_push({name})", "entries": int(rid.shape[0]),
               "sub_tiles": int(jnp.sum(visited)),
               "chunk_visits": int(jnp.sum(jnp.where(
                   visited, (span >> 16) - (span & 0xFFFF) + 1, 0))),
               "interpret": not mosaic, "ms": round(ms, 1), "exact": same}
        log(f"  {row}")
        require(same, f"entry_push {name}: not zeros(rows).at[rid].add(val)")
        out.append(row)
    return out


def check_layout_bin(shape: dict, interpreted: bool) -> list:
    """The binning kernel on a feature-sorted layout's lanes against
    `_bin_by_bisection`, bit for bit, on every 13th lane (the bisection is
    eight gathers an entry) and on the padding."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.models.gbdt import _bin_by_bisection
    from dmlc_core_tpu.ops import pallas_segment as ps
    features, bins = shape["features"], 256
    rid_h, fstart, _station = station_layout(**shape)
    lanes, live = len(rid_h), int(fstart[-1])
    require(interpreted or ps.layout_bin_engages(
        (features, bins - 2), features, lanes, 1),
        f"the layout's binning does not engage at {lanes} lanes on a chip")
    rng = np.random.default_rng(17)
    cuts = np.sort(np.round(rng.standard_normal((features, bins - 2)), 2
                            ).astype(np.float32), axis=1)
    value = np.round(rng.standard_normal(lanes), 2).astype(np.float32)
    value[rng.random(lanes) < 0.01] = np.inf
    value[rng.random(lanes) < 0.01] = -np.inf
    args = (jnp.asarray(value), jnp.asarray(np.append(fstart, lanes).astype(
        np.int32)), jnp.asarray(cuts))
    lowered = jax.jit(ps._bin_runs_pallas, static_argnums=(3, 4, 5)).lower(
        *args, bins, 1, interpreted)
    mosaic = "tpu_custom_call" in lowered.as_text()
    require(mosaic != interpreted, "layout_bin: "
            + ("interpreted on a chip" if not mosaic
               else "compiled by Mosaic in a CPU rehearsal"))
    run = lowered.compile()
    got = jax.block_until_ready(run(*args))
    t0 = time.monotonic()
    jax.block_until_ready(run(*args))
    ms = (time.monotonic() - t0) * 1e3
    at = np.arange(0, live, 13)
    fi = (np.searchsorted(fstart, at, side="right") - 1).astype(np.int32)
    want = fi * bins + np.asarray(_bin_by_bisection(
        args[2], jnp.asarray(fi), jnp.asarray(value[at])))
    same = bool(np.array_equal(np.asarray(got[:live:13]), want)
                and bool(jnp.all(got[live:] == -1)))
    row = {"kernel": "layout_bin", "entries": lanes, "runs": features,
           "checked": len(at), "interpret": not mosaic, "ms": round(ms, 1),
           "exact": same}
    log(f"  {row}")
    require(same, "layout_bin: not the bisection's codes on the sorted lanes")
    return [row]


def check_short_group(rows: int, interpreted: bool) -> list:
    """The dense histogram kernel where the last group of its plan's key
    tiles is short — 67 features of 256 bins: 72 tiles in nine groups, the
    last 5 without a feature, which the kernel does nothing for — against
    the XLA path, at 8 node columns (the parts on one dot) and at 64 (three
    dots): exact to 7e-7 of the largest bucket, as the kernel's docstring
    says of its other shapes."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import pallas_segment as ps
    F, B = 67, 256
    rng = np.random.default_rng(51)
    bins = jnp.asarray(rng.integers(0, B, (rows, F)).astype(np.int32))
    gh = jnp.asarray(rng.standard_normal((rows, 2)).astype(np.float32))
    table = []
    for nn in (8, 64):
        dead = ps.hist_dead_key_tiles(F, B, nn)
        require(dead == 5, f"histogram_gh(short_group) n_nodes={nn}: the "
                f"plan has {dead} padding key tiles, not 5")
        rel = jnp.asarray(rng.integers(0, nn, rows).astype(np.int32))
        fn = jax.jit(functools.partial(
            ps.histogram_gh, n_nodes=nn, num_bins=B, force="pallas"))
        row = run_kernel(
            "histogram_gh(short_group)", nn, fn, (bins, rel, gh),
            ps.histogram_gh(bins, rel, gh, nn, B, force="xla"), interpreted,
            limit=7e-7)
        table.append(dict(row, features=F, dead_key_tiles=dead))
    return table


def phase_kernels(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.models import SparseLinearModel
    from dmlc_core_tpu.ops import pallas_segment as ps
    size = ctx["size"]
    interpreted = ps.pallas_interpret()
    require(interpreted == ctx["args"].rehearse_cpu,
            f"pallas_interpret() is {interpreted} on "
            f"{ctx['device']['platform']}")
    rows, B, F = size["kernel_rows"], size["bins"], FEATURES
    rng = np.random.default_rng(5)
    table = []

    # segment_sum, forward and its custom VJP, inside one SGD step on a
    # staged batch
    batch = ctx["first_batch"]

    def weights():      # non-zero, so the forward sum has something to add
        return {"w": jnp.asarray(np.random.default_rng(7).standard_normal(
            F).astype(np.float32)), "b": jnp.float32(0.1)}

    reference = SparseLinearModel(num_features=F).train_step(weights(), batch)
    m = SparseLinearModel(num_features=F, sdot_backend="pallas")
    table.append(run_kernel("segment_sum(train_step)", None,
                            jax.jit(m.train_step), (weights(), batch),
                            reference, interpreted))

    bins = jnp.asarray(rng.integers(0, B, (rows, F)).astype(np.int32))
    gh = jnp.asarray(rng.standard_normal((rows, 2)).astype(np.float32))
    # the sparse kernel sees the same matrix as COO entries, bin 0 left
    # empty as transform_entries leaves it
    rid = np.repeat(np.arange(rows, dtype=np.int32), F)
    fi = np.tile(np.arange(F, dtype=np.int32), rows)
    eb = rng.integers(1, B, rows * F).astype(np.int32)
    em = np.ones(rows * F, bool)
    layout = ps.sparse_hist_layout(rid, fi, eb, em, F, B)
    log(f"  sparse layout: {layout.nnz_live} entries, "
        f"max {layout.max_tiles} blocks a key tile")
    caps = {"histogram_gh": ps.HIST_NODE_LIMIT,
            "histogram_gh_sparse": ps.SPARSE_HIST_NODE_LIMIT}
    for n in (1, 32, "cap"):
        for name in ("histogram_gh", "histogram_gh_sparse"):
            nn = caps[name] if n == "cap" else n
            rel = jnp.asarray(rng.integers(0, nn, rows).astype(np.int32))
            if name == "histogram_gh":
                fn = jax.jit(functools.partial(
                    ps.histogram_gh, n_nodes=nn, num_bins=B, force="pallas"))
                args = (bins, rel, gh)
                reference = ps.histogram_gh(bins, rel, gh, nn, B, force="xla")
            else:
                def sparse(gkey, lrid, ts, tc, rel, gh, nn=nn):
                    return ps.histogram_gh_sparse_kernel(
                        gkey, rel[lrid], gh[lrid].T, ts, tc,
                        nn, F, B, layout.max_tiles)
                fn = jax.jit(sparse)
                args = (layout.gkey, layout.rid, layout.tstart,
                        layout.tcount, rel, gh)
                reference = ps.histogram_gh_sparse(
                    jnp.asarray(rid), jnp.asarray(fi), jnp.asarray(eb),
                    jnp.asarray(em), rel, gh, nn, F, B, force="xla")
            t0 = time.monotonic()
            jax.block_until_ready(reference)
            log(f"  XLA reference for {name} n_nodes={nn}: "
                f"{time.monotonic() - t0:.1f}s")
            table.append(run_kernel(name, nn, fn, args, reference,
                                    interpreted))
    table += check_entry_lookup(size["lookup"], interpreted)
    table += check_entry_push(size["lookup"], interpreted)
    table += check_layout_bin(size["lookup"], interpreted)
    table += check_short_group(rows, interpreted)
    ctx["kernels"] = table
    return {"calls": len(table), "node_caps": caps}


def phase_checkpoint(ctx: dict) -> dict:

    from dmlc_core_tpu import checkpoint
    forest, model = ctx["forest"], ctx["model"]
    uri = str(ctx["out"] / "forest.ckpt")
    leaves = checkpoint.save(forest, uri)
    back = checkpoint.load(uri, like=model.init())
    require(sorted(back) == sorted(forest), "checkpoint keys differ")
    for k in forest:
        a, b = np.asarray(forest[k]), np.asarray(back[k])
        require(a.dtype == b.dtype and np.array_equal(a, b),
                f"checkpoint leaf {k!r} did not round-trip")
    ctx["forest"] = back      # what is served is what was restored
    return {"leaves": leaves, "bytes": os.path.getsize(uri)}


def post_score(port: int, rows: list) -> dict:
    body = json.dumps({"rows": [{"index": i, "value": v}
                                for i, v in rows]}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/score", data=body,
        headers={"Content-Type": "application/json"})
    t0 = time.monotonic()
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            reply = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        # the server's own account of the failure is in the body
        raise Failure(f"/score answered {e.code} to a {len(rows)}-row "
                      f"request after {time.monotonic() - t0:.1f}s: "
                      f"{e.read()[:2000].decode('utf-8', 'replace')}")
    log(f"  /score {len(rows)} rows {time.monotonic() - t0:.2f}s")
    return reply


def phase_serve(ctx: dict) -> dict:

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.serving import (ScoringIterator, ScoringServer,
                                       pack_snapshot, push_snapshot)
    size, model, forest, binner = (ctx["size"], ctx["model"], ctx["forest"],
                                   ctx["binner"])
    _, index, value, _ = ctx["host"]
    index = index.reshape(-1, FEATURES)
    value = value.reshape(-1, FEATURES)
    requests, at = [], 0
    for n in size["score_rows"]:
        requests.append([(index[r].tolist(), value[r].tolist())
                         for r in range(at, at + n)])
        at += n
    payload = pack_snapshot("gbdt", ctx["config"], forest, binner=binner)
    packer = ScoringIterator(max_batch=4096)
    worst = 0.0
    with ScoringServer(host="127.0.0.1", port=0, http_port=0) as server:
        verdict = push_snapshot("127.0.0.1", server.port, payload, seq=1)
        require(verdict.get("ok"), f"snapshot push refused: {verdict}")
        retraces = []
        for sweep in range(2):        # the first sweep compiles each bucket
            for rows in requests:
                reply = post_score(server.http_port, rows)
                require(reply.get("model") == verdict["digest"],
                        f"/score answered from model {reply.get('model')}, "
                        f"pushed {verdict['digest']}")
                batch, n = packer.pack(rows)
                want = np.asarray(model.predict_batch(forest, batch,
                                                      binner))[:n]
                got = np.asarray(reply["scores"], np.float32)
                require(got.shape == want.shape,
                        f"/score returned {got.shape} scores for {n} rows")
                diff = float(np.max(np.abs(got - want)))
                worst = max(worst, diff)
                require(diff <= 1e-6, f"/score differs from predict_batch "
                        f"by {diff:.3g} on a {n}-row request")
            retraces.append(telemetry.counter_get("models.predict_retrace"))
    require(retraces[1] == retraces[0],
            f"warm /score requests retraced predict "
            f"{retraces[1] - retraces[0]} time(s)")
    return {"requests": 2 * len(requests),
            "rows_per_request": list(size["score_rows"]),
            "max_abs_diff": worst, "snapshot_bytes": len(payload),
            "predict_retrace_after_warmup": retraces[1] - retraces[0],
            "predict_retrace_total": retraces[1]}


def phase_mesh(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dmlc_core_tpu import DeviceStagingIter, telemetry
    from dmlc_core_tpu.models import GBDT
    from dmlc_core_tpu.parallel import MeshPlan
    size, binner = ctx["size"], ctx["binner"]
    n = jax.device_count()
    routed = "pallas" if ctx["args"].rehearse_cpu else "auto"
    plan = MeshPlan.build()
    require(plan.num_shards == n, f"MeshPlan spans {plan.num_shards} of "
            f"{n} devices")
    out = {"plan": plan.describe()}

    # the same file, staged row-sharded: nothing piled on device 0
    it = DeviceStagingIter(ctx["uri"], sharding=plan.data_sharding(),
                           **ctx["stage_opts"])
    batches = list(it)
    it.close()
    for b in batches:
        for name in ("label", "weight", "index", "value"):
            leaf = getattr(b, name)
            shards = leaf.addressable_shards
            require(len({s.device for s in shards}) == n
                    and all(s.data.shape[0] * n == leaf.shape[0]
                            for s in shards),
                    f"staged {name} [{leaf.shape[0]}] is laid out as "
                    f"{[(str(s.device), s.data.shape[0]) for s in shards]}")
    label, index, value, _ = ctx["host"]
    require_same_sums(
        staged_sums(batches),
        content_sums(label, index, value, len(label), len(index)),
        "sharded feed")
    out["sharded_batches"] = len(batches)

    # the reduction on the level histogram's own payload
    payload = 32 * FEATURES * size["bins"] * 2
    x = np.random.default_rng(3).standard_normal(
        (n, payload)).astype(np.float32)
    xs = jax.device_put(x.reshape(-1), plan.data_sharding())
    fn = jax.jit(plan.shard_map(plan.allreduce, in_specs=plan.row_spec,
                                out_specs=P(), check_replication=False))
    require("all_reduce" in fn.lower(xs).as_text(),
            "allreduce lowered without all_reduce")
    err = rel_err(fn(xs), x.sum(axis=0))
    require(err <= 1e-5, f"allreduce off by {err:.3g}")
    out["allreduce"] = {"payload_bytes": payload * 4, "op": "all_reduce",
                        "rel_err": float(f"{err:.3g}")}

    # the same GBDT with the kernel under shard_map
    require(size["rows"] % n == 0,
            f"{size['rows']} rows do not divide over {n} devices")
    bins = jax.device_put(ctx["dense_bins"], plan.data_sharding())
    y = jax.device_put(label, plan.data_sharding())
    # what a level reduces: its built node histograms, the root and then one
    # child of every parent (the siblings are derived after the reduction)
    level_bytes = [max(2 ** (d - 1), 1) * FEATURES * size["bins"] * 8
                   for d in range(size["depth"])]
    # the plan of the benchmark's four-chip cell, built from that cell's own
    # parameters the way its generator builds it (benchmark/traffic/
    # mesh_fit.py: make_plan), so that this leg and the cell cannot drift
    # apart
    cell = json.loads((HERE / "benchmark" / "workloads"
                       / "airline-gbdt.fit-mesh4.json").read_text())["params"]
    m = GBDT(histogram=routed, **ctx["config"], histogram_mesh=MeshPlan.build(
        devices=jax.devices()[:n], collective=cell["collective"],
        overlap_chunks=int(cell["overlap_chunks"])))
    require(set(m.level_backends()) == {"pallas"},
            f"mesh fit resolved levels to {m.level_backends()}")
    t0 = time.monotonic()
    before = telemetry.snapshot()
    forest = jax.block_until_ready(m.fit(bins, y))
    counted = telemetry.counters_delta(before, telemetry.snapshot())
    want = (size["trees"] * size["depth"], size["trees"] * sum(level_bytes))
    got = (counted.get("mesh.allreduce_calls", 0),
           counted.get("mesh.collective_bytes", 0))
    require(got == want, f"mesh fit counted {got} reductions and bytes, "
            f"want {want}")
    loss = float(m.loss(forest, bins, y))
    require(abs(loss - ctx["dense_loss"]) <= 1e-3,
            f"mesh fit loss {loss:.6f} vs one-device fit "
            f"{ctx['dense_loss']:.6f}")
    out["fit"] = {"loss": round(loss, 6),
                  "seconds": round(time.monotonic() - t0, 2)}

    # the sparse kernel under shard_map, on one staged batch
    first = batches[0]
    sm = GBDT(histogram=routed, histogram_mesh=plan, **ctx["config"])
    require(set(sm.level_backends(sparse=True)) == {"pallas"},
            f"mesh fit_batch resolved {sm.level_backends(sparse=True)}")
    f_mesh = jax.device_get(sm.fit_batch(first, binner))
    f_one = GBDT(histogram="xla", **ctx["config"]).fit_batch(
        ctx["first_batch"], binner)
    p_mesh = np.asarray(sm.predict_batch(f_mesh, ctx["first_batch"], binner))
    p_one = np.asarray(sm.predict_batch(f_one, ctx["first_batch"], binner))
    y0 = np.asarray(ctx["first_batch"].label)
    l_mesh, l_one = (logloss_accuracy(p, y0)[0] for p in (p_mesh, p_one))
    require(abs(l_mesh - l_one) <= 1e-3, f"mesh fit_batch loss {l_mesh:.6f} "
            f"vs one-device XLA fit_batch {l_one:.6f}")
    out["fit_batch"] = {"loss": round(l_mesh, 6), "loss_xla": round(l_one, 6)}

    # every family's sharded step against its single-device run
    with contextlib.redirect_stdout(sys.stderr):
        import __graft_entry__
        __graft_entry__.dryrun_multichip(n)
    out["dryrun_multichip"] = n
    return out


PHASES = [("device", phase_device), ("native", phase_native),
          ("feed", phase_feed), ("train", phase_train),
          ("kernels", phase_kernels), ("checkpoint", phase_checkpoint),
          ("serve", phase_serve), ("mesh", phase_mesh)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes, interpreted kernels, CPU backend "
                         "(needs JAX_PLATFORMS=cpu); proves nothing about "
                         "a device")
    ap.add_argument("--expect-devices", type=int, default=None,
                    help="fail unless JAX finds exactly this many devices")
    ap.add_argument("--out", default=str(HERE / "chiprun_out" / "chip_smoke"),
                    help="directory for the data, the checkpoint and "
                         "summary.json (nothing is written elsewhere)")
    args = ap.parse_args()
    out = Path(args.out).resolve()
    out.mkdir(parents=True, exist_ok=True)
    ctx = {"args": args, "out": out,
           "size": TINY if args.rehearse_cpu else FULL}
    summary = {"ok": False, "rehearsal": args.rehearse_cpu, "phases": {}}
    failed = None
    try:
        for name, fn in PHASES:
            if name == "mesh" and ctx["device"]["count"] < 2:
                summary["phases"][name] = {"ok": True, "skipped": "1 device"}
                continue
            log(f"phase {name} ...")
            t0, mark = time.monotonic(), setup_counters()
            try:
                detail = fn(ctx)
            except Exception as exc:  # noqa: BLE001 — reported, then exit 1
                traceback.print_exc(file=sys.stderr)
                failed = f"{name}: {type(exc).__name__}: {exc}"
                summary["phases"][name] = {
                    "ok": False, "seconds": round(time.monotonic() - t0, 2),
                    "error": failed[-2000:]}
                break
            summary["phases"][name] = {
                "ok": True, "seconds": round(time.monotonic() - t0, 2),
                **compile_since(mark), **detail}
            log(f"phase {name} ok: {summary['phases'][name]}")
    finally:
        # the generated inputs are large and reproducible from the seed
        for p in out.glob("higgs_shaped.*"):
            p.unlink()
    for key in ("device", "versions", "kernels"):
        if key in ctx:
            summary[key] = ctx[key]
    if "cache" in ctx:
        cache = ctx["cache"]
        cache["entries_added"] = (cache_entries(cache["dir"])
                                  - cache.pop("entries_before"))
        summary["cache"] = cache
    # set-up (native build, getting executables) apart from the rest, from
    # the program's own counters, and the programs the compile seconds went to
    table = setup_counters()
    setup = {"native_build_seconds": ctx.get("native", {}).get(
        "build_seconds", 0.0), **compile_since(dict.fromkeys(table, 0)),
        **{k: round(v, 2) if isinstance(v, float) else v
           for k, v in table.items()}}
    if "dmlc_core_tpu" in sys.modules:
        setup["slowest_programs"] = slowest_programs()
    summary["setup"] = setup
    log(f"set-up by the program's own counters: {setup}")
    summary["seconds"] = round(time.monotonic() - T0, 2)
    summary["steady_seconds"] = round(
        summary["seconds"] - setup["native_build_seconds"]
        - setup["compile_seconds"], 2)
    summary["ok"] = failed is None
    (out / "summary.json").write_text(json.dumps(summary, indent=1) + "\n")
    if failed is not None:
        log(f"FAILED in phase {failed}")
        return 1
    print(json.dumps(summary))
    # the last line is the verdict alone: "ok" and the device, nothing more
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
