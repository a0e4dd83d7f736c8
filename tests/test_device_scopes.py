"""The program names its device work and its host spans: every scope a
``trace_scope`` layer metric reads is in the lowered programs (forward and
backward), ``telemetry.span`` lands in both sinks, ``telemetry`` itself never
imports jax, and a scope is part of the compile-cache key."""
import contextlib
import glob
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dmlc_core_tpu import compile_cache, telemetry
from dmlc_core_tpu.data.staging import PaddedBatch
from dmlc_core_tpu.models import GBDT
from dmlc_core_tpu.models.ffm import FieldAwareFactorizationMachine
from dmlc_core_tpu.parallel import MeshPlan

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "scripts"))

from analyze import tracespans  # noqa: E402


def named_scopes() -> list:
    """Every scope the benchmark's ``trace_scope`` metrics select by, as
    the analyzer harvests them (program selectors left out)."""
    return sorted({scope for _path, scope in tracespans.metric_scopes(ROOT)})


def paths_of(lowered) -> set:
    """The scope paths (``jit(f)/a.b/op``) of a lowered program's ops."""
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def programs() -> dict:
    """Scope paths of the three device programs, lowered for the CPU at
    tiny sizes: a whole GBDT fit (depth 2, the Pallas route interpreted, so
    that the kernel wrapper's layout ops are there), one FFM ``_train_step``,
    the touched-rows step of a linear model, of a gated factorization
    machine (on one chip, and over tables sharded by key) and of the
    field-aware one, a plan-routed reduction, and the margin update a
    boosting round ends with."""
    model = GBDT(num_features=4, num_trees=1, max_depth=2, num_bins=16,
                 missing_aware=True, histogram="pallas")
    bins = jnp.zeros((64, 4), jnp.uint8)
    fit = jax.jit(lambda b, y: model.fit(b, y)).lower(bins, jnp.zeros(64))
    tree = model._build_tree.lower(model, bins, jnp.zeros(64), jnp.zeros(64),
                                   jnp.ones(4, bool), jax.random.PRNGKey(0))
    # the sparse tree as fit_batch hands it over when every level runs the
    # kernel: the feature-sorted layout and no other copy of the entries
    from dmlc_core_tpu.ops.pallas_segment import sparse_hist_layout
    rid = jnp.repeat(jnp.arange(64, dtype=jnp.int32), 2)
    layout = sparse_hist_layout(rid, rid % 4, 1 + rid % 15,
                                jnp.ones(128, bool), 4, 16)
    sparse_tree = model._build_tree_sparse.lower(
        model, None, layout, jnp.zeros(64), jnp.zeros(64), jnp.ones(4, bool),
        jax.random.PRNGKey(0))
    # the layout's pack program as `fit_batch` runs it where the layout
    # bins the entries itself: the sorted values, the run starts, the cuts
    from dmlc_core_tpu.ops import pallas_segment
    pack = pallas_segment._layout_pack.lower(
        jnp.zeros(128), rid, jnp.zeros(1, jnp.int32),
        jnp.full(1, 128, jnp.int32), 1, 1024,
        rstart=jnp.zeros(6, jnp.int32), cuts=jnp.zeros((4, 14)), nb=16,
        interpret=True)

    # the leaf-wise builder's tree program (`models/gbdt_leafwise.py`)
    best = GBDT(num_features=4, num_trees=1, num_bins=16, histogram="pallas",
                grow_policy="lossguide", max_leaves=4)
    leafwise = best._grow_tree.lower(
        best, jnp.zeros((64, 1), jnp.int32), jnp.zeros(64), jnp.zeros(64),
        jnp.ones(4, bool))
    margin = margin_program(leaves=4, rows=64)
    # a page's visit of the paged fit (`GBDT.fit_paged`): the pass at depth
    # 1, which routes through the root's split and builds one column
    paged = model._page_visit.lower(
        model, 1, jnp.zeros((1, 4, 16, 2)), jnp.zeros(64, jnp.int32),
        (jnp.zeros((16, 4), jnp.uint8), jnp.zeros((16, 4), jnp.uint8)),
        jnp.zeros(64), jnp.zeros(64), np.int32(32),
        (jnp.zeros(1, jnp.int32),) * 3 + (jnp.zeros(1, bool),))

    rows, fields, features = 8, 3, 32
    ffm = FieldAwareFactorizationMachine(num_features=features,
                                         num_fields=fields)
    nnz = rows * fields
    batch = PaddedBatch(
        label=jnp.zeros(rows), weight=jnp.ones(rows),
        row_ptr=jnp.arange(rows + 1, dtype=jnp.int32) * fields,
        index=jnp.zeros(nnz, jnp.int32), value=jnp.ones(nnz),
        num_rows=jnp.asarray(np.int32(rows)),
        field=jnp.asarray(np.tile(np.arange(fields, dtype=np.int32), rows)))
    step = ffm._train_step.lower(ffm, ffm.init(0), batch)
    field_rows = ffm._wide_rows_step.lower(ffm, ffm.init(0), batch)
    from dmlc_core_tpu.models.common import FTRL
    from dmlc_core_tpu.models.linear import SparseLinearModel
    linear = SparseLinearModel(features, optimizer=FTRL())
    touched = linear._touched_rows_step.lower(linear, linear.init(), batch)
    from dmlc_core_tpu.models.common import AdaGrad
    from dmlc_core_tpu.models.fm import FactorizationMachine
    fm = FactorizationMachine(features, 4, threshold=1, optimizer={
        "w": FTRL(), "v": AdaGrad()})
    tables = fm._touched_rows_step.lower(fm, fm.init(), batch)

    plan = MeshPlan.build()
    reduce = jax.jit(plan.shard_map(
        lambda v: plan.allreduce(v, "sum"), in_specs=plan.row_spec,
        out_specs=P(), check_replication=False)).lower(
            jnp.zeros(plan.num_shards * 4))
    # the same step over tables sharded by key: every chip a worker and a
    # server (`TouchedRowsMixin._sharded_rows_step`)
    served = FactorizationMachine(
        features * plan.num_shards, 4, threshold=1, mesh=plan,
        optimizer={"w": FTRL(), "v": AdaGrad()})
    wide = rows * plan.num_shards
    sharded = served._sharded_rows_step.lower(served, served.init(), PaddedBatch(
        label=jnp.zeros(wide), weight=jnp.ones(wide),
        row_ptr=jnp.arange(wide + 1, dtype=jnp.int32) * fields,
        index=jnp.zeros(wide * fields, jnp.int32),
        value=jnp.ones(wide * fields), num_rows=jnp.asarray(np.int32(wide))))
    return {"fit": paths_of(fit), "tree": paths_of(tree),
            "sharded": paths_of(sharded),
            "sparse_tree": paths_of(sparse_tree), "pack": paths_of(pack),
            "leafwise": paths_of(leafwise), "margin": paths_of(margin),
            "paged": paths_of(paged),
            "step": paths_of(step), "touched": paths_of(touched),
            "tables": paths_of(tables), "field_rows": paths_of(field_rows),
            "reduce": paths_of(reduce)}


# scopes of the touched-rows step (`TouchedRowsMixin`), not of `_train_step`
TOUCHED_ROWS = {"sgd.unique", "sgd.gather_rows", "linear.margins", "sgd.ftrl",
                "sgd.scatter_rows"}
# scopes the same step opens for row-shaped tables under a count gate alone
# (the factorization machine's, which carries every scope of TOUCHED_ROWS too)
TOUCHED_TABLES = {"fm.margins", "sgd.adagrad", "sgd.count"}
# scopes of the step over tables sharded by key alone, which carries every
# scope of TOUCHED_ROWS and TOUCHED_TABLES (but the linear model's) too
SHARDED_ROWS = {"mesh.alltoall", "sgd.owner_merge"}
# scopes that only one of the two tree programs opens
DENSE_ONLY = {"gbdt.cast"}
SPARSE_ONLY = {"gbdt.entry_gather", "gbdt.node_totals"}
# scopes of the leaf-wise builder's program, `jit(_grow_tree)`
LEAFWISE = {"gbdt.leafwise.hist", "gbdt.leafwise.partition",
            "gbdt.leafwise.split", "gbdt.leafwise.pick"}
# scopes of the boosting driver, outside both tree programs
DRIVER = {"gbdt.boost", "gbdt.margin"}
# scopes of the sparse layout's pack program, `jit(_layout_pack)`, which
# `fit_batch` runs once, before the first tree
LAYOUT = {"gbdt.layout_bin"}
# scopes of the paged fit's page visit, `jit(_page_visit)`, each nested in
# the resident tree's scope of the same work
PAGED = {"gbdt.page.route": "gbdt.route", "gbdt.page.hist": "gbdt.hist",
         "gbdt.page.accumulate": "gbdt.hist"}


def margin_program(leaves: int, rows: int):
    """The margin update (`gbdt._leaf_values`) lowered at ``leaves`` values
    over ``rows`` rows."""
    from dmlc_core_tpu.models import gbdt
    return gbdt._leaf_values.lower(
        jnp.zeros(leaves), jnp.zeros(rows, jnp.int32), jnp.zeros(rows))


def carries(paths: set, scope: str, under: str = "") -> bool:
    part = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    return any(part.search(p) and under in p for p in paths)


@pytest.mark.parametrize("scope", named_scopes())
def test_every_scope_a_metric_reads_is_in_a_lowered_program(programs, scope):
    where = {"gbdt": "fit", "ops": "fit", "batch": "step", "ffm": "step",
             "sgd": "step", "mesh": "reduce", "linear": "touched",
             "fm": "tables"}[scope.split(".")[0]]
    if scope in LEAFWISE:
        assert carries(programs["leafwise"], scope, under="jit(_grow_tree)")
        assert not carries(programs["tree"], scope)
        return
    if scope in PAGED:
        assert carries(programs["paged"], scope,
                       under=f"jit(_page_visit)/{PAGED[scope]}/")
        assert not carries(programs["tree"], scope)
        return
    if scope in LAYOUT:
        # under the pack program's own name, where the prepare's metric
        # looks for it (`^jit\(_layout_pack\)`); no tree program holds it
        assert carries(programs["pack"], scope, under="jit(_layout_pack)/")
        assert not carries(programs["sparse_tree"], scope)
        return
    if scope in SPARSE_ONLY:
        where = "sparse_tree"
    if scope in TOUCHED_ROWS:
        where = "touched"
        assert carries(programs["tables"], scope,
                       under="jit(_touched_rows_step)")
    if scope in TOUCHED_TABLES:
        where = "tables"
    if scope in SHARDED_ROWS | TOUCHED_TABLES | TOUCHED_ROWS - {
            "linear.margins"}:
        # (the shard_map body is lowered as a function of its own: its
        # ops' paths start at the body, and the compiled program's names
        # hold ``jit(_sharded_rows_step)/shard_map/`` before them,
        # tests/test_chip_names.py)
        assert carries(programs["sharded"], scope)
    if scope in SHARDED_ROWS:
        where = "sharded"
        assert not carries(programs["tables"], scope)
    if scope == "gbdt.margin":
        # a program of its own, nested in the driver's scope (the one-tree
        # fit lowered above never reads its last margins: not in there)
        where = "margin"
        assert carries(programs[where], scope,
                       under="jit(_leaf_values)/gbdt.boost/")
        assert not carries(programs["tree"], scope)
    assert carries(programs[where], scope), (
        f"no op of the {where} program carries the scope {scope}")
    if scope.startswith("gbdt.") and scope not in DRIVER:
        # the tree programs are what the chip runs; the whole-fit lowering
        # above only adds the driver's eager ops to the dense one
        if scope not in SPARSE_ONLY:
            assert carries(programs["tree"], scope, under="jit(_build_tree)")
        if scope not in DENSE_ONLY:
            # split finding is a jitted function of its own inside the
            # tree: its ops carry the scope under the call site's path
            nested = scope == "gbdt.split"
            assert carries(programs["sparse_tree"], scope, under=""
                           if nested else "jit(_build_tree_sparse)")


def test_sparse_tree_runs_the_kernels_under_their_scopes(programs):
    """One program a tree: the sparse histogram kernel under ``gbdt.hist``
    with its layout ops, the Pallas segment sums under ``gbdt.node_totals``
    and ``gbdt.leaf``, split finding under the nested jit's ``gbdt.split``."""
    paths = programs["sparse_tree"]
    tree = "jit(_build_tree_sparse)/"
    for call in ("gbdt.hist/jit(_histogram_gh_sparse_pallas)",
                 "gbdt.node_totals/jit(_segment_sum_pallas)",
                 "gbdt.leaf/jit(_segment_sum_pallas)",
                 "jit(_level_splits_from_hist)",
                 "gbdt.entry_gather/gather", "gbdt.route/while"):
        assert tree + call in paths, call
    assert carries(paths, "ops.hist_layout")
    assert carries(paths, "gbdt.split")
    # the rows are found in their feature's run by bisection: no gather
    # under the route takes an index an entry
    assert not carries(paths, "gbdt.route", under="scatter")


def gathers_under(lowered, scope: str) -> list:
    """The index operand's shape, as a tuple, of every ``stablehlo.gather``
    of a lowered program whose location carries ``scope``."""
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found = []
    for dims, loc in re.findall(
            r'"stablehlo\.gather"\(.*: \(tensor<[^>]*>, tensor<((?:\d+x)*)i32>\)'
            r' -> \S+ loc\((#loc\d+)\)', text):
        if carries({names[loc]}, scope):
            found.append(tuple(int(d) for d in dims.split("x") if d))
    return found


def test_route_gathers_nothing_a_row():
    """``gbdt.route`` is still a scope of the lowered tree program, and no
    gather under it takes an index a row (PR 26: four of them, 10.5M
    indices each, were a quarter of a boosting round on the chip).  The
    same reading finds them when the old routing is put back."""
    from test_gbdt import gather_routed

    rows, features = 192, 4
    kw = dict(num_features=features, num_trees=1, max_depth=3, num_bins=16,
              missing_aware=True, histogram="pallas")
    args = (jnp.zeros((rows, features), jnp.uint8), jnp.zeros(rows),
            jnp.zeros(rows), jnp.ones(features, bool), jax.random.PRNGKey(0))

    def lowered(model):
        return model._build_tree.lower(model, *args)

    tree = lowered(GBDT(**kw))
    assert carries(paths_of(tree), "gbdt.route", under="jit(_build_tree)")
    by_row = [shape for shape in gathers_under(tree, "gbdt.route")
              if int(np.prod(shape)) >= rows]
    assert by_row == []
    old = gathers_under(lowered(gather_routed(**kw)), "gbdt.route")
    assert sum(int(np.prod(shape)) >= rows for shape in old) >= kw["max_depth"]


# what `_boost` hands the margin update in the four GBDT cells: 2 ** depth
# leaves, and the 2 * 255 - 1 nodes of the leaf-wise tree's pointer forest
CELL_LEAVES = {"higgs": 64, "airline": 256, "bosch": 256, "epsilon": 509}


@pytest.mark.parametrize("cell", sorted(CELL_LEAVES))
def test_margin_update_gathers_nothing_a_row(cell):
    """``gbdt.margin`` is a scope of the lowered margin program and nothing
    under it is gathered with an index a row (PR 43: one such gather, 256
    leaves onto 28.75M rows, was 246 ms of a 1,329 ms Airline round on the
    chip).  The same reading finds it when the crossover is set below the
    cell's leaves, which puts the gather back."""
    from test_gbdt_margin import select_ceiling
    leaves, rows = CELL_LEAVES[cell], 1024

    def by_row(lowered):
        assert carries(paths_of(lowered), "gbdt.margin",
                       under="jit(_leaf_values)/gbdt.boost/")
        return [shape for shape in gathers_under(lowered, "gbdt.margin")
                if int(np.prod(shape)) >= rows]

    assert by_row(margin_program(leaves, rows)) == []
    with select_ceiling(leaves - 1):
        assert len(by_row(margin_program(leaves, rows))) == 1


def device_ops(traced, primitive: str) -> list:
    """``(scope path, operands' avals, results' avals)`` of every equation
    of ``primitive`` in a traced program, in program order, nested calls
    (the kernels' jitted wrappers, split finding) walked through."""
    def walk(jaxpr, path):
        for eqn in jaxpr.eqns:
            here = f"{path}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == primitive:
                yield (here, [v.aval for v in eqn.invars],
                       [v.aval for v in eqn.outvars])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, here)
    return list(walk(traced.jaxpr.jaxpr, ""))


def tree_program(features: int, max_depth: int, sparse: bool, rows: int = 192):
    """The traced tree program of a cell's shape (its features, bins and
    depth) over a few rows; the sparse one as `fit_batch` hands it over when
    every level runs the kernel.  Returns it with its entry lanes."""
    model = GBDT(num_features=features, num_trees=1, max_depth=max_depth,
                 num_bins=256, missing_aware=True, histogram="pallas")
    tail = (jnp.zeros(rows), jnp.zeros(rows), jnp.ones(features, bool),
            jax.random.PRNGKey(0))
    if not sparse:
        return model._build_tree.trace(
            model, jnp.zeros((rows, features), jnp.uint8), *tail), 0
    from dmlc_core_tpu.ops.pallas_segment import sparse_hist_layout
    rid = jnp.repeat(jnp.arange(rows, dtype=jnp.int32), 3)
    layout = sparse_hist_layout(rid, (7 * rid + jnp.arange(rid.shape[0]))
                                % features, 1 + rid % 255,
                                jnp.ones(rid.shape, bool), features, 256)
    return (model._build_tree_sparse.trace(model, None, layout, *tail),
            layout.rid.shape[0])


# the three GBDT cells' shapes (benchmark/configs/*-gbdt.json)
CELL_TREES = {"higgs": (28, 6, False), "airline": (13, 8, False),
              "bosch": (968, 8, True)}


@pytest.mark.parametrize("cell", sorted(CELL_TREES))
def test_a_level_asks_its_kernel_for_one_child_of_each_parent(cell):
    """Both kernels' M axis is 6 x node columns (padded to 8): below the
    root a level hands them half its nodes, so the level at depth d runs the
    kernel the level above ran before PR 32."""
    features, max_depth, sparse = CELL_TREES[cell]
    traced, _ = tree_program(features, max_depth, sparse)
    calls = [(path, outs) for path, _ins, outs
             in device_ops(traced, "pallas_call") if "gbdt.hist" in path]
    assert len(calls) == max_depth
    for depth, (path, outs) in enumerate(calls):
        assert len(outs) == 1
        cols = 1 if depth == 0 else 2 ** (depth - 1)
        assert 2 * cols <= outs[0].shape[0] <= 6 * max(8, cols), (path, depth)


@pytest.mark.parametrize("cell", sorted(CELL_TREES))
def test_no_level_gathers_a_row_for_its_histogram(cell):
    """What a row needs to know of the built child reaches it in the pass
    that routes it (the dense tree's packed word) or by the entry gathers
    that were there (the sparse tree): under ``gbdt.hist`` nothing is
    gathered by row or by entry, nor under the dense ``gbdt.route``; and the
    sparse tree gathers its node ids onto the entries once a level below
    the root — the root's are zeros — beside the (grad, hess) pair's once."""
    features, max_depth, sparse = CELL_TREES[cell]
    rows = 192
    traced, lanes = tree_program(features, max_depth, sparse, rows)
    gathers = device_ops(traced, "gather")
    for path, ins, _outs in gathers:
        taken = int(np.prod(ins[1].shape[:-1]))
        if "gbdt.hist" in path or (not sparse and "gbdt.route" in path):
            assert taken < rows, (path, ins)
    by_entry = [ins[0] for path, ins, _outs in gathers
                if "gbdt.entry_gather" in path
                and int(np.prod(ins[1].shape[:-1])) == lanes]
    if not sparse:
        assert by_entry == []
        return
    ids = [a for a in by_entry if a.dtype == jnp.int32]
    assert len(ids) == max_depth - 1 and all(a.shape == (rows,) for a in ids)
    pairs = [a for a in by_entry if a.dtype == jnp.float32]
    assert len(pairs) == 1 and pairs[0].shape == (rows, 2)


@pytest.mark.parametrize("scope", [s for s in named_scopes()
                                   if s.startswith(("ffm.", "sgd.loss"))])
def test_backward_ops_keep_the_forward_scope(programs, scope):
    """One pattern on a scope takes a gather together with its scatter
    twin: the backward pass sits under ``transpose(jvp(sgd.loss))/<scope>``."""
    step = programs["step"]
    assert carries(step, scope, under="jit(_train_step)/jvp(sgd.loss)")
    assert carries(step, scope,
                   under="jit(_train_step)/transpose(jvp(sgd.loss))")


def test_no_metric_names_an_unknown_scope_prefix():
    assert named_scopes(), "the benchmark names no scope at all"
    assert {s.split(".")[0] for s in named_scopes()} <= {
        "gbdt", "ops", "batch", "ffm", "sgd", "mesh", "linear", "fm"}


def test_span_lands_in_the_profiler_trace_and_in_the_native_ring(tmp_path):
    telemetry.trace_start()
    jax.profiler.start_trace(str(tmp_path))
    with telemetry.span("test.two_sinks"):
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    telemetry.trace_stop()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    names = {e.name for line in host.lines for e in line.events}
    assert "dmlctpu.test.two_sinks" in names
    if telemetry.enabled():
        ring = {ev["name"] for ev in telemetry.trace_dump()["traceEvents"]}
        assert "test.two_sinks" in ring


def test_telemetry_itself_never_imports_jax():
    """``telemetry.span`` looks jax up in ``sys.modules`` and never imports
    it.  The package's ``__init__`` does import jax (data, models), so the
    module is loaded here under a bare package, as a JAX-free process would
    have to load it."""
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('dmlc_core_tpu')\n"
        f"pkg.__path__ = [{str(ROOT / 'dmlc_core_tpu')!r}]\n"
        "sys.modules['dmlc_core_tpu'] = pkg\n"
        "from dmlc_core_tpu import telemetry\n"
        "with telemetry.span('test.no_jax'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'telemetry imported jax'\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"


@pytest.fixture
def cache_in(tmp_path):
    """The persistent compile cache on, in ``tmp_path``, as
    ``compile_cache.configure()`` sets it up — except that the CPU platform
    is allowed to cache for the length of the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    before = {n: getattr(jax.config, n) for n in names}
    compile_cache.configure()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    hits = []

    def listener(event, **_):
        hits.append(event == "/jax/compilation_cache/cache_hits")

    jax.monitoring.register_event_listener(listener)
    try:
        yield lambda: sum(hits)
    finally:
        jax.monitoring.unregister_event_listener(listener)
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()


def run_scoped_or_not(scoped: bool) -> None:
    """One program, with or without a scope."""
    def double_plus_one(x):
        with (jax.named_scope("test.scope") if scoped
              else contextlib.nullcontext()):
            return x * 2.0 + 1.0
    jax.jit(double_plus_one)(jnp.arange(8.0)).block_until_ready()


def test_a_scope_is_part_of_the_compile_cache_key(cache_in):
    """What the parent commit compiled must not be handed to the change:
    it would carry the parent's metadata, and the scopes would be gone from
    the profile on exactly the warm runs."""
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    jnp.arange(8.0).block_until_ready()     # its own programs, out of the way
    base = cache_in()
    hits = []
    for scoped in (False, False, True, True):
        run_scoped_or_not(scoped)
        hits.append(cache_in() - base)
    # compiled, fetched, compiled (a scope more), fetched
    assert hits == [0, 1, 1, 2]
    # and JAX's default would have handed the unscoped executable over
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    run_scoped_or_not(False)
    run_scoped_or_not(True)
    assert cache_in() - base == 3


def fresh_program():
    """The same function, jitted anew: no call finds it in memory."""
    def triple_less_one(x):
        with jax.named_scope("test.scope"):
            return x * 3.0 - 1.0
    return jax.jit(triple_less_one)


def call_through_a_frame(fn, x):
    return fn(x)


def test_where_a_program_is_called_from_is_not_in_its_key(cache_in):
    """File, line and callers are no part of a program's key
    (``jax_traceback_in_locations_limit`` 0): one function traced from two
    lines of this test and through a helper's frame is compiled once and
    fetched twice.  With JAX's default every call site compiled it again,
    and so did every edit that shifted a line above a program."""
    assert jax.config.jax_traceback_in_locations_limit == 0
    x = jnp.arange(8.0)
    x.block_until_ready()
    base = cache_in()
    hits = []
    fresh_program()(x).block_until_ready()
    hits.append(cache_in() - base)
    fresh_program()(x).block_until_ready()
    hits.append(cache_in() - base)
    call_through_a_frame(fresh_program(), x).block_until_ready()
    hits.append(cache_in() - base)
    assert hits == [0, 1, 2]


def ftrl_step_on_the_rows_kernel(monkeypatch):
    """The FTRL step, traced, its scatters forced onto the rows kernel at a
    table of two tiles and the kernel compiled, not interpreted."""
    from dmlc_core_tpu.models.common import FTRL
    from dmlc_core_tpu.models.linear import SparseLinearModel
    from dmlc_core_tpu.ops import pallas_rows
    monkeypatch.setattr(pallas_rows, "engages", lambda *_: True)
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    rows, per_row = 64, 16
    batch = PaddedBatch(
        label=jnp.zeros(rows), weight=jnp.ones(rows),
        row_ptr=jnp.arange(rows + 1, dtype=jnp.int32) * per_row,
        index=jnp.zeros(rows * per_row, jnp.int32),
        value=jnp.ones(rows * per_row), num_rows=jnp.asarray(np.int32(rows)))
    linear = SparseLinearModel(2 * pallas_rows.TILE, optimizer=FTRL())
    return linear._touched_rows_step.trace(linear, linear.init(), batch)


def test_lowered_programs_keep_their_scopes_and_name_no_file(
        cache_in, monkeypatch):
    """What ``configure()`` leaves of a program's debug information, in the
    text the TPU's compiler is handed: the scope paths the benchmark's
    readers match on, and no source location."""
    from dmlc_core_tpu.ops import pallas_segment
    monkeypatch.setattr(pallas_segment, "pallas_interpret", lambda: False)

    def level(bins, rel, gh):
        with jax.named_scope("gbdt.hist"):
            return pallas_segment.histogram_gh(bins, rel, gh, 2, 16,
                                               force="pallas")
    rows = 512
    hist = jax.jit(level).trace(
        jnp.zeros((rows, 4), jnp.uint8), jnp.zeros(rows, jnp.int32),
        jnp.ones((rows, 2)))
    step = ftrl_step_on_the_rows_kernel(monkeypatch)
    for traced, scopes in ((hist, ("gbdt.hist", "ops.hist_layout")),
                           (step, sorted(TOUCHED_ROWS))):
        lowered = traced.lower(lowering_platforms=("tpu",))
        paths, text = paths_of(lowered), lowered.as_text(debug_info=True)
        for scope in scopes:
            assert carries(paths, scope), scope
        assert '.py"' not in text
        assert "tpu_custom_call" in text


def test_touched_rows_step_nests_its_scopes_and_keeps_the_shared_ones(
        programs):
    """The FTRL step is one program, `jit(_touched_rows_step)`: the model's
    margins inside `sgd.loss` forward and backward, `batch.row_ids` as the
    FFM step has it, and no dense parameter pass (`sgd.update`)."""
    paths = programs["touched"]
    for scope in sorted(TOUCHED_ROWS):
        assert carries(paths, scope, under="jit(_touched_rows_step)"), scope
    assert carries(paths, "linear.margins", under="sgd.loss")
    assert carries(paths, "linear.margins", under="transpose(")
    assert carries(paths, "batch.row_ids")
    assert not carries(paths, "sgd.update")
    assert not any(TOUCHED_ROWS & set(re.split(r"[/()]", p))
                   for p in programs["step"])


def test_touched_rows_step_runs_the_rows_kernel_under_scatter_rows(
        monkeypatch):
    """Where the table is long against a visit's lanes (forced here, at a
    table of two tiles, and lowered for the TPU) the step's scatters are ONE
    kernel a visit, ``ops/pallas_rows.py``'s, under ``sgd.scatter_rows`` and
    by its own name, which is what `ftrl_scatter_ms_per_step` goes on
    reading; no XLA scatter is left under the scope, and the other four
    scopes are where they were."""
    from dmlc_core_tpu.ops import pallas_rows
    lowered = ftrl_step_on_the_rows_kernel(monkeypatch).lower(
        lowering_platforms=("tpu",))
    paths = paths_of(lowered)
    for scope in sorted(TOUCHED_ROWS):
        assert carries(paths, scope, under="jit(_touched_rows_step)"), scope
    under = [p for p in paths if "/sgd.scatter_rows/" in p]
    assert any(pallas_rows.SCATTER_ROWS_KERNEL in p and "pallas_call" in p
               for p in under), sorted(under)
    assert not any("scatter" in p.rsplit("/", 1)[-1] for p in under), (
        sorted(under))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f'kernel_name = "{pallas_rows.SCATTER_ROWS_KERNEL}"' in text


# ---- the field-aware machine on the touched-rows step ----------------------

FIELD_ROWS = ("ffm.reduce", "ffm.diag", "ffm.linear", "sgd.update",
              "sgd.unique", "sgd.gather_rows", "sgd.scatter_rows")


@pytest.mark.parametrize("scope", FIELD_ROWS)
def test_field_aware_step_keeps_the_scopes_its_metrics_read(programs, scope):
    """Without a penalty the field-aware machine's step is
    ``jit(_wide_rows_step)``: the product's three scopes inside
    ``sgd.loss``, forward and backward, the plain-SGD rule under
    ``sgd.update`` (what `ffm_update_ms_per_step` goes on reading) and the
    step's own three; ``ffm.gather`` is scoring's alone now."""
    paths = programs["field_rows"]
    assert carries(paths, scope, under="jit(_wide_rows_step)")
    if scope.startswith("ffm."):
        assert carries(paths, scope, under="sgd.loss")
        assert carries(paths, scope, under="transpose(")
    assert not carries(paths, "ffm.gather")
    assert not carries(paths, "sgd.ftrl") and not carries(paths, "sgd.adagrad")


def all_equations(jaxpr, path=""):
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        yield here, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_equations(sub, here)


def field_batch(rows: int, fields: int, features: int, pad: int):
    rng = np.random.default_rng(0)
    nnz = rows * fields
    return PaddedBatch(
        label=jnp.zeros(rows), weight=jnp.ones(rows),
        row_ptr=jnp.arange(rows + 1, dtype=jnp.int32) * fields,
        index=jnp.asarray(np.r_[rng.integers(0, features, nnz),
                                np.zeros(pad)].astype(np.int32)),
        value=jnp.asarray(np.r_[np.ones(nnz), np.zeros(pad)]
                          .astype(np.float32)),
        num_rows=jnp.asarray(np.int32(rows)),
        field=jnp.asarray(np.r_[np.tile(np.arange(fields), rows),
                                np.zeros(pad)].astype(np.int32)))


def test_field_aware_step_makes_no_table_and_moves_no_row_an_entry():
    """The traced step holds no value shaped like the table but the donated
    table as the loops hand it on and its scatter's result (the dense step
    makes a gradient and an update of that shape), and nothing under
    ``ffm.*`` is gathered or scattered with an index an entry lane: the
    rows reach the entries by rank under ``sgd.gather_rows``, and the
    product sums whole blocks (the branch for a batch that is not one entry
    a field reads one row a (field, row) slot, fewer than the lanes).  The
    dense step's ``ffm.gather`` and ``ffm.reduce`` do both."""
    rows, fields, features, pad = 8, 3, 64, 8
    lanes = rows * fields + pad
    ffm = FieldAwareFactorizationMachine(num_features=features,
                                         num_fields=fields)
    batch = field_batch(rows, fields, features, pad)
    table = (features, fields, ffm.num_factors)

    def makers(traced):
        return {eqn.primitive.name for _path, eqn
                in all_equations(traced.jaxpr.jaxpr)
                if any(getattr(v.aval, "shape", None) == table
                       for v in eqn.outvars)}

    def by_entry(traced):
        found = []
        for primitive in ("gather", "scatter", "scatter-add", "scatter_add"):
            for path, ins, _outs in device_ops(traced, primitive):
                indices = ins[1].shape[:-1]
                if "ffm." in path and int(np.prod(indices)) >= lanes:
                    found.append((primitive, path))
        return found

    step = ffm._wide_rows_step.trace(ffm, ffm.init(0), batch)
    assert makers(step) <= {"scatter", "while"}, makers(step)
    assert by_entry(step) == []
    dense = ffm._train_step.trace(ffm, ffm.init(0), batch)
    assert not makers(dense) <= {"scatter", "while"}
    moved = by_entry(dense)
    assert any(p == "gather" and "ffm.gather" in path for p, path in moved)
    assert any(p == "scatter-add" and "ffm.reduce" in path
               for p, path in moved), moved


def test_the_entry_lookup_runs_under_the_entry_gather_scope(monkeypatch):
    """Where the rule engages (forced here; on a chip it reads the layout
    and the backend), the sparse tree lays its rows' values onto the entries
    by the lookup kernel, under the scope `entry_gather_ms_per_round` reads:
    the (grad, hess) pair once, the slots once a level below the root, and
    no gather takes an index an entry there.  Lowered for the chip, each is
    a Mosaic call under that scope."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    monkeypatch.setattr(ps, "entry_lookup_engages",
                        lambda rows_ascend, plane_rows: rows_ascend)
    monkeypatch.setattr(ps, "pallas_interpret", lambda: False)
    features, max_depth, _sparse = CELL_TREES["bosch"]
    traced, lanes = tree_program(features, max_depth, True)
    lookups = [(path, outs[0]) for path, _ins, outs
               in device_ops(traced, "pallas_call")
               if "gbdt.entry_gather" in path]
    assert all(path.endswith(f"/gbdt.entry_gather/{ps.ENTRY_LOOKUP_KERNEL}")
               for path, _out in lookups)
    assert [(out.shape, str(out.dtype)) for _path, out in lookups] == (
        [((2, lanes), "float32")] + [((1, lanes), "int32")] * (max_depth - 1))
    assert [ins for path, ins, _outs in device_ops(traced, "gather")
            if "gbdt.entry_gather" in path] == []
    paths = paths_of(traced.lower(lowering_platforms=("tpu",)))
    # (the kernel's jitted wrapper is lowered as a function of its own: the
    # call site carries the scope, the body's paths start at the wrapper)
    assert ("jit(_build_tree_sparse)/gbdt.entry_gather/"
            f"jit({ps.ENTRY_LOOKUP_KERNEL})") in paths
    assert f"{ps.ENTRY_LOOKUP_KERNEL}/pallas_call" in paths
    assert carries(paths, "ops.lookup_layout")


def test_the_entries_push_runs_under_the_route_scope(monkeypatch):
    """Where its rule engages (on a chip it reads the layout, the backend
    and the rows; here the kernels are only said to be compiled), every level
    of the sparse tree routes by the push kernel, under the scope
    `sparse_route_ms_per_round` reads: one call a level into a float32 table
    of the rows, no bisection loop and no gather a row or an entry under
    ``gbdt.route``, and the slots' derivation under ``gbdt.entry_gather``.
    Lowered for the chip, each is a Mosaic call under the route's scope."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    features, max_depth, _sparse = CELL_TREES["bosch"]
    rows = 192
    bisecting, _ = tree_program(features, max_depth, True, rows)
    assert [path for path, _i, _o in device_ops(bisecting, "scan")
            if "gbdt.route" in path]          # the halvings' loop
    monkeypatch.setattr(ps, "pallas_interpret", lambda: False)
    traced, lanes = tree_program(features, max_depth, True, rows)
    pushes = [(path, ins, outs) for path, ins, outs
              in device_ops(traced, "pallas_call") if "gbdt.route" in path]
    assert len(pushes) == max_depth
    for path, ins, outs in pushes:
        assert path.endswith(f"/gbdt.route/{ps.ENTRY_PUSH_KERNEL}")
        assert [i.shape for i in ins[1:]] == [(1, lanes)] * 2
        assert [(o.shape, str(o.dtype)) for o in outs] == [
            ((128, 128), "float32")]
    for primitive in ("scan", "while", "scatter", "scatter-add"):
        assert [path for path, _i, _o in device_ops(traced, primitive)
                if "gbdt.route" in path
                and ps.ENTRY_PUSH_KERNEL not in path] == [], primitive
    # what is still gathered there is `run_spans`' two reads of ``fstart`` a
    # node of the level
    gathered = [int(np.prod(ins[1].shape[:-1])) for path, ins, _o
                in device_ops(traced, "gather") if "gbdt.route" in path]
    assert sorted(gathered) == sorted(
        2 ** d for d in range(max_depth) for _ in range(2))
    assert [path for path, _i, _o in device_ops(traced, "select_n")
            if "gbdt.entry_gather" in path]
    paths = paths_of(traced.lower(lowering_platforms=("tpu",)))
    assert ("jit(_build_tree_sparse)/gbdt.route/"
            f"jit({ps.ENTRY_PUSH_KERNEL})") in paths
    assert f"{ps.ENTRY_PUSH_KERNEL}/pallas_call" in paths
    assert carries(paths, "gbdt.entry_gather")


def test_the_layout_bins_its_entries_under_the_pack_programs_name():
    """The values form of the layout's pack program holds the binning kernel
    under ``jit(_layout_pack)/gbdt.layout_bin``: the head
    `sparse_prepare_ms_per_round` matches and `sparse_boost_ms_per_round`
    leaves out, and the scope `layout_bin_ms_per_round` reads.  One call,
    from the packed values to the packed keys; the codes form holds none.
    Lowered for the chip it is a Mosaic call under that scope."""
    import json
    from dmlc_core_tpu.ops import pallas_segment as ps
    lanes, features = 4096, 968
    args = (jnp.zeros(5000), jnp.zeros(5000, jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.full(1, 4000, jnp.int32), 1, lanes)
    binning = dict(rstart=jnp.zeros(features + 2, jnp.int32),
                   cuts=jnp.zeros((features, 254)), nb=256, interpret=False)
    traced = ps._layout_pack.trace(*args, **binning)
    calls = device_ops(traced, "pallas_call")
    (path, ins, outs), = calls
    assert path.endswith(f"/gbdt.layout_bin/{ps.BIN_RUNS_KERNEL}")
    assert [(o.shape, str(o.dtype)) for o in outs] == [((1, lanes), "int32")]
    assert (1, lanes) in [i.shape for i in ins]
    assert device_ops(ps._layout_pack.trace(
        *((jnp.zeros(5000, jnp.int32),) + args[1:])), "pallas_call") == []
    paths = paths_of(traced.lower(lowering_platforms=("tpu",)))
    assert (f"jit(_layout_pack)/gbdt.layout_bin/jit({ps.BIN_RUNS_KERNEL})"
            in paths)
    assert f"{ps.BIN_RUNS_KERNEL}/pallas_call" in paths
    prepare, boost = (json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text()
    )["args"] for name in ("sparse_prepare_ms_per_round",
                           "sparse_boost_ms_per_round"))
    head = f"jit(_layout_pack)/gbdt.layout_bin/jit({ps.BIN_RUNS_KERNEL})/x"
    assert re.search(prepare["scope"], head)
    assert re.search(boost["exclude"], head)
