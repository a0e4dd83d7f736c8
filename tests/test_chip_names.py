"""What the benchmark finds in a device trace by name is still there after
the TPU's compiler: the two histogram kernels' instructions, and the scopes
of the tree program.  Compiled here for a described v5e, not run (one file,
one topology fixture: only one process may hold the TPU's library)."""
import json
import os
import re
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from dmlc_core_tpu.models import GBDT
from dmlc_core_tpu.ops import pallas_segment

LAYER_METRICS = Path(__file__).resolve().parents[1] / "benchmark" / "layer_metrics"
ROWS, FEATURES, BINS = 65536, 28, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one; keep it out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def instructions(compiled) -> list:
    """``%name`` of every instruction of the compiled module, as a device
    trace names its events."""
    return re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = ", compiled.as_text(),
                      re.M)


def op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def kernel_dots(fn, *shapes) -> list:
    """``(operand dtypes, precision, result dtype)`` of every contraction
    in the Pallas kernels of ``fn``, read from their jaxprs."""
    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside and eqn.primitive.name == "dot_general":
                yield ([str(v.aval.dtype) for v in eqn.invars],
                       eqn.params["precision"],
                       str(eqn.outvars[0].aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, inside
                                or eqn.primitive.name == "pallas_call")
    return list(walk(jax.make_jaxpr(fn)(*shapes).jaxpr, False))


# 1 to 32 nodes: every key tile in one step, the three parts on one dot;
# 64: the parts on three dots; 128: groups of 8 features' tiles on the grid;
# 512 = HIST_NODE_LIMIT: one tile a step
@pytest.mark.parametrize("n_nodes", [1, 16, 32, 64, 128, 512])
def test_dense_histogram_kernel_keeps_the_name_the_benchmark_matches(
        one_chip, quiet_cache, n_nodes):
    assert n_nodes <= pallas_segment.HIST_NODE_LIMIT
    pattern = json.loads((LAYER_METRICS / "hist_ms_per_round.json")
                         .read_text())["args"]["pattern"]
    roofline = json.loads((LAYER_METRICS / "hist_roofline.json")
                          .read_text())["args"]["pattern"]
    assert pattern == roofline

    def level(bins, rel, gh):
        return pallas_segment._histogram_gh_pallas(
            bins.T, rel, gh, n_nodes, BINS, False)

    shapes = (on(one_chip, (ROWS, FEATURES), jnp.int32),
              on(one_chip, (ROWS,), jnp.int32),
              on(one_chip, (ROWS, 2), jnp.float32))
    compiled = jax.jit(level).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    kernels = [n for n in instructions(compiled) if re.search(pattern, n)]
    assert len(kernels) == 1, (pattern, kernels)
    assert kernels[0].startswith("%" + pallas_segment.DENSE_HIST_KERNEL)
    assert any("ops.hist_layout" in n for n in op_names(compiled))
    # one bfloat16 pass with float32 accumulation: no float32 operand and
    # no HIGHEST left in the kernel
    dots = kernel_dots(level, *shapes)
    assert dots and all(
        operands == ["bfloat16", "bfloat16"] and precision is None
        and result == "float32" for operands, precision, result in dots), dots


# the Bosch cell's layout (benchmark/configs/bosch-gbdt.json: 968 features x
# 256 bins; 2.18e8 entry lanes, the fullest key tile's span 2,304 sub-tiles).
# 1 to 32 nodes: the three parts on one dot; 64: on three; 256 =
# SPARSE_HIST_NODE_LIMIT, within the scoped VMEM
@pytest.mark.parametrize("n_nodes", [1, 32, 64, 256])
def test_sparse_histogram_kernel_keeps_the_name_the_benchmark_matches(
        one_chip, quiet_cache, n_nodes):
    assert n_nodes <= pallas_segment.SPARSE_HIST_NODE_LIMIT
    pattern = json.loads((LAYER_METRICS / "sparse_hist_ms_per_round.json")
                         .read_text())["args"]["pattern"]
    roofline = json.loads((LAYER_METRICS / "sparse_hist_roofline.json")
                          .read_text())["args"]["pattern"]
    assert pattern == roofline
    nnz, features, max_tiles = 218103808, 968, 2304
    num_kt = features * BINS // pallas_segment._KEY_TILE

    def level(gkey, rel_e, gh_e, tstart, tcount):
        return pallas_segment._histogram_gh_sparse_pallas(
            gkey, rel_e, gh_e, tstart, tcount, n_nodes, features, BINS,
            max_tiles, False)

    shapes = (on(one_chip, (nnz,), jnp.int32), on(one_chip, (nnz,), jnp.int32),
              on(one_chip, (2, nnz), jnp.float32),
              on(one_chip, (num_kt,), jnp.int32),
              on(one_chip, (num_kt,), jnp.int32))
    compiled = jax.jit(level).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    kernels = [n for n in instructions(compiled) if re.search(pattern, n)]
    assert len(kernels) == 1, (pattern, kernels)
    assert kernels[0].startswith("%" + pallas_segment.SPARSE_HIST_KERNEL)
    # no array of nnz lanes is made for the kernel (the parts are split
    # inside it, the entries go in as they came): the scratch is the
    # histogram's, under one int32 an entry
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * nnz
    dots = kernel_dots(level, *shapes)
    assert len(dots) == (1 if n_nodes <= 32 else 3)
    assert all(operands == ["bfloat16", "bfloat16"] and precision is None
               and result == "float32"
               for operands, precision, result in dots), dots


# the lookup that lays a row's values onto the Bosch cell's 2.18e8 sorted
# entries: a level's slots (one plane of 1,183,747 rows) and a tree's
# (grad, hess) (the six planes of three bfloat16 parts, 14.4 MB of VMEM)
@pytest.mark.parametrize("planes", [1, 6])
def test_entry_lookup_kernel_compiles_at_the_cells_size(one_chip, quiet_cache,
                                                        planes):
    nnz, rows = 218103808, 1183747
    assert planes * rows <= pallas_segment.ENTRY_LOOKUP_PLANE_ROWS
    out_rows, dtype = (1, jnp.int32) if planes == 1 else (2, jnp.float32)

    def lookup(rid, cspan, table):
        return pallas_segment._entry_lookup_pallas(
            rid, cspan, table, out_rows, dtype, False)

    shapes = (on(one_chip, (nnz,), jnp.int32),
              on(one_chip, (nnz // 1024,), jnp.int32),
              on(one_chip, (planes, rows), jnp.bfloat16))
    compiled = jax.jit(lookup).lower(*shapes).compile()
    kernels = [n for n in instructions(compiled) if n.startswith(
        "%" + pallas_segment.ENTRY_LOOKUP_KERNEL)]
    assert len(kernels) == 1 and compiled.as_text().count(
        "tpu_custom_call") == 1
    # what is laid out for the kernel is the table and the spans, not an
    # array of entry lanes; the result is as wide as XLA's gather's
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.output_size_in_bytes == out_rows * nnz * 4
    dots = kernel_dots(lookup, *shapes)
    assert dots == [(["bfloat16", "bfloat16"], None, "float32")]


# the push that routes the Bosch cell's rows: the 2.18e8 entry lanes' row
# ids and what each carries, into a float32 table of 1,183,747 rows (4.8 MB
# of VMEM for the call)
def test_entry_push_kernel_compiles_at_the_cells_size(one_chip, quiet_cache):
    nnz, rows = 218103808, 1183747
    assert rows <= pallas_segment.ROUTE_PUSH_ROWS

    def push(rid, span, val):
        return pallas_segment._entry_push_pallas(rid, span, val, rows, False)

    shapes = (on(one_chip, (nnz,), jnp.int32),
              on(one_chip, (nnz // 1024,), jnp.int32),
              on(one_chip, (nnz,), jnp.int32))
    compiled = jax.jit(push).lower(*shapes).compile()
    kernels = [n for n in instructions(compiled) if n.startswith(
        "%" + pallas_segment.ENTRY_PUSH_KERNEL)]
    assert len(kernels) == 1 and compiled.as_text().count(
        "tpu_custom_call") == 1
    # nothing the size of the entry lanes is made for the kernel, and what
    # comes back is a float a row
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.output_size_in_bytes < 8 * rows
    assert kernel_dots(push, *shapes) == [
        (["bfloat16", "bfloat16"], None, "float32")]
    # the largest table the rule lets in still fits the chip's fast memory
    wide = pallas_segment.ROUTE_PUSH_ROWS
    jax.jit(lambda rid, span, val: pallas_segment._entry_push_pallas(
        rid, span, val, wide, False)).lower(*shapes).compile()


# the binning on the layout's lanes: the Bosch cell's 2.18e8 packed values
# against a table of 968 features' 254 cuts (1 MiB of VMEM for the call), on
# one chip and as a sharded layout's runs (a run's feature by a remainder)
@pytest.mark.parametrize("shards", [1, 4])
def test_bin_runs_kernel_compiles_at_the_cells_size(one_chip, quiet_cache,
                                                    shards):
    nnz, features, cuts = 218103808, 968, 254
    assert pallas_segment._bin_table_shape(features, cuts) == (256, 1024)

    def bin_runs(value, rstart, table):
        return pallas_segment._bin_runs_pallas(value, rstart, table, 256,
                                               shards, False)

    shapes = (on(one_chip, (nnz,), jnp.float32),
              on(one_chip, (shards * (features + 1) + 1,), jnp.int32),
              on(one_chip, (features, cuts), jnp.float32))
    compiled = jax.jit(bin_runs).lower(*shapes).compile()
    kernels = [n for n in instructions(compiled) if n.startswith(
        "%" + pallas_segment.BIN_RUNS_KERNEL)]
    assert len(kernels) == 1 and compiled.as_text().count(
        "tpu_custom_call") == 1
    # what is laid out for the kernel is the table and the sub-tiles' runs,
    # not an array of entry lanes; the keys come back an int32 a lane
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.output_size_in_bytes == nnz * 4
    assert kernel_dots(bin_runs, *shapes) == []      # compares, no MXU
    if shards == 1:
        # the widest table the rule lets in still fits the chip's fast memory
        wide = pallas_segment._BIN_TABLE_BYTES // (256 * 4) - 1
        assert pallas_segment._bin_table_shape(wide, cuts) == (256, wide + 1)
        jax.jit(bin_runs).lower(
            shapes[0], on(one_chip, (wide + 2,), jnp.int32),
            on(one_chip, (wide, cuts), jnp.float32)).compile()


def test_sparse_tree_program_looks_its_entries_up_under_their_scope(
        one_chip, quiet_cache, monkeypatch):
    """The sparse tree program as a chip compiles it where the rule engages
    (rows ascending in the layout, a table that fits): a lookup kernel for
    the tree's (grad, hess) and one a level below the root, each under
    ``gbdt.entry_gather``, the scope `entry_gather_ms_per_round` reads, and
    no gather of an entry's row anywhere in the program."""
    monkeypatch.setattr(pallas_segment, "pallas_interpret", lambda: False)
    rows, nnz, depth = 200_000, 600 * 1024, 4
    model = GBDT(num_features=FEATURES, num_trees=1, max_depth=depth,
                 num_bins=BINS, missing_aware=True, histogram="pallas")
    nb, num_kt = pallas_segment._sparse_geometry(FEATURES, BINS)

    def lanes(n):
        return on(one_chip, (n,), jnp.int32)

    layout = pallas_segment.SparseHistLayout(
        num_features=FEATURES, num_bins=BINS, num_shards=1, nb=nb,
        num_kt=num_kt, max_tiles=48, nnz_pad=nnz, run_bits=16,
        rows_ascend=True, gkey=lanes(nnz), rid=lanes(nnz),
        tstart=lanes(num_kt), tcount=lanes(num_kt),
        fstart=lanes(FEATURES + 1), nnz_live=on(one_chip, (), jnp.int32),
        cspan=lanes(nnz // 1024))
    assert model._entry_lookups(layout, rows) == depth
    compiled = model._build_tree_sparse.lower(
        model, None, layout, on(one_chip, (rows,), jnp.float32),
        on(one_chip, (rows,), jnp.float32),
        on(one_chip, (FEATURES,), jnp.bool_),
        on(one_chip, (2,), jnp.uint32)).compile()
    text = compiled.as_text()
    lookups = re.findall(
        r"^\s*(?:ROOT )?%" + pallas_segment.ENTRY_LOOKUP_KERNEL
        + r"[\w.\-]* = (\S+) custom-call\(.*op_name=\"([^\"]*)\"", text, re.M)
    assert sorted(shape.split("{")[0] for shape, _name in lookups) == (
        [f"f32[2,{nnz}]"] + [f"s32[1,{nnz}]"] * (depth - 1))
    assert all("/gbdt.entry_gather/" in name for _shape, name in lookups)
    assert not re.search(rf"\[{nnz}(,\d+)?\]\S* gather\(", text)
    # and every level routes by the push kernel under ``gbdt.route``, the
    # scope `sparse_route_ms_per_round` reads: no bisection loop is left,
    # and the selects that derive the slots carry ``gbdt.entry_gather``
    pushes = re.findall(
        r"^\s*(?:ROOT )?%" + pallas_segment.ENTRY_PUSH_KERNEL
        + r"[\w.\-]* = (\S+) custom-call\(.*op_name=\"([^\"]*)\"", text, re.M)
    assert len(pushes) == depth
    assert all("/gbdt.route/" in name for _shape, name in pushes)
    assert not any("/gbdt.route/" in name and "while" in name
                   for name in op_names(compiled))
    assert any("/gbdt.entry_gather/" in name and "select_n" in name
               for name in op_names(compiled))


def test_tree_program_keeps_its_scopes_through_the_tpu_compiler(
        one_chip, quiet_cache, monkeypatch):
    """``gbdt.route`` and ``ops.hist_layout`` are in the ``op_name`` of
    instructions of the compiled tree program, which is what a device
    trace's ``tf_op`` carries; and the kernel is there under its name."""
    # the model asks the attached backend (the CPU, here) whether to
    # interpret its kernels; this compile is for the described chip
    monkeypatch.setattr(pallas_segment, "pallas_interpret", lambda: False)
    model = GBDT(num_features=FEATURES, num_trees=1, max_depth=2,
                 num_bins=BINS, missing_aware=True, histogram="pallas")
    compiled = model._build_tree.lower(
        model, on(one_chip, (ROWS, FEATURES), jnp.uint8),
        on(one_chip, (ROWS,), jnp.float32), on(one_chip, (ROWS,), jnp.float32),
        on(one_chip, (FEATURES,), jnp.bool_),
        on(one_chip, (2,), jnp.uint32)).compile()
    names = op_names(compiled)
    for scope in ("gbdt.route", "ops.hist_layout", "gbdt.hist", "gbdt.split",
                  "gbdt.leaf"):
        assert any(f"/{scope}/" in n for n in names), scope
    kernels = [n for n in instructions(compiled)
               if n.startswith("%" + pallas_segment.DENSE_HIST_KERNEL)]
    assert len(kernels) == model.max_depth
    kernel_ops = [n for n in names if n.endswith("/pallas_call")]
    assert kernel_ops and all("/gbdt.hist/" in n for n in kernel_ops)


# the Airline cell's tree program (benchmark/configs/airline-gbdt.json: 13
# features x 256 bins, depth 8) for the four chips of a described v5e 2x2,
# rows sharded, at a row count a chip that is no whole number of row tiles
def test_sharded_tree_program_reduces_once_a_level_and_gathers_nothing(
        topo, quiet_cache, monkeypatch):
    """What a four-chip trace is read by: eight kernels under ``shard_map``
    with the dense kernel's name, each level's built histograms (the root,
    then one child of every parent) reduced once under the scope
    ``mesh.allreduce`` before their siblings are derived, no other reduction
    (the leaves come off the last level's reduced histogram: PR 38 took out
    the row sums' scatter-add, the psum the compiler gave it and the 512 B a
    row of scratch its ``[rows, 2]`` operand was relaid into), and no
    all-gather, all-to-all or permute of a row array anywhere."""
    import numpy as np
    from jax.sharding import Mesh

    from dmlc_core_tpu.parallel import MeshPlan
    monkeypatch.setattr(pallas_segment, "pallas_interpret", lambda: False)
    plan = MeshPlan(Mesh(np.asarray(topo.devices[:4]), ("data",)), ("data",),
                    collective="flat", overlap_chunks=1)
    features, depth, rows_chip = 13, 8, 2_000_000
    rows = 4 * rows_chip
    model = GBDT(num_features=features, num_trees=2, max_depth=depth,
                 num_bins=BINS, learning_rate=0.1, min_child_weight=1.0,
                 histogram="pallas", histogram_mesh=plan)
    by_rows, whole = plan.data_sharding(), plan.replicated_sharding()
    compiled = model._build_tree.lower(
        model, on(by_rows, (rows, features), jnp.uint8),
        on(by_rows, (rows,), jnp.float32), on(by_rows, (rows,), jnp.float32),
        on(whole, (features,), jnp.bool_), on(whole, (2,), jnp.uint32)
    ).compile()
    text = compiled.as_text()
    pattern = json.loads((LAYER_METRICS / "mesh_hist_ms_per_round.json")
                         .read_text())["args"]["pattern"]
    kernels = [n for n in instructions(compiled) if re.search(pattern, n)]
    assert len(kernels) == depth and text.count("tpu_custom_call") == depth
    reduces = re.findall(r"= (\S+) all-reduce(?:-start)?\(.*?op_name=\"([^\"]*)\"",
                         text)
    levels = [shape for shape, name in reduces if "mesh.allreduce" in name]
    # the built columns only: the root, then one child of every parent
    built = [1] + [2 ** (d - 1) for d in range(1, depth)]
    assert sorted(int(s.split("[")[1].split(",")[0]) for s in levels) == built
    out_rows = sorted(int(m) for m in re.findall(
        r"%_histogram_gh_pallas[\w.\-]* = f32\[(\d+),", text))
    assert out_rows == sorted((6 if c <= 32 else 2) * max(8, c) for c in built)
    others = [name for _, name in reduces if "mesh.allreduce" not in name]
    assert others == []
    assert not re.search(r"= \S+ scatter\(", text)
    # a chip's scratch is 102 B a row (the bins as int32, 52 B, and the
    # kernel's padded operands); the row sums' relaid pair was 512 B a row
    # more (the rows are enough for the scratch to lie in HBM, not VMEM)
    assert compiled.memory_analysis().temp_size_in_bytes < 160 * rows_chip
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert not re.search(rf"= .*\b{op}(-start)?\(", text), op
    scope = json.loads((LAYER_METRICS / "allreduce_ms_per_round.json")
                       .read_text())["args"]["scope"]
    assert any(re.search(scope, n) for n in op_names(compiled))
    # a chip holds its own rows and nothing of another's: the arguments are
    # a quarter of the rows each
    assert compiled.memory_analysis().argument_size_in_bytes < rows_chip * 32


@pytest.mark.parametrize("features,kernel_visits", [
    (2 ** 29, [1 << 16, 1 << 17, 1 << 18, 1 << 19, 655360]),
    (2 ** 27, [1 << 16, 1 << 17])])
def test_touched_rows_step_updates_its_tables_in_place(
        one_chip, quiet_cache, monkeypatch, features, kernel_visits):
    """The FTRL step at the ``criteo-tb-ftrl`` cell's shapes (2^29 buckets,
    16,384 x 39 entries padded to 655,360 lanes) and over a quarter of its
    table, through the TPU's compiler: all three tables alias their outputs,
    the temporaries are megabytes (no table-sized array beside the donated
    tables), the five scopes the cell's metrics read are in the compiled
    program.  The visits under the crossover (every one at 2^29 buckets; 2^16
    and 2^17 keys at 2^27) are the in-place kernel, under
    ``sgd.scatter_rows`` and by the name a trace shows, the tables handed
    to it and back by bitcasts; the visits past it keep XLA's three scatters
    (each a loop of one trip or none, its carry in place)."""
    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models.common import FTRL, TOUCHED_ROWS_VISITS
    from dmlc_core_tpu.models.linear import SparseLinearModel
    from dmlc_core_tpu.ops import pallas_rows
    # the code asks the default backend, which is the CPU here
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    rows, lanes = 16384, 655360
    model = SparseLinearModel(features, optimizer=FTRL())
    params = jax.tree.map(lambda a: on(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init))
    batch = PaddedBatch(
        label=on(one_chip, (rows,), jnp.float32),
        weight=on(one_chip, (rows,), jnp.float32),
        row_ptr=on(one_chip, (rows + 1,), jnp.int32),
        index=on(one_chip, (lanes,), jnp.int32),
        value=on(one_chip, (lanes,), jnp.float32),
        num_rows=on(one_chip, (), jnp.int32))
    compiled = model._touched_rows_step.lower(model, params, batch).compile()
    memory = compiled.memory_analysis()
    tables = 3 * 4 * features
    assert memory.alias_size_in_bytes >= tables
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.argument_size_in_bytes < tables + (16 << 20)
    names = op_names(compiled)
    for scope in ("sgd.unique", "sgd.gather_rows", "linear.margins",
                  "sgd.ftrl", "sgd.scatter_rows"):
        part = re.compile(r"[/(]" + re.escape(scope) + r"[/)]")
        assert any(part.search(n) for n in names), scope
    text = compiled.as_text()
    visits = [c for c in TOUCHED_ROWS_VISITS if c < lanes] + [lanes]
    kernel = [c for c in visits
              if pallas_rows.engages(features, c, jnp.float32)]
    assert kernel == kernel_visits
    calls = re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = .* custom-call\(.*"
                       r'op_name="([^"]*)"', text, re.M)
    calls = [(name, op) for name, op in calls
             if pallas_rows.SCATTER_ROWS_KERNEL in name]
    assert len(calls) == len(kernel), calls
    for name, op in calls:
        assert name.startswith("%" + pallas_rows.SCATTER_ROWS_KERNEL)
        assert "/sgd.scatter_rows/" in op, op
    made = re.findall(r"^\s*(?:ROOT )?%([a-z\-]+)[\w.\-]* = "
                      rf"f32\[{features}\]", text, re.M)
    # the tables themselves, handed on; the only ops that make one are the
    # three scatters of each visit past the crossover (each a fusion of its
    # own in the loop's body) and the bitcasts that bring a table back from
    # the kernel's view of it, ``[F / 1024, 8, 128]``
    assert set(made) <= {"scatter", "fusion", "get-tuple-element", "param",
                         "params", "bitcast"}, sorted(set(made))
    xla = len(visits) - len(kernel)
    assert made.count("scatter") == made.count("fusion") == 3 * xla
    assert made.count("bitcast") == 3 * len(kernel)
    viewed = re.findall(r"^\s*(?:ROOT )?%([a-z\-]+)[\w.\-]* = "
                        rf"f32\[{features // 1024},8,128\]", text, re.M)
    # (``%pallas_call.n``: the elements of the kernel's tuple)
    assert set(viewed) <= {"bitcast", "pallas"}, sorted(set(viewed))
    assert viewed.count("bitcast") == 3 * len(kernel)
    # a table is read a DISTINCT key, inside its candidate's loop of one
    # trip or none: ``(w, z, n)`` at each candidate's lanes.  No gather
    # outside the loops has a table for its operand: the entries' weights
    # come from the distinct keys' through the sorts (``w[index]`` at the
    # 655,360 entry lanes was 8.7 ms of an 18.6 ms step)
    shape_of = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])",
                               text, re.M))
    gathers = re.findall(
        r"= f32\[(\d+)\]\S* gather\((%[\w.\-]+), .*op_name=\"([^\"]*)\"", text)
    from_table = [(int(n), op) for n, operand, op in gathers
                  if shape_of[operand] == f"f32[{features}]"]
    assert sorted(n for n, _op in from_table) == sorted(visits * 3)
    for _n, op in from_table:
        assert "/while/body/sgd.gather_rows/" in op, op


def test_gated_step_reads_its_tables_a_distinct_key(one_chip, quiet_cache,
                                                    monkeypatch):
    """The DiFacto step at the ``criteo-tb-difacto`` cell's shapes (2^26
    buckets x ``(w, z, n)``, a count, rows of 16 floats and their AdaGrad
    sums; 655,360 entry lanes), through the TPU's compiler: every gather out
    of a table sits in a candidate's loop of one trip or none (none reads a
    table an entry, as three did for 33 ms of a 76 ms step); the one gather
    an entry lane, a key's row by its rank, reads a fresh array that the
    compiler keeps in fast memory (``S(1)``: out of what a loop hands on, in
    HBM, it cost the 13.9 ms the table's did); the temporaries stay under a
    thirtieth of the tables."""
    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models.common import FTRL, AdaGrad
    from dmlc_core_tpu.models.fm import FactorizationMachine
    from dmlc_core_tpu.ops import pallas_rows
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    rows, lanes, features, width = 16384, 655360, 2 ** 26, 16
    model = FactorizationMachine(
        features, width, optimizer={"w": FTRL(l1=4.0), "v": AdaGrad()},
        threshold=16)
    params = jax.tree.map(lambda a: on(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init, 0))
    batch = PaddedBatch(
        label=on(one_chip, (rows,), jnp.float32),
        weight=on(one_chip, (rows,), jnp.float32),
        row_ptr=on(one_chip, (rows + 1,), jnp.int32),
        index=on(one_chip, (lanes,), jnp.int32),
        value=on(one_chip, (lanes,), jnp.float32),
        num_rows=on(one_chip, (), jnp.int32))
    compiled = model._touched_rows_step.lower(model, params, batch).compile()
    memory = compiled.memory_analysis()
    tables = (4 + 2 * width) * 4 * features
    assert memory.alias_size_in_bytes >= tables
    assert memory.temp_size_in_bytes < 320 << 20
    text = compiled.as_text()
    made = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\]\S*)", text, re.M))
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\((%[\w.\-]+), "
                         r".*op_name=\"([^\"]*)\"", text)
    table_shapes = {f"f32[{features}]", f"s32[{features}]",
                    f"f32[{features},{width}]"}
    from_table = [op for _result, operand, op in gathers
                  if made[operand].split("{")[0] in table_shapes]
    # the count; (w, z, n) and (v, N): six reads a candidate, five of them
    assert len(from_table) == 6 * 5
    for op in from_table:
        assert "/while/body/sgd.gather_rows/" in op, op
    by_rank = [made[operand] for result, operand, op in gathers
               if op == "jit(_touched_rows_step)/sgd.gather_rows/gather"]
    assert len(by_rank) == 1 and by_rank[0].startswith(
        f"f32[{lanes},{width}]") and "S(1)" in by_rank[0], by_rank


# the Epsilon cell's tree program (benchmark/configs/epsilon-lgbm.json: 2,000
# features x 255 bins, 255 leaves best-first) at a fifth of its rows: past a
# chunk, so every static size of a segment's work and the loop of whole chunks
# are in it
def test_leafwise_tree_program_compiles_at_the_cells_width(
        one_chip, quiet_cache, monkeypatch):
    """The leaf-wise builder's program through the TPU's compiler at 2,000
    features: the dense kernel under its name, at one node column, in every
    branch of the ladder and under the scope the cell's metrics read; the
    other scopes there too; and the pool of leaf histograms (1.04 GB) is
    never copied — its parent's read is fenced before the children's writes
    (the first version copied it at every expansion)."""
    from dmlc_core_tpu.models import gbdt_leafwise
    monkeypatch.setattr(pallas_segment, "pallas_interpret", lambda: False)
    rows, features, leaves = 80_000, 2000, 255
    model = GBDT(num_features=features, num_trees=2, num_bins=255,
                 learning_rate=0.1, lambda_=0.0, min_child_weight=100.0,
                 grow_policy="lossguide", max_leaves=leaves, max_depth=0,
                 histogram="pallas")
    compiled = model._grow_tree.lower(
        model, on(one_chip, (rows, features // 4), jnp.int32),
        on(one_chip, (rows,), jnp.float32), on(one_chip, (rows,), jnp.float32),
        on(one_chip, (features,), jnp.bool_)).compile()
    names = op_names(compiled)
    for scope in ("gbdt.leafwise.hist", "gbdt.leafwise.partition",
                  "gbdt.leafwise.split", "gbdt.leafwise.pick",
                  "gbdt.leafwise.subtract", "ops.hist_layout"):
        assert any(f"/{scope}/" in n for n in names), scope
    kernels = [n for n in instructions(compiled)
               if n.startswith("%" + pallas_segment.DENSE_HIST_KERNEL)]
    sizes = gbdt_leafwise._segment_sizes(rows, gbdt_leafwise._SEGMENT_CHUNK)
    assert sizes == [1024, 2048, 4096, 8192, 16384, 32768, 65536]
    # an expansion's ladder, each size once, and its loop of whole chunks;
    # the root's rows are static: its one whole chunk and the rest's size
    assert len(kernels) == len(sizes) + 3
    kernel_ops = [n for n in names if n.endswith("/pallas_call")]
    assert kernel_ops and all("/gbdt.leafwise.hist/" in n for n in kernel_ops)
    text = compiled.as_text()
    pool = rf"f32\[{leaves},{features},255,2\]"
    assert re.search(pool, text)
    assert not re.search(pool + r"\S* copy\(", text)
    # the pool 1.04 GB; a chunk's rows gathered, unpacked and relaid for the
    # kernel, 0.52 GB a copy; the kernel's output 0.1: 3.3 GB in all
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_wide_rows_step_is_a_program_fetched_in_a_second(one_chip,
                                                         quiet_cache):
    """The field-aware machine's step at the ``criteo-ffm`` cell's shapes
    (2^20 keys x 39 fields x 4 floats, 16,384 x 39 entries padded to 655,360
    lanes), through the TPU's compiler.  The program's size is part of what
    it costs: a warm start fetches it, 30-40 ms a megabyte, and the cell's
    ``setup_s`` is 8 s with a tenth of room (a version of five candidate
    visits and eleven sorts was 130 MB and read 12.3 s); a sort of the entry
    lanes is 2-3 MB of it, so they are counted.  The table aliases its
    output; no array an entry holds of the rows is laid with its 4 floats on
    the lanes (32 times its bytes: only a chunk's 65,536 rows pass through
    such a layout, inside the table's gather); the scopes the cell's metrics
    read come through."""
    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models.ffm import FieldAwareFactorizationMachine
    rows, lanes, features, fields, width = 16384, 655360, 1 << 20, 39, 4
    model = FieldAwareFactorizationMachine(features, fields, width,
                                           learning_rate=0.2)
    params = jax.tree.map(lambda a: on(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init, 0))
    batch = PaddedBatch(
        label=on(one_chip, (rows,), jnp.float32),
        weight=on(one_chip, (rows,), jnp.float32),
        row_ptr=on(one_chip, (rows + 1,), jnp.int32),
        index=on(one_chip, (lanes,), jnp.int32),
        value=on(one_chip, (lanes,), jnp.float32),
        num_rows=on(one_chip, (), jnp.int32),
        field=on(one_chip, (lanes,), jnp.int32))
    compiled = model._wide_rows_step.lower(model, params, batch).compile()
    memory = compiled.memory_analysis()
    table = 4 * features * fields * width
    assert memory.alias_size_in_bytes >= table
    assert memory.generated_code_size_in_bytes < 48 << 20
    assert memory.temp_size_in_bytes < 6 << 30
    text = compiled.as_text()
    sorts = [line for line in text.splitlines() if " sort(" in line
             and re.search(rf"= \(?s32\[{lanes}\]", line)]
    assert len(sorts) == 5, sorts
    assert not re.search(rf"f32\[{lanes},39,4\]", text)
    names = op_names(compiled)
    for scope in ("ffm.reduce", "ffm.diag", "ffm.linear", "sgd.update",
                  "sgd.unique", "sgd.gather_rows", "sgd.scatter_rows"):
        part = re.compile(r"[/(]" + re.escape(scope) + r"[/)]")
        assert any(part.search(n) for n in names), scope


@pytest.mark.slow     # two to four minutes of the TPU's compiler
def test_sharded_rows_step_exchanges_keys_and_gathers_no_table(
        topo, quiet_cache, monkeypatch):
    """The sharded touched-rows step at the ``criteo-tb-difacto-ps4`` cell's
    shapes (2^28 buckets x ``(w, z, n)``, a count, rows of 16 floats and
    their AdaGrad sums over four chips; a global batch of 65,536 x 39
    entries), through the TPU's compiler for a described v5e 2x2: every chip
    holds its quarter of every table and nothing of another's (no all-gather
    of anything, the tables aliased in place and never copied); three
    exchanges a capacity candidate under the scope ``mesh.alltoall``, keys
    on the minor axis (a row of 16 floats there would be padded to a tile's
    128 lanes); four reductions through the plan; the scopes the cell's
    metrics read come through; the temporaries stay under an eighth of a
    chip's tables."""
    import numpy as np
    from jax.sharding import Mesh

    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models.common import EXCHANGE_LANES, FTRL, AdaGrad
    from dmlc_core_tpu.models.fm import FactorizationMachine
    from dmlc_core_tpu.ops import pallas_rows
    from dmlc_core_tpu.parallel import MeshPlan
    monkeypatch.setattr(pallas_rows, "pallas_interpret", lambda: False)
    plan = MeshPlan(Mesh(np.asarray(topo.devices[:4]), ("data",)), ("data",))
    features, width, rows = 2 ** 28, 16, 65536
    lanes = rows * 39
    model = FactorizationMachine(
        features, width, optimizer={"w": FTRL(l1=4.0), "v": AdaGrad()},
        threshold=16, mesh=plan)
    by_key, whole = plan.data_sharding(), plan.replicated_sharding()
    params = jax.tree.map(
        lambda a: on(by_key if a.ndim else whole, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(0)))  # the seed a host int
    batch = PaddedBatch(
        label=on(by_key, (rows,), jnp.float32),
        weight=on(by_key, (rows,), jnp.float32),
        row_ptr=on(whole, (rows + 1,), jnp.int32),
        index=on(by_key, (lanes,), jnp.int32),
        value=on(by_key, (lanes,), jnp.float32),
        num_rows=on(whole, (), jnp.int32))
    compiled = model._sharded_rows_step.lower(model, params, batch).compile()
    memory = compiled.memory_analysis()
    shard = (4 + 2 * width) * 4 * features // 4
    assert memory.alias_size_in_bytes >= shard
    assert memory.argument_size_in_bytes < shard + (64 << 20)
    assert memory.temp_size_in_bytes < shard // 8
    text = compiled.as_text()
    exchanges = re.findall(
        r"= (\w+)\[4,(\d+),(\d+)\]\S* all-to-all\(.*?op_name=\"([^\"]*)\"",
        text)
    assert all("/mesh.alltoall/" in name for *_, name in exchanges)
    capacities = (EXCHANGE_LANES[0], lanes // 4)
    assert sorted((dtype, int(r), int(c)) for dtype, r, c, _ in exchanges) \
        == sorted((dtype, r, c) for c in capacities for dtype, r in (
            ("s32", 2), ("f32", 2 + width), ("f32", 1 + width)))
    reduces = re.findall(r"all-reduce(?:-start)?\(.*?op_name=\"([^\"]*)\"",
                         text)
    assert len(reduces) == 4 and all("mesh.allreduce" in n for n in reduces)
    for op in ("all-gather", "collective-permute", "reduce-scatter"):
        assert not re.search(rf"= .*\b{op}(-start)?\(", text), op
    assert not re.search(rf"= \w+\[{features // 4}(,{width})?\]\S* copy\(",
                         text)
    names = op_names(compiled)
    for scope in ("mesh.alltoall", "sgd.owner_merge", "sgd.unique",
                  "sgd.gather_rows", "sgd.count", "sgd.ftrl", "sgd.adagrad",
                  "sgd.scatter_rows", "fm.margins"):
        part = re.compile(r"[/(]" + re.escape(scope) + r"[/)]")
        assert any(part.search(n) for n in names), scope
    pattern = json.loads((LAYER_METRICS / "ps4_scatter_roofline.json")
                         .read_text())["args"]["pattern"]
    assert [n for n in instructions(compiled) if re.search(pattern, n)]


# the paged cell's page visit (benchmark/configs/criteo-xgb-extmem.json: 2^28
# rows resident as row state, a page of 2^22 x 67 codes in eight row slices,
# 256 bins, depth 8) at the root's pass, the deepest histogram pass and the
# pass to the leaves
@pytest.mark.parametrize("depth", [0, 7, 8])
def test_page_visit_compiles_at_the_cells_size_and_updates_in_place(
        one_chip, quiet_cache, monkeypatch, depth):
    """`GBDT._page_visit` through the TPU's compiler at the cell's shapes:
    the dense kernel under its name and under the page's scope (67 features
    are no whole number of the kernel's 8-feature blocks), the routing and
    the sum under theirs, the level's histogram and the rows' ``node``
    donated and never copied (a copy of ``node`` would be 1 GB a visit),
    and temporaries that leave the chip its row state."""
    monkeypatch.setattr(pallas_segment, "pallas_interpret", lambda: False)
    rows, page_rows, features, slices = 1 << 28, 1 << 22, 67, 8
    model = GBDT(num_features=features, num_trees=1, max_depth=8,
                 num_bins=BINS, learning_rate=0.1, min_child_weight=1.0,
                 missing_aware=True, histogram="pallas")
    cols, nodes = 2 ** max(depth - 1, 0), 2 ** max(depth - 1, 0)
    hist = (None if depth == 8
            else on(one_chip, (cols, features, BINS, 2), jnp.float32))
    prev = (None if depth == 0 else
            (on(one_chip, (nodes,), jnp.int32),) * 3
            + (on(one_chip, (nodes,), jnp.bool_),))
    page = (on(one_chip, (page_rows // slices, features), jnp.uint8),) * slices
    compiled = model._page_visit.lower(
        model, depth, hist, on(one_chip, (rows,), jnp.int32), page,
        on(one_chip, (rows,), jnp.float32), on(one_chip, (rows,), jnp.float32),
        on(one_chip, (), jnp.int32), prev).compile()
    names, text = op_names(compiled), compiled.as_text()
    kernels = [n for n in instructions(compiled)
               if n.startswith("%" + pallas_segment.DENSE_HIST_KERNEL)]
    assert len(kernels) == (depth < 8)
    if depth < 8:
        kernel_ops = [n for n in names if n.endswith("/pallas_call")]
        assert kernel_ops and all(
            "jit(_page_visit)/gbdt.hist/gbdt.page.hist/" in n
            for n in kernel_ops)
        assert any("/gbdt.hist/gbdt.page.accumulate/" in n for n in names)
        assert any("/ops.hist_layout/" in n for n in names)
    if depth:
        assert any("jit(_page_visit)/gbdt.route/gbdt.page.route/" in n
                   for n in names)
    # donated and aliased: the histogram (where there is one) and node
    assert "input_output_alias" in text
    assert not re.search(r"s32\[268435456\]\S* copy\(", text)
    memory = compiled.memory_analysis()
    # the page as int32, feature-major (1.12 GB), and the kernel's operands
    assert memory.temp_size_in_bytes < 2 << 30       # 1.66 GB; 0.32 at 8
    assert memory.generated_code_size_in_bytes < 4 << 20     # 1.1 - 2.7 MB
