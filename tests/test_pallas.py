"""Pallas segment-sum kernel vs the XLA scatter reference (interpret mode
on the CPU mesh; the same code path compiles natively on TPU)."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from dmlc_core_tpu.ops.pallas_segment import histogram_gh, segment_sum


def _case(nnz, rows, seed):
    rng = np.random.default_rng(seed)
    row_id = np.sort(rng.integers(0, rows, size=nnz)).astype(np.int32)
    contrib = rng.standard_normal(nnz).astype(np.float32)
    return jnp.asarray(contrib), jnp.asarray(row_id)


def test_matches_xla_segment_sum():
    for nnz, rows, seed in [(1000, 64, 0), (4096, 513, 1), (37, 1024, 2)]:
        contrib, row_id = _case(nnz, rows, seed)
        want = segment_sum(contrib, row_id, rows)                  # xla
        got = segment_sum(contrib, row_id, rows, force="pallas")   # kernel
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_unsorted_and_empty_segments():
    # correctness must not depend on row_id sortedness or full coverage
    rng = np.random.default_rng(3)
    row_id = jnp.asarray(rng.permutation(
        np.repeat(np.arange(0, 50, 2), 7)).astype(np.int32))  # odd rows empty
    contrib = jnp.ones(row_id.shape[0], jnp.float32)
    got = segment_sum(contrib, row_id, 50, force="pallas")
    want = segment_sum(contrib, row_id, 50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    assert float(got[1]) == 0.0  # empty segment stays zero


def test_padding_entries_inert():
    # staging convention: pad entries carry value 0 at row batch-1
    contrib = jnp.asarray([1.0, 2.0, 0.0, 0.0], jnp.float32)
    row_id = jnp.asarray([0, 1, 3, 3], jnp.int32)
    got = segment_sum(contrib, row_id, 4, force="pallas")
    np.testing.assert_allclose(np.asarray(got), [1.0, 2.0, 0.0, 0.0])


def test_multilane_matches_xla():
    """[nnz, L] lanes (the fused (grad, hess) histogram shape) share one
    kernel pass and match per-lane XLA segment sums."""
    rng = np.random.default_rng(4)
    nnz, rows, L = 2048, 300, 2
    row_id = jnp.asarray(rng.integers(0, rows, nnz).astype(np.int32))
    contrib = jnp.asarray(rng.standard_normal((nnz, L)).astype(np.float32))
    got = segment_sum(contrib, row_id, rows, force="pallas")
    want = segment_sum(contrib, row_id, rows)  # xla handles ND natively
    assert got.shape == (rows, L)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_empty_input_returns_zeros():
    got = segment_sum(jnp.zeros((0,), jnp.float32),
                      jnp.zeros((0,), jnp.int32), 8, force="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.zeros(8, np.float32))
    got2 = segment_sum(jnp.zeros((0, 2), jnp.float32),
                       jnp.zeros((0,), jnp.int32), 8, force="pallas")
    assert got2.shape == (8, 2) and not np.asarray(got2).any()


def test_histogram_gh_matches_xla():
    """The dedicated [nodes, features, bins] histogram kernel (the GBDT
    per-level hot op) against the flattened-key XLA scatter formulation,
    across node counts and non-tile-multiple row counts."""
    rng = np.random.default_rng(7)
    # (8, 128) drives n_nodes*B = 1024 = two 512-wide segment tiles, so the
    # st > 0 grid path, the segs offset, and cross-tile slicing execute
    for rows, F, B, n_nodes in [(200, 3, 8, 1), (777, 5, 16, 4),
                                (64, 2, 4, 8), (130, 2, 128, 8)]:
        bins = jnp.asarray(rng.integers(0, B, (rows, F)).astype(np.int32))
        rel = jnp.asarray(rng.integers(0, n_nodes, rows).astype(np.int32))
        gh = jnp.asarray(rng.standard_normal((rows, 2)).astype(np.float32))
        want = histogram_gh(bins, rel, gh, n_nodes, B)                # xla
        got = histogram_gh(bins, rel, gh, n_nodes, B, force="pallas")
        assert got.shape == (n_nodes, F, B, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_histogram_gh_wide_and_narrow_bins_match_xla():
    """The kernel's key-tiling branches beyond the GBDT-default shapes:
    num_bins > 512 routes a feature across several 512-lane key tiles
    (the q>1 branch — kt//q feature select, kt%q in-feature slice), and
    tiny num_bins engages the stride's floor (64 lanes a feature, four
    features a 256-lane tile, most lanes padded).  Neither is reachable
    from GBDT/QuantileBinner (bins <= 256), so they are pinned here on
    the op's public surface."""
    rng = np.random.default_rng(11)
    for rows, F, B, n_nodes in [
            (300, 3, 1024, 4),    # q=2: feature spans two key tiles
            (120, 2, 2048, 2),    # q=4
            (100, 5, 600, 3),     # non-pow2 > 512 -> nb=1024, q=2
            (90, 4, 2, 2),        # stride floor: nb = 64, fpt = 4
            (150, 9, 3, 5),       # non-pow2 tiny bins through the clamp
    ]:
        bins = jnp.asarray(rng.integers(0, B, (rows, F)).astype(np.int32))
        rel = jnp.asarray(rng.integers(0, n_nodes, rows).astype(np.int32))
        gh = jnp.asarray(rng.standard_normal((rows, 2)).astype(np.float32))
        want = histogram_gh(bins, rel, gh, n_nodes, B)                # xla
        got = histogram_gh(bins, rel, gh, n_nodes, B, force="pallas")
        assert got.shape == (n_nodes, F, B, 2)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5,
            err_msg=f"rows={rows} F={F} B={B} n={n_nodes}")


def test_csr_ops_pallas_backend_matches_xla():
    """The linear/FM hot ops (Row::SDot reductions) accept force="pallas"
    and match their XLA scatter-add results — the same backend choice the
    GBDT histogram got, threaded through ops.sparse."""
    from dmlc_core_tpu.ops import (csr_matmul, csr_matvec,
                                   csr_row_sumsq_matmul)
    rng = np.random.default_rng(5)
    nnz, rows, F, K = 3000, 128, 40, 8
    idx = jnp.asarray(rng.integers(0, F, nnz).astype(np.int32))
    val = jnp.asarray(rng.standard_normal(nnz).astype(np.float32))
    rid = jnp.asarray(np.sort(rng.integers(0, rows, nnz)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal(F).astype(np.float32))
    t = jnp.asarray(rng.standard_normal((F, K)).astype(np.float32))
    for fn, dense in [(csr_matvec, w), (csr_matmul, t),
                      (csr_row_sumsq_matmul, t)]:
        a = fn(dense, idx, val, rid, rows)
        b = fn(dense, idx, val, rid, rows, force="pallas")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_pallas_backend_differentiable_grad_parity():
    """The kernel carries a custom VJP (segment-sum's cotangent is a
    gather), so sdot_backend='pallas' survives jax.grad: FM gradients
    match the XLA backend's exactly where it matters (the models TRAIN
    through this path; GBDT alone has analytic grad/hess)."""
    import jax
    from dmlc_core_tpu.data.staging import PaddedBatch
    from dmlc_core_tpu.models import FactorizationMachine
    rng = np.random.default_rng(13)
    B, nnzc = 64, 4
    batch = PaddedBatch(
        label=jnp.asarray((rng.random(B) < 0.5).astype(np.float32)),
        weight=jnp.ones(B, jnp.float32),
        row_ptr=jnp.asarray((np.arange(B + 1) * nnzc).astype(np.int32)),
        index=jnp.asarray(rng.integers(0, 16, B * nnzc).astype(np.int32)),
        value=jnp.asarray(rng.standard_normal(B * nnzc).astype(np.float32)),
        num_rows=jnp.asarray(np.int32(B)), field=None)
    fm_x = FactorizationMachine(num_features=16, num_factors=4)
    fm_p = FactorizationMachine(num_features=16, num_factors=4,
                                sdot_backend="pallas")
    p0 = fm_x.init(3)
    gx = jax.grad(fm_x.loss)(p0, batch)
    gp = jax.grad(fm_p.loss)(p0, batch)
    for k in gx:
        np.testing.assert_allclose(np.asarray(gx[k]), np.asarray(gp[k]),
                                   rtol=2e-5, atol=2e-5)
    # and a full jitted train step runs under the kernel backend
    p1, loss = fm_p.train_step(p0, batch)
    assert np.isfinite(float(loss))


def test_histogram_gh_shardmap_psum_matches_global():
    """The multi-device route for the Pallas histogram: shard_map over
    row shards, each device runs the kernel on ITS rows, psum combines —
    the explicit-collective pattern a sharded-TPU fit uses (GBDT's
    histogram='auto' declines pallas under GSPMD precisely because
    pallas_call has no auto-partitioning rule; THIS is the supported
    sharded path, here proven on the 8-device CPU mesh)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(21)
    rows, F, B, n_nodes = 8 * 40, 3, 8, 2
    bins = rng.integers(0, B, (rows, F)).astype(np.int32)
    rel = rng.integers(0, n_nodes, rows).astype(np.int32)
    gh = rng.standard_normal((rows, 2)).astype(np.float32)

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))

    def local_hist(b, r, g):
        h = histogram_gh(b, r, g, n_nodes, B, force="pallas")
        return jax.lax.psum(h, "data")

    # replication check off: pallas_call's out_shape carries no varying-axes
    # annotation, so the static replication check cannot see through it; the
    # psum makes the output replicated regardless.
    from dmlc_core_tpu.parallel.collective import shard_map_compat
    sharded = jax.jit(shard_map_compat(
        local_hist, mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=P(), check_replication=False))
    rows_sh = NamedSharding(mesh, P("data"))
    got = sharded(jax.device_put(jnp.asarray(bins), rows_sh),
                  jax.device_put(jnp.asarray(rel), rows_sh),
                  jax.device_put(jnp.asarray(gh), rows_sh))
    want = histogram_gh(jnp.asarray(bins), jnp.asarray(rel),
                        jnp.asarray(gh), n_nodes, B)  # global, xla
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # ~20 interpret-mode kernel calls across random shapes
def test_kernel_fuzz_random_shapes_match_xla():
    """Seeded shape fuzz for both kernels: random (rows, features, bins,
    nodes) and (nnz, lanes, segments) configurations — including
    non-tile-multiples, single rows, and empty inputs — must match XLA
    bit-for-tolerance.  The shapes real workloads feed on hardware are
    unpredictable; this sweep is the off-TPU stand-in."""
    rng = np.random.default_rng(0)
    # pinned edge configs FIRST (seed 0 never draws them), then random
    hist_cases = [(1, 1, 2, 1), (1, 3, 8, 4)]
    hist_cases += [(int(rng.integers(1, 1300)), int(rng.integers(1, 7)),
                    int(rng.choice([2, 8, 32, 64])),
                    int(rng.integers(1, 17))) for _ in range(10)]
    for rows, F, B, n_nodes in hist_cases:
        bins = jnp.asarray(rng.integers(0, B, (rows, F)).astype(np.int32))
        rel = jnp.asarray(rng.integers(0, n_nodes, rows).astype(np.int32))
        gh = jnp.asarray(rng.standard_normal((rows, 2)).astype(np.float32))
        want = histogram_gh(bins, rel, gh, n_nodes, B)
        got = histogram_gh(bins, rel, gh, n_nodes, B, force="pallas")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5,
            err_msg=f"rows={rows} F={F} B={B} n={n_nodes}")
    seg_cases = [(0, 7, 2), (0, 1, 1), (1, 1, 3)]
    seg_cases += [(int(rng.integers(0, 5000)), int(rng.integers(1, 900)),
                   int(rng.integers(1, 5))) for _ in range(10)]
    for nnz, segs, L in seg_cases:
        row_id = jnp.asarray(rng.integers(0, segs, nnz).astype(np.int32))
        contrib = jnp.asarray(
            rng.standard_normal((nnz, L)).astype(np.float32))
        if L == 1:
            contrib = contrib[:, 0]
        want = segment_sum(contrib, row_id, segs)
        got = segment_sum(contrib, row_id, segs, force="pallas")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5,
            err_msg=f"nnz={nnz} segs={segs} L={L}")


@pytest.mark.slow  # two full fits through interpret-mode pallas (~30 s)
def test_histogram_gh_gbdt_forests_identical():
    """VERDICT r4 #1 'done' criterion: the SAME forest comes out of a fit
    whether the per-level histogram runs on XLA scatter-add or on the
    Pallas kernel (interpret mode here; native on TPU)."""
    from dmlc_core_tpu.models.gbdt import GBDT, QuantileBinner
    rng = np.random.default_rng(11)
    x = rng.standard_normal((160, 4)).astype(np.float32)
    # well-separated signal so split argmaxes aren't epsilon ties
    y = (x[:, 0] + 0.5 * x[:, 2] > 0).astype(np.float32)
    bins = QuantileBinner(num_bins=8).fit_transform(x)
    kw = dict(num_features=4, num_trees=3, max_depth=3, num_bins=8,
              learning_rate=0.5, seed=0)
    fx = GBDT(histogram="xla", **kw).fit(bins, jnp.asarray(y))
    fp = GBDT(histogram="pallas", **kw).fit(bins, jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(fx["feature"]),
                                  np.asarray(fp["feature"]))
    np.testing.assert_array_equal(np.asarray(fx["threshold"]),
                                  np.asarray(fp["threshold"]))
    np.testing.assert_allclose(np.asarray(fx["leaf"]),
                               np.asarray(fp["leaf"]), rtol=1e-5, atol=1e-6)


# ---- sparse (COO) histogram kernel ------------------------------------------


def _sparse_case(rng, rows, F, B, n_nodes, nnz, n_masked=7):
    """Random COO entries with trailing masked lanes carrying garbage."""
    from dmlc_core_tpu.ops.pallas_segment import histogram_gh_sparse
    del histogram_gh_sparse  # import check only
    rid = rng.integers(0, rows, nnz).astype(np.int32)
    fi = rng.integers(0, F, nnz).astype(np.int32)
    eb = rng.integers(1, B, nnz).astype(np.int32)   # bin 0 reserved: missing
    em = np.ones(nnz, bool)
    if n_masked:
        em[-n_masked:] = False
        # masked lanes: out-of-range junk that must not influence anything
        fi[-n_masked:] = rng.integers(0, 2 ** 20, n_masked)
        eb[-n_masked:] = rng.integers(0, 2 ** 20, n_masked)
    rel = rng.integers(0, n_nodes, rows).astype(np.int32)
    gh = rng.standard_normal((rows, 2)).astype(np.float32)
    return (jnp.asarray(rid), jnp.asarray(fi), jnp.asarray(eb),
            jnp.asarray(em), jnp.asarray(rel), jnp.asarray(gh))


def test_histogram_gh_sparse_matches_scatter():
    """Sparse kernel vs the flattened-key XLA scatter across geometries:
    single/multi key tile (F*nb <=/> 512), non-pow2 bins, nnz not a block
    multiple, and n_nodes crossing the 8-sublane pad."""
    from dmlc_core_tpu.ops.pallas_segment import histogram_gh_sparse
    rng = np.random.default_rng(31)
    for rows, F, B, n_nodes, nnz in [
            (100, 3, 8, 1, 500),       # one key tile
            (200, 5, 16, 4, 2000),     # one key tile, deeper
            (150, 6, 256, 2, 1500),    # nb=256 -> 3 key tiles
            (120, 4, 33, 8, 1111),     # non-pow2 bins -> nb=64
            (90, 2, 8, 16, 257),       # n_nodes past one sublane pad
    ]:
        rid, fi, eb, em, rel, gh = _sparse_case(rng, rows, F, B, n_nodes, nnz)
        want = histogram_gh_sparse(rid, fi, eb, em, rel, gh, n_nodes, F, B)
        got = histogram_gh_sparse(rid, fi, eb, em, rel, gh, n_nodes, F, B,
                                  force="pallas")
        assert got.shape == (n_nodes, F, B, 2)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=4e-6,
            err_msg=f"rows={rows} F={F} B={B} n={n_nodes} nnz={nnz}")


def test_histogram_gh_sparse_padding_lanes_inert():
    """Masked entries (emask=0) with garbage keys AND rows pointing at
    nonzero gh must contribute nothing: the layout drops them in the sort
    and the block-padding lanes are doubly inert (gkey=-1, w=0)."""
    from dmlc_core_tpu.ops.pallas_segment import histogram_gh_sparse
    rng = np.random.default_rng(32)
    rows, F, B, n_nodes = 64, 3, 8, 2
    rid, fi, eb, em, rel, gh = _sparse_case(rng, rows, F, B, n_nodes,
                                            nnz=300, n_masked=50)
    got = histogram_gh_sparse(rid, fi, eb, em, rel, gh, n_nodes, F, B,
                              force="pallas")
    live = np.asarray(em)
    want = histogram_gh_sparse(rid[live], fi[live], eb[live],
                               em[live], rel, gh, n_nodes, F, B,
                               force="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_histogram_gh_sparse_bin0_stays_empty():
    """missing_aware entry codes live in [1, B); the kernel must leave the
    reserved missing bin 0 exactly zero (the builder derives missing mass
    as node total minus present sum from it)."""
    from dmlc_core_tpu.ops.pallas_segment import histogram_gh_sparse
    rng = np.random.default_rng(33)
    rid, fi, eb, em, rel, gh = _sparse_case(rng, 128, 4, 16, 4, 900)
    got = np.asarray(histogram_gh_sparse(rid, fi, eb, em, rel, gh, 4, 4, 16,
                                         force="pallas"))
    assert not got[:, :, 0, :].any()
    assert np.abs(got).sum() > 0  # and the live bins are not trivially zero


def test_sparse_layout_feature_sort_determinism():
    """The stable feature sort makes the layout a pure function of the
    entry stream: rebuilding bit-identical, and permuting the input
    entries changes only accumulation order (allclose histograms)."""
    from dmlc_core_tpu.ops.pallas_segment import (histogram_gh_sparse,
                                                  sparse_hist_layout)
    rng = np.random.default_rng(34)
    rows, F, B, n_nodes = 96, 5, 16, 4
    rid, fi, eb, em, rel, gh = _sparse_case(rng, rows, F, B, n_nodes, 700)
    la = sparse_hist_layout(rid, fi, eb, em, F, B)
    lb = sparse_hist_layout(rid, fi, eb, em, F, B)
    for f in ("gkey", "rid", "tstart", "tcount"):
        np.testing.assert_array_equal(np.asarray(getattr(la, f)),
                                      np.asarray(getattr(lb, f)), err_msg=f)
    ha = histogram_gh_sparse(rid, fi, eb, em, rel, gh, n_nodes, F, B,
                             force="pallas", layout=la)
    perm = rng.permutation(len(np.asarray(rid)))
    hb = histogram_gh_sparse(rid[perm], fi[perm], eb[perm], em[perm],
                             rel, gh, n_nodes, F, B, force="pallas")
    np.testing.assert_allclose(np.asarray(ha), np.asarray(hb), atol=4e-6)


def test_histogram_gh_sparse_shardmap_psum_matches_global():
    """The multi-device sparse route: a num_shards=8 layout packs equal
    per-shard slices, shard_map P('data') in_specs hand each device its
    shard, the kernel runs on local rows, psum combines — mirroring the
    dense test above and gbdt._level_histogram_sparse."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dmlc_core_tpu.ops.pallas_segment import (histogram_gh_sparse,
                                                  histogram_gh_sparse_kernel,
                                                  sparse_hist_layout)
    from dmlc_core_tpu.parallel.collective import shard_map_compat

    rng = np.random.default_rng(35)
    rows, F, B, n_nodes = 8 * 32, 3, 8, 4
    rid, fi, eb, em, rel, gh = _sparse_case(rng, rows, F, B, n_nodes, 1800)
    layout = sparse_hist_layout(rid, fi, eb, em, F, B,
                                num_shards=8, rows=rows)
    mt = layout.max_tiles

    def local(gk, rid_l, ts, tc, rel_l, gh_l):
        rel_e = rel_l[rid_l]
        gh_e = gh_l[rid_l].T
        h = histogram_gh_sparse_kernel(gk, rel_e, gh_e, ts, tc,
                                       n_nodes, F, B, mt)
        return jax.lax.psum(h, "data")

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    sharded = jax.jit(shard_map_compat(
        local, mesh, in_specs=(P("data"),) * 6, out_specs=P(),
        check_replication=False))
    rs = NamedSharding(mesh, P("data"))
    got = sharded(*(jax.device_put(a, rs) for a in
                    (layout.gkey, layout.rid,
                     layout.tstart, layout.tcount, rel, gh)))
    want = histogram_gh_sparse(rid, fi, eb, em, rel, gh, n_nodes, F, B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=4e-6)


def test_segment_sum_empty_shard_dtype_matches_contrib():
    """Regression: the empty-shard early return must honor contrib's dtype
    exactly like the non-empty path's cast-back does — the documented
    drop-in-interchangeability contract covers the zero-shape edge too."""
    from dmlc_core_tpu.ops.pallas_segment import _segment_sum_pallas
    for dtype in (jnp.bfloat16, jnp.float32, jnp.int32):
        empty = segment_sum(jnp.zeros((0,), dtype),
                            jnp.zeros((0,), jnp.int32), 4, force="pallas")
        full = segment_sum(jnp.ones((3,), dtype),
                           jnp.zeros((3,), jnp.int32), 4, force="pallas")
        assert empty.dtype == full.dtype == dtype, (dtype, empty.dtype)
        # and the internal jitted path (public segment_sum casts on top)
        internal = _segment_sum_pallas(jnp.zeros((0, 2), dtype),
                                       jnp.zeros((0,), jnp.int32),
                                       4, interpret=True)
        assert internal.dtype == dtype and internal.shape == (4, 2)


# ---- dense histogram kernel against float64 ---------------------------------


def _hist_f64(bins, rel, gh, n_nodes, num_bins):
    """The histogram summed in float64, bucket by bucket."""
    rows, F = bins.shape
    out = np.zeros((n_nodes, F, num_bins, 2))
    for f in range(F):
        np.add.at(out, (rel, f, bins[:, f]), gh.astype(np.float64))
    return out


def _hist_case(rows, F, B, n_nodes, seed):
    """Gradients spread over six decades, so that the low bits of the large
    ones and the whole of the small ones both have to arrive."""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, (rows, F)).astype(np.int32)
    rel = rng.integers(0, n_nodes, rows).astype(np.int32)
    gh = (rng.standard_normal((rows, 2))
          * 10.0 ** rng.integers(-3, 3, (rows, 2))).astype(np.float32)
    return bins, rel, gh


def _worst(got, want):
    """Largest error as a share of the largest bucket."""
    return np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()


# the kernel's worst over these cases is 1.4e-7; one bfloat16 pass reads
# 1.7e-3 to 2.4e-3 and two of the three parts 0.9e-5 to 2e-5 (the control)
HIST_F64_LIMIT = 5e-7

# rows never a multiple of the 1,024-row tile; F of 1, odd, not a multiple
# of 8, past 32 tiles; strides below the 256-lane tile (fpt 4, 2), at it,
# above 512 (q 2, 4); node columns on one dot (<= 32) and on three
HIST_F64_CASES = [
    # (n_nodes, F, num_bins, rows)
    (1, 1, 16, 700), (1, 28, 256, 2100), (2, 5, 64, 1500), (2, 130, 16, 1100),
    (8, 28, 256, 1030), (8, 5, 1024, 1100), (8, 130, 256, 300),
    (32, 5, 256, 2050), (32, 28, 64, 1300), (32, 1, 1024, 999),
    (64, 28, 256, 1200), (64, 5, 16, 700), (128, 28, 256, 1100),
    (128, 5, 1024, 300), (512, 5, 16, 2050), (512, 28, 256, 1200),
    (512, 3, 2048, 130), (40, 3, 300, 999), (24, 7, 2, 300),
    # a short last group: 67 key tiles in nine groups of 8, 5 tiles dead
    (64, 67, 256, 1200), (8, 67, 256, 2100),
]


@pytest.mark.parametrize("n_nodes,F,num_bins,rows", HIST_F64_CASES)
def test_dense_histogram_kernel_is_float32_exact_against_float64(
        n_nodes, F, num_bins, rows):
    bins, rel, gh = _hist_case(rows, F, num_bins, n_nodes,
                               seed=n_nodes * 1000 + F)
    want = _hist_f64(bins, rel, gh, n_nodes, num_bins)
    got = histogram_gh(jnp.asarray(bins), jnp.asarray(rel), jnp.asarray(gh),
                       n_nodes, num_bins, force="pallas")
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _worst(got, want) < HIST_F64_LIMIT


@pytest.mark.parametrize("F,num_bins,live,num_kt", [
    (67, 256, 67, 72), (130, 256, 130, 136), (130, 16, 33, 34),
    (28, 256, 28, 28), (13, 256, 13, 13), (2000, 256, 2000, 2000)])
def test_dense_histogram_plan_counts_its_live_key_tiles(F, num_bins, live,
                                                        num_kt):
    """The plan says which of its key tiles hold a feature: whole groups on
    the grid (``num_kt``), of which the first ``live_kt`` are real; at every
    node count the same, since the groups are of features."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    for n_nodes in (1, 8, 16, 32, 64):
        p = ps._hist_plan(F, num_bins, n_nodes)
        dead = ps.hist_dead_key_tiles(F, num_bins, n_nodes)
        assert (p.live_kt, p.num_kt) == (live, num_kt), n_nodes
        assert p.live_kt + dead == p.num_kt and p.num_kt % p.tiles == 0
        assert p.live_kt == -(-F * p.nb // p.w) and 0 <= dead < p.tiles


@pytest.mark.parametrize("n_nodes,F,num_bins,rows", [
    (8, 67, 256, 1100), (64, 67, 256, 1100), (8, 130, 16, 1100)])
def test_dense_histogram_kernel_leaves_dead_key_tiles_alone(n_nodes, F,
                                                            num_bins, rows):
    """The kernel's own output, before the caller's slice: every lane of a
    key tile that holds no feature is exactly zero, whatever the bins' last
    block holds past the last feature (here codes like any other: a kernel
    that works through its padding tiles sums them into those lanes; on a
    chip the block's tail is whatever the buffer held), and the lanes that
    are kept are the float64 sums."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    p = ps._hist_plan(F, num_bins, n_nodes)
    assert p.live_kt < p.num_kt
    whole = p.num_kt * p.fpt // p.q         # feature rows of whole groups
    bins, rel, gh = _hist_case(rows, whole, num_bins, n_nodes,
                               seed=51 + n_nodes)
    raw = np.asarray(ps._hist_key_lanes(
        p, jnp.asarray(bins.T), jnp.asarray(rel), jnp.asarray(gh), True))
    bins = bins[:, :F]
    assert raw.shape == (2 * p.parts * p.n_pad, p.num_kt * p.w)
    assert np.abs(raw[:, :p.live_kt * p.w]).max() > 0
    assert not raw[:, p.live_kt * p.w:].any()
    kept = (raw.reshape(p.parts, 2, p.n_pad, -1, p.nb).sum(0)
            [:, :n_nodes, :F, :num_bins].transpose(1, 2, 3, 0))
    want = _hist_f64(bins, rel, gh, n_nodes, num_bins)
    assert _worst(kept, want) < HIST_F64_LIMIT


@pytest.mark.parametrize("step_bytes,tiles", [(6 << 20, 28), (1 << 20, 8),
                                              (1 << 18, 2), (0, 1)])
def test_dense_histogram_kernel_tilings_agree(monkeypatch, step_bytes, tiles):
    """Every tiling the plan can choose — all key tiles a step, the tiles
    of 8 or of 2 features, one tile — gives the same sums but for the order
    of additions.  The room a step may take is squeezed here, in the place
    of shapes large enough to squeeze it."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    monkeypatch.setattr(ps, "_HIST_STEP_BYTES", step_bytes)
    assert ps._hist_plan(28, 256, 8).tiles == tiles
    for n_nodes, F, num_bins, rows in [(8, 28, 256, 1100), (2, 11, 64, 1500),
                                       (64, 5, 1024, 700)]:
        bins, rel, gh = _hist_case(rows, F, num_bins, n_nodes, seed=F)
        want = _hist_f64(bins, rel, gh, n_nodes, num_bins)
        # not through the jit's cache: the plan is read while tracing
        got = ps._histogram_gh_pallas.__wrapped__(
            jnp.asarray(bins.T), jnp.asarray(rel), jnp.asarray(gh),
            n_nodes, num_bins, True)
        assert _worst(got, want) < HIST_F64_LIMIT, (n_nodes, F, num_bins)


def test_one_bfloat16_pass_or_two_parts_would_fail_the_float64_limit():
    """The control: the same histogram from (grad, hess) rounded to one
    bfloat16, and from two of the three parts, summed in float64 — what a
    kernel that dropped a pass would give at best — is outside the limit
    the kernel is held to."""
    from dmlc_core_tpu.ops.pallas_segment import _split_bf16x3
    worst_one, worst_two = 0.0, 0.0
    for n_nodes, F, num_bins, rows in HIST_F64_CASES[:6]:
        bins, rel, gh = _hist_case(rows, F, num_bins, n_nodes,
                                   seed=n_nodes * 1000 + F)
        want = _hist_f64(bins, rel, gh, n_nodes, num_bins)
        hi, mid, _ = (np.asarray(p) for p in _split_bf16x3(jnp.asarray(gh)))
        one = np.asarray(jnp.asarray(gh).astype(jnp.bfloat16)
                         .astype(jnp.float32))
        worst_one = max(worst_one, _worst(
            _hist_f64(bins, rel, one, n_nodes, num_bins), want))
        worst_two = max(worst_two, _worst(
            _hist_f64(bins, rel, hi + mid, n_nodes, num_bins), want))
    assert worst_one > 100 * HIST_F64_LIMIT, worst_one
    assert worst_two > 10 * HIST_F64_LIMIT, worst_two


# ---- sparse histogram kernel against float64 --------------------------------


def _sparse_f64_case(n_nodes, F, num_bins, rows, nnz, empty, seed):
    """COO entries with `_hist_case`'s gradients (six decades); the
    features in ``empty`` hold no entry."""
    rng = np.random.default_rng(seed)
    rid = rng.integers(0, rows, nnz).astype(np.int32)
    fi = rng.choice(np.setdiff1d(np.arange(F), empty), nnz).astype(np.int32)
    eb = rng.integers(1, num_bins, nnz).astype(np.int32)
    rel = rng.integers(0, n_nodes, rows).astype(np.int32)
    gh = (rng.standard_normal((rows, 2))
          * 10.0 ** rng.integers(-3, 3, (rows, 2))).astype(np.float32)
    want = np.zeros((n_nodes, F, num_bins, 2))
    np.add.at(want, (rel[rid], fi, eb), gh[rid].astype(np.float64))
    return rid, fi, eb, rel, gh, want


# one key tile of 512 lanes (F * stride <= 512) and many; strides 2, 16, 256
# and 512 (300 bins); node columns on one dot (<= 32, 5 padded to 8) and on
# three (40 to the cap of 256); a feature with no entries, first, inside and
# last; key tiles whose span is shorter than one sub-tile of 1,024 entries,
# spans of 14 and 21 sub-tiles, and one of 59, longer than a grid step's 32
SPARSE_F64_CASES = [
    # (n_nodes, F, num_bins, rows, nnz, features with no entry)
    (1, 3, 16, 200, 900, ()), (1, 2, 256, 3000, 60000, ()),
    (5, 7, 16, 300, 2500, (3,)), (8, 5, 256, 300, 2500, (0,)),
    (8, 40, 2, 300, 3000, ()), (32, 6, 256, 900, 41000, (5,)),
    (32, 3, 300, 400, 3000, ()), (40, 4, 300, 400, 3000, (1,)),
    (40, 2, 16, 700, 20500, ()), (64, 3, 2, 300, 1500, ()),
    (64, 9, 256, 500, 9000, (2, 3)), (128, 5, 16, 400, 3000, ()),
    (128, 4, 256, 600, 5000, ()), (256, 4, 256, 600, 5000, ()),
    (256, 2, 16, 300, 1100, ()),
]


@pytest.mark.parametrize("n_nodes,F,num_bins,rows,nnz,empty",
                         SPARSE_F64_CASES)
def test_sparse_histogram_kernel_is_float32_exact_against_float64(
        n_nodes, F, num_bins, rows, nnz, empty):
    from dmlc_core_tpu.ops.pallas_segment import histogram_gh_sparse
    rid, fi, eb, rel, gh, want = _sparse_f64_case(
        n_nodes, F, num_bins, rows, nnz, empty, seed=n_nodes * 1000 + F)
    got = histogram_gh_sparse(
        jnp.asarray(rid), jnp.asarray(fi), jnp.asarray(eb),
        jnp.ones(nnz, bool), jnp.asarray(rel), jnp.asarray(gh), n_nodes, F,
        num_bins, force="pallas")
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _worst(got, want) < HIST_F64_LIMIT
    assert not np.asarray(got)[:, list(empty)].any()
    assert not np.asarray(got)[:, :, 0].any()       # bin 0: the absent cells


@pytest.mark.parametrize("sub_tiles", [1, 4, 64])
def test_sparse_histogram_kernel_sub_tiles_a_step_agree(monkeypatch,
                                                        sub_tiles):
    """One sub-tile a grid step (the grid of the layout's own span table),
    4, and more than any span here holds: the same sub-tiles are added in
    the same order, so the sums are the same to the bit."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    assert ps._SPARSE_STEP_TILES == 32
    for n_nodes, F, num_bins, rows, nnz, empty in [
            SPARSE_F64_CASES[1], SPARSE_F64_CASES[5], SPARSE_F64_CASES[10]]:
        rid, fi, eb, rel, gh, want = _sparse_f64_case(
            n_nodes, F, num_bins, rows, nnz, empty, seed=F)
        layout = ps.sparse_hist_layout(rid, fi, eb, np.ones(nnz, bool), F,
                                       num_bins)

        def level():    # not through the jit's cache: read while tracing
            return np.asarray(ps._histogram_gh_sparse_pallas.__wrapped__(
                layout.gkey, jnp.asarray(rel)[layout.rid],
                jnp.asarray(gh)[layout.rid].T, layout.tstart, layout.tcount,
                n_nodes, F, num_bins, layout.max_tiles, True))
        at_32 = level()
        assert _worst(at_32, want) < HIST_F64_LIMIT
        monkeypatch.setattr(ps, "_SPARSE_STEP_TILES", sub_tiles)
        np.testing.assert_array_equal(level(), at_32)
        monkeypatch.undo()


def _f32(bits):
    return np.asarray(bits, np.uint32).view(np.float32)


SPLIT_CASES = {
    "normals of every magnitude": _f32(
        (np.arange(24, 255, dtype=np.uint32)[:, None] << 23)
        | np.random.default_rng(3).integers(0, 1 << 23, (231, 64),
                                            dtype=np.uint32)).ravel(),
    "negative": -_f32((np.arange(24, 255, dtype=np.uint32) << 23) | 0x5A5A5A),
    "every low bit set": _f32((np.arange(24, 255, dtype=np.uint32) << 23)
                              | 0x7FFFFF),
    "zeros": np.array([0.0, -0.0], np.float32),
    "largest float32, past bfloat16's rounding overflow": np.array(
        [np.finfo(np.float32).max, -np.finfo(np.float32).max,
         3.3961775e38, 3.3895314e38], np.float32),
    "powers of two": _f32(np.arange(24, 255, dtype=np.uint32) << 23),
}


@pytest.mark.parametrize("name", list(SPLIT_CASES))
def test_split_bf16x3_is_exact(name):
    """hi + mid + lo == x bit for bit, each part a bfloat16 as it stands."""
    from dmlc_core_tpu.ops.pallas_segment import _split_bf16x3
    x = SPLIT_CASES[name]
    parts = [np.asarray(p) for p in _split_bf16x3(jnp.asarray(x))]
    for p in parts:
        assert np.isfinite(p).all()
        back = np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                          .astype(jnp.float32))
        assert np.array_equal(back.view(np.uint32), p.view(np.uint32))
    total = (parts[0].astype(np.float64) + parts[1].astype(np.float64)
             + parts[2].astype(np.float64))
    assert np.array_equal(total, x.astype(np.float64))
    # and in float32, in the order the kernel's accumulator meets them
    assert np.array_equal((parts[0] + parts[1]) + parts[2], x)


def test_split_bf16x3_below_the_exact_range_and_non_finite():
    """Below 2**-103 the last part is a denormal float32: with denormals
    kept (this CPU) the parts still sum to x, and a chip that flushes them
    loses at most 2**-126 a value.  Denormal inputs keep that bound.  A
    non-finite input stays non-finite in its first part."""
    from dmlc_core_tpu.ops.pallas_segment import _split_bf16x3
    tiny = _f32((np.arange(1, 24, dtype=np.uint32) << 23) | 0x7FFFFF)
    denormal = _f32(np.array([1, 0x7FFFFF, 0x400001], np.uint32))
    for x in (tiny, denormal):
        parts = [np.asarray(p, np.float64)
                 for p in _split_bf16x3(jnp.asarray(x))]
        flushed = [np.where(np.abs(p) < 2.0 ** -126, 0.0, p) for p in parts]
        assert np.abs(sum(flushed) - np.where(np.abs(x) < 2.0 ** -126, 0.0, x)
                      ).max() <= 2.0 ** -126
    hi, _, _ = _split_bf16x3(jnp.asarray([np.inf, -np.inf, np.nan],
                                         jnp.float32))
    assert not np.isfinite(np.asarray(hi)).any()


# ---- values a row onto the sorted entries: the two-level lookup (PR 46) ----

LOOKUP_ROWS = 300_007       # no multiple of 128: the table's last column pads


def _lookup_layout():
    """Row ids shaped like a `SparseHistLayout`'s: a run of a feature in 1%
    of the rows (a sub-tile of it spans many chunks of 16,384 rows), a run
    of one in 60% (a sub-tile spans one or two), the boundary sub-tile that
    holds the end of one and the start of the next, a short run, then
    padding lanes (row 0) past the last whole grid step."""
    from dmlc_core_tpu.ops.pallas_segment import _NNZ_TILE
    rng = np.random.default_rng(46)
    runs = [np.sort(rng.choice(LOOKUP_ROWS, n, replace=False))
            for n in (3_000, 180_000, 57)]
    rid = np.concatenate(runs).astype(np.int32)
    lanes = (len(rid) // _NNZ_TILE + 3) * _NNZ_TILE
    return np.pad(rid, (0, lanes - len(rid)))


@pytest.fixture
def lookup_on(monkeypatch):
    """`entry_values` on the lookup route off the chip: the rule says yes,
    and the kernel runs interpreted because the backend is no TPU."""
    from dmlc_core_tpu.ops import pallas_segment
    monkeypatch.setattr(pallas_segment, "entry_lookup_engages",
                        lambda rows_ascend, plane_rows: True)
    assert pallas_segment.pallas_interpret()


@pytest.mark.parametrize("planes", [1, 6])
def test_entry_lookup_equals_the_gather_bit_for_bit(planes, lookup_on):
    from dmlc_core_tpu.models.gbdt import _NO_SLOT
    from dmlc_core_tpu.ops.pallas_segment import (_LOOKUP_STEP_TILES,
                                                  _NNZ_TILE, _chunk_spans,
                                                  entry_values)
    rng = np.random.default_rng(planes)
    rid = _lookup_layout()
    tiles = len(rid) // _NNZ_TILE
    assert tiles % _LOOKUP_STEP_TILES      # the last grid step is ragged
    cspan = np.asarray(_chunk_spans(jnp.asarray(rid)))
    visits = (cspan >> 16) - (cspan & 0xFFFF) + 1
    # the thin run's sub-tiles span many chunks, the dense run's one or two,
    # the boundary sub-tile every chunk, an all-padding sub-tile chunk 0
    assert visits[0] >= 5 and visits[2] == LOOKUP_ROWS // 16384 + 1
    assert np.median(visits[3:-3]) == 1 and visits[-1] == 1
    if planes == 1:
        table = rng.integers(0, 128, LOOKUP_ROWS).astype(np.int32)
        table[rng.random(LOOKUP_ROWS) < 0.5] = _NO_SLOT
        table[rid[:4]] = [255, 256, _NO_SLOT, 0]
        want = table[rid]
    else:
        table = (rng.standard_normal((LOOKUP_ROWS, 2))
                 * 10.0 ** rng.integers(-20, 20, (LOOKUP_ROWS, 2))
                 ).astype(np.float32)
        big = np.finfo(np.float32).max
        table[rid[:3]] = [[big, -big], [2.0 ** -100, -0.0], [0.0, 1e-30]]
        # a sum that starts at +0.0 gives a -0.0 back as +0.0, the one value
        # not returned bit for bit: a histogram takes the same from both
        want = table[rid].T + np.float32(0.0)
        assert np.signbit(table[rid[1], 1]) and not np.signbit(want[1, 1])
    got = np.asarray(entry_values(jnp.asarray(rid), jnp.asarray(cspan),
                                  jnp.asarray(table), True))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    # padding lanes read row 0's value, as the gather does
    assert np.array_equal(got[..., -_NNZ_TILE:].T,
                          np.broadcast_to(want[..., -1], (_NNZ_TILE,)
                                          + table.shape[1:]))
    assert rid[-1] == 0


def test_entry_lookup_below_the_exact_range_loses_a_denormal_at_most(
        lookup_on):
    """A (grad, hess) under 2**-103 (9.9e-32): its last bfloat16 part is a
    denormal, which a backend may flush (the histogram kernel splits the
    same parts and loses the same): off by 2**-126 at most."""
    from dmlc_core_tpu.ops.pallas_segment import _chunk_spans, entry_values
    table = np.zeros((LOOKUP_ROWS, 2), np.float32)
    table[:4] = [[1e-33, -3e-35], [9.8e-32, 1e-38], [-5e-36, 2e-34], [0, 0]]
    rid = jnp.asarray(np.pad(np.arange(4, dtype=np.int32), (0, 1020)))
    got = np.asarray(entry_values(rid, _chunk_spans(rid), jnp.asarray(table),
                                  True), np.float64)
    assert np.abs(got - table[np.asarray(rid)].T).max() <= 2.0 ** -126


def test_entry_lookup_engages_by_what_the_code_can_see(monkeypatch):
    """Rows ascending in every run, a compiled kernel (a TPU), a table that
    fits: each one missing gives XLA's gather, and no argument, environment
    variable or knob says otherwise."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    cap = ps.ENTRY_LOOKUP_PLANE_ROWS
    assert cap * 2 == 16 << 20 and 6 * 1_183_747 <= cap
    assert not ps.entry_lookup_engages(True, 1000)          # off the chip
    monkeypatch.setattr(ps, "pallas_interpret", lambda: False)
    assert ps.entry_lookup_engages(True, 1000)
    assert ps.entry_lookup_engages(True, cap)
    assert not ps.entry_lookup_engages(False, 1000)         # unsorted rows
    assert not ps.entry_lookup_engages(True, cap + 1)       # over the cap
    monkeypatch.undo()

    rid = jnp.asarray(_lookup_layout()[:2048])
    cspan = ps._chunk_spans(rid)

    def kernels(table, ascend, engaged):
        if engaged:
            monkeypatch.setattr(ps, "entry_lookup_engages",
                                lambda rows_ascend, plane_rows: rows_ascend)
        text = str(jax.make_jaxpr(lambda r, c, t: ps.entry_values(
            r, c, t, ascend))(rid, cspan, table))
        monkeypatch.undo()
        return text.count("pallas_call"), text.count("gather")

    for table in (jnp.zeros(LOOKUP_ROWS, jnp.int32),
                  jnp.zeros((LOOKUP_ROWS, 2), jnp.float32)):
        assert kernels(table, True, engaged=False) == (0, 1)
        assert kernels(table, False, engaged=True) == (0, 1)
        assert kernels(table, True, engaged=True) == (1, 0)


# ---- values an entry pushed onto the rows: the lookup turned round (PR 47) --

_PUSH_RUNS = (3_000, 180_000, 57)       # `_lookup_layout`'s three runs


def _push_case(case):
    """(rid, span, val) on `_lookup_layout`'s lanes.  ``all_live``: every
    lane of every run carries something, the spans are the layout's own (the
    thin run's sub-tiles visit many chunks, the boundary sub-tile every one);
    ``thin_run`` / ``short_run`` / ``two_runs``: only those runs carry, and
    `run_spans` leaves every other sub-tile out — the boundary sub-tile that
    holds the end of one run and the start of the next stays; ``nothing``:
    every span empty."""
    from dmlc_core_tpu.ops.pallas_segment import _chunk_spans, run_spans
    rng = np.random.default_rng(len(case))
    rid = _lookup_layout()
    fstart = np.concatenate([[0], np.cumsum(_PUSH_RUNS)]).astype(np.int32)
    cspan = _chunk_spans(jnp.asarray(rid))
    runs = {"all_live": [0, 1, 2], "thin_run": [0], "short_run": [2, 2, 2],
            "two_runs": [2, 0], "nothing": []}[case]
    val = np.zeros(len(rid), np.int32)
    for f in set(runs):
        val[fstart[f]:fstart[f + 1]] = rng.integers(
            0, 257, fstart[f + 1] - fstart[f])
    if case == "all_live":
        span = cspan
    else:
        span = run_spans(cspan, jnp.asarray(fstart),
                         jnp.asarray(runs, jnp.int32))
    return rid, np.asarray(span), val, fstart, runs


@pytest.mark.parametrize("case", ["all_live", "thin_run", "short_run",
                                  "two_runs", "nothing"])
def test_entry_push_equals_the_scatter_add_exactly(case):
    from dmlc_core_tpu.ops.pallas_segment import (_EMPTY_SPAN, _NNZ_TILE,
                                                  push_to_rows)
    rid, span, val, fstart, runs = _push_case(case)
    tiles = np.arange(len(span))
    live = np.zeros(len(span), bool)
    for f in runs:
        live |= ((tiles >= fstart[f] // _NNZ_TILE)
                 & (tiles <= (fstart[f + 1] - 1) // _NNZ_TILE))
    # a sub-tile is visited where it holds a lane of a run that carries and
    # nowhere else, the padding sub-tiles never; under the layout's own
    # spans those too (row 0, and nothing to add)
    assert np.array_equal(span != _EMPTY_SPAN,
                          live | (case == "all_live"))
    assert live.sum() == {"all_live": 179, "thin_run": 3, "short_run": 1,
                          "two_runs": 4, "nothing": 0}[case]
    if case == "two_runs":      # the boundary sub-tile spans every chunk
        assert (span[178] >> 16) - (span[178] & 0xFFFF) + 1 == 19
    want = np.zeros(LOOKUP_ROWS, np.float32)
    np.add.at(want, rid, val)
    got = np.asarray(push_to_rows(jnp.asarray(rid), jnp.asarray(span),
                                  jnp.asarray(val), LOOKUP_ROWS))
    assert got.dtype == np.float32 and got.shape == (LOOKUP_ROWS,)
    assert np.array_equal(got, want)
    # rows with no entry, and every row where nothing carries, stay 0
    assert (want == 0).sum() > LOOKUP_ROWS // 3
    assert (got != 0).any() == bool(runs)


def test_entry_push_leaves_out_what_an_empty_span_names():
    """The spans are what the kernel visits, not a hint: lanes of a sub-tile
    whose span is empty add nothing, whatever they carry."""
    from dmlc_core_tpu.ops.pallas_segment import _EMPTY_SPAN, push_to_rows
    rid, span, val, *_ = _push_case("all_live")
    span = span.copy()
    span[5:90] = _EMPTY_SPAN
    want = np.zeros(LOOKUP_ROWS, np.float32)
    keep = np.ones(len(rid), bool)
    keep[5 * 1024:90 * 1024] = False
    np.add.at(want, rid[keep], val[keep])
    got = np.asarray(push_to_rows(jnp.asarray(rid), jnp.asarray(span),
                                  jnp.asarray(val), LOOKUP_ROWS))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("missing", ["nothing", "rows_ascend", "a_tpu",
                                     "the_tables_room"])
def test_route_push_engages_by_what_the_code_can_see(missing, monkeypatch):
    """Rows ascending in every run, a compiled kernel (a TPU), a float32
    table that fits its share of VMEM: each one missing gives the bisection,
    and no argument, environment variable or knob says otherwise."""
    from dmlc_core_tpu.ops import pallas_segment as ps
    cap = ps.ROUTE_PUSH_ROWS
    assert cap * 4 == 32 << 20 and cap == ps.ENTRY_LOOKUP_PLANE_ROWS
    assert 1_183_747 <= cap
    if missing != "a_tpu":
        monkeypatch.setattr(ps, "pallas_interpret", lambda: False)
    engages = ps.route_push_engages(missing != "rows_ascend",
                                    cap + (missing == "the_tables_room"))
    assert engages == (missing == "nothing")
