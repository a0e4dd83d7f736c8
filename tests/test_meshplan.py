"""MeshPlan: topology discovery, the reduction, GBDT plan paths.

Everything runs on the conftest-forced virtual 8-device CPU mesh, so the
2-D (host, chip) plan is exercised without TPU hardware.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dmlc_core_tpu.models import GBDT, QuantileBinner
from dmlc_core_tpu.parallel import MeshPlan, make_mesh


# ---------------------------------------------------------------------------
# the reduction: XLA's collective over the plan's axes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hosts", [None, 2])
@pytest.mark.parametrize("op", ["sum", "max", "mean"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.05)])
def test_plan_allreduce_matches_numpy(hosts, op, dtype, tol):
    plan = MeshPlan.build(hosts=hosts)
    assert plan.num_shards == 8
    rng = np.random.default_rng(0)
    # 513 elements per shard: no multiple of the shard count
    x = jnp.asarray(rng.standard_normal((plan.num_shards * 513,)), dtype)

    out = jax.jit(plan.shard_map(
        lambda v: plan.allreduce(v, op), in_specs=plan.row_spec,
        out_specs=P(), check_replication=False))(
            jax.device_put(x, plan.data_sharding()))
    shards = np.asarray(x.astype(jnp.float32)).reshape(8, 513)
    want = getattr(np, op)(shards, axis=0)
    assert out.shape == (513,) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want,
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# topology discovery; the routes that were removed
# ---------------------------------------------------------------------------

def test_build_topology():
    plan = MeshPlan.build()
    assert plan.axes == ("data",)
    plan2 = MeshPlan.build(hosts=2)
    assert plan2.axes == ("host", "chip")
    d = plan2.describe()
    assert d["hosts"] == 2 and d["chips_per_host"] == 4
    assert d["fabric"] == "host"  # CPU devices: no ICI


def test_build_hosts_knob():
    plan = MeshPlan.build(hosts=4)
    assert plan.axes == ("host", "chip")
    assert plan.mesh.shape["host"] == 4 and plan.mesh.shape["chip"] == 2
    with pytest.raises(ValueError, match="do not split over 3 host"):
        MeshPlan.build(hosts=3)


def test_removed_routes_raise_and_say_so():
    """``collective`` and ``overlap_chunks`` take the one value the
    benchmark's generator passes; what PR 44 removed is named."""
    plan = MeshPlan.build(collective="flat", overlap_chunks=1)
    d = plan.describe()
    assert d["collective"] == "flat" and d["overlap_chunks"] == 1
    for route in ("hier", "auto"):
        with pytest.raises(ValueError, match="ppermute ring.*removed in PR 44"):
            MeshPlan.build(collective=route)
    with pytest.raises(ValueError,
                       match="chunked level loop.*removed in PR 44"):
        MeshPlan.build(overlap_chunks=2)


def test_single_shard_plan_stays_flat():
    plan = MeshPlan.build(devices=jax.devices()[:1])
    assert plan.num_shards == 1 and plan.axes == ("data",)
    x = jnp.arange(7, dtype=jnp.float32)
    out = jax.jit(plan.shard_map(
        lambda v: plan.allreduce(v, "sum"), in_specs=plan.row_spec,
        out_specs=P(), check_replication=False))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_make_mesh_raises_instead_of_asserting():
    with pytest.raises(ValueError, match="do not factor the 8 available"):
        make_mesh((3, 5), ("host", "chip"))
    with pytest.raises(ValueError, match="axis_sizes required"):
        make_mesh(None, ("host", "chip"))


# ---------------------------------------------------------------------------
# a plan is one type
# ---------------------------------------------------------------------------

def test_histogram_mesh_takes_a_plan_only():
    mesh = make_mesh((8,), ("data",))
    kw = dict(num_features=4, num_trees=1, max_depth=2, num_bins=8)
    for spec in (mesh, (mesh, "data")):
        with pytest.raises(TypeError, match=r"MeshPlan\(mesh, axes\)"):
            GBDT(histogram_mesh=spec, **kw)
    plan = MeshPlan(mesh, ("data",))
    m = GBDT(histogram_mesh=plan, **kw)
    assert m.mesh_plan is plan and not hasattr(m, "histogram_mesh")
    assert GBDT(**kw).mesh_plan is None


# ---------------------------------------------------------------------------
# GBDT plan routing: forest identity
# ---------------------------------------------------------------------------

_BINS = 16


def _binned_data(rows=2048, feats=8, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, feats)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    return np.asarray(QuantileBinner(num_bins=_BINS).fit_transform(x)), y


def _fit(plan, bins, y, histogram="xla"):
    m = GBDT(num_features=bins.shape[1], num_trees=2, max_depth=4,
             num_bins=_BINS, learning_rate=0.3, histogram=histogram,
             histogram_mesh=plan)
    if plan is not None:
        bins = jax.device_put(bins, plan.data_sharding())
        y = jax.device_put(y, plan.data_sharding())
    return m.fit(bins, y)


@pytest.mark.parametrize("hosts", [None, 2])
@pytest.mark.parametrize("histogram", ["xla", "pallas"])
def test_plan_routed_fit_matches_single_device(hosts, histogram):
    bins, y = _binned_data()
    ref = _fit(None, bins, y, histogram)
    forest = _fit(MeshPlan.build(hosts=hosts), bins, y, histogram)
    # identical tree structure; leaves may differ by reduction rounding
    # between the single-device and collective routes
    np.testing.assert_array_equal(np.asarray(ref["feature"]),
                                  np.asarray(forest["feature"]))
    np.testing.assert_array_equal(np.asarray(ref["threshold"]),
                                  np.asarray(forest["threshold"]))
    np.testing.assert_allclose(np.asarray(ref["leaf"]),
                               np.asarray(forest["leaf"]),
                               rtol=1e-4, atol=1e-6)
