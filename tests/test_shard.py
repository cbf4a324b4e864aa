"""Sharded morphology: mesh lowering, halo exchange, router.

Runs at any device count: shard counts are filtered to what is available,
so the tier-1 single-device run exercises the degenerate n=1 path and the
CI multi-device job (XLA_FLAGS=--xla_force_host_platform_device_count=8)
exercises real collectives. Every case asserts **bit-exactness** against
``lower_xla`` — the sharded path is the same computation, partitioned.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.morph import (
    Var,
    X,
    lower_xla,
    occo_expr,
    reconstruct_by_dilation_expr,
    to_plan,
)
from repro.morph.opt.cost import CostModel
from repro.core.dispatch import DispatchPolicy
from repro.serve.morph import MorphService, ServiceConfig
from repro.shard import (
    ShardedMorphService,
    available_shards,
    exchange_halo,
    image_mesh,
    mesh_axis_sizes,
    to_sharded,
)

N_DEV = available_shards()
SHARD_COUNTS = [n for n in (1, 2, 4, 8) if n <= N_DEV]
MULTI = [n for n in SHARD_COUNTS if n > 1]

rng = np.random.default_rng(42)


def u8(h, w):
    return rng.integers(0, 256, (h, w), dtype=np.uint8)


def sharded(expr, shards, **kw):
    return jax.jit(to_sharded(expr, image_mesh(shards), **kw))


def assert_bitexact(expr, img, shards, **kw):
    ref = np.asarray(lower_xla(expr)(img))
    got = np.asarray(sharded(expr, shards, **kw)(img))
    assert got.dtype == ref.dtype
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------- mesh layer
def test_image_mesh_shapes():
    assert mesh_axis_sizes(image_mesh(1)) == (1, 1)
    assert mesh_axis_sizes(image_mesh((1, 1))) == (1, 1)
    if N_DEV >= 2:
        assert mesh_axis_sizes(image_mesh(2)) == (2, 1)
        assert mesh_axis_sizes(image_mesh((1, 2))) == (1, 2)


def test_image_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="devices"):
        image_mesh(N_DEV + 1)
    with pytest.raises(ValueError):
        image_mesh(0)


def test_mesh_axis_sizes_rejects_foreign_axes():
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="image meshes"):
        mesh_axis_sizes(mesh)


# ------------------------------------------------------------- halo exchange
@pytest.mark.parametrize("n", MULTI or [1])
@pytest.mark.parametrize("wing", [1, 3, 5])
def test_exchange_halo_contents(n, wing):
    """The extended slab holds exactly the neighbor rows (neutral beyond)."""
    if n == 1:
        pytest.skip("needs >= 2 devices")
    mesh = image_mesh(n)
    rows = 6  # wing=5 < 6: single hop; separate case covers multi-hop
    x = rng.integers(0, 256, (rows * n, 8), dtype=np.uint8)
    neutral = np.uint8(255)

    def local(v):
        return exchange_halo(
            v, wing, axis=-2, axis_name="rows", size=n, neutral=neutral
        )

    ext = shard_map(
        local, mesh=mesh, in_specs=P("rows", None),
        out_specs=P("rows", None), check_vma=False,
    )(x)
    ext = np.asarray(ext).reshape(n, rows + 2 * wing, 8)
    padded = np.full((wing + rows * n + wing, 8), neutral, dtype=np.uint8)
    padded[wing:-wing] = x
    for i in range(n):
        np.testing.assert_array_equal(
            ext[i], padded[i * rows : i * rows + rows + 2 * wing]
        )


@pytest.mark.parametrize("n", MULTI or [1])
def test_exchange_halo_multi_hop(n):
    """wing > slab rows: the halo spans several neighbors exactly."""
    if n == 1:
        pytest.skip("needs >= 2 devices")
    mesh = image_mesh(n)
    rows, wing = 3, 7  # 3 hops
    x = rng.integers(0, 256, (rows * n, 4), dtype=np.uint8)
    neutral = np.uint8(0)

    def local(v):
        return exchange_halo(
            v, wing, axis=-2, axis_name="rows", size=n, neutral=neutral
        )

    ext = np.asarray(
        shard_map(local, mesh=mesh, in_specs=P("rows", None),
                  out_specs=P("rows", None), check_vma=False)(x)
    ).reshape(n, rows + 2 * wing, 4)
    padded = np.full((wing + rows * n + wing, 4), neutral, dtype=np.uint8)
    padded[wing:-wing] = x
    for i in range(n):
        np.testing.assert_array_equal(
            ext[i], padded[i * rows : i * rows + rows + 2 * wing]
        )


def test_exchange_halo_noop_cases():
    x = jnp.asarray(u8(8, 8))
    assert exchange_halo(x, 0, axis=-2, axis_name="rows", size=4,
                         neutral=0) is x
    assert exchange_halo(x, 3, axis=-2, axis_name="rows", size=1,
                         neutral=0) is x


# ------------------------------------------------------- sharded bit-exactness
OPS = [
    ("erode", X.erode((5, 5))),
    ("gradient", X.gradient((3, 7))),
    ("open_close", X.opening((3, 3)).closing((5, 5))),
    ("occo", occo_expr(X, (3, 3))),
]


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("name,expr", OPS, ids=[n for n, _ in OPS])
def test_sharded_bitexact_non_divisible(shards, name, expr):
    # 61 rows: indivisible by 2/4/8; 37 cols: indivisible by anything even
    assert_bitexact(expr, u8(61, 37), shards)


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_sharded_wing_larger_than_interior(shards):
    # 8 shards x 33 rows -> slab of 5; wing 15 needs 3 exchange hops
    assert_bitexact(X.erode((31, 3)), u8(33, 24), shards)


@pytest.mark.parametrize("shards", MULTI)
def test_reshard_strategy_bitexact(shards):
    assert_bitexact(X.dilate((9, 5)), u8(50, 40), shards, strategy="reshard")


@pytest.mark.parametrize("strategy", ["exchange", "reshard", "auto"])
def test_strategies_agree(strategy):
    if strategy == "reshard" and not MULTI:
        pytest.skip("reshard needs a multi-device rows mesh")
    shards = MULTI[-1] if MULTI else 1
    assert_bitexact(X.opening((7, 7)), u8(96, 64), shards, strategy=strategy)


def test_bad_strategy_rejected():
    with pytest.raises(ValueError, match="strategy"):
        to_sharded(X.erode((3, 3)), image_mesh(1), strategy="telepathy")
    with pytest.raises(ValueError, match="reshard"):
        to_sharded(X.erode((3, 3)), image_mesh(1), strategy="reshard")


def test_sharded_2d_mesh():
    if N_DEV < 4:
        pytest.skip("2-D mesh needs >= 4 devices")
    mesh = image_mesh((2, 2))
    img = u8(45, 51)
    for _, expr in OPS:
        ref = np.asarray(lower_xla(expr)(img))
        got = np.asarray(jax.jit(to_sharded(expr, mesh))(img))
        np.testing.assert_array_equal(got, ref)


def test_sharded_batch_dims():
    shards = SHARD_COUNTS[-1]
    imgs = rng.integers(0, 256, (3, 41, 29), dtype=np.uint8)
    expr = X.opening((5, 5))
    np.testing.assert_array_equal(
        np.asarray(sharded(expr, shards)(imgs)),
        np.asarray(lower_xla(expr)(imgs)),
    )


def test_sharded_multi_output_shared_graph():
    shards = SHARD_COUNTS[-1]
    er = X.erode((5, 5))
    outs = {"open": er.dilate((5, 5)), "grad": X.gradient((3, 3))}
    img = u8(47, 33)
    ref = lower_xla(outs)(img)
    got = jax.jit(to_sharded(outs, image_mesh(shards)))(img)
    assert set(got) == {"open", "grad"}
    for k in got:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]))


@pytest.mark.parametrize("until_stable", [True, False])
def test_sharded_reconstruction(until_stable):
    shards = SHARD_COUNTS[-1]
    expr = reconstruct_by_dilation_expr(
        X.erode((7, 7)), Var("x"), iters=24, until_stable=until_stable
    )
    img = u8(40, 36)
    np.testing.assert_array_equal(
        np.asarray(sharded(expr, shards)(img)),
        np.asarray(lower_xla(expr)(img)),
    )


def test_sharded_float_and_int_dtypes():
    shards = SHARD_COUNTS[-1]
    expr = X.gradient((5, 3))
    for arr in (
        rng.standard_normal((30, 22)).astype(np.float32),
        rng.integers(-100, 100, (30, 22), dtype=np.int8),
    ):
        np.testing.assert_array_equal(
            np.asarray(sharded(expr, shards)(arr)),
            np.asarray(lower_xla(expr)(arr)),
        )


def test_sharded_input_validation():
    fn = to_sharded(X.erode((3, 3)), image_mesh(1))
    with pytest.raises(ValueError, match="at least one input"):
        fn()
    with pytest.raises(ValueError, match="\\(\\.\\.\\., H, W\\)"):
        fn(np.zeros((8,), np.uint8))


# ------------------------------------------------------ property tests (fast)
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dep
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None)
    @given(
        h=st.integers(9, 70),
        w=st.integers(9, 70),
        se_h=st.sampled_from([1, 3, 7, 17]),
        se_w=st.sampled_from([1, 3, 5]),
        shards=st.sampled_from(SHARD_COUNTS),
        op=st.sampled_from(["erode", "dilate", "gradient"]),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property_sharded_equals_xla(h, w, se_h, se_w, shards, op, seed):
        from repro.morph.plan_compile import op_expr

        img = np.random.default_rng(seed).integers(
            0, 256, (h, w), dtype=np.uint8
        )
        expr = op_expr(op, (se_h, se_w))
        ref = np.asarray(lower_xla(expr)(img))
        got = np.asarray(to_sharded(expr, image_mesh(shards))(img))
        np.testing.assert_array_equal(got, ref)


# ----------------------------------------------------- collective cost model
def test_exchange_wins_analytic_fallback():
    model = CostModel.analytic(DispatchPolicy())
    assert model.collective_cost("ppermute", 1000) is None
    # no measured curves -> byte heuristic: exchange until wing > interior
    assert model.exchange_wins(4, 64, 128)
    assert not model.exchange_wins(65, 64, 128)
    with pytest.raises(ValueError, match="collective method"):
        model.collective_cost("gossip", 10)


def test_sparse_measured_table_keeps_scalar_dispatch():
    """A table holding only collective curves (bench_shard --fit-collective
    on a device never fit by bench_hybrid) must not corrupt 1-D dispatch:
    with no measured 1-D entries, best_method degrades to the recorded
    crossovers — the scalar branch — not an inf-vs-inf coin flip."""
    pol = DispatchPolicy(w0_major=31, w0_minor=15)
    model = dataclasses.replace(
        CostModel.analytic(pol),
        entries={("collective", "ppermute", "uint8"): (100.0, 0.01)},
        source="measured",
    )
    assert model.best_method("major", 31) == "linear_tree"
    assert model.best_method("major", 33) == "vhgw"
    assert model.best_method("minor", 17) == "vhgw"


def test_exchange_wins_measured_curves():
    entries = dict(CostModel.analytic(DispatchPolicy()).entries)
    # ppermute cheap per element but fixed launch cost; all_to_all dearer
    entries[("collective", "ppermute", "uint8")] = (50.0, 0.001)
    entries[("collective", "all_to_all", "uint8")] = (80.0, 0.01)
    model = dataclasses.replace(
        CostModel.analytic(DispatchPolicy()), entries=entries, source="measured"
    )
    assert model.exchange_wins(2, 256, 1024)  # small halo: ppermute
    # huge halo traffic vs tiny reshard: all_to_all wins despite intercept
    assert not model.exchange_wins(500, 4, 1024)


# ------------------------------------------------------------------- router
def test_router_results_match_direct():
    imgs = [u8(30, 40), u8(50, 20), u8(33, 33)]
    expr = X.opening((3, 3))
    refs = [np.asarray(lower_xla(expr)(im)) for im in imgs]
    cfg = ServiceConfig(buckets=((64, 64),), window_ms=1.0)
    with ShardedMorphService(cfg) as svc:
        outs = [np.asarray(svc.run_expr(im, expr)) for im in imgs]
    for got, ref in zip(outs, refs):
        np.testing.assert_array_equal(got, ref)


def test_router_uses_all_devices_and_merges_stats():
    cfg = ServiceConfig(buckets=((32, 32), (64, 64), (128, 128)),
                        window_ms=1.0)
    with ShardedMorphService(cfg) as svc:
        assert len(svc.shards) == N_DEV
        reqs = [(u8(b - 2, b - 2), ("erode", (3, 3))) for b in (32, 64, 128)
                for _ in range(4)]
        futs = [svc.submit(im, op, se) for im, (op, se) in reqs]
        for f in futs:
            f.result()
        stats = svc.stats()
        # a shard's dispatches run on its pinned device and no other
        for shard in svc.shards:
            seen = {k for k in shard.metrics_snapshot() if k.startswith("device.")}
            assert seen <= {f"device.{shard.config.device.id}.dispatches"}
    assert stats["shards"] == N_DEV
    assert stats["requests"] == len(reqs)
    assert len(stats["per_shard"]) == N_DEV
    assert stats["cache"]["misses"] == sum(
        p["cache"]["misses"] for p in stats["per_shard"]
    )
    # distinct buckets hash to distinct shards when devices allow
    if N_DEV >= 2:
        active = sum(p["requests"] > 0 for p in stats["per_shard"])
        assert active >= 2


def test_router_bucket_affinity_is_stable():
    cfg = ServiceConfig(buckets=((64, 64),))
    with ShardedMorphService(cfg) as svc:
        plan = to_plan(X.erode((3, 3)), name="affinity")
        img = u8(10, 10)
        targets = {id(svc._route(plan, img)) for _ in range(16)}
        assert len(targets) == 1  # same (plan, bucket, dtype) -> same shard


def test_router_rejects_mesh_and_devices():
    with pytest.raises(ValueError, match="not both"):
        ShardedMorphService(mesh=image_mesh(1), devices=jax.devices())


def test_router_from_mesh():
    img = u8(16, 16)
    with ShardedMorphService(mesh=image_mesh(1)) as svc:
        assert len(svc.shards) == 1
        np.testing.assert_array_equal(
            np.asarray(svc.run(img, "dilate", (3, 3))),
            np.asarray(lower_xla(X.dilate((3, 3)))(img)),
        )


# --------------------------------------------- convergence-aware BoundedIter
def test_router_reports_bounded_iter_stats():
    marker = X.erode((9, 9))
    expr = reconstruct_by_dilation_expr(
        marker, Var("x"), iters=64, until_stable=False
    )
    img = u8(48, 48)
    ref = np.asarray(lower_xla(expr)(img))
    with ShardedMorphService(ServiceConfig(buckets=((64, 64),))) as svc:
        got = np.asarray(svc.run_expr(img, expr))
        stats = svc.stats()["bounded_iter"]
    np.testing.assert_array_equal(got, ref)
    assert stats["executions"] >= 1
    assert stats["iters_budget"] >= 64
    # a 48x48 image converges before the 64-iteration budget (the geodesic
    # wavefront crosses ~1 px/iter), so the predicated scan must save work
    assert 0 < stats["iters_used"] < stats["iters_budget"]
    assert stats["saved_frac"] > 0.1
