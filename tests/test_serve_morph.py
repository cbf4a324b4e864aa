"""Serving-engine acceptance: bucket round-trips, halo-correct tiling,
executable-cache accounting, micro-batching, and plan/raw-pipeline equality.

The load-bearing invariants:

* every service route (bucketed, tiled) is BIT-exact against running the
  same op/plan directly on the unpadded image — including SEs larger than
  the halo-free tile interior;
* the ``document_cleanup`` plan and ``data/images.py::cleanup_batch`` are
  the same computation;
* the executable cache compiles exactly once per (bucket, op, se) and its
  counters say so.
"""
import time
from concurrent.futures import Future

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import closing, dilate, erode, gradient, opening
from repro.core.dispatch import DispatchPolicy, resolve_interpret
from repro.data.images import cleanup_batch
from repro.serve.morph import (
    MicroBatcher,
    MorphService,
    ServiceConfig,
    build_executor,
    choose_bucket,
    get_plan,
    pad_to_bucket,
    run_tiled,
    single_op_plan,
)
from repro.serve.morph.plans import Plan, Step

RNG = np.random.default_rng(7)

CORE_OPS = {
    "erode": erode,
    "dilate": dilate,
    "opening": opening,
    "closing": closing,
    "gradient": gradient,
}


def rand(shape, dtype=np.uint8):
    if np.issubdtype(dtype, np.floating):
        return RNG.standard_normal(shape).astype(dtype)
    info = np.iinfo(dtype)
    return RNG.integers(info.min, info.max, shape, dtype=dtype)


def tiled_execute(plan):
    ex = build_executor(plan)
    return lambda tiles, rects: ex(jnp.asarray(tiles), jnp.asarray(rects))


# --------------------------------------------------------------------- buckets
def test_choose_bucket_smallest_fit():
    ladder = ((64, 128), (128, 128), (256, 256))
    assert choose_bucket(60, 100, ladder) == (64, 128)
    assert choose_bucket(64, 128, ladder) == (64, 128)
    assert choose_bucket(65, 100, ladder) == (128, 128)
    assert choose_bucket(300, 10, ladder) is None  # -> tiled route


def test_pad_to_bucket_preserves_data():
    img = rand((50, 70))
    padded = pad_to_bucket(img, (64, 128))
    assert padded.shape == (64, 128)
    np.testing.assert_array_equal(padded[:50, :70], img)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("op", ["erode", "dilate", "opening", "closing", "gradient"])
def test_bucket_padding_round_trip_bit_exact(op, dtype):
    """Pad-to-bucket -> masked execute -> crop == the unpadded op, for every
    op (composed ones are the hard case: one fill value can't serve both
    min and max stages — the per-stage masking must)."""
    img = rand((47, 61), dtype)
    with MorphService(ServiceConfig(buckets=((64, 128),), window_ms=1.0)) as svc:
        got = svc.run(img, op=op, se=(5, 7))
    want = np.asarray(CORE_OPS[op](img, (5, 7)))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------------- plans
def test_plan_halo_sums_expanded_wings():
    plan = get_plan("document_cleanup")
    # opening(3,3)=2*1, closing(5,5)=2*2, gradient(3,3)=1 -> 7 per axis
    assert plan.halo() == (7, 7)
    assert single_op_plan("erode", (9, 5)).halo() == (4, 2)
    assert single_op_plan("opening", (3, 7)).halo() == (2, 6)


def test_document_cleanup_plan_matches_cleanup_batch():
    img = rand((70, 90))
    with MorphService(ServiceConfig(buckets=((128, 128),), window_ms=1.0)) as svc:
        res = svc.run_plan(img, "document_cleanup")
    clean, edges = cleanup_batch(img[None])
    np.testing.assert_array_equal(res["clean"], np.asarray(clean[0]))
    np.testing.assert_array_equal(res["edges"], np.asarray(edges[0]))
    assert res["edges"].dtype == np.uint8


def test_kernel_and_jnp_backends_agree():
    img = rand((40, 70))
    plan = get_plan("document_cleanup")
    rect = jnp.asarray([[0, 40, 0, 70]], dtype=jnp.int32)
    x = jnp.asarray(img[None])
    a = build_executor(plan, backend="jnp")(x, rect)
    b = build_executor(plan, backend="kernel", interpret=True)(x, rect)
    for name in ("clean", "edges"):
        np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))


def test_gradient_plan_widens_integers():
    img = rand((30, 40))
    with MorphService(ServiceConfig(buckets=((64, 128),), window_ms=1.0)) as svc:
        got = svc.run(img, op="gradient", se=(3, 3))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, np.asarray(gradient(img, (3, 3))))


# ---------------------------------------------------------------------- tiling
@pytest.mark.parametrize("interior", [(16, 16), (32, 48), (64, 64)])
@pytest.mark.parametrize("se", [(3, 3), (9, 5)])
def test_tiled_vs_untiled_bit_exact(interior, se):
    img = rand((75, 83))
    plan = single_op_plan("erode", se)
    outs = run_tiled(img, plan, tiled_execute(plan),
                     tile_interior=interior, launch_batch=4)
    np.testing.assert_array_equal(outs["out"], np.asarray(erode(img, se)))


def test_tiled_se_larger_than_tile_interior():
    """The halo makes the extended tile big enough even when the SE dwarfs
    the halo-free interior."""
    img = rand((40, 52))
    plan = single_op_plan("gradient", (11, 9))
    assert plan.halo() == (5, 4)
    outs = run_tiled(img, plan, tiled_execute(plan),
                     tile_interior=(8, 8), launch_batch=8)
    np.testing.assert_array_equal(outs["out"], np.asarray(gradient(img, (11, 9))))


def test_tiled_full_plan_bit_exact():
    img = rand((90, 110))
    plan = get_plan("document_cleanup")
    outs = run_tiled(img, plan, tiled_execute(plan),
                     tile_interior=(32, 32), launch_batch=4)
    clean, edges = cleanup_batch(img[None])
    np.testing.assert_array_equal(outs["clean"], np.asarray(clean[0]))
    np.testing.assert_array_equal(outs["edges"], np.asarray(edges[0]))


def test_tile_gather_is_device_resident():
    """Regression for the ROADMAP "streamed tile gather" item: halo tiles
    are assembled with device-side dynamic_slice from one padded device
    copy — not host numpy — and the stitched seams stay bit-exact on a
    shape divisible by neither the interior nor the launch batch."""
    import jax

    from repro.serve.morph.tiling import extract_tiles

    img = rand((71, 93))
    plan = get_plan("document_cleanup")
    tiles, rects, interiors = extract_tiles(img, plan, (32, 32))
    assert isinstance(tiles, jax.Array)  # gathered on device, no host copy
    gh, gw = plan.halo()
    assert tiles.shape[1:] == (32 + 2 * gh, 32 + 2 * gw)
    # seam exactness through the full service tiled route
    outs = run_tiled(img, plan, tiled_execute(plan),
                     tile_interior=(32, 32), launch_batch=4)
    clean, edges = cleanup_batch(img[None])
    np.testing.assert_array_equal(outs["clean"], np.asarray(clean[0]))
    np.testing.assert_array_equal(outs["edges"], np.asarray(edges[0]))


def test_executor_aux_reports_bounded_iters():
    """with_aux=True surfaces BoundedIter convergence depth; plans without
    bounded iteration report a zero budget."""
    from repro.morph import Var, X, reconstruct_by_dilation_expr, to_plan

    plan = to_plan(
        reconstruct_by_dilation_expr(
            X.erode((7, 7)), Var("x"), iters=32, until_stable=False
        ),
        name="aux_recon",
    )
    x = jnp.asarray(rand((24, 24))[None])
    rect = jnp.asarray(np.array([[0, 24, 0, 24]], np.int32))
    outs, aux = build_executor(plan, with_aux=True)(x, rect)
    ref = build_executor(plan)(x, rect)  # default shape: no aux
    np.testing.assert_array_equal(np.asarray(outs["out"]), np.asarray(ref["out"]))
    assert int(aux["iters_budget"]) == 32
    assert 0 < int(aux["iters_used"]) <= 32
    plain = single_op_plan("erode", (3, 3))
    _, aux2 = build_executor(plain, with_aux=True)(x, rect)
    assert int(aux2["iters_budget"]) == 0


def test_service_routes_oversized_images_to_tiling():
    img = rand((200, 150))
    with MorphService(
        ServiceConfig(buckets=((64, 128),), tile_interior=(64, 64),
                      max_tiles_per_launch=4, window_ms=1.0)
    ) as svc:
        got = svc.run(img, op="closing", se=(5, 5))
        stats = svc.stats()
    np.testing.assert_array_equal(got, np.asarray(closing(img, (5, 5))))
    assert stats["tiled_requests"] == 1


# ----------------------------------------------------------------------- cache
def test_cache_one_compile_per_bucket_op_se():
    """N same-bucket requests of varying (h, w) compile exactly once per
    (bucket, op, se); a second wave is all hits."""
    with MorphService(
        ServiceConfig(buckets=((64, 128),), max_batch=8, window_ms=1000.0)
    ) as svc:
        for wave in range(2):
            futs = [
                svc.submit(rand((40 + i, 60 + i)), op="erode", se=(3, 3))
                for i in range(8)  # == max_batch -> dispatches immediately
            ]
            [f.result() for f in futs]
            snap = svc.cache.snapshot()
            assert snap["misses"] == 1, snap
        futs = [svc.submit(rand((40, 60)), op="dilate", se=(5, 5)) for _ in range(8)]
        [f.result() for f in futs]
        snap = svc.cache.snapshot()
    assert snap["misses"] == 2, snap  # one more for the new (op, se)
    assert snap["hits"] >= 1


def test_cache_eviction_counter():
    with MorphService(
        ServiceConfig(buckets=((64, 128),), max_batch=1, window_ms=1.0,
                      cache_size=1)
    ) as svc:
        svc.run(rand((30, 40)), op="erode", se=(3, 3))
        svc.run(rand((30, 40)), op="dilate", se=(3, 3))
        snap = svc.cache.snapshot()
    assert snap["evictions"] >= 1
    assert snap["size"] <= 1


def test_policy_change_is_a_new_cache_key():
    imgs = rand((30, 40))
    cfg = ServiceConfig(buckets=((64, 128),), max_batch=1, window_ms=1.0)
    with MorphService(cfg) as svc:
        svc.run(imgs, op="erode", se=(3, 3))
        misses_a = svc.cache.snapshot()["misses"]
    with MorphService(
        ServiceConfig(buckets=((64, 128),), max_batch=1, window_ms=1.0,
                      policy=DispatchPolicy(w0_fused=3))
    ) as svc:
        svc.run(imgs, op="erode", se=(3, 3))
        misses_b = svc.cache.snapshot()["misses"]
    assert misses_a == misses_b == 1  # separate services, but token differs
    assert DispatchPolicy().cache_token() != DispatchPolicy(w0_fused=3).cache_token()


# --------------------------------------------------------------------- batcher
def test_batcher_coalesces_concurrent_requests():
    with MorphService(
        ServiceConfig(buckets=((64, 128),), max_batch=16, window_ms=200.0)
    ) as svc:
        svc.run(rand((30, 40)), op="erode", se=(3, 3))  # warm the executable
        futs = [svc.submit(rand((30, 40)), op="erode", se=(3, 3)) for _ in range(16)]
        [f.result() for f in futs]
        stats = svc.stats()
    assert stats["requests"] == 17
    # 16 concurrent requests ride in at most a few batches, not 16
    assert stats["batches"] <= 4
    assert stats["mean_batch"] > 1.0


def test_batcher_error_fans_out_to_futures():
    def boom(key, reqs):
        raise RuntimeError("executor exploded")

    class Req:
        def __init__(self):
            self.key = "k"
            self.future = Future()

    b = MicroBatcher(boom, max_batch=4, window_s=0.001)
    reqs = [Req() for _ in range(3)]
    for r in reqs:
        b.submit(r)
    for r in reqs:
        with pytest.raises(RuntimeError, match="executor exploded"):
            r.future.result(timeout=10)
    b.close()


def test_batcher_flush_and_close_drain_everything():
    done = []

    class Req:
        def __init__(self, i):
            self.key = "k"
            self.future = Future()
            self.i = i

    def execute(key, reqs):
        time.sleep(0.01)
        for r in reqs:
            done.append(r.i)
            r.future.set_result(r.i)

    b = MicroBatcher(execute, max_batch=4, window_s=0.05)
    for i in range(10):
        b.submit(Req(i))
    assert b.flush(timeout=30)
    b.close()
    assert sorted(done) == list(range(10))


def test_batch_results_match_request_order():
    imgs = [rand((25 + i, 30 + i)) for i in range(6)]
    with MorphService(
        ServiceConfig(buckets=((64, 128),), max_batch=6, window_ms=500.0)
    ) as svc:
        results = svc.run_batch(imgs, single_op_plan("erode", (3, 3)))
    for img, got in zip(imgs, results):
        assert got.shape == img.shape
        np.testing.assert_array_equal(got, np.asarray(erode(img, (3, 3))))


def test_submit_rejects_batched_input():
    with MorphService(ServiceConfig(buckets=((64, 128),))) as svc:
        with pytest.raises(ValueError, match="single"):
            svc.submit(rand((2, 30, 40)))


# ------------------------------------------------------------------- resolver
def test_resolve_interpret_precedence(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None, DispatchPolicy(interpret=False)) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_interpret(None) is True
    # explicit argument and policy both beat the env var
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None, DispatchPolicy(interpret=True)) is True


def test_resolve_interpret_refuses_interpreter_on_tpu(monkeypatch):
    # on a TPU backend the kernels compile; interpret mode from any source
    # is an error instead of a silent slow path that hides the device
    from repro.core import dispatch

    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(None) is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="TPU"):
        resolve_interpret(True)
    with pytest.raises(ValueError, match="TPU"):
        resolve_interpret(None, DispatchPolicy(interpret=True))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="TPU"):
        resolve_interpret(None)


def test_custom_plan_registration_and_multi_output():
    plan = Plan(
        "open_then_edges",
        (
            Step("opening", (3, 3), save_as="opened"),
            Step("gradient", (3, 3), save_as="edges"),
        ),
    )
    img = rand((40, 50))
    with MorphService(ServiceConfig(buckets=((64, 128),), window_ms=1.0)) as svc:
        res = svc.run_plan(img, plan)
    o = opening(img, (3, 3))
    np.testing.assert_array_equal(res["opened"], np.asarray(o))
    np.testing.assert_array_equal(res["edges"], np.asarray(gradient(o, (3, 3))))
