"""The tiled route's one program per tile grid (``tiling.build_grid_executor``).

Every case runs the same image three ways — the whole-image reference, the
eager ``run_tiled`` over ``extract_tiles``, and the grid program through
``run_grid`` — and all three must agree bit for bit. The service tests pin
what the benchmark's per-layer metrics read: one compile per grid (pages of
other shapes on the same grid hit the cache), one launch and ``ny*nx``
tiles per page, and ``ny*nx*eh*ew`` pixels launched.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import closing, dilate, erode, gradient
from repro.data.images import cleanup_batch
from repro.serve.morph import (
    MorphService,
    ServiceConfig,
    build_executor,
    get_plan,
    run_tiled,
    single_op_plan,
)
from repro.serve.morph.tiling import (
    build_grid_executor,
    place_page,
    run_grid,
    tile_counts,
)

RNG = np.random.default_rng(14)


def rand(shape):
    return RNG.integers(0, 256, shape, dtype=np.uint8)


def cleanup_reference(img):
    clean, edges = cleanup_batch(img[None])
    return {"clean": np.asarray(clean[0]), "edges": np.asarray(edges[0])}


# (id, shape, plan, reference, interior, cap, backend)
CASES = [
    ("erode3_16x16_cap4", (75, 83), single_op_plan("erode", (3, 3)),
     lambda x: {"out": np.asarray(erode(x, (3, 3)))}, (16, 16), 4, "jnp"),
    ("erode9x5_32x48_cap4", (75, 83), single_op_plan("erode", (9, 5)),
     lambda x: {"out": np.asarray(erode(x, (9, 5)))}, (32, 48), 4, "jnp"),
    ("one_by_one_grid", (20, 30), single_op_plan("closing", (5, 5)),
     lambda x: {"out": np.asarray(closing(x, (5, 5)))}, (32, 32), 4, "jnp"),
    ("se_wider_than_interior", (40, 52), single_op_plan("gradient", (11, 9)),
     lambda x: {"out": np.asarray(gradient(x, (11, 9)))}, (8, 8), 8, "jnp"),
    ("cleanup_two_outputs_u8", (90, 110), get_plan("document_cleanup"),
     cleanup_reference, (32, 32), 4, "jnp"),
    ("short_last_chunk", (71, 93), get_plan("document_cleanup"),
     cleanup_reference, (16, 16), 7, "jnp"),  # 30 tiles: 4 chunks of 7 + 2
    ("chunks_divide_evenly", (71, 93), single_op_plan("dilate", (5, 3)),
     lambda x: {"out": np.asarray(dilate(x, (5, 3)))}, (16, 16), 6, "jnp"),
    ("cap_above_tile_count", (71, 93), get_plan("document_cleanup"),
     cleanup_reference, (32, 32), 16, "jnp"),
    ("kernel_backend_interpret", (40, 70), get_plan("document_cleanup"),
     cleanup_reference, (16, 32), 4, "kernel"),
]


def grid_execute(plan, interior, cap, backend):
    chunk = build_executor(plan, backend=backend, interpret=True, with_aux=True)

    def execute(grid, page, rects):
        fn = build_grid_executor(plan, chunk, grid, interior,
                                 max_tiles_per_launch=cap)
        return fn(page, rects)
    return execute


@pytest.mark.parametrize(
    "shape,plan,reference,interior,cap,backend",
    [c[1:] for c in CASES], ids=[c[0] for c in CASES],
)
def test_grid_program_bit_exact(shape, plan, reference, interior, cap, backend):
    img = rand(shape)
    want = reference(img)
    got, aux = run_grid(img, plan, grid_execute(plan, interior, cap, backend),
                        tile_interior=interior)
    ex = build_executor(plan, backend=backend, interpret=True)
    eager = run_tiled(img, plan,
                      lambda t, r: ex(jnp.asarray(t), jnp.asarray(r)),
                      tile_interior=interior, launch_batch=cap)
    assert set(got) == set(want) == set(eager)
    for name in want:
        assert got[name].dtype == want[name].dtype
        assert got[name].shape == shape and got[name].flags.c_contiguous
        np.testing.assert_array_equal(got[name], want[name])
        np.testing.assert_array_equal(got[name], eager[name])
    assert int(aux["iters_budget"]) == 0 and int(aux["iters_used"]) == 0


def test_place_page_zero_fills_the_grid_extent():
    img = rand((71, 93))
    plan = get_plan("document_cleanup")
    page, rects = place_page(img, plan, (32, 32))
    assert page.shape == (3 * 32, 3 * 32) and page.dtype == img.dtype
    np.testing.assert_array_equal(page[:71, :93], img)
    assert not page[71:].any() and not page[:, 93:].any()
    gh, gw = plan.halo()
    # the last tile's valid rect ends at the image edge, in tile coordinates
    assert rects.shape == (9, 4)
    assert list(rects[-1]) == [0, 71 - 64 + gh, 0, 93 - 64 + gw]


def test_grid_program_sums_bounded_iteration_aux():
    """The aux of every chunk is summed inside the program: the budget is
    the plan's per-chunk budget times the number of chunks."""
    from repro.morph import Var, X, reconstruct_by_dilation_expr, to_plan

    plan = to_plan(
        reconstruct_by_dilation_expr(
            X.erode((5, 5)), Var("x"), iters=6, until_stable=False
        ),
        name="aux_grid",
    )
    img = rand((40, 40))
    interior = (16, 16)  # 3x3 grid, cap 4: chunks of 4, 4 and 1
    got, aux = run_grid(img, plan, grid_execute(plan, interior, 4, "jnp"),
                        tile_interior=interior)
    assert int(aux["iters_budget"]) == 3 * 6
    assert 0 < int(aux["iters_used"]) <= 3 * 6
    ex = build_executor(plan)
    eager = run_tiled(img, plan, lambda t, r: ex(jnp.asarray(t), jnp.asarray(r)),
                      tile_interior=interior, launch_batch=4)
    np.testing.assert_array_equal(got["out"], eager["out"])


# ------------------------------------------------------------------ service
def counters(svc):
    return {k: v["value"] for k, v in svc.metrics_snapshot().items()
            if v["type"] == "counter"}


def test_service_one_compile_and_one_launch_per_grid():
    """A page of another shape on the same grid compiles nothing; each page
    is one launch of ``ny*nx`` tiles and ``ny*nx*eh*ew`` launched pixels."""
    interior = (32, 32)
    cfg = ServiceConfig(buckets=((64, 128),), tile_interior=interior,
                        max_tiles_per_launch=4, window_ms=1.0)
    plan = single_op_plan("closing", (5, 5))
    gh, gw = plan.halo()
    eh, ew = interior[0] + 2 * gh, interior[1] + 2 * gw
    first, second, other = rand((100, 90)), rand((97, 70)), rand((130, 90))
    assert tile_counts(*first.shape, interior) == tile_counts(*second.shape, interior)
    assert tile_counts(*other.shape, interior) != tile_counts(*first.shape, interior)
    with MorphService(cfg) as svc:
        for i, img in enumerate((first, second, other)):
            before, misses = counters(svc), svc.cache.misses
            got = svc.run(img, op="closing", se=(5, 5))
            np.testing.assert_array_equal(got, np.asarray(closing(img, (5, 5))))
            after = counters(svc)
            ny, nx = tile_counts(*img.shape, interior)
            assert svc.cache.misses - misses == (0 if i == 1 else 1)
            assert after["tiled.launches"] - before.get("tiled.launches", 0) == 1
            assert after["tiled.tiles"] - before.get("tiled.tiles", 0) == ny * nx
            assert (after["executor.pixels_launched"]
                    - before.get("executor.pixels_launched", 0)) == ny * nx * eh * ew
            assert (after["executor.pixels_valid"]
                    - before.get("executor.pixels_valid", 0)) == img.size
        assert svc.stats()["tiled_requests"] == 3
