"""Fused 2-D megakernel vs oracles, two-pass A/B, and launch-count checks.

The acceptance contract for the fused path (kernels/morph_fused.py):

* bit-exact against the naive non-separable ``morph2d_naive`` oracle and
  against the legacy two-pass + double-transpose pipeline, across dtypes,
  asymmetric SEs, non-tile-aligned shapes, and batched inputs;
* the default ``erode2d_tpu``/``dilate2d_tpu`` path issues exactly ONE
  ``pallas_call`` (verified by walking the jaxpr), versus four for the
  two-pass path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DispatchPolicy, morph2d_naive
from repro.kernels import (
    dilate2d_tpu,
    erode2d_tpu,
    gradient2d_fused,
    gradient2d_tpu,
    morph2d_fused,
)
from repro.kernels.ref import gradient2d_ref, morph2d_ref

RNG = np.random.default_rng(11)


def rand(shape, dtype):
    if np.issubdtype(dtype, np.floating):
        return jnp.asarray(RNG.standard_normal(shape).astype(dtype))
    info = np.iinfo(dtype)
    return jnp.asarray(RNG.integers(info.min, info.max, shape, dtype=dtype))


# ------------------------------------------------------------- jaxpr walking
def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else [val]
            for v in vals:
                # duck-typed: a ClosedJaxpr wraps its Jaxpr in ``.jaxpr``,
                # and the classes' import path moves between JAX releases
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _iter_jaxprs(inner)


def count_pallas_calls(fn, *args) -> int:
    closed = jax.make_jaxpr(fn)(*args)
    return sum(
        eqn.primitive.name == "pallas_call"
        for j in _iter_jaxprs(closed.jaxpr)
        for eqn in j.eqns
    )


# ----------------------------------------------------------- oracle equality
@pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.float32])
@pytest.mark.parametrize("se", [(3, 3), (3, 31), (31, 3), (63, 63)])
def test_fused_vs_naive(dtype, se):
    x = rand((97, 141), dtype)
    for op in ("min", "max"):
        got = np.asarray(morph2d_fused(x, se, op=op))
        np.testing.assert_array_equal(got, np.asarray(morph2d_ref(x, se, op=op)))


@pytest.mark.parametrize("shape", [(257, 191), (128, 128), (37, 260)])
def test_fused_nonaligned_shapes(shape):
    x = rand(shape, np.uint8)
    for se in ((3, 3), (5, 9)):
        np.testing.assert_array_equal(
            np.asarray(morph2d_fused(x, se, op="min")),
            np.asarray(morph2d_naive(x, se, op="min")),
        )


@pytest.mark.parametrize("se", [(3, 3), (3, 31), (31, 3), (9, 9)])
def test_fused_vs_two_pass(se):
    x = rand((130, 150), np.uint8)
    np.testing.assert_array_equal(
        np.asarray(erode2d_tpu(x, se, fused=True)),
        np.asarray(erode2d_tpu(x, se, fused=False)),
    )
    np.testing.assert_array_equal(
        np.asarray(dilate2d_tpu(x, se, fused=True)),
        np.asarray(dilate2d_tpu(x, se, fused=False)),
    )


def test_fused_batched():
    xb = rand((5, 64, 200), np.uint8)
    got = np.asarray(morph2d_fused(xb, (5, 7), op="min"))
    np.testing.assert_array_equal(got, np.asarray(morph2d_naive(xb, (5, 7), op="min")))
    # batch grid == per-image results
    for i in range(xb.shape[0]):
        np.testing.assert_array_equal(
            got[i], np.asarray(morph2d_fused(xb[i], (5, 7), op="min"))
        )


def test_fused_method_override():
    x = rand((90, 110), np.uint8)
    a = np.asarray(morph2d_fused(x, (15, 15), op="max", method="linear"))
    b = np.asarray(morph2d_fused(x, (15, 15), op="max", method="vhgw"))
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, np.asarray(morph2d_naive(x, (15, 15), op="max")))


def test_wide_se_still_fused():
    # wing_w in (128, 512]: auto block sizing widens the strip to cover it.
    x = rand((24, 300), np.uint8)
    assert count_pallas_calls(lambda a: erode2d_tpu(a, (3, 259)), x) == 1
    np.testing.assert_array_equal(
        np.asarray(erode2d_tpu(x, (3, 259))),
        np.asarray(morph2d_naive(x, (3, 259), op="min")),
    )


def test_giant_se_falls_back_to_two_pass():
    # wing_w > 512 exceeds the fused policy range; dispatch falls back cleanly.
    x = rand((16, 1100), np.uint8)
    got = np.asarray(erode2d_tpu(x, (3, 1031)))
    np.testing.assert_array_equal(got, np.asarray(morph2d_naive(x, (3, 1031), op="min")))


def test_batched_two_pass_fallback():
    # (B, H, W) must also work on the legacy path (vmap-of-kernels).
    xb = rand((3, 40, 70), np.uint8)
    got = np.asarray(erode2d_tpu(xb, (3, 5), fused=False))
    np.testing.assert_array_equal(got, np.asarray(morph2d_naive(xb, (3, 5), op="min")))


# ------------------------------------- VMEM widening and lane-aligned halo
# SE widths 1, 3, 121, 127 give wing_w 0, 1, 60, 63; the image widths are
# not multiples of 128, so the last column block is partly padding.
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int8, np.int16])
@pytest.mark.parametrize("w_w", [1, 3, 121, 127])
def test_fused_narrow_ints_vs_naive(dtype, w_w):
    x = rand((2, 37, 260), dtype)
    se = (5, w_w)
    for op in ("min", "max"):
        got = morph2d_fused(x, se, op=op)
        assert got.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(morph2d_naive(x, se, op=op))
        )


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int8, np.int16])
@pytest.mark.parametrize("w_w", [1, 3, 121, 127])
def test_fused_gradient_narrow_ints_vs_ref(dtype, w_w):
    x = rand((29, 200), dtype)
    got = gradient2d_fused(x, (3, w_w))
    want = gradient2d_ref(x, (3, w_w))
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("w_w", [1, 3, 121, 127])
def test_bool_mask_widens_once(fused, w_w):
    x = jnp.asarray(RNG.random((2, 24, 150)) < 0.7)
    for fn, op in ((erode2d_tpu, "min"), (dilate2d_tpu, "max")):
        got = fn(x, (3, w_w), fused=fused)
        assert got.dtype == np.bool_
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(morph2d_naive(x, (3, w_w), op=op))
        )


@pytest.mark.parametrize("narrow,wide", [
    (np.uint16, np.uint32), (np.int8, np.int32), (np.int16, np.int32),
])
def test_two_pass_narrow_ints_match_wide(narrow, wide):
    # the two-pass kernels widen in VMEM too; the result must equal the
    # same image run in a wide dtype, cast back
    x = rand((40, 140), narrow)
    for se in ((3, 5), (35, 3)):  # linear ladder and vHGW
        got = erode2d_tpu(x, se, fused=False)
        assert got.dtype == narrow
        want = erode2d_tpu(x.astype(wide), se, fused=False).astype(narrow)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("wing_w", [0, 1, 60, 63])
def test_fused_blocks_are_lane_aligned(wing_w):
    # Mosaic's block-shape rule: the last block dim is a whole number of
    # 128-lane tiles or the array's full extent
    from repro.kernels.morph_fused import _pad_for_grid

    x = jnp.zeros((2, 30, 300), jnp.uint8)
    arrays, specs, gw = _pad_for_grid(x, 2, wing_w, 128, 0)
    assert len(arrays) == (2 if wing_w else 1) == len(specs)
    for arr, spec in zip(arrays, specs):
        last = spec.block_shape[-1]
        assert last % 128 == 0 or last == arr.shape[-1]
        assert arr.shape[-1] == gw * last


# ------------------------------------------------------------ fused gradient
@pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.float32])
def test_gradient2d_fused_vs_ref(dtype):
    x = rand((80, 144), dtype)
    for se in ((3, 3), (3, 15), (15, 3)):
        got = np.asarray(gradient2d_fused(x, se))
        want = np.asarray(gradient2d_ref(x, se))
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
@pytest.mark.parametrize("se", [(3, 3), (3, 9), (9, 3), (9, 9)])
def test_gradient_integer_widening_consistent_across_paths(dtype, se):
    """Fused and two-pass gradient2d_tpu must agree on integer widening
    (u8/i8 -> i32) for SEs on BOTH sides of the w0_fused crossover — a
    w0_fused=5 policy puts 3-wide passes on the linear side and 9-wide
    passes on the vHGW side without needing giant SEs."""
    x = rand((40, 60), dtype)
    policy = DispatchPolicy(w0_fused=5)
    fused = np.asarray(gradient2d_tpu(x, se, fused=True, policy=policy))
    two_pass = np.asarray(gradient2d_tpu(x, se, fused=False, policy=policy))
    assert fused.dtype == np.int32
    assert two_pass.dtype == np.int32
    np.testing.assert_array_equal(fused, two_pass)
    # floats keep their dtype on both paths
    xf = rand((40, 60), np.float32)
    assert gradient2d_tpu(xf, se, fused=True, policy=policy).dtype == np.float32
    assert gradient2d_tpu(xf, se, fused=False, policy=policy).dtype == np.float32


def test_gradient2d_tpu_paths_agree():
    x = rand((3, 70, 90), np.uint8)
    two_pass = jnp.stack([gradient2d_tpu(x[i], (5, 5), fused=False) for i in range(3)])
    np.testing.assert_array_equal(
        np.asarray(gradient2d_tpu(x, (5, 5), fused=True)), np.asarray(two_pass)
    )


# -------------------------------------------------------- launch-count tests
def test_default_erode_is_one_pallas_call():
    x = rand((64, 128), np.uint8)
    assert count_pallas_calls(lambda a: erode2d_tpu(a, (5, 9)), x) == 1
    assert count_pallas_calls(lambda a: dilate2d_tpu(a, (5, 9)), x) == 1


def test_batched_erode_is_one_pallas_call():
    xb = rand((4, 64, 128), np.uint8)
    assert count_pallas_calls(lambda a: erode2d_tpu(a, (3, 3)), xb) == 1


def test_gradient_is_one_pallas_call():
    x = rand((64, 128), np.uint8)
    assert count_pallas_calls(lambda a: gradient2d_tpu(a, (3, 3)), x) == 1


def test_two_pass_is_four_pallas_calls():
    x = rand((64, 128), np.uint8)
    n = count_pallas_calls(lambda a: erode2d_tpu(a, (5, 9), fused=False), x)
    assert n == 4  # H pass + (transpose, W pass, transpose)


def test_policy_knob_disables_fusion():
    x = rand((64, 128), np.uint8)
    policy = DispatchPolicy(fused_2d=False)
    n = count_pallas_calls(lambda a: erode2d_tpu(a, (5, 9), policy=policy), x)
    assert n == 4
    np.testing.assert_array_equal(
        np.asarray(erode2d_tpu(x, (5, 9), policy=policy)),
        np.asarray(erode2d_tpu(x, (5, 9))),
    )
