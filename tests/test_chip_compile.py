"""The main path's Pallas kernels compile for a described TPU v5e chip.

Interpret mode cannot see Mosaic's rules (block shapes aligned to the
(8, 128) tiling, no 8/16-bit integer min/max, the VMEM limit); the TPU
compiler, which is installed even where no chip is attached, can. Each
test lowers and compiles one kernel at the paper's deployment size against
``v5e:2x2`` device 0, about a second or two each, so a change that would
fail on the chip fails here first.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under pytest-xdist every worker
imports this file while only the worker given it should load the library.
Keep these compiles in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import DispatchPolicy
from repro.kernels import gradient2d_fused, transpose_tiled
from repro.kernels.ops import raw_morph2d

PAPER = (1, 600, 800)  # configs/morphology.py: 800x600 u8, batch of one
FUSED = DispatchPolicy()
TWO_PASS = DispatchPolicy(fused_2d=False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # the compiler logs to /tmp otherwise
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def compile_for_chip(fn, sharding, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is there
    return compiled


@pytest.mark.parametrize("shape,dtype", [
    (PAPER, jnp.uint8),
    (PAPER, jnp.float32),
    (PAPER, jnp.bool_),
    ((8, 1024, 1024), jnp.uint8),
])
def test_fused_erode_compiles(one_chip, shape, dtype):
    compile_for_chip(
        lambda x: raw_morph2d(x, (15, 15), "min", policy=FUSED, interpret=False),
        one_chip, shape, dtype,
    )


def test_fused_gradient_u8_compiles(one_chip):
    compile_for_chip(
        lambda x: gradient2d_fused(x, (5, 5), policy=FUSED, interpret=False),
        one_chip, PAPER, jnp.uint8,
    )


def test_two_pass_u8_compiles(one_chip):
    # H pass, then transpose -> W pass -> transpose: four kernels
    compile_for_chip(
        lambda x: raw_morph2d(x, (15, 15), "min", policy=TWO_PASS, interpret=False),
        one_chip, PAPER[1:], jnp.uint8,
    )


def test_transpose_tiled_u8_compiles(one_chip):
    compile_for_chip(
        lambda x: transpose_tiled(x, interpret=False), one_chip, PAPER[1:], jnp.uint8
    )
