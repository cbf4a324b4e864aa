"""Tiled matrix-transpose Pallas kernel (paper §4, adapted to TPU).

The paper builds 8x8.16 / 16x16.8 transposes from VTRN 2x2-block ladders so
that the vertical morphology pass can run on contiguous data. On TPU the
vector unit is an (8, 128) tile and Mosaic owns the in-register shuffle
network, so the adaptation (DESIGN.md §2) is:

* grid over (TILE x TILE) blocks held in VMEM,
* out block (j, i) <- in block (i, j) transposed in-register,
* the in-tile ``.T`` lowers to the TPU transpose/permute unit — the exact
  analog of the paper's VTRN ladder, with the 2x2 recursion replaced by the
  sublane/lane exchange Mosaic emits.

The kernel exists so the W-axis (lane-axis) morphology pass can be executed
as transpose -> sublane pass -> transpose, which is the paper's §5.2
baseline strategy, and so its cost can be compared against the direct
lane-shift pass in the §Perf log.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret
from repro.core.types import Array


def _transpose_kernel(x_ref, o_ref):
    # In-tile transpose: one VMEM tile in, one out. Mosaic lowers this to
    # the lane/sublane exchange network (the VTRN-ladder analog).
    o_ref[...] = x_ref[...].T


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def transpose_tiled(
    x: Array, *, tile: int = 128, interpret: bool | None = None
) -> Array:
    """Transpose the last two dims of ``x`` with an explicitly tiled kernel.

    ``tile`` is the square VMEM block edge; 128 matches the TPU lane width
    (the paper's "8" / "16" matched the NEON register width in elements).
    Non-multiple shapes are padded and cropped.
    """
    interpret = resolve_interpret(interpret)
    *lead, h, w = x.shape
    if lead:
        flat = x.reshape((-1, h, w))
        out = jax.vmap(lambda m: transpose_tiled(m, tile=tile, interpret=interpret))(flat)
        return out.reshape(tuple(lead) + (w, h))

    ph, pw = -h % tile, -w % tile
    xp = jnp.pad(x, ((0, ph), (0, pw)))
    gh, gw = (h + ph) // tile, (w + pw) // tile

    out = pl.pallas_call(
        _transpose_kernel,
        grid=(gh, gw),
        in_specs=[pl.BlockSpec((tile, tile), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tile, tile), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((w + pw, h + ph), x.dtype),
        interpret=interpret,
        name="transpose_tiled",
    )(xp)
    return out[:w, :h]
