"""Pallas kernel: linear (O(w)) 1-D morphology pass along the sublane axis.

This is the paper's §5.1.2 linear implementation mapped to TPU. The paper
vectorizes 16 u8 pixels per `vminq_u8`; here one `jnp.minimum` inside the
kernel covers an (8, 128) vreg and the window walk happens along sublanes
(the H axis of the block), where shifted operands are free re-slices of the
VMEM block rather than lane rotations — the TPU-side reason this pass is
the "good axis" pass (DESIGN.md §2).

Tiling: grid over W in BW-wide strips; each kernel instance holds the whole
padded column strip (H + 2*wing, BW) in VMEM and writes (H, BW). VMEM
budget: (H + w) * BW * itemsize, e.g. 4096x128xf32 = 2 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret
from repro.core.types import Array, as_op, check_window, vmem_dtype


def _linear_kernel(x_ref, o_ref, *, w: int, opname: str):
    op = as_op(opname)
    h = o_ref.shape[0]
    # Paper's inner loop: a single accumulator reduced against w shifted
    # loads; slices along sublanes are offset reads of the same VMEM block,
    # widened once to the compute dtype (core.types.vmem_dtype).
    x = x_ref[...].astype(vmem_dtype(x_ref.dtype))
    val = x[0:h, :]
    for k in range(1, w):
        val = op.reduce(val, x[k : k + h, :])
    o_ref[...] = val.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("w", "op", "block_w", "interpret")
)
def morph_linear_sublane(
    x: Array,
    *,
    w: int,
    op: str = "min",
    block_w: int = 128,
    interpret: bool | None = None,
) -> Array:
    """Running min/max of window ``w`` along axis -2 of a 2-D array."""
    interpret = resolve_interpret(interpret)
    w = check_window(w)
    mop = as_op(op)
    if x.ndim != 2:
        raise ValueError("kernel operates on (H, W); vmap for batches")
    h, wid = x.shape
    if w == 1:
        return x
    wing = (w - 1) // 2
    pw = -wid % block_w
    xp = jnp.pad(
        x,
        ((wing, wing), (0, pw)),
        constant_values=mop.neutral(x.dtype),
    )
    grid = ((wid + pw) // block_w,)
    out = pl.pallas_call(
        functools.partial(_linear_kernel, w=w, opname=mop.name),
        grid=grid,
        in_specs=[pl.BlockSpec((h + 2 * wing, block_w), lambda j: (0, j))],
        out_specs=pl.BlockSpec((h, block_w), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((h, wid + pw), x.dtype),
        interpret=interpret,
        name=f"morph_linear_{mop.name}",
    )(xp)
    return out[:, :wid]
