"""Pallas kernel: van Herk/Gil-Werman 1-D morphology pass (sublane axis).

Paper §5.1.1 baseline, adapted to TPU (DESIGN.md §2):

* The paper streams the forward/backward running-min buffers F and B
  through two image-sized scratch arrays; here both live entirely in VMEM
  for the current (nseg*w, BW) strip — no HBM round trip.
* The paper computes F/B with a sequential O(1)-per-pixel loop (good on a
  scalar/short-vector core). A sequential loop over sublanes would serialize
  the VPU, so the scans are computed with a Hillis-Steele doubling ladder:
  ceil(log2 w) vector ops per segment instead of w, at full (8,128) width.
  Per-pixel cost: ~2*ceil(log2 w) + 1 vector ops — still O(1)-ish in w and
  independent of window *position*, preserving the paper's key property.

VMEM budget: 3 copies of the (ceil((H+w-1)/w)*w, BW) strip, at the
widened compute width (``core.types.vmem_dtype``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret
from repro.core.types import Array, as_op, check_window, vmem_dtype


def _scan_segments(segs, op, neutral, reverse: bool):
    """Inclusive prefix (or suffix) min/max within each length-w segment.

    Hillis-Steele doubling: after step s, F[t] covers segs[t-2s+1 .. t].
    Neutral-element fill keeps the scan confined to its segment.
    """
    nseg, w, bw = segs.shape
    out, s = segs, 1
    while s < w:
        if reverse:
            shifted = jnp.concatenate(
                [out[:, s:, :], jnp.full((nseg, s, bw), neutral, segs.dtype)], axis=1
            )
        else:
            shifted = jnp.concatenate(
                [jnp.full((nseg, s, bw), neutral, segs.dtype), out[:, :-s, :]], axis=1
            )
        out = op.reduce(out, shifted)
        s *= 2
    return out


def _vhgw_kernel(x_ref, o_ref, *, w: int, opname: str, nseg: int):
    op = as_op(opname)
    neutral = op.neutral(x_ref.dtype)
    h = o_ref.shape[0]
    bw = o_ref.shape[1]
    segs = x_ref[...].astype(vmem_dtype(x_ref.dtype)).reshape(nseg, w, bw)
    fwd = _scan_segments(segs, op, neutral, reverse=False).reshape(nseg * w, bw)
    bwd = _scan_segments(segs, op, neutral, reverse=True).reshape(nseg * w, bw)
    # out[i] = op(B[i], F[i + w - 1]): window [i, i+w-1] spans <= 2 segments.
    out = op.reduce(bwd[0:h, :], fwd[w - 1 : w - 1 + h, :])
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("w", "op", "block_w", "interpret"))
def morph_vhgw_sublane(
    x: Array,
    *,
    w: int,
    op: str = "min",
    block_w: int = 128,
    interpret: bool | None = None,
) -> Array:
    """vHGW running min/max of window ``w`` along axis -2 of a 2-D array."""
    interpret = resolve_interpret(interpret)
    w = check_window(w)
    mop = as_op(op)
    if x.ndim != 2:
        raise ValueError("kernel operates on (H, W); vmap for batches")
    h, wid = x.shape
    if w == 1:
        return x
    wing = (w - 1) // 2
    padded = h + 2 * wing
    nseg = -(-padded // w)
    extra = nseg * w - padded
    pw = -wid % block_w
    xp = jnp.pad(
        x,
        ((wing, wing + extra), (0, pw)),
        constant_values=mop.neutral(x.dtype),
    )
    grid = ((wid + pw) // block_w,)
    out = pl.pallas_call(
        functools.partial(_vhgw_kernel, w=w, opname=mop.name, nseg=nseg),
        grid=grid,
        in_specs=[pl.BlockSpec((nseg * w, block_w), lambda j: (0, j))],
        out_specs=pl.BlockSpec((h, block_w), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((h, wid + pw), x.dtype),
        interpret=interpret,
        name=f"morph_vhgw_{mop.name}",
    )(xp)
    return out[:, :wid]
