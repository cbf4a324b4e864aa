"""Fused separable 2-D morphology megakernel: one ``pallas_call`` per op.

The paper's core win (§4, §5.2) is that the vertical pass never sees data in
a slow layout: the transpose happens *inside the working set* via the VTRN
in-register ladder, so a full erode/dilate costs one read and one write of
the image. The previous TPU port lost exactly that — ``erode2d_tpu`` issued
two morphology ``pallas_call``s plus two full ``transpose_tiled`` kernels,
i.e. four HBM traversals. This kernel restores the paper's structure:

* grid ``(B, W/BW)`` — a leading batch dimension so ``(B, H, W)`` stacks run
  as one launch instead of ``vmap``-of-kernels;
* per grid cell, a haloed ``(H + w_h - 1, BW + w_w - 1)`` strip is assembled
  in VMEM from the center block plus a narrow pre-gathered halo block
  (``2 * wing_w`` columns per grid cell, rounded up to whole 128-lane
  tiles, built by one cheap XLA gather over
  ~``2*wing_w/BW`` of the image), so each cell reads ``BW + w_w - 1``
  columns — not three full blocks, and not a second HBM traversal;
* the sublane (H) pass runs first — linear ladder for small windows, vHGW
  Hillis-Steele scans for large, per ``DispatchPolicy`` thresholds;
* the block is transposed *inside the kernel* (``.T`` on the VMEM value —
  Mosaic's lane/sublane exchange, the TPU analog of the paper's VTRN ladder,
  i.e. ``transpose_tiled``'s in-tile trick without the HBM round trip);
* the lane-turned-sublane (W) pass runs, the block is transposed back, and
  the single output store happens.

HBM traffic per operator: ~(1 + w_w/BW) reads + 1 write versus 4 full
read+write round trips for the two-pass + double-transpose path.

Narrow integers (u8/u16/i8/i16) are widened to int32 once the blocks are
in VMEM and stored back in their own dtype (``core.types.vmem_dtype``):
Mosaic has no 8/16-bit integer min/max on TPU. Every block's last dimension
is a whole number of 128-lane tiles or the array's full extent, which is
Mosaic's block-shape rule; the halo block is padded up to whole lanes.

VMEM budget per grid cell (see DESIGN.md §5): the (Hp, BW) center block,
the (Hp, halo_cols) halo block, the assembled (Hp, BW + w_w - 1) strip, and
the transposed (BW + w_w - 1, H) scratch, the last two at the widened
compute width; ``_pick_block_w`` sizes BW against a 12 MB soft budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import DispatchPolicy, resolve_interpret
from repro.core.types import MAX, MIN, Array, as_op, check_window, vmem_dtype
from repro.kernels.morph_vhgw import _scan_segments


def _resolve_methods(se, method, policy: DispatchPolicy | None, dtype="uint8"):
    """Per-axis linear-vs-vHGW choice. Both fused passes are sublane passes
    (the W pass runs after the in-kernel transpose), and both work on a
    VMEM-resident strip, so the dedicated ``fused`` axis-kind cost curves
    apply — not the HBM-pass major/minor curves (see DESIGN.md §5). The
    query goes through the per-device cost model
    (``repro.morph.opt.cost.cost_model_for``); without a measured table it
    degrades to the policy's ``w <= w0_fused`` scalar branch exactly."""
    policy = policy or DispatchPolicy.calibrated()
    if method == "auto":
        from repro.morph.opt.cost import cost_model_for

        model = cost_model_for(policy)
        dt = jnp.dtype(dtype).name
        return tuple(
            model.best_method("fused", w, dt, small="linear") for w in se
        )
    if method in ("linear", "vhgw"):
        return (method, method)
    raise ValueError(f"fused kernel supports 'auto'|'linear'|'vhgw', got {method!r}")


def _vmem_pass(block, w: int, op, neutral, method: str, n_out: int):
    """Running min/max of window ``w`` along axis 0 of an in-VMEM value.

    ``block`` has ``n_out + w - 1`` rows (the haloed extent); returns
    ``n_out`` rows. Slices along sublanes are free offset reads of the same
    VMEM value, exactly like the two standalone kernels.
    """
    if w == 1:
        return block
    if method == "linear":
        val = block[0:n_out, :]
        for k in range(1, w):
            val = op.reduce(val, block[k : k + n_out, :])
        return val
    # vHGW: pad rows to a whole number of w-segments, then the forward /
    # backward Hillis-Steele scans of morph_vhgw, all inside VMEM.
    rows, cols = block.shape
    nseg = -(-rows // w)
    extra = nseg * w - rows
    if extra:
        block = jnp.concatenate(
            [block, jnp.full((extra, cols), neutral, block.dtype)], axis=0
        )
    segs = block.reshape(nseg, w, cols)
    fwd = _scan_segments(segs, op, neutral, reverse=False).reshape(nseg * w, cols)
    bwd = _scan_segments(segs, op, neutral, reverse=True).reshape(nseg * w, cols)
    return op.reduce(bwd[0:n_out, :], fwd[w - 1 : w - 1 + n_out, :])


def _load_strip(refs, wing_w: int):
    """Haloed strip (Hp, BW + 2*wing_w) of one padded view, widened to the
    VMEM compute dtype: the center block, plus, when the SE has a W wing,
    the lane-aligned halo block, whose first ``2*wing_w`` columns hold the
    left wing then the right wing (the rest is alignment padding)."""
    dt = vmem_dtype(refs[0].dtype)
    xc = refs[0][0].astype(dt)
    if not wing_w:
        return xc
    xh = refs[1][0].astype(dt)
    return jnp.concatenate([xh[:, :wing_w], xc, xh[:, wing_w : 2 * wing_w]], axis=1)


def _fused_pipeline(strip, *, w_h, w_w, op, neutral, method_h, method_w, h_out):
    """H pass -> in-VMEM transpose -> W pass -> transpose back."""
    y = _vmem_pass(strip, w_h, op, neutral, method_h, h_out)
    yt = y.T  # in-VMEM transpose: Mosaic's lane/sublane exchange (paper §4)
    bw = yt.shape[0] - (w_w - 1)
    z = _vmem_pass(yt, w_w, op, neutral, method_w, bw)
    return z.T


def _fused_kernel(*refs, w_h, w_w, opname, method_h, method_w, wing_w):
    *ins, o_ref = refs
    op = as_op(opname)
    out = _fused_pipeline(
        _load_strip(ins, wing_w), w_h=w_h, w_w=w_w, op=op,
        neutral=op.neutral(ins[0].dtype),
        method_h=method_h, method_w=method_w, h_out=o_ref.shape[1],
    )
    o_ref[0] = out.astype(o_ref.dtype)


def _gradient_kernel(*refs, w_h, w_w, method_h, method_w, wing_w):
    """Shared-load fused gradient: the min (erode) and max (dilate) pipelines
    run over the same haloed strip in one kernel; only the pad borders differ
    (each op needs its own neutral element), hence two padded views."""
    *ins, o_ref = refs
    n = len(ins) // 2
    h_out = o_ref.shape[1]
    e = _fused_pipeline(
        _load_strip(ins[:n], wing_w),
        w_h=w_h, w_w=w_w, op=MIN, neutral=MIN.neutral(ins[0].dtype),
        method_h=method_h, method_w=method_w, h_out=h_out,
    )
    d = _fused_pipeline(
        _load_strip(ins[n:], wing_w),
        w_h=w_h, w_w=w_w, op=MAX, neutral=MAX.neutral(ins[n].dtype),
        method_h=method_h, method_w=method_w, h_out=h_out,
    )
    o_ref[0] = d.astype(o_ref.dtype) - e.astype(o_ref.dtype)


_LANES = 128  # TPU lane width: a block's last dim must be a multiple of it


def _halo_cols(wing_w: int) -> int:
    """Per-cell halo block width: both wings, rounded up to whole lanes."""
    return -(-2 * wing_w // _LANES) * _LANES


def _pad_for_grid(x, wing_h: int, wing_w: int, block_w: int, neutral):
    """Neutral-pad (B, H, W) into the kernel's blocked inputs.

    Returns ``(arrays, in_specs, gw)``: the (B, Hp, gw * BW) center array,
    and, when the SE has a W wing, a halo array (B, Hp, gw * halo_cols)
    holding for each column block its left wing, its right wing, and
    padding up to whole lanes (Mosaic's block-shape rule). The halo is
    gathered by two strided reshapes, one cheap XLA pass over
    ~2*wing_w/BW of the image, and it is what lets every grid cell read
    BW + 2*wing_w columns instead of three full blocks.
    """
    b, _, wid = x.shape
    gw = -(-wid // block_w)
    pw = gw * block_w - wid
    xp = jnp.pad(
        x,
        ((0, 0), (wing_h, wing_h), (wing_w, pw + wing_w)),
        constant_values=neutral,
    )
    hp = xp.shape[1]
    core = xp[:, :, wing_w : wing_w + gw * block_w]
    specs = [pl.BlockSpec((1, hp, block_w), lambda bi, j: (bi, 0, j))]
    if wing_w == 0:
        return [core], specs, gw

    def wings(v):  # the first wing_w columns of each BW-wide block of v
        return v.reshape(b, hp, gw, block_w)[..., :wing_w]

    # block j's left wing starts at padded column j*BW, its right wing at
    # wing_w + (j+1)*BW; both read whole BW-blocks (wing_w <= BW)
    left = wings(xp[:, :, : gw * block_w])
    right = wings(jnp.pad(xp[:, :, wing_w + block_w :],
                          ((0, 0), (0, 0), (0, block_w - wing_w))))
    cols = _halo_cols(wing_w)
    halo = jnp.pad(
        jnp.concatenate([left, right], axis=-1),
        ((0, 0), (0, 0), (0, 0), (0, cols - 2 * wing_w)),
        constant_values=neutral,
    ).reshape(b, hp, gw * cols)
    specs.append(pl.BlockSpec((1, hp, cols), lambda bi, j: (bi, 0, j)))
    return [core, halo], specs, gw


_VMEM_SOFT_BUDGET = 12 * 2**20  # leave headroom under the ~16 MB/core VMEM
_MAX_AUTO_BLOCK_W = 512  # widest strip _pick_block_w will choose


def fused_supports(se) -> bool:
    """Whether the fused kernel's auto block sizing covers this SE's W-halo
    (the single capability predicate ops.py dispatches on)."""
    return (check_window(se[1]) - 1) // 2 <= _MAX_AUTO_BLOCK_W


def _pick_block_w(wing_w: int, h: int, w_h: int, dtype, strips: int) -> int:
    """Auto block width: widen the strip until the W-halo overhead
    ((BW + w_w - 1) / BW) is small, then shrink back while the estimated
    VMEM working set of ``strips`` pipelines exceeds the soft budget
    (DESIGN.md §5). Input blocks count at the input itemsize
    (double-buffered); the strip and its transpose count at the widened
    compute width (``core.types.vmem_dtype``)."""
    itemsize = strips * jnp.dtype(dtype).itemsize
    vmem_itemsize = strips * vmem_dtype(dtype).itemsize
    min_bw = _LANES
    while min_bw < wing_w:  # correctness floor: the halo must fit one block
        min_bw *= 2
    bw = min_bw
    while bw < _MAX_AUTO_BLOCK_W and wing_w > bw // 16:
        bw *= 2
    while bw > min_bw:
        hp = h + w_h - 1
        strip_w = bw + 2 * wing_w
        est = (2 * hp * (bw + _halo_cols(wing_w)) * itemsize
               + (hp * strip_w + 2 * strip_w * h) * vmem_itemsize)
        if est <= _VMEM_SOFT_BUDGET:
            break
        bw //= 2
    return bw


def _check_fusable(se, block_w: int | None) -> tuple[int, int]:
    w_h, w_w = (check_window(w) for w in se)
    if block_w is not None and (w_w - 1) // 2 > block_w:
        raise ValueError(
            f"fused kernel needs wing_w <= block_w ({(w_w - 1) // 2} > {block_w}); "
            "use the two-pass path (fused=False) for such wide SEs"
        )
    return w_h, w_w


@functools.partial(
    jax.jit,
    static_argnames=("se", "op", "method", "policy", "block_w", "interpret"),
)
def morph2d_fused(
    x: Array,
    se=(3, 3),
    *,
    op: str = "min",
    method: str = "auto",
    policy: DispatchPolicy | None = None,
    block_w: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """Separable 2-D erosion/dilation as a single ``pallas_call``.

    ``x`` is ``(H, W)`` or ``(B, H, W)``; batches run as a leading grid
    dimension, not ``vmap``-of-kernels. ``interpret=None`` resolves through
    ``core.dispatch.resolve_interpret`` (compiled on TPU).
    """
    interpret = resolve_interpret(interpret, policy)
    w_h, w_w = _check_fusable(se, block_w)
    mop = as_op(op)
    if x.ndim == 2:
        return morph2d_fused(
            x[None], se, op=mop.name, method=method, policy=policy,
            block_w=block_w, interpret=interpret,
        )[0]
    if x.ndim != 3:
        raise ValueError("morph2d_fused operates on (H, W) or (B, H, W)")
    if w_h == 1 and w_w == 1:
        return x
    b, h, wid = x.shape
    wing_h, wing_w = (w_h - 1) // 2, (w_w - 1) // 2
    if block_w is None:
        block_w = _pick_block_w(wing_w, h, w_h, x.dtype, strips=1)
    method_h, method_w = _resolve_methods((w_h, w_w), method, policy, x.dtype)
    arrays, specs, gw = _pad_for_grid(
        x, wing_h, wing_w, block_w, mop.neutral(x.dtype)
    )
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, w_h=w_h, w_w=w_w, opname=mop.name,
            method_h=method_h, method_w=method_w, wing_w=wing_w,
        ),
        grid=(b, gw),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, h, block_w), lambda bi, j: (bi, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, h, gw * block_w), x.dtype),
        interpret=interpret,
    )(*arrays)
    return out[:, :, :wid]


@functools.partial(
    jax.jit,
    static_argnames=("se", "method", "policy", "block_w", "interpret"),
)
def gradient2d_fused(
    x: Array,
    se=(3, 3),
    *,
    method: str = "auto",
    policy: DispatchPolicy | None = None,
    block_w: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """Fused 2-D morphological gradient (dilate - erode) in one launch.

    Both pipelines run over the strip inside one kernel, but two padded
    views of the image are shipped (erode and dilate need different neutral
    border values), so the cost is 2 reads + 1 write — versus ~9 traversals
    for two-pass dilate/erode plus the subtraction. Integer inputs widen to
    int32 (i8 differences overflow i8), floats keep their dtype.
    """
    interpret = resolve_interpret(interpret, policy)
    w_h, w_w = _check_fusable(se, block_w)
    if x.ndim == 2:
        return gradient2d_fused(
            x[None], se, method=method, policy=policy,
            block_w=block_w, interpret=interpret,
        )[0]
    if x.ndim != 3:
        raise ValueError("gradient2d_fused operates on (H, W) or (B, H, W)")
    out_dtype = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else x.dtype
    if w_h == 1 and w_w == 1:
        return jnp.zeros_like(x, dtype=out_dtype)
    b, h, wid = x.shape
    wing_h, wing_w = (w_h - 1) // 2, (w_w - 1) // 2
    if block_w is None:
        # gradient holds two strips (min and max pipelines)
        block_w = _pick_block_w(wing_w, h, w_h, x.dtype, strips=2)
    method_h, method_w = _resolve_methods((w_h, w_w), method, policy, x.dtype)
    arrays_min, specs, gw = _pad_for_grid(
        x, wing_h, wing_w, block_w, MIN.neutral(x.dtype)
    )
    arrays_max, _, _ = _pad_for_grid(
        x, wing_h, wing_w, block_w, MAX.neutral(x.dtype)
    )
    out = pl.pallas_call(
        functools.partial(
            _gradient_kernel, w_h=w_h, w_w=w_w,
            method_h=method_h, method_w=method_w, wing_w=wing_w,
        ),
        grid=(b, gw),
        in_specs=specs + specs,
        out_specs=pl.BlockSpec((1, h, block_w), lambda bi, j: (bi, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, h, gw * block_w), out_dtype),
        interpret=interpret,
    )(*arrays_min, *arrays_max)
    return out[:, :, :wid]
