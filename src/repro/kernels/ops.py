"""Jit'd public wrappers over the Pallas kernels (the paper's §5.3 hybrid).

``morph_1d_tpu`` selects:

* algorithm — ``linear`` kernel for small windows, ``vhgw`` kernel for
  large ones (paper's w0 dispatch; thresholds from core.dispatch policy);
* axis strategy — the sublane (-2) axis runs natively; the lane (-1) axis
  runs as transpose-kernel -> sublane pass -> transpose-kernel, the paper's
  §5.2 transpose trick (or an XLA transpose, selectable, for §Perf A/B).

The 2-D operators (``erode2d_tpu`` / ``dilate2d_tpu`` / ``opening2d_tpu`` /
``closing2d_tpu`` / ``gradient2d_tpu``) are thin wrappers over the
morphology expression IR: each builds its graph (``repro.morph.expr``) and
lowers it through ``repro.morph.lower_kernel``, whose primitives are
``raw_morph2d`` / ``raw_gradient2d`` below — the fused megakernel
(kernels/morph_fused.py, one ``pallas_call`` doing H pass -> in-VMEM
transpose -> W pass) when the policy and SE allow, the legacy two-pass +
double-transpose pipeline otherwise. The lowering recognizes the
``Sub(Dilate, Erode)`` gradient pattern and emits the single-launch fused
gradient kernel.

.. deprecated:: the per-call ``fused=`` / ``method=`` / ``lane_strategy=``
    kwargs. Every dispatch decision now lives on :class:`DispatchPolicy`
    (``fused_2d`` / ``method`` / ``lane_strategy`` / ``interpret``); the
    kwargs keep working as shims that fold into the policy
    (``DispatchPolicy.with_overrides``) so A/B harnesses and old callers
    don't break.

All entry points accept ``interpret=``; the default ``None`` defers to the
single resolver (``core.dispatch.resolve_interpret``): explicit argument >
``DispatchPolicy.interpret`` > ``REPRO_PALLAS_INTERPRET`` env var > backend
default (compiled on TPU, interpret elsewhere) — so CPU CI validates the
same code that targets TPU without production ever silently running
interpreted Pallas.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.dispatch import DispatchPolicy, resolve_interpret
from repro.core.types import Array, as_op, check_window, widen_dtype, widened_sub
from repro.kernels.fused_gradient import gradient_linear_sublane
from repro.kernels.morph_fused import fused_supports, gradient2d_fused, morph2d_fused
from repro.kernels.morph_linear import morph_linear_sublane
from repro.kernels.morph_vhgw import morph_vhgw_sublane
from repro.kernels.transpose import transpose_tiled
from repro.morph.expr import X
from repro.morph.lower_kernel import lower_kernel

LaneStrategy = Literal["transpose_kernel", "xla"]


def _sublane_pass(x, w, op, method, policy: DispatchPolicy, interpret):
    if method == "auto":
        method = "linear" if w <= policy.w0_major else "vhgw"
    elif method != "vhgw":
        # linear_tree / linear_paired are jnp-only variants; the linear
        # ladder kernel is their analog here (same family, same crossover
        # side), so a forced linear-family method stays linear-family
        # instead of silently flipping to vHGW.
        method = "linear"
    fn = morph_linear_sublane if method == "linear" else morph_vhgw_sublane
    return fn(x, w=w, op=op, interpret=interpret)


def morph_1d_tpu(
    x: Array,
    w: int,
    *,
    axis: int = -2,
    op: str = "min",
    method: str = "auto",
    lane_strategy: LaneStrategy | None = None,
    policy: DispatchPolicy | None = None,
    interpret: bool | None = None,
) -> Array:
    """Kernel-backed running min/max along ``axis`` of a 2-D array."""
    w = check_window(w)
    op = as_op(op).name
    policy = policy or DispatchPolicy.calibrated()
    interpret = resolve_interpret(interpret, policy)
    if lane_strategy is None:
        lane_strategy = policy.lane_strategy
    if x.ndim != 2:
        raise ValueError("morph_1d_tpu operates on (H, W); vmap for batches")
    axis = axis % 2
    if w == 1:
        return x
    if axis == 0:  # sublane axis: native
        return _sublane_pass(x, w, op, method, policy, interpret)
    # lane axis: paper's transpose trick
    if lane_strategy == "transpose_kernel":
        t = transpose_tiled(x, interpret=interpret)
        t = _sublane_pass(t, w, op, method, policy, interpret)
        return transpose_tiled(t, interpret=interpret)
    xt = jnp.swapaxes(x, 0, 1)
    out = _sublane_pass(xt, w, op, method, policy, interpret)
    return jnp.swapaxes(out, 0, 1)


def _morph2d_two_pass(x, se, op, policy, interpret):
    if x.ndim == 3:  # the fused path's batch grid has no two-pass analog
        return jax.vmap(
            lambda m: _morph2d_two_pass(m, se, op, policy, interpret)
        )(x)
    w_h, w_w = se
    y = morph_1d_tpu(
        x, w_h, axis=0, op=op, method=policy.method,
        lane_strategy=policy.lane_strategy, policy=policy, interpret=interpret,
    )
    return morph_1d_tpu(
        y, w_w, axis=1, op=op, method=policy.method,
        lane_strategy=policy.lane_strategy, policy=policy, interpret=interpret,
    )


def _fused_method(policy: DispatchPolicy) -> str:
    # the fused kernel knows only the linear/vhgw pair; forced linear-family
    # variants (linear_tree/linear_paired) map to its linear ladder — the
    # same coercion _sublane_pass applies, so both kernel paths honor the
    # policy's family even when the exact jnp variant has no kernel analog
    if policy.method in ("auto", "linear", "vhgw"):
        return policy.method
    return "linear"


def _fused_wins(se, dtype, policy: DispatchPolicy, *, gradient: bool = False) -> bool:
    """Per-node fused-vs-two-pass decision from the per-device cost model.

    With no measured ``cost_table.json`` (or a hand-tuned policy) the
    analytic model always answers True, preserving the historical
    ``policy.fused_2d``-only dispatch; a measured table lets a device where
    the two-pass pipeline wins for some SE/dtype route just those nodes."""
    from repro.morph.opt.cost import cost_model_for

    return cost_model_for(policy).fused_wins(
        se, jnp.dtype(dtype).name, gradient=gradient
    )


def raw_morph2d(
    x: Array, se, op: str, *, policy: DispatchPolicy, interpret: bool | None = None
) -> Array:
    """Backend primitive for the kernel lowering: fused megakernel when the
    policy, the SE, and the per-node cost model allow; two-pass + transpose
    pipeline otherwise."""
    interpret = resolve_interpret(interpret, policy)
    if x.dtype == jnp.bool_:
        # Mosaic has no boolean blocks; min/max on the 0/1 u8 image is the
        # boolean AND/OR bit for bit, so widen once here for both routes
        return raw_morph2d(
            x.astype(jnp.uint8), se, op, policy=policy, interpret=interpret
        ).astype(jnp.bool_)
    if (
        policy.fused_2d
        and fused_supports(se)
        and x.ndim in (2, 3)
        and _fused_wins(se, x.dtype, policy)
    ):
        return morph2d_fused(
            x, tuple(se), op=op, method=_fused_method(policy),
            policy=policy, interpret=interpret,
        )
    return _morph2d_two_pass(x, se, op, policy, interpret)


def raw_gradient2d(
    x: Array, se, *, policy: DispatchPolicy, interpret: bool | None = None
) -> Array:
    """Backend primitive for the gradient pattern: the shared-strip fused
    gradient kernel, or two-pass dilate/erode plus a widened subtraction."""
    interpret = resolve_interpret(interpret, policy)
    if x.dtype == jnp.bool_:
        # a boolean gradient is defined in the widened dtype anyway
        # (core.types.widen_dtype), and the fused kernel's in-kernel sub has
        # no boolean form — lattice ops on the widened 0/1 image are
        # bit-identical, so widen once up front
        x = x.astype(widen_dtype(x.dtype))
    if (
        policy.fused_2d
        and fused_supports(se)
        and x.ndim in (2, 3)
        and _fused_wins(se, x.dtype, policy, gradient=True)
    ):
        return gradient2d_fused(
            x, tuple(se), method=_fused_method(policy),
            policy=policy, interpret=interpret,
        )
    two = dataclasses.replace(policy, fused_2d=False)
    d = raw_morph2d(x, se, "max", policy=two, interpret=interpret)
    e = raw_morph2d(x, se, "min", policy=two, interpret=interpret)
    return widened_sub(d, e)


def _folded_policy(policy, fused, method, lane_strategy, interpret) -> DispatchPolicy:
    policy = policy or DispatchPolicy.calibrated()
    return policy.with_overrides(
        fused=fused, method=method, lane_strategy=lane_strategy, interpret=interpret
    )


def _run2d(expr, x, policy, fused, method, lane_strategy, interpret) -> Array:
    policy = _folded_policy(policy, fused, method, lane_strategy, interpret)
    return lower_kernel(expr, policy=policy)(x)


def erode2d_tpu(
    x: Array,
    se=(3, 3),
    *,
    fused: bool | None = None,
    method: str = "auto",
    lane_strategy: LaneStrategy | None = None,
    policy: DispatchPolicy | None = None,
    interpret: bool | None = None,
) -> Array:
    """2-D erosion: ``lower_kernel(X.erode(se))`` — one fused
    ``pallas_call`` by default (``fused=False`` selects two-pass for A/B)."""
    return _run2d(X.erode(se), x, policy, fused, method, lane_strategy, interpret)


def dilate2d_tpu(
    x: Array,
    se=(3, 3),
    *,
    fused: bool | None = None,
    method: str = "auto",
    lane_strategy: LaneStrategy | None = None,
    policy: DispatchPolicy | None = None,
    interpret: bool | None = None,
) -> Array:
    """2-D dilation: ``lower_kernel(X.dilate(se))``."""
    return _run2d(X.dilate(se), x, policy, fused, method, lane_strategy, interpret)


def opening2d_tpu(
    x: Array,
    se=(3, 3),
    *,
    fused: bool | None = None,
    method: str = "auto",
    lane_strategy: LaneStrategy | None = None,
    policy: DispatchPolicy | None = None,
    interpret: bool | None = None,
) -> Array:
    """Erode then dilate: two fused launches by default (was eight)."""
    return _run2d(X.opening(se), x, policy, fused, method, lane_strategy, interpret)


def closing2d_tpu(
    x: Array,
    se=(3, 3),
    *,
    fused: bool | None = None,
    method: str = "auto",
    lane_strategy: LaneStrategy | None = None,
    policy: DispatchPolicy | None = None,
    interpret: bool | None = None,
) -> Array:
    """Dilate then erode: two fused launches by default (was eight)."""
    return _run2d(X.closing(se), x, policy, fused, method, lane_strategy, interpret)


def gradient2d_tpu(
    x: Array,
    se=(3, 3),
    *,
    fused: bool | None = None,
    method: str = "auto",
    lane_strategy: LaneStrategy | None = None,
    policy: DispatchPolicy | None = None,
    interpret: bool | None = None,
) -> Array:
    """2-D morphological gradient (dilate - erode, widened for integers).

    ``X.gradient(se)`` is a ``Sub(Dilate, Erode)`` over a shared child; the
    kernel lowering pattern-matches it into the fused gradient kernel (one
    launch sharing the haloed strip between both pipelines) when the policy
    allows, and otherwise into the two-pass pair plus a widened subtraction
    — the same centralized rule (``core.types.widened_sub``) every gradient
    path in the repo now shares.
    """
    return _run2d(X.gradient(se), x, policy, fused, method, lane_strategy, interpret)


def gradient_1d_tpu(
    x: Array, w: int, *, axis: int = -2, interpret: bool | None = None
) -> Array:
    """Fused 1-D morphological gradient (beyond-paper kernel)."""
    w = check_window(w)
    interpret = resolve_interpret(interpret)
    if x.ndim != 2:
        raise ValueError("gradient_1d_tpu operates on (H, W); vmap for batches")
    if axis % 2 == 0:
        return gradient_linear_sublane(x, w=w, interpret=interpret)
    t = transpose_tiled(x, interpret=interpret)
    g = gradient_linear_sublane(t, w=w, interpret=interpret)
    return transpose_tiled(g, interpret=interpret)
