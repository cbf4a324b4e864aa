"""Shared type helpers for the morphology core.

The paper works on 8-bit unsigned images. On TPU we additionally support
int8 / bfloat16 / float32 so the same primitives can be reused on masks,
spectrograms and feature maps. Every algorithm in this package is expressed
in terms of an associative, commutative, idempotent reduction ``op`` (min or
max) together with its *neutral element*, which is what the paper's
"process edges separately" becomes in a branch-free padded formulation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray
Op = Callable[[Array, Array], Array]


@dataclasses.dataclass(frozen=True)
class MorphOp:
    """A lattice operation (min for erosion, max for dilation)."""

    name: str
    reduce: Op

    def neutral(self, dtype) -> np.generic:
        dtype = jnp.dtype(dtype)
        if dtype == jnp.bool_:
            # Boolean lattice: erosion (min/AND) is neutral on True, dilation
            # (max/OR) on False — the binary-mask case the RLE backend serves.
            return np.bool_(self.name == "min")
        if jnp.issubdtype(dtype, jnp.floating):
            inf = np.array(np.inf, dtype=dtype)
            return inf if self.name == "min" else -inf
        info = jnp.iinfo(dtype)
        return np.array(info.max if self.name == "min" else info.min, dtype=dtype)


MIN = MorphOp("min", jnp.minimum)
MAX = MorphOp("max", jnp.maximum)


def as_op(name_or_op) -> MorphOp:
    if isinstance(name_or_op, MorphOp):
        return name_or_op
    if name_or_op in ("min", "erode", "erosion"):
        return MIN
    if name_or_op in ("max", "dilate", "dilation"):
        return MAX
    raise ValueError(f"unknown morphological op: {name_or_op!r}")


def check_window(w: int) -> int:
    """Windows are odd (anchor at center), per the paper's 2*wing+1 form."""
    w = int(w)
    if w < 1 or w % 2 == 0:
        raise ValueError(f"structuring-element extent must be odd and >= 1, got {w}")
    return w


def widen_dtype(dtype) -> jnp.dtype:
    """Dtype in which morphological differences are computed.

    Integer images widen to ``promote_types(dtype, int32)`` (an i8/u8
    difference overflows its own type); floats keep their dtype. This is the
    single source of truth for the widening rule that used to be copied in
    ``core.morphology.gradient``, ``kernels.ops.gradient2d_tpu`` and the
    serving-plan gradient step.
    """
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bool_:
        # bool is not an integer subdtype, but a boolean difference is not a
        # bool either (gradient of a mask counts 0/1 edges): widen like the
        # narrow integers do.
        return jnp.dtype(jnp.int32)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.promote_types(dtype, jnp.int32)
    return dtype


def vmem_dtype(dtype) -> jnp.dtype:
    """Dtype the Pallas kernels run min/max in once a block is in VMEM.

    Mosaic has no 8- or 16-bit integer min/max on TPU (``arith.minui`` /
    ``arith.minsi`` fail to legalize), so narrow integers widen to int32 in
    VMEM and are stored back in their own dtype. The widening is
    order-preserving, so results stay bit-exact; HBM traffic is unchanged.
    """
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer) and dtype.itemsize < 4:
        return jnp.dtype(jnp.int32)
    return dtype


def widened_sub(a: Array, b: Array) -> Array:
    """``a - b`` computed (and returned) in ``widen_dtype`` of the inputs."""
    wide = widen_dtype(jnp.result_type(a, b))
    return a.astype(wide) - b.astype(wide)
