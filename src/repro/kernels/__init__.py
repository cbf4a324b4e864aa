"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel file pairs with a pure-jnp oracle in ref.py; ops.py exposes the
jit'd hybrid dispatch API. The 2-D operators default to the fused
single-``pallas_call`` megakernel (morph_fused.py). Every entry point
defaults to ``interpret=None``: compiled Mosaic on a TPU, the Pallas
interpreter on CPU (``core.dispatch.resolve_interpret``).
"""
from repro.kernels.fused_gradient import gradient_linear_sublane
from repro.kernels.morph_fused import gradient2d_fused, morph2d_fused
from repro.kernels.morph_linear import morph_linear_sublane
from repro.kernels.morph_vhgw import morph_vhgw_sublane
from repro.kernels.ops import (
    closing2d_tpu,
    dilate2d_tpu,
    erode2d_tpu,
    gradient_1d_tpu,
    gradient2d_tpu,
    morph_1d_tpu,
    opening2d_tpu,
)
from repro.kernels.transpose import transpose_tiled
