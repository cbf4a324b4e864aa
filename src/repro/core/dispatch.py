"""Hybrid dispatch between the linear and vHGW 1-D passes (paper §5.3).

The paper measures crossover windows w_x0 = 59 and w_y0 = 69 on Exynos 5422
and selects the linear implementation below the crossover, vHGW+SIMD above.
The two thresholds differ because the two passes touch memory differently —
the same asymmetry exists on TPU, where the lane (minor) axis pays a
lane-roll per shifted operand while the sublane axis does not.

Here the thresholds are a :class:`DispatchPolicy` value: defaults come from
the CPU calibration run (benchmarks/bench_hybrid.py writes
``calibration.json``), and an analytic TPU estimate is documented in
EXPERIMENTS.md. The policy is a static (trace-time) decision, like the
paper's branch — no runtime cost.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.linear_pass import linear_1d, linear_1d_paired, linear_1d_tree
from repro.core.types import Array, as_op, check_window
from repro.core.vhgw import vhgw_1d

Method = Literal["auto", "linear", "linear_paired", "linear_tree", "vhgw"]

_CALIBRATION_FILE = os.path.join(os.path.dirname(__file__), "calibration.json")

# calibrated() memo: {"policy": ((calib_mtime, cost_mtime), DispatchPolicy)}
_CALIBRATED_CACHE: dict = {}


def _file_mtime(path: str):
    try:
        return os.stat(path).st_mtime_ns
    except OSError:
        return None


def _cost_table_mtime():
    from repro.morph.opt.cost import COST_TABLE_FILE

    return _file_mtime(COST_TABLE_FILE)

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def resolve_interpret(
    interpret: bool | None, policy: "DispatchPolicy | None" = None
) -> bool:
    """Single resolver for the Pallas ``interpret`` flag.

    Precedence: explicit argument > ``DispatchPolicy.interpret`` >
    ``REPRO_PALLAS_INTERPRET`` env var > backend default (compiled Mosaic on
    TPU, interpret elsewhere). Every kernel entry point defaults to
    ``interpret=None`` and calls this, so nothing interprets on a TPU by
    default; asking for interpret mode on a TPU is an error, not a slow
    fallback that would hide the device.
    """
    if interpret is None and policy is not None:
        interpret = policy.interpret
    if interpret is None:
        env = os.environ.get(INTERPRET_ENV)
        if env is not None:
            interpret = env.strip().lower() not in ("0", "false", "no", "off", "")
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpreted Pallas requested on a TPU backend (argument, policy "
            f"or {INTERPRET_ENV}); the kernels compile there"
        )
    return bool(interpret)


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """Crossover thresholds per axis kind.

    ``w0_minor``: threshold for passes along the minormost (lane) axis.
    ``w0_major``: threshold for passes along any other (sublane/batch) axis.
    Both mirror the paper's (w_x0, w_y0) pair.

    ``fused_2d``: whether the kernel-backed 2-D operators run as the fused
    single-``pallas_call`` megakernel (kernels/morph_fused.py — one HBM read
    + one write per operator) or as the legacy two-pass + double-transpose
    pipeline (four HBM traversals; kept for A/B and for SEs too wide for the
    fused halo). Like the method thresholds this is a trace-time decision.
    """

    w0_minor: int = 15
    w0_major: int = 31
    small_method: Method = "linear_tree"  # beyond-paper default; paper used "linear"
    # IR optimizer level applied by the lowerings (repro.morph.opt.optimize):
    # 0 = off, 1 = structural passes (CSE / folding / dead-output elim /
    # gradient canonicalization), 2 = plus cost-model-driven SE
    # decomposition. Part of the policy so serving cache keys capture it and
    # so callers opt out per call site (DispatchPolicy(opt_level=0)).
    opt_level: int = 2
    fused_2d: bool = True
    # Pallas interpret-mode override: None defers to the env var / backend
    # default (see resolve_interpret). Part of the policy so serving cache
    # keys capture it.
    interpret: bool | None = None
    # Force a specific 1-D algorithm for every pass ("auto" = threshold
    # dispatch via w0_*/small_method). Collapses the old per-call ``method=``
    # kwarg into the policy, so cache keys capture it too. The Pallas paths
    # implement only the linear/vhgw pair; a forced linear_tree/linear_paired
    # runs the kernels' linear ladder there (nearest same-family analog).
    method: Method = "auto"
    # Lane-axis strategy for the two-pass kernel pipeline: the paper's §5.2
    # transpose-kernel sandwich or an XLA transpose (§Perf A/B). Collapses
    # the old per-call ``lane_strategy=`` kwarg.
    lane_strategy: str = "transpose_kernel"  # "transpose_kernel" | "xla"
    # Crossover for passes inside the fused megakernel. Much higher than
    # w0_major: the fused linear ladder is slice-reductions over a
    # VMEM-resident strip that the compiler fuses into one loop nest, while
    # the vHGW doubling scans materialize a full strip per step — measured
    # crossover ~255 on the CPU-interpret harness (DESIGN.md §5); expected
    # to drop when recalibrated on real TPU Mosaic lowering.
    w0_fused: int = 255

    def cache_token(self) -> tuple:
        """Stable, hashable fingerprint of every dispatch-relevant field.

        The serving layer keys its executable cache on this (alongside
        bucket/dtype/op), so two policies that compile identically share an
        executable and any differing field forces a fresh compile.
        """
        return tuple(
            (f.name, getattr(self, f.name)) for f in dataclasses.fields(self)
        )

    def with_overrides(
        self,
        *,
        fused: bool | None = None,
        method: "Method | None" = None,
        lane_strategy: str | None = None,
        interpret: bool | None = None,
    ) -> "DispatchPolicy":
        """Fold the deprecated per-call kwargs into a policy value.

        The kernel entry points (``kernels/ops.py``) and ``core.morphology``
        keep their old ``fused=`` / ``method=`` / ``lane_strategy=`` /
        ``interpret=`` keywords as shims; each non-default value becomes the
        corresponding policy field so one ``DispatchPolicy`` carries every
        dispatch decision (``method="auto"`` and ``None`` mean "no change").
        """
        changes: dict = {}
        if fused is not None:
            changes["fused_2d"] = bool(fused)
        if method is not None and method != "auto":
            changes["method"] = method
        if lane_strategy is not None:
            changes["lane_strategy"] = lane_strategy
        if interpret is not None:
            changes["interpret"] = bool(interpret)
        return dataclasses.replace(self, **changes) if changes else self

    @classmethod
    def paper(cls) -> "DispatchPolicy":
        """Thresholds as published for Exynos 5422 + NEON."""
        return cls(w0_minor=59, w0_major=69, small_method="linear")

    @classmethod
    def calibrated(cls) -> "DispatchPolicy":
        """The machine-local policy, memoized on calibration-file mtimes.

        Thresholds come from the measured per-device cost table
        (``cost_table.json``, fit by ``bench_hybrid --fit-cost-table``) when
        one exists for this device — its fitted curves imply the crossovers
        — else from the scalar ``calibration.json``, else the defaults.
        This used to re-``os.path.exists`` + ``json.load`` on *every*
        ``morph_1d`` call; now a stat comparison is the steady-state cost
        and a refit (new mtime) invalidates the cache.
        """
        mt = (_file_mtime(_CALIBRATION_FILE), _cost_table_mtime())
        cached = _CALIBRATED_CACHE.get("policy")
        if cached is not None and cached[0] == mt:
            return cached[1]
        policy = cls._load_calibrated()
        _CALIBRATED_CACHE["policy"] = (mt, policy)
        return policy

    @classmethod
    def _load_calibrated(cls) -> "DispatchPolicy":
        kw: dict = {}
        if os.path.exists(_CALIBRATION_FILE):
            with open(_CALIBRATION_FILE) as f:
                d = json.load(f)
            kw = dict(
                w0_minor=int(d.get("w0_minor", cls.w0_minor)),
                w0_major=int(d.get("w0_major", cls.w0_major)),
                small_method=d.get("small_method", "linear_tree"),
                fused_2d=bool(d.get("fused_2d", True)),
                w0_fused=int(d.get("w0_fused", cls.w0_fused)),
            )
        # the measured cost table, when present for this device, supersedes
        # the scalar calibration: its curves *imply* the crossovers
        from repro.morph.opt.cost import load_measured

        measured = load_measured()
        if measured is not None:
            for field in ("w0_minor", "w0_major", "w0_fused", "small_method"):
                if field in measured.crossovers:
                    v = measured.crossovers[field]
                    kw[field] = v if field == "small_method" else int(v)
        return cls(**kw)


_METHODS = {
    "linear": linear_1d,
    "linear_paired": linear_1d_paired,
    "linear_tree": linear_1d_tree,
    "vhgw": vhgw_1d,
}


def morph_1d(
    x: Array,
    w: int,
    *,
    axis: int = -1,
    op="min",
    method: Method = "auto",
    policy: DispatchPolicy | None = None,
) -> Array:
    """1-D running min/max with hybrid method selection.

    ``method="auto"`` consults the per-device cost model
    (``repro.morph.opt.cost``): measured per-(axis kind, method, dtype)
    curves when a fitted ``cost_table.json`` matches the policy, else the
    analytic model built from the policy's own thresholds — which
    reproduces the historical ``w <= w0`` branch exactly.
    """
    op = as_op(op)
    w = check_window(w)
    if method == "auto":
        policy = policy or DispatchPolicy.calibrated()
        if policy.method != "auto":
            method = policy.method
        else:
            from repro.morph.opt.cost import cost_model_for

            kind = "minor" if (axis % x.ndim) == x.ndim - 1 else "major"
            method = cost_model_for(policy).best_method(
                kind, w, jnp.dtype(x.dtype).name, small=policy.small_method
            )
    return _METHODS[method](x, w, axis=axis, op=op)
