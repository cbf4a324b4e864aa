"""Calibrated per-device cost model for morphology dispatch.

The paper picks linear-vs-vHGW per pass from a *measured* crossover (§5.3);
until now that insight lived in three hand-edited scalars on
``DispatchPolicy`` (``w0_minor`` / ``w0_major`` / ``w0_fused``). This module
replaces the scalars with per-``(axis kind, method, dtype)`` affine cost
curves fit from real sweeps:

    cost_us(w) = c0 + c1 * feature(method, w)

where the feature is the method's complexity driver — ``w`` for the linear
accumulator ladder, ``ceil(log2 w)`` for the doubling tree, ``w^2`` for
vHGW (amortized-flat in theory, but its strided reshapes bend upward with
``w`` in practice, and that convexity is what makes SE decomposition
winnable — see :func:`feature`). The intercept ``c0`` is the per-pass overhead
(launch + padding + layout), which is exactly the term that decides whether
decomposing one large-window pass into k small ones can ever win.

Tables are fit by ``python -m benchmarks.bench_hybrid --fit-cost-table`` and
persisted in ``cost_table.json`` next to ``calibration.json``, keyed by JAX
device kind so a checkout shared between a laptop and a TPU host keeps one
table per device. Loading is memoized on file mtime.

When no table exists (or a policy carries hand-set thresholds that disagree
with the measured crossovers) the **analytic fallback** reconstructs cost
curves *from the policy's own thresholds*, so every consumer below degrades
to exactly the historical scalar-threshold behavior:

* ``best_method`` — queried by ``core.dispatch.morph_1d`` (axis kinds
  ``major``/``minor``) and the fused megakernel's per-axis choice
  (axis kind ``fused``, replacing the bare ``w <= w0_fused`` branch);
* ``fused_wins`` — the per-node fused-vs-two-pass decision in
  ``kernels.ops.raw_morph2d`` / ``raw_gradient2d``;
* ``decompose`` — the optimizer's SE-decomposition pass (a large-window
  primitive as k iterated small-window primitives), the paper's hybrid
  insight promoted from a runtime branch to a graph rewrite.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

import jax

from repro.core.dispatch import DispatchPolicy

COST_TABLE_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "core",
    "cost_table.json",
)

AXIS_KINDS = ("major", "minor", "fused", "collective", "repr")
_SMALL_METHODS = ("linear", "linear_paired", "linear_tree")
# Methods under the "collective" axis kind (sharded execution, repro.shard):
# affine in *elements moved*, fit by bench_shard --fit-collective.
COLLECTIVE_METHODS = ("ppermute", "all_to_all")
# Methods under the "repr" axis kind (representation choice for boolean
# plans, repro.rle): "rle" is affine in the *run count*, "dense" in the
# *pixel count* — the drivers differ per method, which is the whole point
# of the axis. Fit by bench_rle --fit-cost-table.
REPR_METHODS = ("rle", "dense")


def feature(method: str, w: int) -> float:
    """The per-method complexity driver the affine cost model is linear in.

    ``linear``/``linear_paired`` walk the window (feature ``w``);
    ``linear_tree`` is a doubling ladder (``ceil(log2 w)``). vHGW is
    amortized O(1) per element in theory, but its strided segment reshapes
    bend measurably upward with ``w`` on both backends — and *convexity* is
    the one thing that can make an iterated-small-SE schedule beat a single
    large pass (affine-in-``w`` curves are subadditive over Minkowski
    composition, so they provably never decompose). The quadratic feature
    lets a fit capture that bend where it is real; flat sweeps simply fit
    ``c1 ~ 0`` and decomposition stays off.
    """
    if w <= 1:
        return 0.0
    if method == "linear_tree":
        return float(math.ceil(math.log2(w)))
    if method == "vhgw":
        return float(w) * float(w)
    # linear / linear_paired accumulator ladders (driver: window), and the
    # collective methods (driver: elements moved — callers pass elems as w)
    return float(w)


def fit_affine(points) -> tuple[float, float]:
    """Least-squares ``(c0, c1)`` for ``t = c0 + c1 * f`` over ``(f, t)``
    pairs; degenerate sweeps (single distinct feature) fit a constant."""
    pts = [(float(f), float(t)) for f, t in points]
    if not pts:
        raise ValueError("cannot fit a cost curve from zero samples")
    n = len(pts)
    mf = sum(f for f, _ in pts) / n
    mt = sum(t for _, t in pts) / n
    var = sum((f - mf) ** 2 for f, _ in pts)
    if var == 0.0:
        return mt, 0.0
    c1 = sum((f - mf) * (t - mt) for f, t in pts) / var
    return mt - c1 * mf, c1


def device_kind() -> str:
    """Cost tables are keyed by this (e.g. ``cpu``, ``TPU v5 lite``). A
    backend that fails to start raises: a table keyed by a guess would
    steer dispatch on the wrong device."""
    return str(jax.devices()[0].device_kind)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-(axis kind, method, dtype) affine cost curves, in microseconds.

    ``entries`` maps ``(kind, method, dtype_name) -> (c0, c1)``; lookups
    fall back dtype -> ``uint8`` -> analytic-from-thresholds, so a table fit
    only on the paper's u8 image still covers every dtype. ``crossovers``
    records the thresholds the curves imply (what ``calibrated()`` adopts);
    ``source`` is ``"measured"`` or ``"analytic"``.
    """

    entries: "dict[tuple[str, str, str], tuple[float, float]]"
    crossovers: "dict[str, object]"
    source: str = "analytic"
    # measured whole-op 2-D costs: (path, dtype) -> (c0, c1) affine in h+w,
    # path in {"fused", "two_pass", "gradient_fused", "gradient_two_pass"}
    op2d: "dict[tuple[str, str], tuple[float, float]]" = dataclasses.field(
        default_factory=dict
    )

    # ------------------------------------------------------------ construction
    @classmethod
    def analytic(cls, policy: DispatchPolicy | None = None) -> "CostModel":
        """Cost curves reconstructed from a policy's thresholds.

        Normalized so the small method and vHGW cost exactly 1.0 at the
        threshold (ties prefer the small method), reproducing the historical
        ``w <= w0`` dispatch bit-for-bit. Intercepts are zero: with no
        measured per-pass overhead, k small passes always cost more than one
        large pass, so the analytic model never decomposes and always says
        the fused kernel wins — the pre-cost-model defaults.
        """
        policy = policy or DispatchPolicy.calibrated()
        entries: dict[tuple[str, str, str], tuple[float, float]] = {}
        for kind, w0 in (
            ("major", policy.w0_major),
            ("minor", policy.w0_minor),
            ("fused", policy.w0_fused),
        ):
            small = policy.small_method if kind != "fused" else "linear"
            f0 = max(feature(small, int(w0)), 1.0)
            entries[(kind, small, "uint8")] = (0.0, 1.0 / f0)
            entries[(kind, "vhgw", "uint8")] = (1.0, 0.0)
            # the fused kernel only knows the plain linear ladder; keep a
            # curve for it too so "fused"/"linear" lookups always resolve
            if small != "linear":
                fl = max(feature("linear", int(w0)), 1.0)
                entries[(kind, "linear", "uint8")] = (0.0, 1.0 / fl)
        crossovers = {
            "w0_major": policy.w0_major,
            "w0_minor": policy.w0_minor,
            "w0_fused": policy.w0_fused,
            "small_method": policy.small_method,
        }
        return cls(entries=entries, crossovers=crossovers, source="analytic")

    @classmethod
    def from_table(cls, table: dict) -> "CostModel":
        entries = {
            tuple(k.split("/")): tuple(v) for k, v in table["entries"].items()
        }
        op2d = {
            tuple(k.split("/")): tuple(v)
            for k, v in table.get("op2d", {}).items()
        }
        return cls(
            entries=entries,
            crossovers=dict(table.get("crossovers", {})),
            source="measured",
            op2d=op2d,
        )

    # ----------------------------------------------------------------- queries
    def _entry(self, kind: str, method: str, dtype: str):
        e = self.entries.get((kind, method, dtype))
        if e is None:
            e = self.entries.get((kind, method, "uint8"))
        return e

    def cost_1d(self, kind: str, method: str, w: int, dtype: str = "uint8") -> float:
        """Modeled cost (µs for measured tables, threshold-normalized units
        for analytic ones) of one 1-D pass of window ``w``.

        A *measured* model never mixes units: an unmeasured linear-family
        method borrows the measured ``linear`` curve (same family, same
        crossover side), and a method with no measured family proxy costs
        +inf — conservatively never chosen — rather than comparing analytic
        ~1.0-unit numbers against microsecond curves.
        """
        e = self._entry(kind, method, dtype)
        if e is None and self.source == "measured":
            if method in _SMALL_METHODS:
                proxy = self._entry(kind, "linear", dtype)
                if proxy is not None:
                    c0, c1 = proxy
                    return c0 + c1 * feature("linear", w)
            return float("inf")
        if e is None:
            e = (0.0, 1.0)  # analytic model missing a kind: benign default
        c0, c1 = e
        # clamp: a flat sweep can fit a tiny negative slope that the w^2
        # feature amplifies into nonsense-negative costs when extrapolated
        return max(0.0, c0 + c1 * feature(method, w))

    def best_method(
        self, kind: str, w: int, dtype: str = "uint8", *, small: str = "linear_tree"
    ) -> str:
        """Cheapest of ``small`` vs ``vhgw`` at window ``w`` (ties -> small,
        preserving the historical ``w <= w0`` inclusive threshold). The
        analytic model dispatches on its thresholds directly — bit-for-bit
        the old scalar branch (the log feature's coarse buckets would
        otherwise blur the crossover by up to one doubling)."""
        if w <= 1:
            return small
        if self.source == "analytic":
            w0 = int(self.crossovers.get(f"w0_{kind}", 0))
            return small if w <= w0 else "vhgw"
        cs = self.cost_1d(kind, small, w, dtype)
        cv = self.cost_1d(kind, "vhgw", w, dtype)
        if math.isinf(cs) and math.isinf(cv):
            # a measured table sparse in this axis kind (e.g. only the
            # collective curves were fit, bench_shard --fit-collective):
            # degrade to the recorded crossover thresholds — bit-for-bit
            # the scalar branch, never an arbitrary inf-vs-inf tie
            w0 = int(self.crossovers.get(f"w0_{kind}", 0))
            return small if w <= w0 else "vhgw"
        return small if cs <= cv else "vhgw"

    def crossover(self, kind: str, *, small: str = "linear_tree",
                  dtype: str = "uint8", sweep=None) -> int:
        """First odd w where vHGW beats ``small`` (the scalar a table
        distills to — what ``DispatchPolicy.calibrated()`` adopts)."""
        ws = sweep or range(3, 1026, 2)
        for w in ws:
            if self.best_method(kind, w, dtype, small=small) == "vhgw":
                return int(w)
        return int(max(ws))

    def prim_cost_2d(
        self, se, dtype: str = "uint8", *, kinds=("major", "fused"),
        small: str = "linear_tree",
    ) -> float:
        """Modeled cost of one separable 2-D primitive: the H pass at
        ``kinds[0]`` plus the W pass at ``kinds[1]``, each with its best
        method. Intercepts make this launch-count aware."""
        w_h, w_w = int(se[0]), int(se[1])
        total = 0.0
        for kind, w in zip(kinds, (w_h, w_w)):
            s = small if kind != "fused" else "linear"
            m = self.best_method(kind, w, dtype, small=s)
            total += self.cost_1d(kind, m, w, dtype)
        return total

    def collective_cost(self, method: str, elems: int, dtype: str = "uint8"):
        """Modeled µs for moving ``elems`` elements via ``method``
        (``"ppermute"`` / ``"all_to_all"``), or ``None`` when this model has
        no measured curve — collectives have no analytic reconstruction (the
        scalar thresholds never described them), so absence means "fall back
        to the byte-count heuristic", not "cost zero".
        """
        if method not in COLLECTIVE_METHODS:
            raise ValueError(
                f"collective method must be one of {COLLECTIVE_METHODS}, "
                f"got {method!r}"
            )
        e = self._entry("collective", method, dtype)
        if e is None:
            return None
        c0, c1 = e
        return max(0.0, c0 + c1 * float(elems))

    def exchange_wins(
        self, wing: int, interior: int, row_elems: int, dtype: str = "uint8"
    ) -> bool:
        """Halo-exchange vs reshard for one sharded separable pass.

        Exchange moves ``2 * wing`` rows total but issues ``2 * k`` ppermute
        launches for ``k = ceil(wing / interior)`` hops (halo.exchange_halo's
        multi-hop form), so each extra launch pays the intercept again;
        resharding moves the whole slab through two ``all_to_all``s
        (``2 * interior`` rows per shard). Measured collective curves decide
        when both exist; otherwise the byte-count heuristic: exchange wins
        unless the wing exceeds the shard interior — exactly the regime
        (SE wider than a slab) where multi-hop exchange degenerates into an
        all-gather anyway.
        """
        pc = self.collective_cost("ppermute", 2 * wing * row_elems, dtype)
        launch = self.collective_cost("ppermute", 0, dtype)
        ac = self.collective_cost("all_to_all", 2 * interior * row_elems, dtype)
        if pc is None or ac is None:
            return wing <= interior
        k = max(1, -(-wing // max(1, interior)))
        # collective_cost already includes one intercept; exchange issues
        # 2k launches (one pair per hop), all_to_all issues two
        pc = pc + (2 * k - 1) * launch
        ac = ac + self.collective_cost("all_to_all", 0, dtype)
        return pc <= ac

    def repr_cost(self, method: str, driver: int, dtype: str = "bool"):
        """Modeled µs for one boolean-plan execution under ``method``
        (``"rle"`` driven by run count, ``"dense"`` by pixel count), or
        ``None`` when unmeasured — like the collectives, representation
        curves have no analytic reconstruction (no historical scalar ever
        described them), so absence means "use the density heuristic".
        """
        if method not in REPR_METHODS:
            raise ValueError(
                f"representation method must be one of {REPR_METHODS}, "
                f"got {method!r}"
            )
        e = self._entry("repr", method, dtype)
        if e is None:
            return None
        c0, c1 = e
        return max(0.0, c0 + c1 * float(driver))

    def rle_wins(self, runs: int, pixels: int, dtype: str = "bool") -> bool:
        """Representation choice for one boolean request: run-domain vs
        dense, given the request's measured run count and its pixel count.

        Measured curves (``bench_rle --fit-cost-table``) decide when both
        exist; otherwise the density heuristic: run-domain work is a few
        vector ops per run against a few elementwise passes per pixel, so
        RLE wins comfortably below ~5% runs/pixel on every host we have
        measured — a deliberately conservative default (the measured
        crossover is usually higher).
        """
        rc = self.repr_cost("rle", runs, dtype)
        dc = self.repr_cost("dense", pixels, dtype)
        if rc is None or dc is None:
            return runs <= 0.05 * pixels
        return rc <= dc

    def fused_wins(self, se, dtype: str = "uint8", *, gradient: bool = False) -> bool:
        """Per-node fused-megakernel vs two-pass+transpose decision.

        Measured tables compare the whole-op affine fits; without them the
        answer is True (the fused kernel's 1-vs-4 HBM-traversal structure),
        which is the pre-cost-model behavior ``policy.fused_2d`` encoded.
        """
        a = "gradient_fused" if gradient else "fused"
        b = "gradient_two_pass" if gradient else "two_pass"
        fa = self.op2d.get((a, dtype)) or self.op2d.get((a, "uint8"))
        fb = self.op2d.get((b, dtype)) or self.op2d.get((b, "uint8"))
        if fa is None or fb is None:
            return True
        s = float(int(se[0]) + int(se[1]))
        return fa[0] + fa[1] * s <= fb[0] + fb[1] * s

    def decompose(
        self, se, dtype: str = "uint8", *, kinds=("major", "fused"),
        small: str = "linear_tree", margin: float = 0.9,
        max_step_wing: int = 7,
    ):
        """Schedule a large-SE primitive as iterated small-SE primitives.

        Returns a list of SE pairs whose per-axis wings sum to the
        original's (so the chain is bit-identical and halo-preserving), or
        ``None`` when one direct pass is modeled cheaper. A candidate must
        beat direct cost by ``margin`` to win — the hysteresis that keeps
        borderline fits from flapping between schedules across refits.
        """
        wing_h, wing_w = (int(se[0]) - 1) // 2, (int(se[1]) - 1) // 2
        if max(wing_h, wing_w) <= 1:
            return None
        direct = self.prim_cost_2d(se, dtype, kinds=kinds, small=small)
        best_cost, best_sched = direct * margin, None
        for step in range(1, max_step_wing + 1):
            k = max(-(-wing_h // step) if wing_h else 0,
                    -(-wing_w // step) if wing_w else 0)
            if k <= 1:
                continue
            sched = []
            for i in range(k):
                hw = wing_h * (i + 1) // k - wing_h * i // k
                ww = wing_w * (i + 1) // k - wing_w * i // k
                sched.append((2 * hw + 1, 2 * ww + 1))
            cost = sum(
                self.prim_cost_2d(s, dtype, kinds=kinds, small=small)
                for s in sched
            )
            if cost < best_cost:
                best_cost, best_sched = cost, sched
        return best_sched

    def matches(self, policy: DispatchPolicy) -> bool:
        """Whether this model's implied thresholds are the policy's — i.e.
        the policy was not hand-tuned away from the measured table."""
        c = self.crossovers
        return (
            int(c.get("w0_major", -1)) == policy.w0_major
            and int(c.get("w0_minor", -1)) == policy.w0_minor
            and int(c.get("w0_fused", -1)) == policy.w0_fused
            and c.get("small_method", policy.small_method) == policy.small_method
        )


# --------------------------------------------------------------- persistence
_TABLE_CACHE: dict[tuple, "CostModel | None"] = {}


def load_measured(path: str | None = None, device: str | None = None):
    """The measured :class:`CostModel` for this device, or ``None``.

    Memoized on (path, mtime, device); a refit (new mtime) reloads, exactly
    like the calibration-scalar cache in ``core.dispatch``.
    """
    path = path or COST_TABLE_FILE
    device = device or device_kind()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    key = (path, mtime, device)
    if key not in _TABLE_CACHE:
        try:
            with open(path) as f:
                table = json.load(f)
            per_dev = table.get("devices", {}).get(device)
            _TABLE_CACHE[key] = (
                CostModel.from_table(per_dev) if per_dev else None
            )
        except (OSError, ValueError, KeyError):
            _TABLE_CACHE[key] = None
    return _TABLE_CACHE[key]


def save_measured(
    entries: dict, crossovers: dict, *, op2d: dict | None = None,
    path: str | None = None, device: str | None = None,
) -> str:
    """Merge one device's fitted table into ``cost_table.json``.

    ``entries`` keys are ``(kind, method, dtype)`` tuples (stored as
    ``kind/method/dtype`` strings); other devices' tables are preserved.
    """
    path = path or COST_TABLE_FILE
    device = device or device_kind()
    table: dict = {"version": 1, "devices": {}}
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        pass
    table.setdefault("devices", {})[device] = {
        "entries": {"/".join(k): list(v) for k, v in entries.items()},
        "op2d": {"/".join(k): list(v) for k, v in (op2d or {}).items()},
        "crossovers": dict(crossovers),
    }
    with open(path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    return path


_MODEL_CACHE: dict = {}


def cost_model_for(policy: DispatchPolicy | None = None) -> CostModel:
    """The model dispatch decisions should consult for this policy.

    The measured table applies only when the policy's thresholds agree with
    it (``DispatchPolicy.calibrated()`` adopts the table's crossovers, so
    calibrated policies match); a hand-tuned policy — tests pinning
    ``w0_fused=5``, A/B harnesses — gets the analytic model built from its
    own scalars, preserving explicit overrides exactly.

    Memoized on (policy, table mtime): ``morph_1d`` calls this twice per
    primitive during tracing, so the steady-state cost must be one dict
    lookup plus a stat — not a fresh analytic-model build per pass (the
    same per-call overhead class the ``calibrated()`` memo removed).
    """
    policy = policy or DispatchPolicy.calibrated()
    try:
        mtime = os.stat(COST_TABLE_FILE).st_mtime_ns
    except OSError:
        mtime = None
    key = (policy, COST_TABLE_FILE, mtime)
    model = _MODEL_CACHE.get(key)
    if model is None:
        measured = load_measured()
        if measured is not None and measured.matches(policy):
            model = measured
        else:
            model = CostModel.analytic(policy)
        _MODEL_CACHE[key] = model
    return model
