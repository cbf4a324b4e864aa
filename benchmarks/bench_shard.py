"""Sharded morphology benchmark: scaling curve + halo-exchange vs reshard.

Three measurements, written to ``benchmarks/results/BENCH_shard.json``:

* **scaling** — large-image operators through ``repro.shard.to_sharded``
  at shard counts 1/2/4/8 (capped by available devices) vs the
  single-device ``lower_xla`` path. The interesting number is img/s at the
  max shard count over the single-device baseline (the ISSUE 5 bar: >= 2x
  at 8 shards).
* **ab** — halo-exchange vs reshard schedules at several SE wings on the
  max-shard mesh: the measured form of the decision
  ``CostModel.exchange_wins`` makes from the ``collective`` axis kind.
* **--fit-collective** — times raw ``ppermute`` / ``all_to_all`` sweeps
  inside ``shard_map``, fits the affine ``cost_us(elems)`` curves, and
  merges them into ``src/repro/core/cost_table.json`` under this device —
  after which ``strategy="auto"`` decides from measurements instead of the
  wing-vs-interior byte heuristic.

Run with forced host devices to exercise on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.bench_shard [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import time_fn_amortized as _time

RESULTS = os.path.join(os.path.dirname(__file__), "results", "BENCH_shard.json")



def _cases(smoke: bool):
    from repro.morph import X, occo_expr

    h, w = (512, 512) if smoke else (4096, 4096)
    return h, w, [
        ("erode15", X.erode((15, 15))),
        ("gradient7", X.gradient((7, 7))),
        ("occo5", occo_expr(X, (5, 5))),
    ]


def bench_scaling(img, exprs, shard_counts, reps) -> list[dict]:
    import jax

    from repro.morph import lower_xla
    from repro.shard import image_mesh, to_sharded

    rows = []
    for name, expr in exprs:
        base_s = _time(jax.jit(lower_xla(expr)), img, reps=reps)
        entry = {
            "case": name,
            "shape": list(img.shape),
            "single_device_s": round(base_s, 5),
            "per_shards": [],
        }
        for n in shard_counts:
            fn = jax.jit(to_sharded(expr, image_mesh(n)))
            s = _time(fn, img, reps=reps)
            entry["per_shards"].append(
                {"shards": n, "time_s": round(s, 5),
                 "speedup": round(base_s / s, 2)}
            )
        best = entry["per_shards"][-1]
        print(f"{name:10s} single={base_s*1e3:8.1f} ms   "
              + "  ".join(f"{p['shards']}sh={p['time_s']*1e3:.1f}ms"
                          f"({p['speedup']}x)" for p in entry["per_shards"]))
        entry["max_shards_speedup"] = best["speedup"]
        rows.append(entry)
    return rows


def bench_ab(img, shards, reps) -> list[dict]:
    """Exchange vs reshard for one erode at growing wings."""
    import jax

    from repro.morph import X
    from repro.shard import image_mesh, to_sharded

    mesh = image_mesh(shards)
    rows = []
    interior = img.shape[-2] // shards
    for se_h in (3, 15, 63):
        expr = X.erode((se_h, 3))
        ex_s = _time(jax.jit(to_sharded(expr, mesh, strategy="exchange")),
                     img, reps=reps)
        rs_s = _time(jax.jit(to_sharded(expr, mesh, strategy="reshard")),
                     img, reps=reps)
        rows.append({
            "se_h": se_h,
            "wing": (se_h - 1) // 2,
            "shard_interior": interior,
            "exchange_s": round(ex_s, 5),
            "reshard_s": round(rs_s, 5),
            "exchange_vs_reshard": round(rs_s / ex_s, 2),
        })
        print(f"A/B se_h={se_h:3d}: exchange={ex_s*1e3:.1f} ms  "
              f"reshard={rs_s*1e3:.1f} ms  ratio={rows[-1]['exchange_vs_reshard']}x")
    return rows


def fit_collective(shards, width, reps) -> dict:
    """Fit affine cost_us(elems) curves for ppermute/all_to_all and merge
    them into cost_table.json (the ``collective`` axis kind)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.dispatch import DispatchPolicy
    from repro.morph.opt.cost import (
        fit_affine,
        load_measured,
        save_measured,
    )
    from repro.shard import image_mesh

    mesh = image_mesh(shards)
    points: dict[str, list] = {"ppermute": [], "all_to_all": []}
    for rows in (8, 32, 128, 512):
        x = jnp.asarray(
            np.random.default_rng(0).integers(
                0, 256, (rows * shards, width), dtype=np.uint8
            )
        )
        elems = rows * width  # per-device elements in flight

        def pp(v):
            return lax.ppermute(
                v, "rows", [(i, i + 1) for i in range(shards - 1)]
            )

        def a2a(v):
            return lax.all_to_all(v, "rows", split_axis=v.ndim - 1,
                                  concat_axis=v.ndim - 2, tiled=True)

        for name, f in (("ppermute", pp), ("all_to_all", a2a)):
            fn = jax.jit(shard_map(
                f, mesh=mesh, in_specs=P("rows", None),
                out_specs=P("rows", None), check_vma=False,
            ))
            t = _time(fn, x, reps=reps)
            points[name].append((float(elems), t * 1e6))
    fits = {m: fit_affine(pts) for m, pts in points.items()}
    measured = load_measured()
    if measured is not None:
        entries = dict(measured.entries)
        crossovers = dict(measured.crossovers)
        op2d = dict(measured.op2d)
    else:
        # seed crossovers from the active policy so calibrated() (which
        # adopts a table's crossovers) keeps matching this table
        p = DispatchPolicy.calibrated()
        entries, op2d = {}, {}
        crossovers = {"w0_major": p.w0_major, "w0_minor": p.w0_minor,
                      "w0_fused": p.w0_fused, "small_method": p.small_method}
    for m, (c0, c1) in fits.items():
        # a collective cannot have negative launch cost; a fit can (noise
        # at the small end of the sweep), and a negative intercept would
        # make small transfers read as free
        entries[("collective", m, "uint8")] = (round(max(0.0, c0), 3),
                                               round(max(0.0, c1), 8))
    path = save_measured(entries, crossovers, op2d=op2d)
    print(f"fit collectives -> {path}: "
          + ", ".join(f"{m}: {c0:.1f}us + {c1*1e3:.4f}ns/elem"
                      for m, (c0, c1) in fits.items()))
    return {m: list(f) for m, f in fits.items()}


def run(smoke: bool = False, fit: bool = False) -> dict:
    import jax

    n_dev = len(jax.devices())
    shard_counts = [n for n in (1, 2, 4, 8) if n <= n_dev]
    if n_dev == 1:
        print("WARNING: one device only — scaling sweep is degenerate; "
              "run with XLA_FLAGS=--xla_force_host_platform_device_count=8")
    reps = 2 if smoke else 5
    h, w, exprs = _cases(smoke)
    img = np.random.default_rng(0).integers(0, 256, (h, w), dtype=np.uint8)
    out = {
        "devices": n_dev,
        "device_kind": str(jax.devices()[0].device_kind),
        "shape": [h, w],
        "smoke": smoke,
        "scaling": bench_scaling(img, exprs, shard_counts, reps),
        "ab": (bench_ab(img, shard_counts[-1], reps)
               if shard_counts[-1] > 1 else []),
    }
    if fit and shard_counts[-1] > 1:
        out["collective_fit"] = fit_collective(shard_counts[-1], w, reps)
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {RESULTS}")
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="small image + few reps (CI)")
    p.add_argument("--fit-collective", action="store_true",
                   help="fit ppermute/all_to_all cost curves into "
                        "cost_table.json")
    args = p.parse_args()
    out = run(smoke=args.smoke, fit=args.fit_collective)
    worst = min((r["max_shards_speedup"] for r in out["scaling"]), default=0.0)
    if out["devices"] > 1 and worst < 2.0:
        print(f"WARNING: weakest case scaled {worst}x at "
              f"{out['scaling'][0]['per_shards'][-1]['shards']} shards — "
              f"below the 2x ISSUE bar")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
