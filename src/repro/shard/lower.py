"""``to_sharded``: lower a morphology expression onto an image-plane mesh.

The fourth lowering of the IR, next to ``lower_xla`` / ``lower_kernel`` /
``to_plan``: the same evaluator walk, with primitives that partition each
separable pass across the mesh. Per 1-D pass along a sharded axis there are
two legal schedules:

* **exchange** — keep the standing sharding and extend each slab with the
  pass's ``wing`` halo rows via ``lax.ppermute``
  (:func:`repro.shard.halo.exchange_halo`; multi-hop when the wing exceeds
  a slab, neutral fill at the global boundary);
* **reshard** — ``lax.all_to_all`` the slab so the pass's axis becomes
  fully local (rows-sharded data resharding to column strips for the
  vertical pass), run the pass halo-free, and ``all_to_all`` back.

``strategy="auto"`` picks per pass via the cost model's ``collective`` axis
kind (:meth:`repro.morph.opt.cost.CostModel.exchange_wins`): measured
ppermute/all_to_all curves when ``bench_shard --fit-collective`` has run,
else the byte-count heuristic (exchange until the wing exceeds the shard
interior). Passes along unsharded axes are local and free of collectives.

Bit-exactness against ``lower_xla`` holds for *any* input shape and graph:

* non-divisible extents pad up to the mesh grid, and every primitive's
  input is re-masked with that op's neutral outside the true image — the
  serving executor's valid-rect mechanism, reused verbatim, so composed
  graphs needing both neutrals (gradient) just work;
* halo fill at global boundaries is the op's neutral — identical to the
  1-D kernels' virtual border;
* ``BoundedIter`` convergence checks are made *global* (``lax.psum`` of the
  changed flag over the mesh axes) so every shard runs the same iteration
  count and the collectives inside the loop body stay in lockstep.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.dispatch import DispatchPolicy, morph_1d
from repro.morph.expr import MorphExpr
from repro.morph.interp import evaluate
from repro.shard.halo import exchange_halo
from repro.shard.mesh import COLS, ROWS, image_mesh, mesh_axis_sizes

ShardStrategy = Literal["auto", "exchange", "reshard"]
_STRATEGIES = ("auto", "exchange", "reshard")


def _check_strategy(strategy: str) -> str:
    if strategy not in _STRATEGIES:
        raise ValueError(
            f"strategy must be one of {_STRATEGIES}, got {strategy!r}"
        )
    return strategy


def _reshard_pass(v, w: int, op, axis_name: str, policy) -> jnp.ndarray:
    """Run the sublane-axis pass halo-free by resharding rows -> cols.

    ``all_to_all`` turns a ``(..., R, W)`` row slab into ``(..., H, W/n)``
    column strips (full height locally), the pass runs with zero halo, and
    the inverse ``all_to_all`` restores row sharding. Requires the padded
    width to be divisible by the mesh axis (``to_sharded`` pads for it).
    """
    nd = v.ndim
    t = lax.all_to_all(v, axis_name, split_axis=nd - 1, concat_axis=nd - 2,
                       tiled=True)
    t = morph_1d(t, w, axis=-2, op=op, policy=policy)
    return lax.all_to_all(t, axis_name, split_axis=nd - 2, concat_axis=nd - 1,
                          tiled=True)


def _exchange_pass(v, w: int, op, *, axis: int, axis_name: str, size: int,
                   policy) -> jnp.ndarray:
    wing = (w - 1) // 2
    ext = exchange_halo(
        v, wing, axis=axis, axis_name=axis_name, size=size,
        neutral=op.neutral(v.dtype),
    )
    out = morph_1d(ext, w, axis=axis, op=op, policy=policy)
    r = v.shape[axis % v.ndim]
    return lax.slice_in_dim(out, wing, wing + r, axis=axis % v.ndim)


def to_sharded(
    outputs,
    mesh=None,
    *,
    policy: DispatchPolicy | None = None,
    strategy: ShardStrategy = "auto",
):
    """``expr | {name: expr}`` -> ``fn(x=None, **vars) -> array | {name: array}``
    executing across ``mesh`` (default: all local devices on a 1-D rows
    axis), bit-identical to ``lower_xla`` on the same inputs.

    All inputs must share one ``(..., H, W)`` shape; leading batch dims are
    replicated (each shard sees the full batch of its strip — morphology
    batches are small next to the image plane). ``strategy`` picks the
    halo-exchange-vs-reshard schedule per pass (see module docstring);
    resharding applies only to 1-D row meshes, where the width axis is free
    to re-partition.
    """
    policy = policy or DispatchPolicy.calibrated()
    strategy = _check_strategy(strategy)
    from repro.morph.opt import cost_model_for, optimize

    single = isinstance(outputs, MorphExpr)
    outs = {"out": outputs} if single else dict(outputs)
    outs = optimize(outs, policy=policy, kinds=("major", "minor"))

    mesh = mesh if mesh is not None else image_mesh()
    nr, nc = mesh_axis_sizes(mesh)
    # Resharding re-partitions the width axis across the row shards; a 2-D
    # mesh already owns that axis, so only 1-D row meshes may reshard.
    may_reshard = strategy != "exchange" and nr > 1 and nc == 1
    if strategy == "reshard" and not may_reshard:
        raise ValueError(
            "strategy='reshard' needs a 1-D rows mesh with >1 shard "
            f"(got rows={nr}, cols={nc})"
        )
    model = cost_model_for(policy)
    axis_names = tuple(
        n for n, sz in ((ROWS, nr), (COLS, nc)) if sz > 1
    )

    def fn(x=None, **env):
        if x is not None:
            env.setdefault("x", x)
        if not env:
            raise ValueError("to_sharded functions need at least one input")
        shapes = {v.shape for v in env.values()}
        if len(shapes) != 1:
            raise ValueError(
                f"all sharded inputs must share one shape, got {sorted(shapes)}"
            )
        (shape,) = shapes
        if len(shape) < 2:
            raise ValueError(f"inputs must be (..., H, W), got shape {shape}")
        h, w = int(shape[-2]), int(shape[-1])
        nd = len(shape)
        rl = -(-h // nr)  # local slab rows
        wdiv = nc * (nr if may_reshard else 1)  # all_to_all splits width by nr
        wl_total = -(-w // wdiv) * wdiv
        hp, wp = rl * nr, wl_total
        cl = wp // nc  # local slab cols
        pad = [(0, 0)] * (nd - 2) + [(0, hp - h), (0, wp - w)]
        env_p = {k: jnp.pad(jnp.asarray(v), pad) for k, v in env.items()}

        spec = P(*([None] * (nd - 2)
                   + [ROWS if nr > 1 else None, COLS if nc > 1 else None]))
        masked = hp != h or wp != w

        def local(env_l):
            r0 = lax.axis_index(ROWS) * rl if nr > 1 else 0
            c0 = lax.axis_index(COLS) * cl if nc > 1 else 0

            def pre(v, op):
                # serving's valid-rect masking, shard-local: everything past
                # the true image reads as this op's own neutral before every
                # primitive — what keeps grid padding bit-exact for composed
                # graphs (a single fill could not serve both min and max).
                rows = r0 + jnp.arange(v.shape[-2], dtype=jnp.int32)
                cols = c0 + jnp.arange(v.shape[-1], dtype=jnp.int32)
                valid = (rows < h)[:, None] & (cols < w)[None, :]
                return jnp.where(valid, v, jnp.asarray(op.neutral(v.dtype)))

            def prim(op, v, se):
                wh, ww = int(se[0]), int(se[1])
                wing_h = (wh - 1) // 2
                if nr > 1 and wing_h > 0:
                    if may_reshard and (
                        strategy == "reshard"
                        or not model.exchange_wins(
                            wing_h, rl, wp, jnp.dtype(v.dtype).name
                        )
                    ):
                        v = _reshard_pass(v, wh, op, ROWS, policy)
                    else:
                        v = _exchange_pass(
                            v, wh, op, axis=-2, axis_name=ROWS, size=nr,
                            policy=policy,
                        )
                else:
                    v = morph_1d(v, wh, axis=-2, op=op, policy=policy)
                wing_w = (ww - 1) // 2
                if nc > 1 and wing_w > 0:
                    v = _exchange_pass(
                        v, ww, op, axis=-1, axis_name=COLS, size=nc,
                        policy=policy,
                    )
                else:
                    v = morph_1d(v, ww, axis=-1, op=op, policy=policy)
                return v

            def stable_reduce(changed):
                # global convergence: every shard must agree on the loop
                # trip count or the body's collectives deadlock
                return lax.psum(changed.astype(jnp.int32), axis_names) > 0

            memo: dict = {}
            return {
                k: evaluate(
                    e, env_l, prim=prim,
                    pre_prim=pre if masked else None,
                    stable_reduce=stable_reduce if axis_names else None,
                    memo=memo,
                )
                for k, e in outs.items()
            }

        run = shard_map(
            local, mesh=mesh,
            in_specs=({k: spec for k in env_p},),
            out_specs={k: spec for k in outs},
            check_vma=False,
        )
        res = run(env_p)
        crop = (Ellipsis, slice(0, h), slice(0, w))
        res = {k: v[crop] for k, v in res.items()}
        return res["out"] if single else res

    return fn
