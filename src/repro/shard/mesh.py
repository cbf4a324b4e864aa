"""Device meshes over the image plane for sharded morphology.

The LM stack's production meshes (``launch/mesh.py``) partition *parameter*
axes ("data" / "model"); morphology wants the *image plane* partitioned —
strips of rows (1-D, the common case: the lane-axis pass stays local and
only the sublane-axis pass exchanges halos) or a rows x cols grid (2-D, for
images so tall *and* wide that one axis cannot absorb all devices).

Axis names are fixed (:data:`ROWS` / :data:`COLS`) so the halo-exchange and
lowering layers can address collectives without threading names through
every call. Mesh construction is a function, never an import side effect —
jax device state locks at first use, same rule as ``launch/mesh.py``.
"""
from __future__ import annotations

import jax

ROWS = "rows"
COLS = "cols"


def available_shards() -> int:
    """Local device count — the max useful 1-D shard count on this host."""
    return len(jax.devices())


def image_mesh(shards: "int | tuple[int, int] | None" = None):
    """Build a mesh over the image plane.

    ``shards``: an int (or None = all local devices) gives a 1-D
    ``(n,) -> ("rows",)`` mesh; a ``(rows, cols)`` pair gives a 2-D grid.
    A 1-element axis is dropped (a ``(n, 1)`` request builds the 1-D mesh),
    so degenerate configurations don't pay for dead collective axes.
    """
    if shards is None:
        shards = available_shards()
    if isinstance(shards, int):
        shape: tuple[int, ...] = (shards,)
        axes: tuple[str, ...] = (ROWS,)
    else:
        r, c = int(shards[0]), int(shards[1])
        if c == 1:
            shape, axes = (r,), (ROWS,)
        elif r == 1:
            shape, axes = (c,), (COLS,)
        else:
            shape, axes = (r, c), (ROWS, COLS)
    n = 1
    for s in shape:
        if s < 1:
            raise ValueError(f"shard counts must be >= 1, got {shape}")
        n *= s
    if n > available_shards():
        raise ValueError(
            f"image_mesh{shape} needs {n} devices; only "
            f"{available_shards()} available (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N to emulate on CPU)"
        )
    # Auto axes: the lowering places data with shard_map specs and crops the
    # padded global result afterwards, which explicit-axis sharding types
    # (JAX's make_mesh default) refuse for extents the mesh does not divide
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_axis_sizes(mesh) -> tuple[int, int]:
    """``(rows, cols)`` shard counts of an image mesh (1 for absent axes)."""
    names = set(mesh.axis_names)
    extra = names - {ROWS, COLS}
    if extra:
        raise ValueError(
            f"image meshes use axes {ROWS!r}/{COLS!r}; got extra {sorted(extra)}"
        )
    return (
        int(mesh.shape.get(ROWS, 1)),
        int(mesh.shape.get(COLS, 1)),
    )
