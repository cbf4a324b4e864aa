"""Halo-correct tiled execution for images beyond one launch's budget.

An image too large for the bucket ladder is split into a grid of interior
tiles of fixed size ``(th, tw)``; each tile is read with a halo of the
plan's total contamination radius (``Plan.halo()`` — SE wings summed over
sequential passes), executed through the same masked executor as bucketed
requests (``plans.build_executor``), and only the tile *interior* is
stitched back. Because:

* the halo supplies exact neighbor data for every sequential pass, and
* the part of a border tile's halo that falls outside the image is masked
  to each op's neutral element before every pass (plans.mask_outside),

the stitched result is bit-exact against running the plan on the whole
image — including when an SE is larger than the halo-free tile interior.

The served route runs **one jitted program per tile grid**
(:func:`build_grid_executor`): it takes the page, zero-placed by the host
into the grid's extent ``(ny*th, nx*tw)``, and the tiles' valid rects;
pads the halo and gathers the tiles with static slices, runs the
plan's masked passes over them in chunks of at most ``max_tiles_per_launch``
tiles (no dummy tiles: every shape is static inside the trace), and
stitches the interiors with a reshape and transpose. The host makes one
call per page; the page crosses to the device once and each named output
crosses back once, cropped to ``(h, w)`` on the host (:func:`run_grid`).
The executable is keyed by the grid ``(ny, nx)``, not the page's shape:
the rects are runtime data, so pages of different sizes that share a grid
share one compile, the way frames of different sizes share a bucket.

:func:`run_tiled` over :func:`extract_tiles` is the eager form of the same
computation — a ``lax.dynamic_slice`` per tile, launch batches padded with
empty-rect dummies, stitching by concatenation, each step its own device
dispatch — kept as the reference the grid program is tested against. It is
also the single-device degenerate case of ``repro.shard.halo``: same halo
algebra, ``dynamic_slice`` standing in for ``ppermute``.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.serve.morph.plans import Plan


def tile_counts(h: int, w: int, interior: tuple[int, int]) -> tuple[int, int]:
    th, tw = interior
    return math.ceil(h / th), math.ceil(w / tw)


def tile_layout(
    h: int, w: int, gh: int, gw: int, interior: tuple[int, int]
) -> tuple[list[tuple[int, int]], np.ndarray, list[tuple[int, int, int, int]]]:
    """Static per-tile geometry: padded-image slice origins, valid rects in
    extended-tile coordinates, and the (y0, x0, ih, iw) image region each
    tile owns."""
    th, tw = interior
    eh, ew = th + 2 * gh, tw + 2 * gw
    ny, nx = tile_counts(h, w, interior)
    origins, rects, interiors = [], [], []
    for ty in range(ny):
        for tx in range(nx):
            y0, x0 = ty * th, tx * tw
            origins.append((y0, x0))
            rects.append(
                [
                    max(0, gh - y0),
                    min(eh, h - y0 + gh),
                    max(0, gw - x0),
                    min(ew, w - x0 + gw),
                ]
            )
            interiors.append((y0, x0, min(th, h - y0), min(tw, w - x0)))
    return origins, np.asarray(rects, dtype=np.int32), interiors


def extract_tiles(
    img, plan: Plan, interior: tuple[int, int]
) -> tuple[jnp.ndarray, np.ndarray, list[tuple[int, int, int, int]]]:
    """Split (H, W) into halo-extended tiles, gathered on device.

    Returns ``(tiles (N, eh, ew) device array, rects (N, 4), interiors)``
    where ``rects`` are the in-image valid rectangles in extended-tile
    coordinates and ``interiors`` the (y0, x0, ih, iw) image regions each
    tile owns. The image crosses to the device once; each tile is a
    ``dynamic_slice`` of the padded copy — no host-side assembly.
    """
    if img.ndim != 2:
        raise ValueError("extract_tiles operates on a single (H, W) image")
    gh, gw = plan.halo()
    th, tw = interior
    eh, ew = th + 2 * gh, tw + 2 * gw
    h, w = img.shape
    ny, nx = tile_counts(h, w, interior)
    origins, rects, interiors = tile_layout(h, w, gh, gw, interior)
    # One zero-padded device copy; the fill never leaks because the executor
    # masks outside each tile's valid rect before every pass.
    padded = jnp.pad(
        jnp.asarray(img),
        ((gh, gh + ny * th - h), (gw, gw + nx * tw - w)),
    )
    tiles = jnp.stack(
        [lax.dynamic_slice(padded, (y0, x0), (eh, ew)) for y0, x0 in origins]
    )
    return tiles, rects, interiors


def run_tiled(
    img,
    plan: Plan,
    execute,
    *,
    tile_interior: tuple[int, int],
    launch_batch: int,
) -> dict[str, np.ndarray]:
    """Execute ``plan`` over ``img`` in halo tiles and stitch the interiors,
    eagerly (the reference for :func:`run_grid`).

    ``execute(tiles (B, eh, ew), rects (B, 4)) -> {name: (B, eh, ew)}`` is
    a jitted executor call — always invoked with ``B`` from the
    power-of-two ladder below ``launch_batch``, short chunks padded with
    dummy tiles (empty valid rect). Tiles arrive as device arrays and
    interiors stitch on device; each named output crosses to the host once.
    """
    gh, gw = plan.halo()
    tiles, rects, interiors = extract_tiles(img, plan, tile_interior)
    n = int(tiles.shape[0])
    h, w = img.shape
    ny, nx = tile_counts(h, w, tile_interior)
    launch_batch = max(1, min(launch_batch, 1 << (n - 1).bit_length() if n else 1))
    crops: dict[str, list] = {}
    for i0 in range(0, n, launch_batch):
        real = min(launch_batch, n - i0)
        chunk = tiles[i0 : i0 + launch_batch]
        crect = rects[i0 : i0 + launch_batch]
        pad = launch_batch - real
        if pad:
            chunk = jnp.concatenate(
                [chunk, jnp.zeros((pad, *chunk.shape[1:]), chunk.dtype)]
            )
            crect = np.concatenate([crect, np.zeros((pad, 4), np.int32)])
        res = execute(chunk, crect)
        for name, val in res.items():
            slots = crops.setdefault(name, [None] * n)
            for j in range(real):
                _, _, ih, iw = interiors[i0 + j]
                slots[i0 + j] = lax.slice(val[j], (gh, gw), (gh + ih, gw + iw))
    # Stitch by row-wise concatenation — O(H*W) total, vs a full-image copy
    # per tile that eager dynamic_update_slice would cost — still device-
    # side; each named output crosses to the host exactly once.
    outs: dict[str, np.ndarray] = {}
    for name, slots in crops.items():
        rows = [
            jnp.concatenate(slots[r * nx : (r + 1) * nx], axis=1)
            if nx > 1 else slots[r * nx]
            for r in range(ny)
        ]
        outs[name] = np.asarray(
            jnp.concatenate(rows, axis=0) if ny > 1 else rows[0]
        )
    return outs


# ------------------------------------------------------- one program per grid
def place_page(
    img: np.ndarray, plan: Plan, interior: tuple[int, int]
) -> tuple[np.ndarray, np.ndarray]:
    """The grid program's host-side inputs: ``img`` at the origin of a zero
    buffer of the grid's extent ``(ny*th, nx*tw)``, and each tile's valid
    rect in extended-tile coordinates (N, 4). The zero fill never leaks:
    the executor masks outside each rect before every pass. (The halo pad
    is added on device: at 512-multiple extents the TPU keeps the buffer
    row-major, so the transfer needs no relayout.)"""
    gh, gw = plan.halo()
    th, tw = interior
    h, w = img.shape
    ny, nx = tile_counts(h, w, interior)
    page = np.zeros((ny * th, nx * tw), dtype=img.dtype)
    page[:h, :w] = img
    _, rects, _ = tile_layout(h, w, gh, gw, interior)
    return page, rects


def build_grid_executor(
    plan: Plan,
    execute,
    grid: tuple[int, int],
    interior: tuple[int, int],
    *,
    max_tiles_per_launch: int,
):
    """Jitted ``(page, rects (ny*nx, 4)) -> ({name: (ny*th, nx*tw)}, aux)``
    for one tile grid — gather, masked passes and stitch in one program.

    ``execute(tiles (B, eh, ew), rects (B, 4)) -> (outs, aux)`` is the
    plan's masked executor, ``plans.build_executor(plan, with_aux=True)``:
    the very function the bucketed route jits, traced here inside the grid
    program. ``page`` is :func:`place_page`'s ``(ny*th, nx*tw)`` buffer.
    The tiles run through ``execute`` in chunks of ``max_tiles_per_launch``
    (a ``lax.map`` over the full chunks, so the program's size does not
    grow with the grid) and one short last chunk; ``aux`` is the plan's
    bounded-iteration telemetry summed over the chunks. The outputs cover
    the whole grid; the caller crops them to the page.
    """
    ny, nx = grid
    th, tw = interior
    gh, gw = plan.halo()
    eh, ew = th + 2 * gh, tw + 2 * gw
    n = ny * nx
    cap = max(1, min(max_tiles_per_launch, n))
    full = n - n % cap

    def program(page, rects):
        # static slices of the halo-padded page: a band of rows per tile
        # row, then a tile per column
        page = jnp.pad(page, ((gh, gh), (gw, gw)))
        bands = jnp.stack([page[ty * th : ty * th + eh] for ty in range(ny)])
        tiles = jnp.stack(
            [bands[:, :, tx * tw : tx * tw + ew] for tx in range(nx)], axis=1
        ).reshape(n, eh, ew)
        # full chunks under lax.map (cap <= n, so there is at least one),
        # then the short last chunk, if any
        outs, aux = lax.map(
            lambda c: execute(*c),
            (tiles[:full].reshape(full // cap, cap, eh, ew),
             rects[:full].reshape(full // cap, cap, 4)),
        )
        outs = {k: v.reshape(full, eh, ew) for k, v in outs.items()}
        aux = {k: v.sum(dtype=jnp.int32) for k, v in aux.items()}
        if full < n:
            last, last_aux = execute(tiles[full:], rects[full:])
            outs = {k: jnp.concatenate([v, last[k]]) for k, v in outs.items()}
            aux = {k: v + last_aux[k] for k, v in aux.items()}
        stitched = {
            k: v[:, gh : gh + th, gw : gw + tw]
            .reshape(ny, nx, th, tw)
            .transpose(0, 2, 1, 3)
            .reshape(ny * th, nx * tw)
            for k, v in outs.items()
        }
        return stitched, aux

    return jax.jit(program)


def run_grid(
    img, plan: Plan, execute, *, tile_interior: tuple[int, int], stage=None
) -> tuple[dict[str, np.ndarray], dict]:
    """Serve ``img`` through one grid program: place, call, copy back, crop.

    ``execute(grid, page, rects) -> (outs, aux)`` calls the (cached)
    :func:`build_grid_executor` program for ``grid = (ny, nx)``.
    ``stage(name)``, where given, returns a context manager that times
    ``tile.gather`` (host placement and the transfer to the device),
    ``tile.launch`` (the program call) and ``tile.stitch`` (the blocking
    copy of the outputs back and the crop). Returns the ``(h, w)`` outputs
    and the program's ``aux``.
    """
    if stage is None:
        stage = _no_stage
    h, w = img.shape
    grid = tile_counts(h, w, tile_interior)
    with stage("tile.gather"):
        page, rects = place_page(img, plan, tile_interior)
        page, rects = jnp.asarray(page), jnp.asarray(rects)
    with stage("tile.launch"):
        outs, aux = execute(grid, page, rects)
    with stage("tile.stitch"):
        # device_get starts every output's copy back before it blocks on
        # the first; the crop is copied so each output is one contiguous
        # (h, w) array, as the reply sends it
        outs = {
            k: np.ascontiguousarray(v[:h, :w])
            for k, v in jax.device_get(outs).items()
        }
    return outs, aux


def _no_stage(name: str):
    return contextlib.nullcontext()
