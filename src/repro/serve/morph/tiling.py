"""Halo-correct tiled execution for images beyond one launch's budget.

An image too large for the bucket ladder is split into a grid of interior
tiles of fixed size ``(th, tw)``; each tile is read with a halo of the
plan's total contamination radius (``Plan.halo()`` — SE wings summed over
sequential passes), executed through the same masked executor as bucketed
requests, and only the tile *interior* is stitched back. Because:

* the halo supplies exact neighbor data for every sequential pass, and
* the part of a border tile's halo that falls outside the image is masked
  to each op's neutral element before every pass (plans.mask_outside),

the stitched result is bit-exact against running the plan on the whole
image — including when an SE is larger than the halo-free tile interior.

Tile gather and stitch are **device-resident**: the image is padded once on
device and every halo tile is a ``lax.dynamic_slice`` view of it; outputs
assemble via ``lax.dynamic_update_slice`` and cross to the host once per
output at the end. (The original implementation assembled tiles in host
numpy — one host round trip per oversized image, the ROADMAP "streamed tile
gather" item. This is also the single-device degenerate case of
``repro.shard.halo``: same halo algebra, ``dynamic_slice`` standing in for
``ppermute``.) Everything stays eager — per-image shapes vary freely
without compiling per-shape gather executables; only the plan executor
itself is jitted, exactly as before.

Every extended tile has the same shape ``(th + 2*gh, tw + 2*gw)`` and tiles
are executed in fixed-size launch batches (the last one padded with dummy
tiles whose valid rect is empty), so tiled traffic reuses a single cached
executable per (plan, tile shape, dtype) exactly like bucketed traffic.
"""
from __future__ import annotations

import contextlib
import math

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
    stage=None,
    on_launch=None,
) -> dict[str, np.ndarray]:
    """Execute ``plan`` over ``img`` in halo tiles and stitch the interiors.

    ``execute(tiles (B, eh, ew), rects (B, 4)) -> {name: (B, eh, ew)}`` is
    the (cached, jitted) executor call — always invoked with ``B`` from the
    power-of-two ladder below ``launch_batch``, short chunks padded with
    dummy tiles (empty valid rect), so a handful of executables serves any
    image size instead of one compile per distinct tile count. Tiles arrive
    as device arrays and interiors stitch on device; each named output
    crosses to the host exactly once.

    ``stage(name)``, where given, returns a context manager that times the
    sections ``tile.gather``, ``tile.launch`` and ``tile.stitch``;
    ``on_launch(tiles, valid_px, launched_px)`` is called once per launch
    with its real tiles, the image pixels they own and the pixels launched.
    """
    if stage is None:
        stage = _no_stage
    gh, gw = plan.halo()
    with stage("tile.gather"):
        tiles, rects, interiors = extract_tiles(img, plan, tile_interior)
    n = int(tiles.shape[0])
    h, w = img.shape
    ny, nx = tile_counts(h, w, tile_interior)
    launch_batch = max(1, min(launch_batch, 1 << (n - 1).bit_length() if n else 1))
    crops: dict[str, list] = {}
    for i0 in range(0, n, launch_batch):
        real = min(launch_batch, n - i0)
        with stage("tile.launch"):
            chunk = tiles[i0 : i0 + launch_batch]
            crect = rects[i0 : i0 + launch_batch]
            pad = launch_batch - real
            if pad:
                chunk = jnp.concatenate(
                    [chunk, jnp.zeros((pad, *chunk.shape[1:]), chunk.dtype)]
                )
                crect = np.concatenate([crect, np.zeros((pad, 4), np.int32)])
            res = execute(chunk, crect)
        if on_launch is not None:
            on_launch(real, sum(ih * iw for _, _, ih, iw in interiors[i0 : i0 + real]),
                      int(chunk.size))
        with stage("tile.stitch"):
            for name, val in res.items():
                slots = crops.setdefault(name, [None] * n)
                for j in range(real):
                    _, _, ih, iw = interiors[i0 + j]
                    slots[i0 + j] = lax.slice(val[j], (gh, gw), (gh + ih, gw + iw))
    # Stitch by row-wise concatenation — O(H*W) total, vs a full-image copy
    # per tile that eager dynamic_update_slice would cost — still device-
    # side; each named output crosses to the host exactly once.
    outs: dict[str, np.ndarray] = {}
    with stage("tile.stitch"):
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


def _no_stage(name: str):
    return contextlib.nullcontext()
