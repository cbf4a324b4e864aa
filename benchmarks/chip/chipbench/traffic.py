"""One general generator: a cell's request stream from its configuration, its
traffic mix and the seed.

Every seed gets the same work in another order. The request kinds are dealt
in shuffled blocks, one of each kind per block, and an open loop's gaps are
the exponential quantiles of the stated rate, shuffled; so a seed changes the
order of arrivals and kinds, never their totals.
"""
from __future__ import annotations

import math
import zlib

import numpy as np

SEED_MOD = 2**63  # seeds are any whole number; numpy takes it in 64 bits


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose, so adding one never shifts another."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) % SEED_MOD, tag])


def request_kinds(config: dict) -> list[dict]:
    """The configuration's request kinds, each ``{"op", "se"}`` or ``{"plan"}``."""
    kinds = []
    for entry in config["requests"]:
        if "plan" in entry:
            kinds.append({"plan": entry["plan"]})
        else:
            for op in entry["ops"]:
                for s in entry["se_sizes"]:
                    kinds.append({"op": op, "se": [int(s), int(s)]})
    return kinds


def kind_sequence(n_kinds: int, n: int, seed: int) -> np.ndarray:
    """``n`` kind indices: blocks of one of each kind, each block shuffled. A
    longer sequence of one seed begins with the shorter one."""
    rng = rng_for(seed, "kinds")
    blocks = math.ceil(n / n_kinds)
    return np.argsort(rng.random((blocks, n_kinds)), axis=1).ravel()[:n]


def open_loop_due(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson stream at ``rate_per_s``: the
    ``n = rate * seconds`` gaps are the exponential distribution's quantiles
    at (i + 0.5) / n, in an order drawn from the seed."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_per_s}/s over {seconds} s sends no request")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    gaps *= seconds / gaps.sum()  # exactly n arrivals in the window
    gaps = rng_for(seed, "gaps").permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def smooth_frames(h: int, w: int, count: int, seed: int) -> np.ndarray:
    """Grayscale frames with structure at every scale the SE sweep spans: a
    coarse random field upsampled, blobs and pixel noise (uniform noise
    would erode to a constant under large SEs and hide a wrong window)."""
    rng = rng_for(seed, "frames")
    out = np.empty((count, h, w), dtype=np.uint8)
    yy = np.linspace(0, 1, h)[:, None]
    xx = np.linspace(0, 1, w)[None, :]
    for i in range(count):
        coarse = rng.uniform(40, 215, (9, 12))
        cy = np.interp(np.linspace(0, 8, h), np.arange(9), np.arange(9))
        cx = np.interp(np.linspace(0, 11, w), np.arange(12), np.arange(12))
        y0 = np.floor(cy).astype(int).clip(0, 7)
        x0 = np.floor(cx).astype(int).clip(0, 10)
        fy = (cy - y0)[:, None]
        fx = (cx - x0)[None, :]
        base = ((1 - fy) * (1 - fx) * coarse[y0][:, x0]
                + (1 - fy) * fx * coarse[y0][:, x0 + 1]
                + fy * (1 - fx) * coarse[y0 + 1][:, x0]
                + fy * fx * coarse[y0 + 1][:, x0 + 1])
        for _ in range(12):
            by, bx = rng.uniform(0, 1, 2)
            r = rng.uniform(0.02, 0.12)
            base += rng.uniform(-60, 60) * (((yy - by) ** 2 + (xx - bx) ** 2) < r * r)
        base += rng.normal(0, 12, (h, w))
        out[i] = np.clip(base, 0, 255).astype(np.uint8)
    return out


def scanned_pages(h: int, w: int, count: int, seed: int) -> np.ndarray:
    """Scanned text pages: paper-white background with grain, lines of
    glyph-sized ink strokes, and salt-and-pepper specks."""
    rng = rng_for(seed, "pages")
    out = np.empty((count, h, w), dtype=np.uint8)
    cell_h, cell_w, line_h = 6, 4, 54  # 300 dpi: ~36 px glyphs, 1.5 line pitch
    for i in range(count):
        page = rng.normal(232, 6, (h, w))
        ink = np.zeros((h, w), dtype=bool)
        top, left = 180, 150  # margins
        for y in range(top, h - top - 36, line_h):
            glyphs = rng.random((36 // cell_h, (w - 2 * left) // cell_w)) < 0.38
            spaces = rng.random(glyphs.shape[1]) < 0.12
            glyphs[:, spaces] = False
            block = np.repeat(np.repeat(glyphs, cell_h, 0), cell_w, 1)
            ink[y:y + block.shape[0], left:left + block.shape[1]] = block
        page[ink] = rng.normal(35, 10, int(ink.sum()))
        specks = rng.random((h, w))
        page[specks < 0.002] = 0
        page[specks > 0.998] = 255
        out[i] = np.clip(page, 0, 255).astype(np.uint8)
    return out


GENERATORS = {"smooth_frames": smooth_frames, "scanned_pages": scanned_pages}


def image_pool(config: dict, count: int, seed: int) -> np.ndarray:
    im = config["image"]
    gen = GENERATORS[im["content"]]
    return gen(int(im["height"]), int(im["width"]), count, seed)


def stamp(base: np.ndarray, index: int) -> np.ndarray:
    """A copy of ``base`` made unique to request ``index``: its number written
    into eight pixels of a row chosen by it, so no two requests of a run send
    the same bytes."""
    img = base.copy()
    row = index % img.shape[0]
    img[row, :8] = np.frombuffer(int(index).to_bytes(8, "little"), dtype=np.uint8)
    return img


def check_priority(seed: int, index: int) -> int:
    """A request's draw for the checked sample: the answers with the lowest
    draws among those finished are compared with the reference."""
    return zlib.crc32(f"{int(seed)}:{int(index)}".encode())
