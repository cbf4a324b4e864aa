"""Scanned text pages: paper-white background with grain, lines of glyph-sized
ink strokes, and salt-and-pepper specks. 8-bit pixels."""
import numpy as np

from chipbench.traffic import rng_for


def make(image: dict, count: int, seed: int) -> np.ndarray:
    h, w = int(image["height"]), int(image["width"])
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
