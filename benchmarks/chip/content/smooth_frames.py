"""Grayscale frames with structure at every scale the SE sweep spans: a coarse
random field upsampled, blobs and pixel noise (uniform noise would erode to a
constant under large SEs and hide a wrong window). 8-bit pixels."""
import numpy as np

from chipbench.traffic import rng_for


def make(image: dict, count: int, seed: int) -> np.ndarray:
    h, w = int(image["height"]), int(image["width"])
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
