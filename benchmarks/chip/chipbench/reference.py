"""Plain reference for the served morphology: numpy, exact, and independent of
the program.

Flat rectangular structuring elements centred on the pixel (odd sizes), and
the border outside the image ignored: each pass pads with its own neutral
element (the dtype's max for erosion, its min for dilation; on a bool mask,
True for erosion and False for dilation, where ``np.minimum`` and
``np.maximum`` are and and or). A rectangle's min is the min of its rows'
mins, so each pass runs over rows, then columns. Every pixel type the service
takes is covered: u8, u16, i8, i16 and bool.
"""
from __future__ import annotations

import numpy as np


def _slide(x: np.ndarray, w: int, axis: int, fn, neutral) -> np.ndarray:
    if w % 2 != 1:
        raise ValueError(f"the reference takes odd SE sizes, got {w}")
    r = w // 2
    if r == 0:
        return x.copy()
    pad = [(0, 0), (0, 0)]
    pad[axis] = (r, r)
    p = np.pad(x, pad, constant_values=neutral)
    n = x.shape[axis]

    def shifted(k):
        idx = [slice(None), slice(None)]
        idx[axis] = slice(k, k + n)
        return p[tuple(idx)]

    out = shifted(0).copy()
    for k in range(1, w):
        fn(out, shifted(k), out=out)
    return out


def erode(x: np.ndarray, se) -> np.ndarray:
    hi = True if x.dtype == np.bool_ else np.iinfo(x.dtype).max
    return _slide(_slide(x, int(se[0]), 0, np.minimum, hi), int(se[1]), 1, np.minimum, hi)


def dilate(x: np.ndarray, se) -> np.ndarray:
    lo = False if x.dtype == np.bool_ else np.iinfo(x.dtype).min
    return _slide(_slide(x, int(se[0]), 0, np.maximum, lo), int(se[1]), 1, np.maximum, lo)


def gradient(x: np.ndarray, se) -> np.ndarray:
    """Dilation minus erosion as int32: the type the service answers a
    gradient in, as it widens bool and every integer narrower than 4 bytes."""
    return dilate(x, se).astype(np.int32) - erode(x, se).astype(np.int32)


OPS = {
    "erode": erode,
    "dilate": dilate,
    "opening": lambda x, se: dilate(erode(x, se), se),
    "closing": lambda x, se: erode(dilate(x, se), se),
    "gradient": gradient,
}


def run_steps(x: np.ndarray, steps: list) -> dict[str, np.ndarray]:
    """A pipeline of ``{"op", "se", "save_as"?, "astype"?}`` steps, each on the
    previous step's result; ``save_as`` names an output (cast by ``astype``)."""
    outs, cur = {}, x
    for st in steps:
        cur = OPS[st["op"]](cur, st["se"])
        if "save_as" in st:
            outs[st["save_as"]] = cur.astype(st["astype"]) if "astype" in st else cur
    return outs


def expected(x: np.ndarray, kind: dict, plans: dict) -> dict[str, np.ndarray]:
    """The answer to one request kind: ``{"out": ...}`` for one operator, the
    named outputs for a plan the configuration spells out in ``plans``."""
    if "plan" in kind:
        return run_steps(x, plans[kind["plan"]])
    return {"out": OPS[kind["op"]](x, kind["se"])}


def _narrower(se) -> list[int]:
    return [max(1, int(s) - 2) for s in se]


def control(x: np.ndarray, kind: dict, plans: dict) -> dict[str, np.ndarray]:
    """The reference one step below what the configuration states (exact
    answers on its pixel type). The comparison has to refuse it.

    - Integers: pixels kept to the top half of their bits, as a path of half
      the width would hold them: ``0xF0`` on 8 bits, ``0xFF00`` on 16; a
      signed type takes the same mask on its bits.
    - bool has no lower precision: the reference with each SE extent cut by 2
      (not below 1), a wrong window."""
    if x.dtype == np.bool_:
        if "plan" in kind:
            plans = {**plans, kind["plan"]: [{**st, "se": _narrower(st["se"])}
                                             for st in plans[kind["plan"]]]}
        else:
            kind = {**kind, "se": _narrower(kind["se"])}
        return expected(x, kind, plans)
    bits = 8 * x.dtype.itemsize
    unsigned = np.dtype(f"u{x.dtype.itemsize}")
    mask = unsigned.type(((1 << bits) - 1) ^ ((1 << bits // 2) - 1))
    return expected((x.view(unsigned) & mask).view(x.dtype), kind, plans)


def mismatches(got: dict, want: dict) -> int:
    """Pixels that differ, over every output; a missing or misshapen output
    counts every pixel it should have had."""
    bad = 0
    for name, w in want.items():
        g = got.get(name)
        if g is None or g.shape != w.shape or g.dtype != w.dtype:
            bad += w.size
        else:
            bad += int(np.count_nonzero(g != w))
    return bad
