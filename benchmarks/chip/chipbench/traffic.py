"""One general generator: a cell's request stream from its configuration, its
traffic mix and the seed.

Every seed gets the same work in another order. The request kinds are dealt
in shuffled blocks, one of each kind per block, and an open loop's gaps are
the exponential quantiles of the stated rate, shuffled; so a seed changes the
order of arrivals and kinds, never their totals. The pixels come from the
configuration's own content generator, found by name (``image_pool``).
"""
from __future__ import annotations

import math
import os
import zlib

import numpy as np

from chipbench import BenchError, load_file

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


def image_pool(config: dict, count: int, seed: int, bench_dir: str) -> np.ndarray:
    """``count`` images of the configuration's ``image`` entry from its content
    generator, found by name: ``make(image, count, seed)`` in
    ``content/<image["content"]>.py`` under ``bench_dir``. The generator gets
    the whole entry, so it may read keys of its own."""
    im = config["image"]
    path = os.path.join(bench_dir, "content", im["content"] + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no content generator {im['content']!r} under {bench_dir}/content")
    pool = load_file(path, f"chipbench_content_{im['content']}").make(im, count, seed)
    want = (np.dtype(im["dtype"]), (count, int(im["height"]), int(im["width"])))
    got = (getattr(pool, "dtype", None), getattr(pool, "shape", None))
    if got != want:
        raise BenchError(f"content {im['content']!r} made {got[0]} {got[1]}, "
                         f"the configuration states {want[0]} {want[1]}")
    return pool


def stamp(base: np.ndarray, index: int) -> np.ndarray:
    """A copy of ``base`` made unique to request ``index``: its number written
    into a row chosen by it, so no two requests of a run send the same bytes.
    Integer pixels take its eight bytes, one a pixel; a bool mask takes its 64
    bits, one a pixel, running on into the next row where the row is narrower."""
    img = base.copy()
    row = index % img.shape[0]
    data = np.frombuffer(int(index).to_bytes(8, "little"), dtype=np.uint8)
    if img.dtype == np.bool_:
        bits = np.unpackbits(data, bitorder="little").astype(bool)
        at = (row * img.shape[1] + np.arange(bits.size)) % img.size
        img.reshape(-1)[at] = bits
    else:
        img[row, :8] = data
    return img


def check_priority(seed: int, index: int) -> int:
    """A request's draw for the checked sample: the answers with the lowest
    draws among those finished are compared with the reference."""
    return zlib.crc32(f"{int(seed)}:{int(index)}".encode())
