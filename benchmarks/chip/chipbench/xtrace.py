"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

A trace is first brought to a plain form, which the tests use with a small
recorded trace:

    {"devices": {"<plane name>": [[op name, start_ns, dur_ns], ...]},
     "host": [[span name, start_ns, dur_ns], ...]}

``devices`` holds the operations each chip ran (the plane's ``XLA Ops``
line); ``host`` holds the named host spans open while they ran: the
benchmark's own ``bench:`` spans and the service's ``morph_serve:`` profiler
annotations. All times are on the profiler's one clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench:", "morph_serve:")


def load_xplane(logdir: str) -> dict:
    """The plain form of the one ``.xplane.pb`` that ``jax.profiler`` wrote
    under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {logdir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def window_of(trace: dict, name: str = "bench:window") -> tuple[int, int]:
    """[start, end) in ns of the host span that brackets the measured window."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == name]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {name!r} span in the trace, found {len(spans)}")
    return spans[0]


def merged(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """The union of the events' intervals, clipped to [t0, t1)."""
    iv = sorted((max(s, t0), min(s + d, t1)) for _, s, d in events
                if s < t1 and s + d > t0)
    out: list[list[int]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out if b > a]


def busy_ns(events, t0: int, t1: int) -> int:
    return sum(b - a for a, b in merged(events, t0, t1))


def gaps(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """The idle intervals of [t0, t1): where no operation ran."""
    out, cur = [], t0
    for a, b in merged(events, t0, t1):
        if a > cur:
            out.append((cur, a))
        cur = b
    if cur < t1:
        out.append((cur, t1))
    return out


def op_name(hlo: str) -> str:
    """An ``XLA Ops`` event's name, which is its whole HLO instruction, cut
    to the instruction's name, result type and opcode:
    ``%morph2d_fused.1 = u8[8,608,1024]{...} custom-call(...)`` becomes
    ``morph2d_fused.1 u8[8,608,1024] custom-call``."""
    lhs, eq, rhs = hlo.partition(" = ")
    if not eq:
        return hlo
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)  # layouts, which hold no spaces or parens
    if rhs.startswith("("):  # a tuple result: "(u8[..], u8[..]) opcode(...)"
        typ, _, rest = rhs[1:].partition(")")
        typ = "(" + typ.replace(" ", "") + ")"
        rest = rest.lstrip()
    else:
        typ, _, rest = rhs.partition(" ")
    return f"{lhs.lstrip('%')} {typ} {rest.split('(', 1)[0]}"


def attribute(gap: tuple[int, int], host) -> str:
    """What the host was doing in a gap: the named host span that covers most
    of it ("host:unnamed" where none does)."""
    a, b = gap
    best, best_ov = "host:unnamed", 0
    for name, s, d in host:
        if name == "bench:window":
            continue
        ov = min(b, s + d) - max(a, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce(trace: dict, devices: list[str] | None = None) -> dict:
    """Busy and idle time per chip over the measured window, the operations
    that took the most device time, and the longest idle gaps by what the
    host was doing in them. ``devices`` names the planes of the chips the cell
    uses (all device planes when None)."""
    t0, t1 = window_of(trace)
    names = devices if devices is not None else sorted(trace["devices"])
    busy = {n: busy_ns(trace["devices"].get(n, []), t0, t1) for n in names}
    per_op: dict[str, int] = {}
    all_gaps = []
    for n in names:
        evs = trace["devices"].get(n, [])
        for name, s, d in evs:
            ov = min(t1, s + d) - max(t0, s)
            if ov > 0:
                key = op_name(name)
                per_op[key] = per_op.get(key, 0) + ov
        all_gaps.extend(gaps(evs, t0, t1))
    all_gaps.sort(key=lambda g: g[0] - g[1])
    window_s = (t1 - t0) / 1e9
    busy_s = sum(busy.values()) / len(names) / 1e9 if names else 0.0
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_device": {n: v / 1e9 for n, v in busy.items()},
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[f"{attribute(g, trace['host'])} @{(g[0] - t0) / 1e6:.3f}ms",
                       (g[1] - g[0]) / 1e9] for g in all_gaps[:10]],
    }
