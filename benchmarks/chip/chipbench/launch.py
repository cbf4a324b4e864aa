"""The process a cell runs in. A configuration may state the environment its
server process runs with (``"environment": {NAME: value}``), such as the C
allocator's settings, which the C library reads once, when the process starts.
``run.py`` calls :func:`relaunch` first thing: where the environment lacks a
stated value, the process replaces itself (same process, new image) with one
that has it, before anything is imported or allocated. Standard library only,
so that nothing has run before the relaunch."""
from __future__ import annotations

import argparse
import json
import os
import sys

T_START_VAR = "CHIPBENCH_T_START"  # the first image's start, carried across exec


def stated_environment(argv: list[str], root: str) -> dict[str, str]:
    """The environment the cell's configuration states; empty where it states
    none, or where the cell cannot be found (the harness reports that)."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    workload = ap.parse_known_args(argv)[0].workload
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        cell = {w["name"]: w for w in manifest["workloads"]}[workload]
        entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
        with open(os.path.join(root, entry["file"])) as f:
            config = json.load(f)
    except (OSError, ValueError, KeyError):
        return {}
    return {str(k): str(v) for k, v in config.get("environment", {}).items()}


def relaunch(argv: list[str], root: str, t_start: float) -> float:
    """Start this program again under the stated environment, where the
    current one lacks it; else return when set-up began (``perf_counter`` is
    the system's monotonic clock, the same after exec)."""
    env = stated_environment(argv, root)
    if any(os.environ.get(k) != v for k, v in env.items()):
        new = dict(os.environ, **env)
        new[T_START_VAR] = repr(t_start)
        sys.stdout.flush()
        sys.stderr.flush()
        os.execve(sys.executable, [sys.executable, os.path.abspath(sys.argv[0]), *argv], new)
    if env:
        print(f"process environment: {env}", file=sys.stderr, flush=True)
    return float(os.environ.pop(T_START_VAR, t_start))
