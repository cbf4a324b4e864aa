#!/usr/bin/env python3
"""Run one benchmark cell once and print one JSON line.

    python3 benchmarks/chip/run.py --workload paper.closed64 --seed 7 --seconds 10 --trace 0

From the root of a checkout, on a machine with the cell's TPU chips. The cell,
its configuration, traffic mix and metrics come from BENCHMARK.json; see
``chipbench/harness.py``. Exits non-zero without a result line where JAX finds
no TPU, fewer chips than the cell asks for, or a device not in peaks.json.
Where the cell's configuration states an environment for its process, the run
first starts itself again under it (``chipbench/launch.py``).
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench.launch import relaunch  # noqa: E402

T_START = relaunch(sys.argv[1:], os.path.dirname(os.path.dirname(HERE)), T_START)

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
