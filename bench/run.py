#!/usr/bin/env python3
"""Entry of the chip benchmark; see `bench/harness.py`.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the run's checks on standard error and, as the last line of standard
output, one JSON object with `correct`, `attempted`, `failed`, `metrics`,
`device` (and `breakdown` with `--trace 1`). Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

CLOCK0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def _since_process_start() -> float:
    """Seconds between the process's start and this module's first line
    (the interpreter's own start-up), from /proc; 0 where it is missing."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, age - (time.perf_counter() - CLOCK0))
    except (OSError, ValueError, IndexError):
        return 0.0


ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], clock0=CLOCK0,
                          pre_s=_since_process_start()))
