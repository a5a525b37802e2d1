"""Run one benchmark cell once on the accelerator; the last line of
standard output is the result.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Without a TPU (or with fewer chips than the cell asks for) it exits with
code 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if not __package__:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
