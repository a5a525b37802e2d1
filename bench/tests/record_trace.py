"""Record the small trace that test_trace.py reduces.  Run on one TPU chip:

    python3 -m bench.tests.record_trace <directory>

It traces a short window of chaneq-mr30.fit-b512 at a small size (both
kernels run) and leaves the profiler's output under <directory>.
"""

import sys
from pathlib import Path

from bench import harness

SMALL = {"config": {"task.n_symbols": 1200}, "mix": {"instances_per_call": 64,
                                                     "rotations": 1}}

if __name__ == "__main__":
    out = Path(sys.argv[1]).resolve()
    harness.run_cell("chaneq-mr30.fit-b512", 1234, 0.3, True, scale=SMALL,
                     keep_trace=out)
