"""Read the check's numbers of a sweep cell over many seeds, for the
program and for its controls, at the cell's own size:

    python3 -m bench.calibrate_sweep [workload] [--seeds 6]
                                     [--control-seeds 3] [--seconds 1]

Every reading is a whole run through ``harness.run_cell`` in one process,
with a short window: the program's on ``--seeds`` seeds; then, on other
seeds, the control ``reference_high`` (the reference with its readout at
``Precision.HIGH``) and the witness ``reference_device`` (the reference
computed on the accelerator) in the program's place, and ``program_bf16``,
the program with bfloat16 state chunks.  Prints one JSON line per reading
and, last, ``bench.calibrate.summary`` of them.  The limits in ``bench/limits`` lie
between the program's largest readings and the smallest of the control
(``readout_excess``, ``readout_eval_gap``: the readout on the states it was
fitted on) or of the bfloat16 states (the numbers on the reference's
states), so that each fails at least one (PERF.md).  ``bench.calibrate``
reads the fit and serve kinds.
"""

from __future__ import annotations

import argparse
import json
import time

from bench import calibrate, harness


def readings(workload, seeds, seconds, variant=None, scale=None, name=None):
    _, _, _, _, limits = harness.cell_spec(workload)
    rows = []
    for seed in seeds:
        numbers, t0 = {}, time.perf_counter()
        harness.run_cell(workload, seed, seconds, False, variant=variant,
                         scale=scale, numbers_out=numbers)
        rows.append(calibrate._row(seed, name or variant or "program", numbers,
                                   limits, t0))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workload", nargs="?", default="chaneq-cmt30.sweep-g4096")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=3_100_001)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    cseeds = [args.first_seed + 104729 + 7919 * i for i in range(args.control_seeds)]
    rows = readings(args.workload, seeds, args.seconds)
    for variant in ("reference_high", "reference_device"):
        rows += readings(args.workload, cseeds, args.seconds, variant=variant)
    rows += readings(args.workload, cseeds, args.seconds, scale=calibrate.BF16["fit"],
                     name="program_bf16")
    print(json.dumps({"workload": args.workload, "summary": calibrate.summary(rows)}))


if __name__ == "__main__":
    main()
