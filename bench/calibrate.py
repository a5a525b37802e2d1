"""Read the check's numbers over many seeds, for the program and for its
controls, at the cell's own size:

    python3 -m bench.calibrate <workload> [--seeds 12] [--control-seeds 3]
                               [--seconds 1] [--first-seed 3000001]

The program's readings are whole runs through ``harness.run_cell`` in one
process (the programs compile once), with a short window.  Then, on other
seeds:

* ``reference_high`` -- the control: the reference with its readout
  matmuls at ``Precision.HIGH`` (three bfloat16 passes, emulated exactly),
  the step below the ``HIGHEST`` the configuration states, in the
  program's place;
* ``program_bf16`` -- the program's own path one step below the float32
  states the configuration states (bfloat16 state chunks), a whole run;
* ``reference_device`` -- a witness: the same float32 reference computed on
  the accelerator instead of the host, judged against the host's.

The references answer the same sampled instances, or pool streams at the
cell's stream length with admission ticks drawn from the seed, and need no
window.  Prints one JSON line per reading and, last, for every number its
largest reading over the program's seeds and its smallest over each
control's.  The limits in ``bench/limits`` are set between those readings
(PERF.md gives them).
"""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from bench import harness

BF16 = {"fit": {"config": {"fit.stream_state_dtype": "bfloat16"}},
        "serve": {"config": {"serve.state_dtype": "bfloat16"}}}


def _row(seed, variant, numbers, limits, t0):
    correct, _ = harness.check_numbers(numbers, limits["limits"])
    row = {"seed": seed, "variant": variant, "correct": correct, "numbers": numbers,
           "run_s": round(time.perf_counter() - t0, 2)}
    print(json.dumps(row), flush=True)
    return row


def program(workload, seeds, seconds, scale=None, name="program"):
    _, _, _, _, limits = harness.cell_spec(workload, held_out=True)
    rows = []
    for seed in seeds:
        numbers = {}
        t0 = time.perf_counter()
        harness.run_cell(workload, seed, seconds, False, scale=scale,
                         numbers_out=numbers, held_out=True)
        rows.append(_row(seed, name, numbers, limits, t0))
    return rows


def reference_variant(workload, seeds, variant, scale=None):
    """Readings of a reference variant put in the program's place."""
    from bench import reference
    from bench.kinds import fit, serve
    from bench.traffic import generate

    _, _, config, mix, limits = harness.cell_spec(workload, scale, held_out=True)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        mask = generate.mask(config)
        if mix["kind"] == "fit":
            pool = generate.fit_pool(config, mix, seed)
            data = [a[fit.sample_rows(seed, pool[0].shape[0], int(limits["sample"]))]
                    for a in pool]
            states = reference.fit_states(config, mask, data[0], data[2])
            calls = fit.variant_calls(variant, config, mask, data, states)
            numbers = fit.compare(config, data, states, calls, 0)
        else:
            pool = generate.serve_pool(config, mix, seed)
            rng = np.random.default_rng([seed % 2**64, 11])
            pick = rng.choice(len(pool), size=min(int(limits["sample"]), len(pool)),
                              replace=False)
            every = int(config["serve"]["refresh_every"])
            streams = [(*pool[k], int(rng.integers(0, every))) for k in pick]
            numbers = serve.compare(config, streams,
                                    serve.variant_served(variant, config, mask, streams),
                                    reference.sessions(config, mask, streams))
        rows.append(_row(seed, variant, numbers, limits, t0))
    return rows


def summary(rows) -> dict:
    """Per number: the program's largest reading, each control's smallest."""
    def finite(variant, k):
        return [r["numbers"][k] for r in rows if r["variant"] == variant
                and r["numbers"].get(k) is not None and math.isfinite(r["numbers"][k])]

    variants = sorted({r["variant"] for r in rows} - {"program"})
    names = sorted({k for r in rows for k in r["numbers"]})
    return {k: {"program_max": max(finite("program", k), default=None),
                **{f"{v}_min": min(finite(v, k), default=None) for v in variants}}
            for k in names}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=3_000_001)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    cseeds = [args.first_seed + 104729 + 7919 * i for i in range(args.control_seeds)]
    kind = harness.cell_spec(args.workload, held_out=True)[3]["kind"]
    rows = program(args.workload, seeds, args.seconds)
    rows += reference_variant(args.workload, cseeds, "reference_high")
    rows += reference_variant(args.workload, cseeds, "reference_device")
    rows += program(args.workload, cseeds, args.seconds, BF16[kind], "program_bf16")
    print(json.dumps({"workload": args.workload, "summary": summary(rows)}))


if __name__ == "__main__":
    main()
