"""The one generator every traffic mix goes through.

A mix is a data file ``bench/traffic/<mix>.json``; its ``kind`` says which
of the functions here reads it.  The input mask is the configuration's own
(``mask_seed``), as one device has one mask.  A fit's instances are one
fixed set (the mix's ``pool_seed``) in an order drawn from the run seed, so
that every seed fits the same work: the solver's iterations depend on the
data.  A serving pool's streams come from the run seed.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import tasks

_ORDER, _INSTANCES, _STREAMS = 1, 2, 3


def _rng(seed: int, purpose: int):
    return np.random.default_rng([int(seed) % 2**64, purpose])


def mask(config: dict) -> np.ndarray:
    """The configuration's MLS input mask [N] (float32)."""
    return tasks.make_mask(int(config["n_nodes"]),
                           levels=tuple(config["mask_levels"]),
                           seed=int(config["mask_seed"]))


def fit_pool(config: dict, mix: dict, seed: int):
    """One call's instances: (train in, train target, test in, test target),
    each [B, T] float32, one task instance per row, in the run seed's order."""
    task = config["task"]
    batch = int(mix["instances_per_call"])
    seeds = _rng(int(mix["pool_seed"]), _INSTANCES).integers(0, 2**62, size=batch)
    seeds = _rng(seed, _ORDER).permutation(seeds)
    if task["name"] == "narma10":
        arrays = tasks.narma10_batch(seeds, task["n_samples"],
                                     train_frac=task["train_frac"])
    elif task["name"] == "channel_equalization":
        links = [tasks.channel_equalization(task["n_symbols"],
                                            snr_db=task["snr_db"],
                                            train_frac=task["train_frac"],
                                            seed=int(s)) for s in seeds]
        arrays = [np.stack(a) for a in zip(*links)]
    else:
        raise ValueError(f"no generator for task {task['name']!r}")
    return tuple(np.ascontiguousarray(a, np.float32) for a in arrays)


def serve_pool(config: dict, mix: dict, seed: int):
    """The pool of full-length streams, (j [K], y [K]) float32 each."""
    task = config["task"]
    if task["name"] != "channel_equalization":
        raise ValueError(f"no stream generator for task {task['name']!r}")
    chunk = int(config["serve"]["chunk_k"])
    base = int(_rng(seed, _STREAMS).integers(0, 2**62))
    return tasks.chan_eq_streams(int(mix["pool_streams"]),
                                 int(mix["stream_chunks"]) * chunk, chunk,
                                 snr_db=task["snr_db"], seed=base)


def first_wave_chunks(mix: dict, client: int) -> int:
    """Length, in chunks, of a client's first stream: staggered over
    1..stream_chunks so that completions spread evenly over the ticks."""
    full = int(mix["stream_chunks"])
    return 1 + client % full
