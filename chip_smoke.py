"""Bring-up check: the DFRC fit and the online server on a TPU, with the
Pallas kernels compiled by Mosaic.

Run from the root of a checkout (no install, no network; all data is made
from seeds):

    python chip_smoke.py               # one chip: the fit and serve phases
    python chip_smoke.py --four-chips  # four chips: sharded fit vs device 0

Phases (one process; any failed phase makes the script exit non-zero):

* fit — the paper's NARMA10 Silicon MR operating point (N = 900, washout
  60, five-value λ grid) over 512 task instances through ``Experiment.run``
  on the streaming path with both kernels; held to the NRMSE bounds of
  tests/test_pipeline.py (the per-instance one on its 8 seeds), to the
  plain ``fast``/einsum path on the first 8 instances, and to Mosaic having
  compiled both kernels.
* serve — ``DFRServer`` with the serving CLI's session settings on the
  kernel path, 512 slots, 1024 channel-equalization streams; every stream
  must finish and its predictions must match a ``fast``/einsum server's.
* fit_sharded (``--four-chips`` only) — the fit with its instance axis
  sharded over a 4-device ("data",) mesh against the same fit on device 0.

Times printed are bring-up readings of one cold run, compilation included
where stated; they are not benchmark numbers.  The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed on a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

FIT_BATCH = 512          # NARMA10 task instances (seeds 0..511)
FIT_REF_BATCH = 8        # instances re-run on the plain fast/einsum path
FIT_CHUNK = 256          # stream_chunk_k of the streaming fit
SERVE_SLOTS = 512
SERVE_REQUESTS = 1024
SERVE_STREAM_LEN = 512
BROKEN_NRMSE = 0.8       # a broken readout (tests/test_pipeline.py docstring)
PARITY_TOL = 1e-3        # kernel vs fast path (tests/test_pipeline.py)
SHARD_TOL = 1e-5         # four-chip sharded fit vs device 0


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named pass/fail checks of one phase, each printed as it is made."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        log(f"  check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def peak_bytes() -> int:
    import jax

    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# fit
# ---------------------------------------------------------------------------


def narma_batch(n: int):
    """Seeds 0..n-1 of NARMA10 at the generator's default 2000 samples,
    split 1000/1000: (train in, train target, test in, test target)."""
    from repro.core import tasks

    dss = [tasks.narma10(seed=s) for s in range(n)]
    return tuple(np.stack([getattr(d, f) for d in dss]).astype(np.float32)
                 for f in ("inputs_train", "targets_train",
                           "inputs_test", "targets_test"))


def fit_config(path: str):
    """The paper's NARMA10 Silicon MR operating point on the streaming
    path: ``kernel`` (dfr_scan + ridge_gram) or the plain ``fast``/einsum
    reference."""
    from repro.configs import dfrc_tasks
    from repro.pipeline import ExperimentConfig

    paths = {"kernel": dict(state_method="kernel", readout_use_kernel=True),
             "fast": dict(state_method="fast", readout_use_kernel=False)}
    base = ExperimentConfig.from_dfrc(dfrc_tasks()["narma10"]["Silicon MR"])
    return dataclasses.replace(base, stream_chunk_k=FIT_CHUNK,
                               state_noise_mode="diagonal", **paths[path])


def max_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def phase_fit(check: Checks) -> None:
    from repro.analysis import mosaic_kernels
    from repro.pipeline import Experiment
    from repro.pipeline.experiment import _run_pipeline

    t0 = time.perf_counter()
    batch = narma_batch(FIT_BATCH)
    log(f"  data: {FIT_BATCH} NARMA10 instances, T=1000/1000, made in "
        f"{time.perf_counter() - t0:.1f} s (host, bring-up reading)")

    cfg = fit_config("kernel")
    exp = Experiment(cfg)
    log(f"  config: N={cfg.n_nodes} washout={cfg.washout} "
        f"lambdas={cfg.ridge_l2} chunk={cfg.stream_chunk_k} "
        f"noise={cfg.state_noise_rel}/{cfg.state_noise_mode}")
    t0 = time.perf_counter()
    res = exp.run(*batch)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = exp.run(*batch)
    t_warm = time.perf_counter() - t0
    log(f"  kernel fit B={FIT_BATCH}: first call {t_cold:.2f} s (compile "
        f"included), second call {t_warm:.2f} s; peak_bytes_in_use="
        f"{peak_bytes()} (bring-up readings)")
    worst = np.argsort(res.nrmse)[::-1][:4]
    log(f"  NRMSE: mean={res.nrmse.mean():.4f} min={res.nrmse.min():.4f} "
        f"max={res.nrmse.max():.4f}; {int(np.sum(res.nrmse >= 0.72))} of "
        f"{FIT_BATCH} at or above 0.72; worst seeds "
        f"{dict(zip(worst.tolist(), np.round(res.nrmse[worst], 4).tolist()))}")
    check(bool(np.all(np.isfinite(res.nrmse))), "every NRMSE finite")
    check(float(res.nrmse.mean()) < 0.65, "mean NRMSE < 0.65")
    check(bool(np.all(res.nrmse > 0.2)), "every NRMSE > 0.2 (no leakage)")
    # tests/test_pipeline.py's per-instance bound is held over its own 8
    # seeds.  Over 512 seeds the task's own tail exceeds it: seed 407 scores
    # 0.736 even with a float64 host solve.  So the whole batch is held to
    # the bound that marks a broken readout or λ selection.
    check(bool(np.all(res.nrmse[:FIT_REF_BATCH] < 0.72)),
          f"NRMSE < 0.72 on seeds 0..{FIT_REF_BATCH - 1}")
    check(bool(np.all(res.nrmse < BROKEN_NRMSE)),
          f"every NRMSE < {BROKEN_NRMSE} (no broken readout)")

    t0 = time.perf_counter()
    text = _run_pipeline.lower(cfg, exp.mask, *batch).compile().as_text()
    kernels = mosaic_kernels(text)
    log(f"  compiled fit program: Mosaic kernels {dict(kernels)} "
        f"(lower+compile {time.perf_counter() - t0:.2f} s)")
    check(kernels["dfr_scan"] > 0, "dfr_scan compiled as tpu_custom_call")
    check(kernels["ridge_gram_into"] > 0,
          "ridge_gram_into compiled as tpu_custom_call")

    ref_batch = tuple(a[:FIT_REF_BATCH] for a in batch)
    t0 = time.perf_counter()
    ref = Experiment(fit_config("fast")).run(*ref_batch)
    log(f"  fast/einsum fit B={FIT_REF_BATCH}: {time.perf_counter() - t0:.2f}"
        f" s (compile included, bring-up reading)")
    d_nrmse = max_diff(ref.nrmse, res.nrmse[:FIT_REF_BATCH])
    d_pred = max_diff(ref.y_pred, res.y_pred[:FIT_REF_BATCH])
    log(f"  kernel vs fast, first {FIT_REF_BATCH}: max|dNRMSE|={d_nrmse:.3e} "
        f"max|dy|={d_pred:.3e}")
    check(d_nrmse <= PARITY_TOL, f"kernel vs fast NRMSE within {PARITY_TOL}")
    check(d_pred <= PARITY_TOL, f"kernel vs fast predictions within {PARITY_TOL}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve(path: str, requests):
    """Drain ``requests`` (fresh copies) through a ``DFRServer`` with the
    serving CLI's session settings (launch/serve_dfr.py defaults) on
    ``path``; returns (server, warmup seconds, drain seconds)."""
    from repro.launch.serve_dfr import DFRServer, StreamRequest
    from repro.pipeline.session import SessionConfig

    kernel = path == "kernel"
    cfg = SessionConfig(n_nodes=64, washout=32, chunk_k=32, forgetting=0.99,
                        refresh_every=4, ridge_l2=(1e-8, 1e-6, 1e-4),
                        state_method=path, use_kernel=kernel)
    server = DFRServer(cfg, SERVE_SLOTS)
    t0 = time.perf_counter()
    server.warmup()
    t_warm = time.perf_counter() - t0
    for r in requests:
        server.submit(StreamRequest(rid=r.rid, j=r.j, y=r.y))
    t0 = time.perf_counter()
    server.drain()
    return server, t_warm, time.perf_counter() - t0


def phase_serve(check: Checks) -> None:
    import jax.numpy as jnp

    from repro.analysis import mosaic_kernels
    from repro.launch.serve_dfr import chan_eq_requests, online_ser

    requests = chan_eq_requests(SERVE_REQUESTS, SERVE_STREAM_LEN, 32)
    preds = {}
    for path in ("kernel", "fast"):
        server, t_warm, t_drain = serve(path, requests)
        done = {r.rid: np.concatenate(r.y_hat) for r in server.completed}
        preds[path] = done
        ser, ser_tail = online_ser(server.completed, server.cfg.washout)
        stats = server.stats()
        log(f"  {path} server: {SERVE_SLOTS} slots, {len(done)}/"
            f"{SERVE_REQUESTS} streams in {stats['tick']} ticks; warmup "
            f"{t_warm:.2f} s (compile included), drain {t_drain:.2f} s; "
            f"peak_bytes_in_use={peak_bytes()} (bring-up readings)")
        log(f"  {path} server: online SER={ser:.4f} steady SER={ser_tail:.4f}"
            f" quarantine_events={stats['quarantine_events']}")
        check(len(done) == SERVE_REQUESTS and not server.evicted,
              f"{path} server finished every request")
        check(all(len(y) == len(r.j) for r, y in
                  ((r, done.get(r.rid, ())) for r in requests)),
              f"{path} server predicted every period")
        check(all(np.all(np.isfinite(y)) for y in done.values()),
              f"{path} server predictions finite")
        if path == "kernel":
            z = jnp.zeros((SERVE_SLOTS, 32), jnp.float32)
            text = server._step.lower(
                server.cfg, server.mask, server.state, z, z, refresh=True,
                n_valid=jnp.zeros((SERVE_SLOTS,), jnp.int32),
                reset=jnp.zeros((SERVE_SLOTS,), bool)).compile().as_text()
            kernels = mosaic_kernels(text)
            log(f"  compiled serving step: Mosaic kernels {dict(kernels)}")
            check(kernels["dfr_scan"] > 0 and kernels["ridge_gram_into"] > 0,
                  "serving step holds both kernels as tpu_custom_call")
    common = sorted(set(preds["kernel"]) & set(preds["fast"]))
    d_pred = max((max_diff(preds["kernel"][r], preds["fast"][r])
                  for r in common), default=float("inf"))
    log(f"  kernel vs fast server: max|dy|={d_pred:.3e} over {len(common)} "
        f"streams")
    check(len(common) == SERVE_REQUESTS and d_pred <= PARITY_TOL,
          f"kernel vs fast server predictions within {PARITY_TOL}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_fit_sharded(check: Checks) -> None:
    import jax

    from repro.compat import make_mesh, use_mesh
    from repro.pipeline import Experiment
    from repro.pipeline.experiment import _run_pipeline

    batch = narma_batch(FIT_BATCH)
    cfg = fit_config("kernel")
    exp = Experiment(cfg)
    mesh = make_mesh((4,), ("data",))
    t0 = time.perf_counter()
    with use_mesh(mesh):
        y, nrmse, _, lam, _ = jax.block_until_ready(
            _run_pipeline(cfg, exp.mask, *batch))
    log(f"  sharded fit B={FIT_BATCH} over {mesh.devices.size} devices: "
        f"{time.perf_counter() - t0:.2f} s (compile included, bring-up "
        f"reading)")
    for name, out in (("y_pred", y), ("nrmse", nrmse), ("lam", lam)):
        devs = {s.device for s in out.addressable_shards}
        rows = sorted({s.data.shape[0] for s in out.addressable_shards})
        log(f"  {name}: {out.sharding.spec} on {len(devs)} devices, "
            f"rows per shard {rows}")
        check(len(devs) == 4 and rows == [FIT_BATCH // 4],
              f"{name} spans 4 devices, {FIT_BATCH // 4} rows each")

    t0 = time.perf_counter()
    one = exp.run(*batch)
    log(f"  device-0 fit: {time.perf_counter() - t0:.2f} s (compile "
        f"included, bring-up reading); NRMSE mean={one.nrmse.mean():.4f}")
    d_nrmse = max_diff(nrmse, one.nrmse)
    d_pred = max_diff(y, one.y_pred)
    log(f"  sharded vs device 0: max|dNRMSE|={d_nrmse:.3e} max|dy|={d_pred:.3e}")
    check(d_nrmse <= SHARD_TOL, f"sharded NRMSE within {SHARD_TOL}")
    check(d_pred <= SHARD_TOL, f"sharded predictions within {SHARD_TOL}")
    check(bool(np.array_equal(np.asarray(lam), one.lam)), "same λ choices")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fit on four chips and the "
                         "same fit on device 0")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s), JAX found "
              f"{len(devices)} {dev.platform!r} device(s); no phase run",
              file=sys.stderr)
        return 2
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    phases = ([("fit_sharded", phase_fit_sharded)] if args.four_chips
              else [("fit", phase_fit), ("serve", phase_serve)])
    failed = []
    for name, phase in phases:
        log(f"phase {name}")
        check = Checks()
        t0 = time.perf_counter()
        try:
            phase(check)
        except Exception:
            traceback.print_exc()
            check(False, f"phase {name} raised")
        log(f"phase {name}: {'FAILED' if check.failed else 'ok'} "
            f"({time.perf_counter() - t0:.1f} s, bring-up reading)")
        if check.failed:
            failed.append(name)
    if failed:
        log(f"chip_smoke: failed phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
