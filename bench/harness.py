"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` -- the configuration (the deployment);
* ``bench/traffic/<traffic>.json`` -- the traffic mix; its ``kind`` picks
  the module ``bench/kinds/<kind>.py``, which sets up, runs the measured
  window and checks what the window produced against ``bench/reference.py``;
* ``bench/limits/<workload>.json`` -- the limit of each number compared;
* ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``
  -- one reader per metric: ``read(ctx)`` returns the value, or None when
  the run holds nothing to read.

``run_cell`` is the whole run without the look for a chip, so the tests
can rehearse a cell on the CPU at a small size (``scale``) and plant
faults underneath (``fault``).
"""

from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def _override(d: dict, overrides: dict | None) -> dict:
    """Copy of ``d`` with dotted keys (``"fit.stream_chunk_k"``) replaced."""
    d = json.loads(json.dumps(d))
    for key, value in (overrides or {}).items():
        node = d
        *path, last = key.split(".")
        for p in path:
            node = node[p]
        node[last] = value
    return d


def cell_spec(workload: str, scale: dict | None = None, held_out: bool = False):
    """(benchmark, cell, config, mix, limits) for ``workload``.

    ``held_out`` also finds the cells of ``bench/held_out.json``: cells
    kept out of ``BENCHMARK.json`` while the program fails their check
    on the chip, whose yardstick stays tested on the CPU (PERF.md).
    """
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells and held_out:
        bench = load_json(BENCH / "held_out.json")
        cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[cell["config"]]
    scale = scale or {}
    config = _override(load_json(ROOT / cfg_file), scale.get("config"))
    mix = _override(load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
                    scale.get("mix"))
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    return bench, cell, config, mix, limits


def metrics_of(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or its per-layer metrics when traced."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            or ("workloads" not in m and m["moves"] in reported)]


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(ctx, metrics: list[dict], folder: str) -> dict:
    out = {}
    for m in metrics:
        value = _load(BENCH / folder / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class CompileLog:
    """Compile and persistent-cache events, from jax.monitoring."""

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        self.seconds = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.counts.update([name]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.seconds.update({name: secs}))

    def backend_seconds(self) -> float:
        """Seconds in XLA compiles or persistent-cache loads."""
        return self.seconds["/jax/core/compile/backend_compile_duration"]

    def compiles(self) -> int:
        return self.counts["/jax/compilation_cache/compile_requests_use_cache"]

    def summary(self) -> dict:
        return {"cache_hits": self.counts["/jax/compilation_cache/cache_hits"],
                "cache_misses": self.counts["/jax/compilation_cache/cache_misses"],
                "compile_requests": self.compiles(),
                "seconds": {k.rsplit("/", 1)[-1]: round(v, 4)
                            for k, v in self.seconds.items()}}


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or the
    directory ``JAX_COMPILATION_CACHE_DIR`` names); every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries(path: str, top: int = 6) -> list:
    try:
        files = [(f.name, f.stat().st_size) for f in Path(path).iterdir()]
    except OSError:
        return []
    return sorted(files, key=lambda f: -f[1])[:top]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


@contextlib.contextmanager
def traced(enabled: bool, trace_dir: Path):
    """Profile the block (Python tracing off) when enabled."""
    import jax

    if not enabled:
        yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def check_numbers(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit; a missing or non-finite number fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, scale: dict | None = None,
             variant: str | None = None, fault: str | None = None,
             keep_trace: Path | None = None,
             numbers_out: dict | None = None, held_out: bool = False) -> dict:
    """One run of ``workload``: set up, measure, check; the result dict.

    ``variant`` checks other answers in place of the program's:
    ``"reference_high"``, the control (the reference with its readout at
    ``Precision.HIGH``), or ``"reference_device"``, the reference computed
    on the accelerator; ``fault`` plants a fault under the timed path.
    Neither is used by the benchmark's own runs.  ``numbers_out`` receives
    the numbers the check computed; ``held_out`` admits the cells of
    ``bench/held_out.json``.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell, config, mix, limits = cell_spec(workload, scale, held_out)
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from bench import counts, trace as trace_mod

    cache_dir = enable_compile_cache()
    clog = CompileLog()
    kind = _load(BENCH / "kinds" / f"{mix['kind']}.py")
    ctx = types.SimpleNamespace(
        workload=workload, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), bench=bench, cell=cell, config=config, mix=mix,
        variant=variant, fault=fault, device=device_info(), counts=counts,
        split={"imports_s": time.perf_counter() - t_start}, record={},
        trace_summary=None, limits_sample=limits["sample"], log=log)
    peaks = load_json(BENCH / "peaks.json")
    ctx.peak = peaks.get(ctx.device["kind"])

    kind.setup(ctx, clog)
    ctx.setup_s = time.perf_counter() - t_start
    log("setup split: " + json.dumps({k: round(v, 3) for k, v in ctx.split.items()})
        + f" total {ctx.setup_s:.3f} s")
    log(f"compile cache {cache_dir}: " + json.dumps(clog.summary())
        + " largest entries " + json.dumps(cache_entries(cache_dir)))

    compiles_before = clog.compiles()
    trace_dir = keep_trace or ROOT / ".bench_trace" / workload
    with traced(ctx.trace, trace_dir):
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            kind.window(ctx)
    ctx.record["window_compiles"] = clog.compiles() - compiles_before
    ctx.peak_bytes = peak_bytes()
    if ctx.trace:
        ctx.trace_summary = trace_mod.reduce_dir(
            str(trace_dir), kernels=kind.KERNELS)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log("window: " + json.dumps({k: v for k, v in ctx.record.items()
                                 if isinstance(v, (int, float, str))}))

    numbers = kind.check(ctx)
    if numbers_out is not None:
        numbers_out.update(numbers)
    correct, checks = check_numbers(numbers, limits["limits"])
    if ctx.record.get("window_compiles"):
        log(f"note: {ctx.record['window_compiles']} compile request(s) inside "
            "the measured window")

    trace_on = ctx.trace
    metrics = metrics_of(bench, workload, trace_on)
    folder = "layer_metrics" if trace_on else "end_to_end"
    result = {
        "correct": bool(correct),
        "attempted": int(ctx.record["attempted"]),
        "failed": int(ctx.record["failed"]),
        "metrics": read_metrics(ctx, metrics, folder),
        "device": {**ctx.device, "memory_peak_bytes": ctx.peak_bytes},
    }
    if trace_on and ctx.trace_summary is not None:
        result["device"]["busy_s"] = ctx.trace_summary["busy_s"]
        result["device"]["window_s"] = ctx.trace_summary["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace_summary["device_ops"],
                               "idle_gaps": ctx.trace_summary["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, *_ = cell_spec(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform!r} device(s)")
        return 2
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0
