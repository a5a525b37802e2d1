import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Must precede all other imports (jax locks device count at first init).

"""Structure-calibrated cost extraction (DESIGN.md §6).

XLA's ``cost_analysis()`` counts a ``while`` body ONCE regardless of trip
count, so a scanned-layers model under-reports FLOPs / bytes / collectives
by ~n_layers × microbatches.  Rather than unrolling the full program (which
explodes compile time), we compile cheap *variants at full tensor dims* and
solve for the per-component costs exactly:

  train:  A = opt + emb + 1·unit                 (U'=1, M'=1)
          B = opt + emb + 2·unit                 (U'=2 fully unrolled, M'=1)
          C = opt + 2·(emb + 1·unit)             (U'=1, M'=2 fully unrolled)
          -> unit = B−A;  emb = C−A−unit;  opt = A−unit−emb
          total(U, M) = opt + M·(emb + U·unit)
  serve:  A = base + 1·unit;  B = base + 2·unit
          -> unit = B−A;  total(U) = base + U·unit
  (+ an E'=2 encoder variant for enc-dec archs.)

Known residual under-counts (inner ``while`` loops inside one unit body,
counted once per body): sLSTM's sequence scan, the ReservoirMixer period
scan, and the chunked-attention KV scan.

Writes experiments/dryrun/calib__<arch>__<shape>__pod.json.
"""

import argparse
import dataclasses
import json
import time

import jax

from repro.configs import get_config, input_specs, list_archs, runnable_cells, SHAPES
from repro.launch.dryrun import OUT_DIR, build_step, collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.compat import use_mesh


def _variant(cfg, *, units: int, microbatches: int, enc_layers: int | None = None):
    return dataclasses.replace(
        cfg,
        n_layers=units * len(cfg.unit),
        microbatches=microbatches,
        analysis_unroll=max(units, microbatches),
        n_encoder_layers=(enc_layers if enc_layers is not None else cfg.n_encoder_layers),
    )


def _resize_batch(specs, batch: int):
    """Shrink the batch dim of train/prefill input specs (not decode caches)."""
    out = {}
    for k, v in specs.items():
        if k == "cache":
            out[k] = v
        else:
            out[k] = jax.ShapeDtypeStruct((batch, *v.shape[1:]), v.dtype)
    return out


def _measure(cfg, shape, mesh, batch: int | None = None):
    specs = input_specs(cfg, shape)
    if batch is not None:
        specs = _resize_batch(specs, batch)
    with use_mesh(mesh):
        fn, args = build_step(cfg, shape, mesh, specs=specs)
        compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["total"]),
    }


def _sub(a, b):
    return {k: max(0.0, a[k] - b[k]) for k in a}


def calibrate_cell(arch: str, shape: str, *, force: bool = False,
                   overrides: dict | None = None, tag: str = "") -> dict:
    from repro.launch.dryrun import apply_overrides

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    out_path = OUT_DIR / f"calib__{arch}__{shape}__pod{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = apply_overrides(get_config(arch), overrides)
    mesh = make_production_mesh()
    kind = SHAPES[shape]["kind"]
    enc = cfg.n_encoder_layers

    # Train variants run at the *microbatch* batch size, so the measured
    # per-unit / per-embedding costs are exactly one microbatch's worth.
    b_mb = None
    if kind == "train":
        b_mb = SHAPES[shape]["batch"] // cfg.microbatches

    a = _measure(_variant(cfg, units=1, microbatches=1, enc_layers=min(1, enc)),
                 shape, mesh, batch=b_mb)
    b = _measure(_variant(cfg, units=2, microbatches=1, enc_layers=min(1, enc)),
                 shape, mesh, batch=b_mb)
    unit = _sub(b, a)

    rec = {"arch": arch, "shape": shape, "unit": unit, "n_units": cfg.n_units}
    if kind == "train":
        c = _measure(_variant(cfg, units=1, microbatches=2, enc_layers=min(1, enc)),
                     shape, mesh, batch=2 * b_mb)
        emb = _sub(_sub(c, a), unit)
        opt = _sub(_sub(a, unit), emb)
        rec.update({"emb": emb, "opt": opt, "microbatches": cfg.microbatches})
        total = {k: opt[k] + cfg.microbatches * (emb[k] + cfg.n_units * unit[k]) for k in unit}
    else:
        base = _sub(a, unit)
        rec["base"] = base
        total = {k: base[k] + cfg.n_units * unit[k] for k in unit}

    if enc:
        d = _measure(_variant(cfg, units=1, microbatches=1, enc_layers=2),
                     shape, mesh, batch=b_mb)
        enc_unit = _sub(d, a)
        rec["enc_unit"] = enc_unit
        mult = cfg.microbatches if kind == "train" else 1
        for k in total:
            total[k] += mult * (enc - 1) * enc_unit[k]

    rec["total"] = total
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.set)

    cells = []
    if args.all:
        for arch in list_archs(include_extras=True):
            for shape in runnable_cells(arch):
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    for arch, shape in cells:
        t0 = time.time()
        try:
            rec = calibrate_cell(arch, shape, force=args.force,
                                 overrides=overrides, tag=args.tag)
            msg = f"ok flops={rec['total']['flops']:.3e} coll={rec['total']['coll']:.3e}B"
        except Exception as e:  # noqa: BLE001
            msg = f"FAIL {type(e).__name__}: {e}"
        print(f"[{time.time()-t0:7.1f}s] calib {arch:24s} {shape:12s} {msg}", flush=True)


if __name__ == "__main__":
    main()
