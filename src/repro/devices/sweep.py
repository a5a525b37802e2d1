"""Design-space exploration: a (detuning × loss × power) robustness map
as ONE compiled program (DESIGN.md §14).

The naive sweep — one ``Experiment`` per grid point — retraces and
recompiles per point, because device models are frozen-dataclass jit
*statics*.  This module folds the grid into **batch lanes** instead: the
grid's G = D·L·P points become G rows of a ``CMTSweepParams`` pytree whose
leaves are ``[G]`` *operands*, the task's train/test series are broadcast
over the same G lanes, and the whole robustness map runs through one
``Experiment.run(…, dev_params=…)`` call — one trace, one XLA program, no
full-stream state tensor (the streaming path), every lane vectorised over
the batch axis exactly like the paper's seed/SNR sweeps.

``repro.analysis`` gates the structure (``device_sweep*`` entry points), and
``pipeline_cache_size()`` exposes the jit cache counter the benchmark uses
to prove a second sweep with NEW grid values compiles nothing.

>>> grid = SweepGrid(detune=(-1.0, 0.0, 1.0), loss_scale=(0.5, 1.0),
...                  power=(0.0, 1.0))
>>> res = run_device_sweep(model, grid, tasks.narma10(1200))
>>> res.nrmse.shape                      # (3, 2, 2) — the folded map
>>> res.stable_region(nrmse_max=0.4)     # boolean map + flagged summary
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from .cmt import CMTSweepParams, MRCavityCMT


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """A (detune × loss_scale × power) parameter box, axis values as tuples."""

    detune: tuple[float, ...]
    loss_scale: tuple[float, ...]
    power: tuple[float, ...]

    def __post_init__(self):
        for f in ("detune", "loss_scale", "power"):
            if not isinstance(getattr(self, f), tuple):
                object.__setattr__(self, f, tuple(float(v)
                                                  for v in getattr(self, f)))
            if not getattr(self, f):
                raise ValueError(f"grid axis {f!r} is empty")

    @property
    def shape(self) -> tuple[int, int, int]:
        return (len(self.detune), len(self.loss_scale), len(self.power))

    @property
    def size(self) -> int:
        return math.prod(self.shape)

    def lanes(self) -> CMTSweepParams:
        """The grid raveled into per-lane ``[G]`` leaves (row-major: detune
        slowest, power fastest — ``fold`` is the inverse)."""
        d, l, p = jnp.meshgrid(jnp.asarray(self.detune, jnp.float32),
                               jnp.asarray(self.loss_scale, jnp.float32),
                               jnp.asarray(self.power, jnp.float32),
                               indexing="ij")
        return CMTSweepParams(detune=d.ravel(), loss_scale=l.ravel(),
                              power=p.ravel())

    def fold(self, values) -> np.ndarray:
        """Per-lane ``[G]`` results back into the ``(D, L, P)`` map."""
        return np.asarray(values).reshape(self.shape)

    def point(self, idx: tuple[int, int, int]) -> dict:
        return {"detune": self.detune[idx[0]],
                "loss_scale": self.loss_scale[idx[1]],
                "power": self.power[idx[2]]}


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """The folded robustness map: one cell per grid point, numpy on host."""

    grid: SweepGrid
    nrmse: np.ndarray      # [D, L, P]
    ser: np.ndarray        # [D, L, P]
    lam: np.ndarray        # [D, L, P] — GCV-selected ridge λ per point

    def stable_region(self, *, nrmse_max: float = 0.4) -> dict:
        """Flag the stable operating region: finite NRMSE under the bound.

        Returns the boolean map plus a JSON-ready summary (fraction stable,
        the best point, and the stable bounding box per axis) — what the
        benchmark artifact records and a DSE user reads first.
        """
        ok = np.isfinite(self.nrmse) & (self.nrmse <= nrmse_max)
        summary = {"nrmse_max": nrmse_max,
                   "n_stable": int(ok.sum()), "n_total": int(ok.size),
                   "stable_fraction": round(float(ok.mean()), 4)}
        if ok.any():
            masked = np.where(ok, self.nrmse, np.inf)
            best = np.unravel_index(int(np.argmin(masked)), ok.shape)
            summary["best_point"] = {**self.grid.point(best),
                                     "nrmse": round(float(self.nrmse[best]), 4),
                                     "ser": round(float(self.ser[best]), 4)}
            axes = ("detune", "loss_scale", "power")
            for ax, name in enumerate(axes):
                hit = ok.any(axis=tuple(i for i in range(3) if i != ax))
                vals = [getattr(self.grid, name)[i]
                        for i in np.flatnonzero(hit)]
                summary[f"stable_{name}"] = [min(vals), max(vals)]
        return {"map": ok, "summary": summary}


def _tile(x, g: int) -> jnp.ndarray:
    x = jnp.asarray(x, jnp.float32)
    return jnp.broadcast_to(x[None, :], (g,) + x.shape)


def run_device_sweep(model: MRCavityCMT, grid: SweepGrid, dataset, *,
                     n_nodes: int = 50, washout: int = 50,
                     stream_chunk_k: int | None = 256,
                     ridge_l2: tuple[float, ...] = (1e-8, 1e-6, 1e-4),
                     state_method: str = "fast",
                     mask_seed: int = 1) -> SweepResult:
    """The whole robustness map from ONE compiled vmapped Experiment.

    ``dataset`` is a ``core.tasks`` Dataset (one task instance); its series
    are broadcast over the G grid lanes, so every lane sees the *same* data
    and the map isolates the device physics.  ``stream_chunk_k`` keeps the
    run on the streaming path (no [G, T, N] state tensor — the jaxpr-gated
    contract); ``None`` falls back to the materialized path for short tasks.

    Swept parameters ride the batch lanes as operands, so calling this again
    with a same-shape grid of different VALUES reuses the compiled program
    (``pipeline_cache_size()`` proves it).  ``state_method="kernel"`` runs
    the map on the Pallas ``dfr_scan``, the parameters as per-lane tiles.
    """
    # lazy import: repro.pipeline imports repro.core, which must finish
    # initialising before the devices package pulls the pipeline in
    from repro.pipeline import Experiment, ExperimentConfig

    cfg = ExperimentConfig(model=model, n_nodes=n_nodes, washout=washout,
                           ridge_l2=ridge_l2, state_method=state_method,
                           stream_chunk_k=stream_chunk_k,
                           state_noise_rel=0.0, collect_y_pred=False)
    g = grid.size
    res = Experiment(cfg).run(
        _tile(dataset.inputs_train, g), _tile(dataset.targets_train, g),
        _tile(dataset.inputs_test, g), _tile(dataset.targets_test, g),
        dev_params=grid.lanes())
    return SweepResult(grid=grid, nrmse=grid.fold(res.nrmse),
                       ser=grid.fold(res.ser), lam=grid.fold(res.lam))


def pipeline_cache_size() -> int:
    """Compiled-program count of the pipeline entry — the no-retrace proof:
    two sweeps with different same-shape grids must leave this unchanged."""
    from repro.pipeline.experiment import _run_pipeline
    return int(_run_pipeline._cache_size())
