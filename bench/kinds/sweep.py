"""A device design-space sweep: one ``Experiment.run`` call is a whole
(detuning x loss x power) robustness map of the CMT microring cavity.

The grid (the mix's ``detune``, ``loss_scale`` and ``power_mw`` axes) is
fixed, so every seed runs the same work; its G points ride the batch lanes
as per-lane device parameters (``dev_params``), the call
``repro.devices.run_device_sweep`` makes: metrics only
(``collect_y_pred=False``), so a call's answers are each grid point's
NRMSE, SER, lambda and readout.  One task link is broadcast over the lanes,
so that the map isolates the device: set-up makes a pool of ``links``
channel-equalization links (the mix's ``pool_seed``), and the calls cycle
through them in an order drawn from the run seed.  Each call sends one link
to the device in one transfer (its four series end to end) and tiles it
there in one dispatch; calls run one after another, as
``run_device_sweep`` makes them.  The run seed also draws the
lanes whose answers are compared with the reference
(``bench/reference_cmt.py`` states, ``bench.reference``'s ridge system;
``compare``).
"""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from bench import counts_cmt, reference, reference_cmt
from bench.kinds import fit
from bench.traffic import generate, tasks

KERNELS = fit.KERNELS
# the purpose of the link pool's random stream (bench/traffic/generate.py uses 1-3)
_LINK_POOL = 4


def axis(spec: dict) -> np.ndarray:
    """A grid axis from ``{"linspace": [a, b, n]}`` or ``{"geomspace": ...}``."""
    (how, (a, b, n)), = spec.items()
    return getattr(np, how)(float(a), float(b), int(n)).astype(np.float32)


def grid_lanes(mix: dict) -> dict:
    """The grid raveled into [G] lanes: detune slowest, power fastest."""
    d, ls, p = np.meshgrid(axis(mix["detune"]), axis(mix["loss_scale"]),
                           axis(mix["power_mw"]), indexing="ij")
    return {"detune": d.ravel(), "loss_scale": ls.ravel(), "power": p.ravel()}


def link_pool(config: dict, mix: dict) -> list:
    """The fixed pool of links: (train in, train target, test in, test
    target) float32 [T] arrays each."""
    task = config["task"]
    seeds = np.random.default_rng([int(mix["pool_seed"]), _LINK_POOL]).integers(
        0, 2**62, size=int(mix["links"]))
    return [tuple(np.asarray(a, np.float32) for a in tasks.channel_equalization(
        task["n_symbols"], snr_db=task["snr_db"], train_frac=task["train_frac"],
        seed=int(s))) for s in seeds]


def model(config: dict):
    from repro.devices import MRCavityCMT

    params = dict(config["model"])
    params.pop("name")
    return MRCavityCMT(**params)


def experiment_config(config: dict):
    from repro.pipeline import ExperimentConfig

    fit_cfg = config["fit"]
    return ExperimentConfig(
        model=model(config), n_nodes=int(config["n_nodes"]),
        mask_levels=tuple(config["mask_levels"]),
        input_gain=float(config["input_gain"]),
        normalize_input=bool(config["normalize_input"]),
        washout=int(config["washout"]),
        ridge_l2=tuple(float(v) for v in config["ridge_l2"]),
        state_noise_rel=float(config["state_noise_rel"]),
        state_method=fit_cfg["state_method"],
        readout_use_kernel=bool(fit_cfg["readout_use_kernel"]),
        quantize=bool(config["quantize"]),
        stream_chunk_k=int(fit_cfg["stream_chunk_k"]),
        state_noise_mode=fit_cfg["state_noise_mode"],
        stream_state_dtype=fit_cfg["stream_state_dtype"],
        collect_y_pred=False)


class Sweep:
    """``Experiment.run`` over one link tiled on the device across the
    grid's lanes, at the lanes' device parameters; ``lowered`` is the same
    program lowered (``bench/scopes.py`` reads its scopes), and ``states``
    the program's states of given lanes (``check``)."""

    def __init__(self, exp, lanes: dict, lengths):
        import jax
        import jax.numpy as jnp

        from repro.devices import CMTSweepParams

        self.exp = exp
        self.g = len(lanes["detune"])
        self.params = CMTSweepParams(**{k: jnp.asarray(v) for k, v in lanes.items()})
        cuts = [int(c) for c in np.cumsum(lengths)[:-1]]
        # a link's four series, end to end in one array (``packed``), go to
        # the device in one transfer and are cut and tiled there
        self._tiled = jax.jit(lambda flat: [
            jnp.broadcast_to(a[None], (self.g,) + a.shape)
            for a in jnp.split(flat, cuts)])

    @staticmethod
    def packed(link) -> np.ndarray:
        """A link's four series end to end, as ``run`` takes them."""
        return np.concatenate(link)

    def run(self, flat):
        return self.exp.run(*self._tiled(flat), dev_params=self.params)

    def lowered(self, flat):
        return self.exp.lowered(*self._tiled(flat), dev_params=self.params)

    def states(self, tr_in, te_in, lanes: dict):
        """The program's (fit window past the washout, test) states of
        [S, T] inputs, lane s at the operating point ``lanes[leaf][s]``."""
        from repro.devices import CMTSweepParams

        tr, te = self.exp.states(tr_in, te_in, dev_params=CMTSweepParams(**lanes))
        washout = self.exp.config.washout
        return (np.asarray(tr, np.float32)[:, washout:], np.asarray(te, np.float32))


def _faulty(sweep: Sweep, fault: str):
    """``Sweep.run`` with one of the planted faults."""
    import jax

    def call(flat):
        if fault == "half_batch":
            # half the lanes swept; the rest get the first half's answers
            half = sweep.g // 2
            tiled = [a[:half] for a in sweep._tiled(flat)]
            params = jax.tree.map(lambda p: p[:half], sweep.params)
            res = sweep.exp.run(*tiled, dev_params=params)
            return dataclasses.replace(
                res, **{k: np.concatenate([getattr(res, k)] * 2)
                        for k in ("nrmse", "ser", "lam", "readout_w")})
        res = sweep.run(flat)
        if fault == "unchanged_state":
            # the readouts left as they started
            return dataclasses.replace(res, readout_w=np.zeros_like(res.readout_w))
        if fault == "alter_answer":
            # every grid point's metrics delivered to another point's lane
            return dataclasses.replace(res, nrmse=np.roll(res.nrmse, sweep.g // 2),
                                       ser=np.roll(res.ser, sweep.g // 2))
        raise ValueError(f"unknown fault {fault!r}")

    return call


def _dispatch_log() -> list:
    """The ``dfr_scan`` dispatches traced so far (the kernel's trace-time
    counter), or nothing where the program has no such counter."""
    from repro.kernels.dfr_scan import ops

    counts = getattr(ops, "dispatch_counts", None)
    return counts() if counts is not None else []


def setup(ctx, clog) -> None:
    import jax.numpy as jnp

    from repro.pipeline import Experiment

    config, mix = ctx.config, ctx.mix
    t0 = time.perf_counter()
    links = link_pool(config, mix)
    order = np.random.default_rng([ctx.seed % 2**64, 1]).permutation(len(links))
    lanes = grid_lanes(mix)
    mask = generate.mask(config)
    ctx.links, ctx.lanes, ctx.mask = links, lanes, mask
    # the rotations bench/scopes.py traces: the links in the seed's order
    ctx.rotations = [(int(i), (Sweep.packed(links[i]),)) for i in order]
    ctx.split["data_s"] = time.perf_counter() - t0

    exp = Experiment(experiment_config(config))
    exp.mask = jnp.asarray(mask)
    sweep = Sweep(exp, lanes, [len(a) for a in links[0]])
    ctx.sweep = sweep
    ctx.run = sweep.run if ctx.fault is None else _faulty(sweep, ctx.fault)
    t0 = time.perf_counter()
    c0 = clog.backend_seconds()
    ctx.run(*ctx.rotations[0][1])
    warm = time.perf_counter() - t0
    compile_s = clog.backend_seconds() - c0
    ctx.split["compile_or_cache_load_s"] = compile_s
    ctx.split["first_call_s"] = warm - compile_s
    ctx.log("dfr_scan dispatches: " + json.dumps(_dispatch_log()))

    t_train, t_test = len(links[0][0]), len(links[0][2])
    ctx.shape = dict(lanes=sweep.g, t_train=t_train, t_test=t_test,
                     n=int(config["n_nodes"]), washout=int(config["washout"]),
                     chunk=int(config["fit"]["stream_chunk_k"]),
                     n_lambdas=len(config["ridge_l2"]),
                     n_substeps=int(config["model"]["n_substeps"]))


def window(ctx) -> None:
    import jax

    # the grid lanes whose answers are compared, drawn from the seed
    sample = fit.sample_rows(ctx.seed, ctx.shape["lanes"], int(ctx.limits_sample))
    calls, call_s, t0 = [], [], time.perf_counter()
    while True:
        link, arrays = ctx.rotations[len(calls) % len(ctx.rotations)]
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("fit.call"):
            res = ctx.run(*arrays)
        w = np.asarray(res.readout_w).reshape(ctx.shape["lanes"], -1)
        finite = int(np.sum(~np.isfinite(res.nrmse))
                     + np.sum(~np.all(np.isfinite(w), axis=1)))
        calls.append({"link": link, "nrmse": res.nrmse[sample], "ser": res.ser[sample],
                      "lam": res.lam[sample], "w": w[sample], "not_finite": finite})
        call_s.append(time.perf_counter() - t1)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    n, g = len(calls), ctx.shape["lanes"]
    t = ctx.shape["t_train"] + ctx.shape["t_test"]
    ctx.calls, ctx.sample = calls, sample
    ctx.record.update(calls=n, window_s=window_s, periods=n * g * t,
                      attempted=n * g, failed=sum(c["not_finite"] for c in calls))
    ctx.sweep_counts = counts_cmt.sweep_call(**ctx.shape)
    ms = 1e3 * np.asarray(call_s)
    ctx.log("call ms: " + json.dumps({"first": round(ms[0], 3), "min": round(ms.min(), 3),
                                      "median": round(float(np.median(ms)), 3),
                                      "max": round(ms.max(), 3), "last": round(ms[-1], 3)}))


def readout_config(config: dict) -> dict:
    """The configuration as ``bench.reference``'s ridge system reads it.
    That system takes the Silicon MR's theta and tau_ph from the model,
    which the readout does not use; the cavity's theta and tau_L stand in
    for them (its zero-power map is the Silicon MR's with tau_ph = tau_L)."""
    m = config["model"]
    return {**config, "model": {"theta_ps": m["theta_ps"], "tau_ph_ps": m["tau_l_ps"],
                                "gamma": m["gamma"]}}


def _gaps(config: dict, data, states, calls) -> tuple[float, float, float]:
    """(objective excess, NRMSE gap, SER gap) of the readouts ``calls`` on
    ``states``, each the fit kind's quantile over the sampled lanes, the
    widest over calls."""
    washout = int(config["washout"])
    lams = np.log(np.asarray(config["ridge_l2"], np.float32))
    x_fit, x_te = states
    target = data[3].astype(np.float64)
    systems = [reference.ridge64(config, x_fit[i], data[1][i, washout:])
               for i in range(len(target))]
    xt = np.concatenate([x_te, np.ones(x_te.shape[:2] + (1,), x_te.dtype)], axis=2)
    xt = xt.astype(np.float64)
    sym = np.asarray(reference.SYMBOLS)
    n = [0.0, 0.0, 0.0]
    for c in calls:
        k = np.argmin(np.abs(lams[None, :] - np.log(c["lam"])[:, None]), axis=1)
        excess = [s.objective_excess(w, int(ki)) for s, w, ki in zip(systems, c["w"], k)]
        raw = np.einsum("stf,sf->st", xt, c["w"].astype(np.float64))
        decided = sym[np.argmin(np.abs(raw[..., None] - sym), axis=-1)]
        for i, v in enumerate((excess, np.abs(c["nrmse"] - fit._nrmse(raw, target)),
                               np.abs(c["ser"] - np.mean(decided != target, axis=1)))):
            n[i] = max(n[i], float(np.quantile(v, fit.QUANTILE)))
    return n[0], n[1], n[2]


def compare(config: dict, data, states, calls, own) -> dict:
    """The numbers of the check for a map's answers ``calls`` (one dict a
    call: ``nrmse``, ``ser``, ``lam`` and readout ``w`` of the sampled
    lanes) on the lanes' data ``data`` (four [S, T] arrays), whose
    reference states are ``states`` (``reference_cmt.fit_states``) and
    whose answers were computed from the states ``own``:

    * ``solve_excess`` and ``eval_gap`` -- as ``kinds/fit.compare``: the
      readout's objective excess in the float64 ridge system of the
      reference's states, and |reported NRMSE - NRMSE of that readout on
      the reference's test states|;
    * ``ser_gap`` -- |reported SER - SER of that readout's symbol decisions
      on the reference's test states|: the test states and the decisions;
    * ``readout_excess`` and ``readout_eval_gap`` -- the first two on the
      states ``own``.  They judge the readout solve and the test evaluation
      alone: the TPU rounds the cavity's exponentials and divisions apart
      from the host, and the loop carries that through the states by more
      than a readout one precision step down costs (PERF.md).

    Each the fit kind's quantile over the sampled lanes, the widest over
    calls."""
    excess, eval_gap, ser_gap = _gaps(config, data, states, calls)
    own_excess, own_eval_gap, _ = _gaps(config, data, own, calls)
    return {"solve_excess": excess, "eval_gap": eval_gap, "ser_gap": ser_gap,
            "readout_excess": own_excess, "readout_eval_gap": own_eval_gap}


def _reference_calls(config: dict, data, states, precision="highest", device=None):
    """The reference's answers in the program's form: at ``precision="high"``
    the control."""
    y, nrmse, idx, w = reference.fit_readout(config, states, data[1], data[3],
                                             precision=precision, device=device)
    return [{"nrmse": nrmse, "ser": np.mean(y != data[3], axis=1), "w": w,
             "lam": np.asarray(config["ridge_l2"], np.float32)[idx]}]


def check(ctx) -> dict:
    """Every call's answers for the sampled lanes against the reference
    (``compare``), link by link; each number the widest over links."""
    sample = ctx.sample
    lanes = {k: v[sample] for k, v in ctx.lanes.items()}
    used = sorted({c["link"] for c in ctx.calls})
    rcfg = readout_config(ctx.config)
    # every used link's sampled lanes in one reference call
    data = [np.concatenate([np.broadcast_to(ctx.links[i][a], (len(sample),)
                                            + ctx.links[i][a].shape) for i in used])
            for a in range(4)]
    all_lanes = {k: np.tile(v, len(used)) for k, v in lanes.items()}
    states = reference_cmt.fit_states(ctx.config, ctx.mask, data[0], data[2],
                                      all_lanes)
    if ctx.variant is None:
        # the states the program's answers were computed from
        own = ctx.sweep.states(data[0], data[2], all_lanes)
    elif ctx.variant == "reference_high":
        own = states
    elif ctx.variant == "reference_device":
        import jax

        device = jax.devices()[0]
        own = reference_cmt.fit_states(ctx.config, ctx.mask, data[0], data[2],
                                       all_lanes, device=device)
    else:
        raise ValueError(f"unknown variant {ctx.variant!r}")
    numbers = {}
    for k, link in enumerate(used):
        rows = slice(k * len(sample), (k + 1) * len(sample))
        d = [a[rows] for a in data]
        st = tuple(s[rows] for s in states)
        own_k = tuple(s[rows] for s in own)
        calls = [c for c in ctx.calls if c["link"] == link]
        if ctx.variant == "reference_high":
            calls = _reference_calls(rcfg, d, st, "high")
        elif ctx.variant == "reference_device":
            calls = _reference_calls(rcfg, d, own_k, device=device)
        for key, v in compare(rcfg, d, st, calls, own_k).items():
            numbers[key] = max(numbers.get(key, 0.0), v)
    numbers["not_finite"] = float(ctx.record["failed"])
    ctx.log("numbers: " + json.dumps(numbers)
            + f" ({len(sample)} sampled lanes x {len(ctx.calls)} calls,"
            f" {len(used)} links)")
    return numbers
