"""Batched readout fits: ``Experiment.run`` calls back to back.

Set-up makes a pool of B task instances from the seed, ``rotations``
copies of it with the rows in another lane order, and the input mask;
it builds the ``Experiment`` and makes one warm call, which compiles (or
loads from the cache) the whole fit program.  The window then runs calls
back to back, cycling through the rotations, until ``--seconds`` have
passed; the last call is let finish.  Every call's answers for the sampled
pool instances are compared with the reference after the window.
"""

from __future__ import annotations

import json
import time

import numpy as np

from bench import reference
from bench.traffic import generate

KERNELS = ("dfr_scan", "ridge_gram_into")
# decision boundaries of the 4-PAM symbols, and the distance from them
# beyond which float32 rounding cannot change a decision
BOUNDARIES = np.asarray([-2.0, 0.0, 2.0])
MARGIN = 1e-3
# the share of a call's sampled instances that each compared number holds
QUANTILE = 0.9


def _model(config: dict):
    from repro.core import nonlinear

    params = dict(config["model"])
    return getattr(nonlinear, params.pop("name"))(**params)


def experiment_config(config: dict):
    from repro.pipeline import ExperimentConfig

    fit = config["fit"]
    return ExperimentConfig(
        model=_model(config), n_nodes=int(config["n_nodes"]),
        mask_levels=tuple(config["mask_levels"]),
        input_gain=float(config["input_gain"]),
        normalize_input=bool(config["normalize_input"]),
        washout=int(config["washout"]),
        ridge_l2=tuple(float(v) for v in config["ridge_l2"]),
        state_noise_rel=float(config["state_noise_rel"]),
        state_method=fit["state_method"],
        readout_use_kernel=bool(fit["readout_use_kernel"]),
        quantize=bool(config["quantize"]),
        stream_chunk_k=int(fit["stream_chunk_k"]),
        state_noise_mode=fit["state_noise_mode"],
        stream_state_dtype=fit["stream_state_dtype"])


def _faulty(run, fault: str, batch: int):
    """Wrap ``Experiment.run`` with one of the planted faults."""
    import dataclasses

    def call(*arrays):
        if fault == "half_batch":
            # half the instances fitted; the rest get the first half's answers
            res = run(*(a[: batch // 2] for a in arrays))
            return dataclasses.replace(
                res, **{k: np.concatenate([getattr(res, k)] * 2)
                        for k in ("y_pred", "nrmse", "ser", "lam", "readout_w")})
        res = run(*arrays)
        if fault == "unchanged_state":
            # the readout left as it started: zero weights, zero predictions
            return dataclasses.replace(res, y_pred=np.zeros_like(res.y_pred),
                                       readout_w=np.zeros_like(res.readout_w))
        if fault == "alter_answer":
            # every instance's answers delivered one period late
            return dataclasses.replace(res, y_pred=np.roll(res.y_pred, 1, axis=1))
        raise ValueError(f"unknown fault {fault!r}")

    return call


def setup(ctx, clog) -> None:
    import jax.numpy as jnp

    from repro.pipeline import Experiment

    config, mix = ctx.config, ctx.mix
    t0 = time.perf_counter()
    pool = generate.fit_pool(config, mix, ctx.seed)
    mask = generate.mask(config)
    batch = pool[0].shape[0]
    shifts = [r * batch // int(mix["rotations"]) for r in range(int(mix["rotations"]))]
    ctx.rotations = [(s, tuple(np.ascontiguousarray(np.roll(a, s, axis=0))
                               for a in pool)) for s in shifts]
    ctx.pool, ctx.mask = pool, mask
    ctx.split["data_s"] = time.perf_counter() - t0

    exp = Experiment(experiment_config(config))
    exp.mask = jnp.asarray(mask)
    ctx.run = exp.run if ctx.fault is None else _faulty(exp.run, ctx.fault, batch)
    t0 = time.perf_counter()
    c0 = clog.backend_seconds()
    ctx.run(*ctx.rotations[0][1])
    warm = time.perf_counter() - t0
    compile_s = clog.backend_seconds() - c0
    ctx.split["compile_or_cache_load_s"] = compile_s
    ctx.split["first_call_s"] = warm - compile_s

    t_train, t_test = pool[0].shape[1], pool[2].shape[1]
    ctx.shape = dict(batch=batch, t_train=t_train, t_test=t_test,
                     n=int(config["n_nodes"]), c=1, washout=int(config["washout"]),
                     chunk=int(config["fit"]["stream_chunk_k"]),
                     n_lambdas=len(config["ridge_l2"]))


def window(ctx) -> None:
    import jax

    sample = _sample(ctx)
    calls, t0 = [], time.perf_counter()
    while True:
        shift, arrays = ctx.rotations[len(calls) % len(ctx.rotations)]
        with jax.profiler.TraceAnnotation("fit.call"):
            res = ctx.run(*arrays)
        rows = (sample + shift) % ctx.shape["batch"]
        w = np.asarray(res.readout_w)
        finite = int(np.sum(~np.isfinite(res.nrmse))
                     + np.sum(~np.all(np.isfinite(res.y_pred), axis=1))
                     + np.sum(~np.all(np.isfinite(w.reshape(len(w), -1)), axis=1)))
        calls.append({"y_pred": res.y_pred[rows], "nrmse": res.nrmse[rows],
                      "lam": res.lam[rows], "w": w[rows].reshape(len(rows), -1),
                      "not_finite": finite})
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    n = len(calls)
    b, t = ctx.shape["batch"], ctx.shape["t_train"] + ctx.shape["t_test"]
    ctx.calls = calls
    ctx.record.update(calls=n, window_s=window_s, periods=n * b * t,
                      attempted=n * b, failed=sum(c["not_finite"] for c in calls))
    ctx.fit_counts = ctx.counts.fit_call(**ctx.shape)


def sample_rows(seed: int, batch: int, size: int) -> np.ndarray:
    """Pool rows whose answers are compared, drawn from the seed."""
    rng = np.random.default_rng([seed % 2**64, 7])
    return np.sort(rng.choice(batch, size=min(size, batch), replace=False))


def _sample(ctx) -> np.ndarray:
    return sample_rows(ctx.seed, ctx.shape["batch"], int(ctx.limits_sample))


def _nrmse(y, target):
    err = y - target
    return np.sqrt(np.mean(err * err, axis=-1) / (np.var(target, axis=-1) + reference.VAR_EPS))


def compare(config: dict, data, states, calls, failed: int) -> dict:
    """The numbers of the check for the answers ``calls`` (one dict a call:
    ``y_pred``, ``nrmse``, ``lam`` and readout ``w`` of the sampled
    instances) to the instances ``data`` (four [S, T] arrays), whose
    reference states are ``states`` (``reference.fit_states``).

    * ``solve_excess`` -- the relative excess of the training
      objective that an instance's readout reaches at its lambda over the
      least one, in the float64 ridge system of the reference's states,
      over the eigen-directions that float32 determines
      (``reference.Ridge64.objective_excess``).  It judges the Gram fold,
      the noise diagonal and the solve by what the solve is for; the
      directions near float32's cut-off, which the fit leaves to rounding,
      do not count.
    * ``eval_gap`` -- the gap between an instance's reported test
      NRMSE and the NRMSE of its readout on the reference's test states.
    * ``answer_gap`` -- for every test answer, its readout on the
      reference's test states: the RMS gap, in units of the target's
      standard deviation, or for quantized answers the share of
      symbols that differ where that readout lies more than ``MARGIN`` from
      a decision boundary (nearer, rounding may decide).  With
      ``eval_gap`` it judges the states (``dfr_scan``) over both segments
      and the test evaluation.
    * ``not_finite`` -- non-finite answers, NRMSEs or weights in the window.

    Each of the first three is the ``QUANTILE`` over the sampled instances
    of a call, the widest over calls.  The Silicon MR node map is
    discontinuous (charge where the input exceeds the left neighbour's
    state, else discharge), so where one node update lies within rounding
    of that tie, two sound float32 chains can take different branches and
    the instance's states part for some tens of periods.  About one
    instance in several hundred does so; the quantile leaves out the few
    of a call's sample (PERF.md).
    """
    quantize = bool(config["quantize"])
    washout = int(config["washout"])
    lams = np.asarray(config["ridge_l2"], np.float32)
    x_fit, x_te = states
    target = data[3].astype(np.float64)
    systems = [reference.ridge64(config, x_fit[i], data[1][i, washout:])
               for i in range(len(target))]
    xt = np.concatenate([x_te, np.ones(x_te.shape[:2] + (1,), x_te.dtype)], axis=2)
    xt = xt.astype(np.float64)
    sym = np.asarray(reference.SYMBOLS)
    n = {"solve_excess": 0.0, "eval_gap": 0.0, "answer_gap": 0.0}
    for c in calls:
        k = np.argmin(np.abs(np.log(lams)[None, :] - np.log(c["lam"])[:, None]), axis=1)
        excess = [s.objective_excess(w, int(ki)) for s, w, ki in zip(systems, c["w"], k)]
        raw = np.einsum("stf,sf->st", xt, c["w"].astype(np.float64))
        if quantize:
            decided = sym[np.argmin(np.abs(raw[..., None] - sym), axis=-1)]
            clear = np.min(np.abs(raw[..., None] - BOUNDARIES), axis=-1) > MARGIN
            answer = np.mean((c["y_pred"] != decided) & clear, axis=1)
        else:
            answer = np.sqrt(np.mean((c["y_pred"] - raw) ** 2, axis=1)) / np.std(target, axis=1)
        for key, v in (("solve_excess", excess), ("answer_gap", answer),
                       ("eval_gap", np.abs(c["nrmse"] - _nrmse(raw, target)))):
            n[key] = max(n[key], float(np.quantile(v, QUANTILE)))
    n["not_finite"] = float(failed)
    return n


def reference_calls(config: dict, data, states, precision: str = "highest",
                    device=None) -> list:
    """The reference's answers, in the form of the program's: at
    ``precision="high"`` the control, put in the program's place."""
    y, nrmse, idx, w = reference.fit_readout(config, states, data[1], data[3],
                                             precision=precision, device=device)
    return [{"y_pred": y, "nrmse": nrmse, "w": w,
             "lam": np.asarray(config["ridge_l2"], np.float32)[idx]}]


def check(ctx) -> dict:
    """Every call's answers for the sampled instances against the
    reference (``compare``)."""
    data = [a[_sample(ctx)] for a in ctx.pool]
    states = reference.fit_states(ctx.config, ctx.mask, data[0], data[2])
    calls = ctx.calls
    if ctx.variant is not None:
        calls = variant_calls(ctx.variant, ctx.config, ctx.mask, data, states)
    numbers = compare(ctx.config, data, states, calls, ctx.record["failed"])
    ctx.log("numbers: " + json.dumps(numbers)
            + f" ({len(data[0])} sampled instances x {len(calls)} calls)")
    return numbers


def variant_calls(variant: str, config: dict, mask, data, states) -> list:
    """Answers that stand in for the program's: ``reference_high``, the
    control; ``reference_device``, the reference computed on the default
    device (the accelerator), a witness of its own arithmetic."""
    if variant == "reference_high":
        return reference_calls(config, data, states, "high")
    if variant == "reference_device":
        import jax

        dev = jax.devices()[0]
        dev_states = reference.fit_states(config, mask, data[0], data[2], device=dev)
        return reference_calls(config, data, dev_states, device=dev)
    raise ValueError(f"unknown variant {variant!r}")
