"""Plain reference of the DFRC fit and of the online sessions.

Written from the method's description (paper Eq. 6-7 and the repository's
DESIGN.md sections 8 and 10), independent of the program: it imports
nothing from ``repro`` and takes only the benchmark's own inputs (data and
mask made from the seed).  It runs in float32, the configuration's
precision (every f32 product exact to f32), on the host CPU unless a
device is given, one sampled instance or stream per batch lane:

* states -- the Silicon MR node chain, node by node and period by period
  (a node's state depends on its left neighbour's through the branch);
* the readout -- Gram statistics over the fit window with the washout rows
  dropped, digitiser noise as its expected Tikhonov diagonal, and the GCV
  choice of lambda from the eigendecomposition of G (eigenvalues below
  4 eps of the largest dropped);
* the ridge system in float64 (``ridge64``), built from the float32 states,
  against which a readout is judged by the training objective it reaches
  at its lambda;
* sessions -- predict with the readout solved from earlier chunks, fold the
  chunk (washout by the per-stream period count) into the lambda-decayed
  statistics, and re-solve on every refresh tick of the server.

``precision="high"`` computes every matmul of the readout as the three
bfloat16 passes of ``Precision.HIGH`` (emulated exactly, so it reads the
same on every device): the control, one step below the ``highest`` the
configuration states.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VAR_EPS = 1e-30
# eigen-directions of G at or above this share of the largest eigenvalue
# count in a readout's objective excess (``Ridge64.objective_excess``)
WELL_DETERMINED = 1e-2
SYMBOLS = (-3.0, -1.0, 1.0, 3.0)


def cpu():
    return jax.devices("cpu")[0]


def _split(x):
    """x = hi + lo, both bfloat16 values held in float32."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b, precision: str, spec: str | None = None):
    """Matmul (or einsum ``spec``) in float32 at ``highest``, or as the
    three bfloat16 passes of ``high`` (hi*hi + hi*lo + lo*hi, each product
    exact in float32), the same on every device."""
    full = jax.lax.Precision.HIGHEST

    def mul(x, y):
        if spec is None:
            return jnp.matmul(x, y, precision=full)
        return jnp.einsum(spec, x, y, precision=full)

    if precision == "highest":
        return mul(a, b)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return mul(a_hi, b_hi) + (mul(a_hi, b_lo) + mul(a_lo, b_hi))


def alpha_of(model: dict) -> float:
    return 1.0 - math.exp(-model["theta_ps"] / model["tau_ph_ps"])


def _states(j, mask, s0, alpha, gamma):
    """j [S, K], mask [N], s0 [S, N] -> (states [S, K, N], final [S, N])."""
    a = jnp.float32(alpha)
    g = jnp.float32(gamma)
    keep = jnp.float32(1.0) - a

    def period(s_prev, j_k):
        u = j_k[:, None] * mask[None, :]

        def node(left, xs):
            u_i, s_tau = xs
            pre = a * (u_i + g * s_tau)
            s_i = jnp.where(u_i > left, pre + left, pre + left * keep)
            return s_i, s_i

        _, s_new = jax.lax.scan(node, s_prev[:, -1], (u.T, s_prev.T))
        s_new = s_new.T
        return s_new, s_new

    fin, states = jax.lax.scan(period, s0, j.T)
    return jnp.moveaxis(states, 0, 1), fin


def gcv_solve(g, c, y2, n_samples, lambdas, precision="highest"):
    """Ridge (G + lam*tr(G)/F I) w = c with the lambda of least GCV score."""
    f = g.shape[0]
    evals, q = jnp.linalg.eigh(g)
    evals = jnp.maximum(evals, 0.0)
    qc = _mm(q.T, c, precision)
    valid = evals > evals[-1] * jnp.float32(4 * np.finfo(np.float32).eps)
    qc = jnp.where(valid[:, None], qc, 0.0)
    qc2 = jnp.sum(qc * qc, axis=1)
    lamp = jnp.asarray(lambdas, jnp.float32) * (jnp.sum(evals) / f)

    def one(lam):
        inv = jnp.where(valid, 1.0 / (evals + lam), 0.0)
        w = _mm(q, qc * inv[:, None], precision)
        dof = jnp.sum(evals * inv)
        fit = jnp.sum(qc2 * jnp.where(valid, (evals + 2 * lam) * inv * inv, 0.0))
        rss = jnp.maximum(y2 - fit, 0.0)
        return w, n_samples * rss / jnp.maximum(n_samples - dof, 1.0) ** 2

    ws, scores = jax.vmap(one)(lamp)
    idx = jnp.argmin(scores)
    return ws[idx], idx


def _quantize(y):
    sym = jnp.asarray(SYMBOLS, y.dtype)
    return sym[jnp.argmin(jnp.abs(y[..., None] - sym), axis=-1)]


def _drive(p, tr_in, te_in):
    """Inputs normalised by the training segment's range, times the gain."""
    if p.normalize:
        lo = jnp.min(tr_in, axis=1, keepdims=True)
        scale = 1.0 / (jnp.max(tr_in, axis=1, keepdims=True) - lo + 1e-12)
    else:
        lo, scale = 0.0, 1.0
    return (tr_in - lo) * scale * p.gain, (te_in - lo) * scale * p.gain


@functools.partial(jax.jit, static_argnames=("p",))
def _fit_states(p, mask, tr_in, te_in):
    j_tr, j_te = _drive(p, tr_in, te_in)
    s0 = jnp.zeros((tr_in.shape[0], mask.shape[0]), jnp.float32)
    st_tr, s_end = _states(j_tr, mask, s0, p.alpha, p.gamma)
    st_te, _ = _states(j_te, mask, s_end, p.alpha, p.gamma)
    return st_tr[:, p.washout:], st_te


@functools.partial(jax.jit, static_argnames=("p",))
def _readout(p, xs, y_fit, x_te, y_te):
    n = xs.shape[2]
    t_fit = xs.shape[1]

    def one(x, y, x_te, y_te):
        xb = jnp.concatenate([x, jnp.ones((t_fit, 1), x.dtype)], axis=1)
        g = _mm(xb.T, xb, p.precision)
        c = _mm(xb.T, y[:, None], p.precision)
        y2 = jnp.sum(y * y)
        if p.noise_rel:
            cnt = jnp.float32(t_fit * n)
            var = jnp.maximum(jnp.sum(x * x) / cnt - (jnp.sum(x) / cnt) ** 2, 0.0)
            dn = jnp.arange(n)
            g = g.at[dn, dn].add(jnp.float32(p.noise_rel ** 2) * var * t_fit)
        w, idx = gcv_solve(g, c, y2, t_fit, p.lambdas, p.precision)
        xt = jnp.concatenate([x_te, jnp.ones((x_te.shape[0], 1), x.dtype)], axis=1)
        y_hat = _mm(xt, w, p.precision)[:, 0]
        err = y_hat - y_te
        nrmse = jnp.sqrt(jnp.mean(err * err) / (jnp.var(y_te) + VAR_EPS))
        y_out = _quantize(y_hat) if p.quantize else y_hat
        return y_out, nrmse, idx, w[:, 0]

    return jax.vmap(one)(xs, y_fit, x_te, y_te)


@dataclasses.dataclass(frozen=True)
class FitParams:
    """Static parameters of the reference fit."""

    alpha: float
    gamma: float
    washout: int
    lambdas: tuple
    noise_rel: float
    gain: float
    normalize: bool
    quantize: bool
    precision: str = "highest"


def fit_params(config: dict, precision="highest") -> FitParams:
    return FitParams(alpha=alpha_of(config["model"]),
                     gamma=float(config["model"]["gamma"]),
                     washout=int(config["washout"]),
                     lambdas=tuple(float(v) for v in config["ridge_l2"]),
                     noise_rel=float(config["state_noise_rel"]),
                     gain=float(config["input_gain"]),
                     normalize=bool(config["normalize_input"]),
                     quantize=bool(config["quantize"]),
                     precision=precision)


def _on(device, fn, *arrays):
    """``fn`` of float32 copies of ``arrays`` on ``device`` (the host CPU
    when None); the outputs as host arrays."""
    device = device or cpu()
    args = [jax.device_put(np.asarray(a, np.float32), device) for a in arrays]
    with jax.default_device(device):
        out = fn(*args)
    return tuple(np.asarray(o) for o in out)


def fit_states(config: dict, mask, tr_in, te_in, device=None):
    """Float32 states of [S, T] instances: (the fit window past the washout
    [S, T_fit, N], the test segment [S, T_test, N]) as host arrays."""
    p = fit_params(config)
    return _on(device, functools.partial(_fit_states, p), mask, tr_in, te_in)


def fit_readout(config: dict, states, tr_tg, te_tg, *, precision="highest",
                device=None):
    """The reference readout on ``states`` (from ``fit_states``): (answers
    [S, T_test], NRMSE [S], lambda index [S], weights [S, N + 1])."""
    p = fit_params(config, precision)
    x_fit, x_te = states
    return _on(device, functools.partial(_readout, p), x_fit,
               np.asarray(tr_tg)[:, p.washout:], x_te, te_tg)


@dataclasses.dataclass
class Ridge64:
    """One instance's ridge system in float64: the eigenbasis of
    G = [X 1]^T [X 1] plus the noise diagonal, c = [X 1]^T y in it, and the
    effective lambda of each grid entry."""

    evals: np.ndarray
    q: np.ndarray
    qc: np.ndarray
    y2: float
    lamp: np.ndarray
    valid: np.ndarray

    def objective_excess(self, w, k: int) -> float:
        """(J(w) - J*) / J* of the training objective
        J(w) = w^T (G + lam'_k I) w - 2 c^T w + ||y||^2 at grid lambda k,
        J* its least value, over the eigen-directions of G whose eigenvalue
        is at least ``WELL_DETERMINED`` of the largest: those a float32
        solve determines, to about eps / WELL_DETERMINED."""
        z = self.q.T @ np.asarray(w, np.float64)
        d = self.evals + self.lamp[k]
        err = z - np.where(self.valid, self.qc / d, 0.0)
        keep = self.evals >= WELL_DETERMINED * self.evals[-1]
        j_min = float(self.y2 - np.sum(np.where(self.valid, self.qc ** 2 / d, 0.0)))
        return float(np.sum(np.where(keep, d * err * err, 0.0))) / j_min


def ridge64(config: dict, x_fit, y_fit) -> Ridge64:
    """The float64 ridge system of one instance's float32 fit-window states
    ``x_fit`` [T_fit, N] and targets ``y_fit`` [T_fit]."""
    p = fit_params(config)
    x = np.asarray(x_fit, np.float64)
    t_fit, n = x.shape
    xb = np.concatenate([x, np.ones((t_fit, 1))], axis=1)
    g = xb.T @ xb
    if p.noise_rel:
        cnt = t_fit * n
        var = max(float(np.sum(x * x)) / cnt - (float(np.sum(x)) / cnt) ** 2, 0.0)
        g[np.arange(n), np.arange(n)] += p.noise_rel ** 2 * var * t_fit
    evals, q = np.linalg.eigh(g)
    evals = np.maximum(evals, 0.0)
    y = np.asarray(y_fit, np.float64)
    qc = q.T @ (xb.T @ y)
    valid = evals > evals[-1] * 4 * float(np.finfo(np.float32).eps)
    lamp = np.asarray(p.lambdas) * (np.sum(evals) / len(evals))
    return Ridge64(evals, q, qc, float(y @ y), lamp, valid)


@functools.partial(jax.jit, static_argnames=("p",))
def _sessions(p, mask, j, y, length, refresh):
    """j, y [S, K] (zero past each stream's ``length``); refresh
    [S, n_chunks] bool -> y_hat [S, K]."""
    s_n, k = j.shape
    n = mask.shape[0]
    f = n + 1
    ck = p.chunk
    js = jnp.moveaxis(j.reshape(s_n, k // ck, ck), 1, 0)
    ys = jnp.moveaxis(y.reshape(s_n, k // ck, ck), 1, 0)
    lam = jnp.float32(p.forgetting)
    carry0 = (jnp.zeros((s_n, n), jnp.float32),
              jnp.zeros((s_n, f, f), jnp.float32),
              jnp.zeros((s_n, f, 1), jnp.float32),
              jnp.zeros((s_n,), jnp.float32),
              jnp.zeros((s_n,), jnp.float32),
              jnp.zeros((s_n, f, 1), jnp.float32),
              jnp.zeros((s_n,), jnp.int32))

    def tick(carry, xs):
        s, g, c, y2, tcnt, w, step = carry
        jc, yc, t, ref = xs
        n_valid = jnp.clip(length - t * ck, 0, ck)
        act = n_valid > 0
        states, s_new = _states(jc, mask, s, p.alpha, p.gamma)
        x = jnp.concatenate([states, jnp.ones((s_n, ck, 1), jnp.float32)], axis=2)
        y_hat = _mm(x, w, p.precision, "stf,sfc->stc")[..., 0]
        local = jnp.arange(ck)[None, :]
        vfit = ((step[:, None] + local >= p.washout) & (local < n_valid[:, None]))
        vfit = vfit.astype(jnp.float32)
        xv = x * vfit[:, :, None]
        yv = yc * vfit
        g_new = lam * g + _mm(xv, xv, p.precision, "stf,stg->sfg")
        c_new = lam * c + _mm(xv, yv, p.precision, "stf,st->sf")[..., None]
        y2_new = lam * y2 + jnp.sum(yv * yv, axis=1)
        tcnt_new = lam * tcnt + jnp.sum(vfit, axis=1)
        w_sol, _ = jax.vmap(lambda gb, cb, y2b, nb: gcv_solve(
            gb, cb, y2b, nb, p.lambdas, p.precision))(g_new, c_new, y2_new, tcnt_new)
        ok = jnp.all(jnp.isfinite(w_sol.reshape(s_n, -1)), axis=1)
        w_new = jnp.where((ref & ok)[:, None, None], w_sol, w)
        a = act[:, None]
        a3 = act[:, None, None]
        new = (jnp.where(a, s_new, s), jnp.where(a3, g_new, g),
               jnp.where(a3, c_new, c), jnp.where(act, y2_new, y2),
               jnp.where(act, tcnt_new, tcnt), jnp.where(a3, w_new, w),
               jnp.where(act, step + ck, step))
        return new, y_hat

    ticks = jnp.arange(k // ck)
    _, y_hat = jax.lax.scan(tick, carry0, (js, ys, ticks, refresh.T))
    return jnp.moveaxis(y_hat, 0, 1).reshape(s_n, k)


@dataclasses.dataclass(frozen=True)
class SessionParams:
    """Static parameters of the reference sessions."""

    alpha: float
    gamma: float
    washout: int
    lambdas: tuple
    chunk: int
    forgetting: float
    precision: str = "highest"


def sessions(config: dict, mask, streams, *, precision="highest", device=None):
    """Reference online predictions of sampled streams.

    ``streams`` is a list of (j [K], y [K], admit_tick) for streams served
    to their end; the server re-solves on ticks where tick % refresh_every
    == 0, and a stream's last chunk may be short.  Returns one [K]
    prediction array per stream.
    """
    serve = config["serve"]
    p = SessionParams(alpha=alpha_of(config["model"]),
                      gamma=float(config["model"]["gamma"]),
                      washout=int(config["washout"]),
                      lambdas=tuple(float(v) for v in config["ridge_l2"]),
                      chunk=int(serve["chunk_k"]),
                      forgetting=float(serve["forgetting"]),
                      precision=precision)
    ck = p.chunk
    n_chunks = max(-(-len(js) // ck) for js, _, _ in streams)
    k = n_chunks * ck
    j = np.zeros((len(streams), k), np.float32)
    y = np.zeros((len(streams), k), np.float32)
    length = np.array([len(js) for js, _, _ in streams], np.int32)
    refresh = np.zeros((len(streams), n_chunks), bool)
    every = int(serve["refresh_every"])
    for i, (js, ys, admit) in enumerate(streams):
        j[i, :len(js)] = js
        y[i, :len(js)] = ys
        refresh[i] = (admit + np.arange(n_chunks)) % every == 0
    dev = device or cpu()
    args = [jax.device_put(a, dev) for a in
            (np.asarray(mask, np.float32), j, y, length, refresh)]
    with jax.default_device(dev):
        y_hat = np.asarray(_sessions(p, *args))
    return [y_hat[i, :n] for i, n in enumerate(length)]
