"""Plain reference of the CMT microring cavity's states, one device
operating point per lane.

Written from the cavity's equations (DESIGN.md section 14, after
arXiv:2310.09433), independent of the program: it imports nothing from
``repro``.  Float32, node by node and period by period, on the host CPU
unless a device is given.  Per virtual-node tick of length theta, with the
drive P = max(u + gamma * s(t - tau), 0), the left neighbour's energy as
the starting E, and M = ``n_substeps`` steps of dt = theta / M:

    charging  = u > s_left                      (the paper's Eq. 6-7 branch)
    kappa     = kappa_c if charging else kappa_d
    r_lin     = 0 if charging else loss_scale / tau_L
    N, T      = fc_gain (pw E)^2, th_gain pw E  (closed at tick start)
    each substep:
      delta = detune - fcd N + th_shift T
      r     = r_lin + tpa pw E + fca N
      E     = E e^(-r dt) + kappa P dt / (1 + delta^2) * phi1(r dt)
      N     = N + (1 - e^(-dt / tau_fc)) (fc_gain (pw E)^2 - N)
      T     = T + (1 - e^(-dt / tau_th)) (th_gain pw E - T)

with phi1(x) = (1 - e^(-x)) / x = -expm1(-x) / x (1 at x = 0), and the
pump couplings calibrated at the configuration's own operating point
(detune d0, loss l0): kappa_d = (1 + d0^2) l0 / tau_L, kappa_c =
alpha (1 + d0^2) / theta, alpha = 1 - e^(-theta l0 / tau_L).  Each lane
has its own (detune, loss_scale, power); the states feed the ridge
reference of ``bench.reference``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

LEAVES = ("detune", "loss_scale", "power")


def couplings(model: dict) -> tuple[float, float]:
    """(kappa_c, kappa_d) calibrated at the configuration's operating point."""
    theta, tau_l = model["theta_ps"], model["tau_l_ps"]
    d0, l0 = model["detune"], model["loss_scale"]
    alpha = 1.0 - math.exp(-theta * l0 / tau_l)
    return alpha * (1.0 + d0 * d0) / theta, (1.0 + d0 * d0) * l0 / tau_l


def _phi1(x):
    small = x <= 0.0
    safe = jnp.where(small, 1.0, x)
    return jnp.where(small, 1.0, -jnp.expm1(-safe) / safe)


def _tick(m: dict, lane, u, s_tau, s_left):
    """One node's tick over the lanes: the equations of the module's
    docstring, in float32."""
    f32 = jnp.float32
    detune, loss_scale, pw = lane
    kappa_c, kappa_d = couplings(m)
    dt = f32(m["theta_ps"] / m["n_substeps"])
    g_fc = f32(-math.expm1(-m["theta_ps"] / m["n_substeps"] / m["tau_fc_ps"]))
    g_th = f32(-math.expm1(-m["theta_ps"] / m["n_substeps"] / m["tau_th_ps"]))
    drive = jnp.maximum(u + f32(m["gamma"]) * s_tau, 0.0)
    charging = u > s_left
    kappa = jnp.where(charging, f32(kappa_c), f32(kappa_d))
    r_lin = jnp.where(charging, 0.0, loss_scale / f32(m["tau_l_ps"]))
    e = jnp.maximum(s_left, 0.0)
    n_fc = f32(m["fc_gain"]) * (pw * e) ** 2
    t_th = f32(m["th_gain"]) * (pw * e)
    for _ in range(int(m["n_substeps"])):
        delta = detune - f32(m["fcd"]) * n_fc + f32(m["th_shift"]) * t_th
        r = r_lin + f32(m["tpa"]) * (pw * e) + f32(m["fca"]) * n_fc
        x = r * dt
        e = e * jnp.exp(-x) + kappa * drive * dt / (1.0 + delta * delta) * _phi1(x)
        n_fc = n_fc + g_fc * (f32(m["fc_gain"]) * (pw * e) ** 2 - n_fc)
        t_th = t_th + g_th * (f32(m["th_gain"]) * (pw * e) - t_th)
    return e


def _states(m: dict, j, mask, s0, lane):
    """j [S, K], mask [N], s0 [S, N], lane three [S] -> (states [S, K, N],
    final [S, N])."""

    def period(s_prev, j_k):
        u = j_k[:, None] * mask[None, :]

        def node(left, xs):
            u_i, s_tau = xs
            s_i = _tick(m, lane, u_i, s_tau, left)
            return s_i, s_i

        _, s_new = jax.lax.scan(node, s_prev[:, -1], (u.T, s_prev.T))
        s_new = s_new.T
        return s_new, s_new

    fin, states = jax.lax.scan(period, s0, j.T)
    return jnp.moveaxis(states, 0, 1), fin


@functools.partial(jax.jit, static_argnames=("key",))
def _fit_states(key, mask, tr_in, te_in, detune, loss_scale, power):
    config = dict(key)
    m = dict(config["model"])
    lo = jnp.min(tr_in, axis=1, keepdims=True)
    scale = 1.0 / (jnp.max(tr_in, axis=1, keepdims=True) - lo + 1e-12)
    if not config["normalize_input"]:
        lo, scale = 0.0, 1.0
    gain = jnp.float32(config["input_gain"])
    j_tr, j_te = (tr_in - lo) * scale * gain, (te_in - lo) * scale * gain
    lane = (detune, loss_scale, power)
    s0 = jnp.zeros((tr_in.shape[0], mask.shape[0]), jnp.float32)
    st_tr, s_end = _states(m, j_tr, mask, s0, lane)
    st_te, _ = _states(m, j_te, mask, s_end, lane)
    return st_tr[:, config["washout"]:], st_te


def _key(config: dict):
    """The parts of the configuration the states depend on, hashable."""
    return (("model", tuple(sorted(config["model"].items()))),
            ("normalize_input", bool(config["normalize_input"])),
            ("input_gain", float(config["input_gain"])),
            ("washout", int(config["washout"])))


def fit_states(config: dict, mask, tr_in, te_in, lanes: dict, device=None):
    """Float32 states of [S, T] inputs, lane s at the operating point
    ``lanes[leaf][s]``: (the fit window past the washout [S, T_fit, N], the
    test segment [S, T_test, N]) as host arrays."""
    key = _key(config)
    return reference._on(device, functools.partial(_fit_states, key), mask,
                         tr_in, te_in, *(np.asarray(lanes[k]) for k in LEAVES))
