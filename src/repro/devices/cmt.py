"""Coupled-mode-theory (CMT) microring cavity model (DESIGN.md §14).

The paper's :class:`~repro.core.nonlinear.SiliconMR` is one fixed closed-form
per-tick map.  "Effects of cavity nonlinearities and linear losses on silicon
microring-based reservoir computing" (arXiv:2310.09433) shows that the
physics *behind* that map — two-photon absorption (TPA), free-carrier
absorption/dispersion, thermal dispersion, linear loss — changes RC
performance materially across the (detuning, loss, input-power) box.  This
module models those mechanisms explicitly:

:class:`MRCavityCMT` integrates three coupled cavity variables *inside* each
virtual-node tick (length θ), with ``n_substeps`` exact-exponential substeps:

    E  — intracavity energy (the value carried between virtual nodes; the
         reservoir contract's scalar state),
    N  — free-carrier density, generated ∝ (power·E)² (TPA pairs), relaxing
         with lifetime τ_fc,
    T  — mode temperature, driven ∝ power·E (absorbed-power heating),
         relaxing with lifetime τ_th.

Per substep of length dt = θ/n_substeps:

    δ_eff = δ − fcd·N + th_shift·T               (carrier blue / thermal red)
    L(δ)  = 1 / (1 + δ_eff²)                     (Lorentzian line shape)
    r     = r_lin·[discharging] + tpa·pw·E + fca·N   (total loss rate)
    E    ←  E·e^{−r·dt} + κ·L(δ)·P·dt·φ1(r·dt)   (exact exponential step)
    N    ←  N + (1 − e^{−dt/τ_fc})·(fc_gain·(pw·E)² − N)
    T    ←  T + (1 − e^{−dt/τ_th})·(th_gain·pw·E − T)

with P = max(u + γ·s(t−τ), 0) the pumped drive, φ1(x) = (1 − e^{−x})/x the
exponential-integrator weight, and the paper's charge/discharge asymmetry
(Eq. 6-7) modeled as carrier-injection gain cancelling the linear loss on the
charging branch (u > s(t−θ)) plus a branch-dependent coupling κ_c / κ_d.
N and T are closed adiabatically at tick start from the carried energy
(N₀ = fc_gain·(pw·E₀)², T₀ = th_gain·pw·E₀) — the scalar reservoir carry
stays one f32 per node, so every existing execution path (ref / fast /
Pallas ``kernels/dfr_scan`` tile loop, ``stream_chunk_k`` streaming,
``ReservoirGraph`` stages) accepts the model unchanged.

Exactness of the zero-power limit: at ``power_mw = 0`` the nonlinear terms
vanish, r and the pump are substep-constant, and the exponential step
telescopes exactly over any number of substeps — the auto-calibrated κ
(below) then reproduce ``SiliconMR``'s θ-corrected Eq. (6-7) to float
rounding for ANY ``n_substeps`` (devices/calibrate.py proves it).

Design-space sweeps: the (detuning, loss, power) operating point exists
twice — as frozen dataclass floats (hashable jit statics; the legacy
contract) and as a :class:`CMTSweepParams` *traced* pytree accepted by the
``*_p`` method variants, whose leaves may be per-batch-lane ``[B]`` arrays.
That is what lets ``devices/sweep.py`` fold a whole parameter grid into
batch lanes of ONE compiled program instead of retracing per point.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class CMTSweepParams(NamedTuple):
    """Traced operating-point parameters for design-space sweeps.

    Leaves are scalars or per-lane ``[B]`` arrays (one grid point per batch
    lane).  This is the ``dev_params`` pytree ``generate_states`` /
    ``fit_ridge_streaming`` / ``Experiment.run`` thread down to
    ``MRCavityCMT.node_update_p`` — an *operand*, so sweeping it never
    retraces the program.
    """

    detune: object = 0.0       # normalised detuning δ = 2(ω_p − ω_0)/Δω_FWHM
    loss_scale: object = 1.0   # linear loss multiplier on 1/τ_L
    power: object = 0.0        # input power scale (mW) — drives all NL terms


def _bparam(x, like):
    """Broadcast a sweep-parameter leaf against an elementwise operand.

    A scalar passes through; a ``[B]`` leaf gains trailing singleton dims to
    ride the leading batch axis of ``like`` (``[B]``, ``[B, N]``, …)."""
    x = jnp.asarray(x, like.dtype)
    if x.ndim == 0 or x.ndim >= like.ndim:
        return x
    return x.reshape(x.shape + (1,) * (like.ndim - x.ndim))


# φ1's Taylor coefficients (−1)^k/(k+1)!, k = 0..8: below _PHI1_SERIES_X the
# series is exact to ~5e-10 relative, above it (1 − e^{−x})/x loses < 3 ulp
_PHI1_SERIES = tuple((-1.0) ** k / math.factorial(k + 1) for k in range(9))
_PHI1_SERIES_X = 0.5


def _phi1(x, decay):
    """φ1(x) = (1 − e^{−x})/x — the exact exponential-integrator pump weight,
    given ``decay`` = e^{−x}.

    Small x (the charging branch at zero power has r = 0 exactly) takes the
    Taylor series, where 1 − e^{−x} would cancel; elsewhere the closed form.
    Only exp, products and one division: the forms the Pallas TPU kernel can
    lower (Mosaic has no ``expm1``).
    """
    series = jnp.full_like(x, _PHI1_SERIES[-1])
    for c in _PHI1_SERIES[-2::-1]:
        series = series * x + c
    small = x < _PHI1_SERIES_X
    safe = jnp.where(small, jnp.ones_like(x), x)
    return jnp.where(small, series, (1.0 - decay) / safe)


@dataclasses.dataclass(frozen=True)
class MRCavityCMT:
    """CMT microring cavity neuron — physics-fidelity device model.

    Fields are Python floats (frozen dataclass: a hashable jit static, like
    every ``core/nonlinear.py`` model).  Geometry/operating point:

    * ``theta_ps``      — virtual-node tick θ (one integration window),
    * ``tau_l_ps``      — linear (photon-lifetime) loss time τ_L,
    * ``gamma``         — delayed-feedback strength (drive P = u + γ·s(t−τ)),
    * ``detune``        — normalised pump detuning δ at the operating point,
    * ``loss_scale``    — linear loss multiplier (waveguide/coupler excess),
    * ``power_mw``      — input power scale; 0 switches every nonlinear
      mechanism off (the calibrated-``SiliconMR`` small-signal limit),
    * ``n_substeps``    — fixed substeps per tick (static: the Pallas kernel
      unrolls them inside its VMEM tile loop).

    Nonlinear coefficients (normalised repro units, rates per ps): ``tpa``
    (two-photon absorption loss per mW·E), ``fca``/``fcd`` (free-carrier
    absorption / blue-shift per carrier), ``th_shift`` (thermal red-shift per
    unit ΔT), ``fc_gain``/``th_gain`` (carrier generation / self-heating
    drive), ``tau_fc_ps``/``tau_th_ps`` (carrier / thermal lifetimes).

    ``kappa_charge``/``kappa_discharge`` override the pump couplings; the
    default ``None`` auto-calibrates them at the dataclass operating point so
    the zero-power tick map IS ``SiliconMR``'s θ-corrected Eq. (6-7):

        κ_d = loss_scale·(1 + δ²)/τ_L        (discharge: α·P + (1−α)·E₀)
        κ_c = α·(1 + δ²)/θ                   (charge:    α·P + E₀)

    with α = 1 − exp(−θ·loss_scale/τ_L).  The κ stay anchored at the
    calibration detuning when ``CMTSweepParams`` sweeps δ — moving the pump
    off resonance *loses* Lorentzian coupling, which is the robustness
    physics the sweep exists to measure.
    """

    theta_ps: float = 50.0
    tau_l_ps: float = 50.0
    gamma: float = 0.9
    detune: float = 0.0
    loss_scale: float = 1.0
    power_mw: float = 1.0
    n_substeps: int = 4
    kappa_charge: float | None = None
    kappa_discharge: float | None = None
    tpa: float = 0.01
    fca: float = 0.05
    fcd: float = 4.0
    th_shift: float = 0.4
    fc_gain: float = 0.2
    th_gain: float = 0.5
    tau_fc_ps: float = 1000.0
    tau_th_ps: float = 10000.0

    name: str = dataclasses.field(default="MR cavity (CMT)", repr=False)

    def __post_init__(self):
        if self.n_substeps < 1:
            raise ValueError(f"n_substeps must be >= 1, got {self.n_substeps}")
        for f in ("theta_ps", "tau_l_ps", "tau_fc_ps", "tau_th_ps"):
            if getattr(self, f) <= 0.0:
                raise ValueError(f"{f} must be positive, got {getattr(self, f)}")
        if self.loss_scale < 0.0 or self.power_mw < 0.0:
            raise ValueError("loss_scale and power_mw must be non-negative")

    # -- calibrated small-signal quantities (Python floats: jit statics) -----
    @property
    def alpha(self) -> float:
        """Zero-power per-tick linear response 1 − exp(−θ·loss_scale/τ_L)."""
        return 1.0 - math.exp(-self.theta_ps * self.loss_scale / self.tau_l_ps)

    @property
    def kappa_d(self) -> float:
        if self.kappa_discharge is not None:
            return self.kappa_discharge
        return (1.0 + self.detune ** 2) * self.loss_scale / self.tau_l_ps

    @property
    def kappa_c(self) -> float:
        if self.kappa_charge is not None:
            return self.kappa_charge
        return self.alpha * (1.0 + self.detune ** 2) / self.theta_ps

    def sweep_point(self) -> CMTSweepParams:
        """The dataclass operating point as a (float-leaf) sweep pytree —
        the unswept contract methods evaluate exactly this point."""
        return CMTSweepParams(detune=self.detune, loss_scale=self.loss_scale,
                              power=self.power_mw)

    # -- swept-parameter tick integration ------------------------------------
    def node_update_p(self, p: CMTSweepParams, u, s_tau, s_prev_node):
        """One virtual-node tick at traced operating point ``p``.

        Elementwise over any leading shape (the ref path's ``[B]`` slices,
        the Pallas kernel's ``[S, L]`` VMEM tiles); ``p`` leaves broadcast
        against the leading batch axis.  The substep loop is a Python loop —
        ``n_substeps`` is static, so the kernel unrolls it in-register.
        """
        dt = self.theta_ps / self.n_substeps
        det = _bparam(p.detune, u)
        lin = _bparam(p.loss_scale, u) * jnp.asarray(1.0 / self.tau_l_ps, u.dtype)
        pw = _bparam(p.power, u)

        drive = jnp.maximum(u + self.gamma * s_tau, 0.0)
        charging = u > s_prev_node
        kap = jnp.where(charging, jnp.asarray(self.kappa_c, u.dtype),
                        jnp.asarray(self.kappa_d, u.dtype))
        # carrier-injection gain cancels the linear loss while charging
        lin_eff = jnp.where(charging, jnp.zeros_like(lin), lin)

        e = jnp.maximum(s_prev_node, 0.0)
        # slow states closed adiabatically at tick start from the carried E₀
        n_fc = self.fc_gain * (pw * e) ** 2
        t_th = self.th_gain * (pw * e)
        g_fc = -math.expm1(-dt / self.tau_fc_ps)
        g_th = -math.expm1(-dt / self.tau_th_ps)
        for _ in range(self.n_substeps):
            delta = det - self.fcd * n_fc + self.th_shift * t_th
            lor = 1.0 / (1.0 + delta * delta)
            r = lin_eff + self.tpa * (pw * e) + self.fca * n_fc
            x = r * dt
            decay = jnp.exp(-x)
            e = e * decay + (kap * lor * drive) * (dt * _phi1(x, decay))
            n_fc = n_fc + g_fc * (self.fc_gain * (pw * e) ** 2 - n_fc)
            t_th = t_th + g_th * (self.th_gain * (pw * e) - t_th)
        return e

    def period_update_p(self, p: CMTSweepParams, u_k, s_prev, s_last):
        """Whole-period chain at traced point ``p`` — sequential over nodes
        (the realised energy feeds the next node's branch, like SiliconMR)."""

        def node(s_pn, xs):
            u_i, s_tau_i = xs
            s_i = self.node_update_p(p, u_i, s_tau_i, s_pn)
            return s_i, s_i

        xs = (jnp.moveaxis(u_k, -1, 0), jnp.moveaxis(s_prev, -1, 0))
        _, s_nodes = jax.lax.scan(node, s_last, xs)
        return jnp.moveaxis(s_nodes, 0, -1)

    # -- the core/nonlinear.py model contract --------------------------------
    def node_update(self, u, s_tau, s_prev_node):
        return self.node_update_p(self.sweep_point(), u, s_tau, s_prev_node)

    def period_update(self, u_k, s_prev, s_last):
        return self.period_update_p(self.sweep_point(), u_k, s_prev, s_last)
