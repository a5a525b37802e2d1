"""Delayed-feedback reservoir (DFR) state generation.

Produces the N virtual-node states for every input period (paper Fig. 2(b),
Eq. (1-2)).  Three interchangeable execution paths:

* ``method="ref"``    — nested ``lax.scan`` over periods × nodes: the node
  chain is evaluated strictly sequentially, exactly as the physical device
  evolves in time.  This is the oracle every other path is tested against.
* ``method="fast"``   — ``lax.scan`` over periods, O(log N) associative-scan
  parallelism inside each period (see nonlinear.py docstring).  Pure jnp; the
  default on CPU and the building block the LM-side ReservoirMixer uses.
* ``method="kernel"`` — the Pallas TPU kernel (kernels/dfr_scan), which fuses
  masking + candidate computation + the in-period scan, tiled in VMEM.

All paths take the *unmasked* sample series ``j`` [..., K] plus the mask [N]
and return states [..., K, N].
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .masking import masked_input
from .nonlinear import NLModel


def init_state(model: NLModel, batch_shape: tuple[int, ...], n_nodes: int, dtype=jnp.float32):
    """Zero initial reservoir state (dark waveguide / discharged node)."""
    del model
    return jnp.zeros((*batch_shape, n_nodes), dtype=dtype)


def _canon(j: jnp.ndarray) -> tuple[jnp.ndarray, bool]:
    """Canonicalise j to [B, K]; report whether a batch dim was added."""
    j = jnp.asarray(j)
    if j.ndim == 1:
        return j[None, :], True
    if j.ndim == 2:
        return j, False
    raise ValueError(f"j must be [K] or [B, K], got shape {j.shape}")


@partial(jax.jit, static_argnames=("model",))
def _states_ref(model: NLModel, u: jnp.ndarray, s0: jnp.ndarray) -> jnp.ndarray:
    """u: [B, K, N], s0: [B, N] -> [B, K, N].  Sequential oracle."""

    def period(carry, u_k):
        s_prev, s_last = carry  # [B, N], [B]

        def node(s_prev_node, xs):
            u_i, s_tau_i = xs  # [B], [B]
            s_i = model.node_update(u_i, s_tau_i, s_prev_node)
            return s_i, s_i

        xs = (jnp.moveaxis(u_k, -1, 0), jnp.moveaxis(s_prev, -1, 0))  # [N, B]
        s_last_new, s_nodes = jax.lax.scan(node, s_last, xs)
        s_new = jnp.moveaxis(s_nodes, 0, -1)  # [B, N]
        return (s_new, s_last_new), s_new

    (_, _), states = jax.lax.scan(period, (s0, s0[..., -1]), jnp.moveaxis(u, 1, 0))
    return jnp.moveaxis(states, 0, 1)


@partial(jax.jit, static_argnames=("model",))
def _states_fast(model: NLModel, u: jnp.ndarray, s0: jnp.ndarray) -> jnp.ndarray:
    """u: [B, K, N], s0: [B, N] -> [B, K, N].  Parallel-in-period."""

    def period(carry, u_k):
        s_prev, s_last = carry
        s_new = model.period_update(u_k, s_prev, s_last)
        return (s_new, s_new[..., -1]), s_new

    (_, _), states = jax.lax.scan(period, (s0, s0[..., -1]), jnp.moveaxis(u, 1, 0))
    return jnp.moveaxis(states, 0, 1)


# Swept-parameter variants (DESIGN.md §14): identical scans, but the model's
# operating point arrives as a TRACED ``p`` pytree (leaves scalar or [B] —
# one device grid point per batch lane) through the model's ``*_p`` method
# contract.  Parameters are operands, so a design-space sweep over them
# never retraces; the model itself stays the hashable jit static.

@partial(jax.jit, static_argnames=("model",))
def _states_ref_p(model, p, u: jnp.ndarray, s0: jnp.ndarray) -> jnp.ndarray:
    """Sequential oracle at traced per-lane device parameters ``p``."""

    def period(carry, u_k):
        s_prev, s_last = carry

        def node(s_prev_node, xs):
            u_i, s_tau_i = xs
            s_i = model.node_update_p(p, u_i, s_tau_i, s_prev_node)
            return s_i, s_i

        xs = (jnp.moveaxis(u_k, -1, 0), jnp.moveaxis(s_prev, -1, 0))
        s_last_new, s_nodes = jax.lax.scan(node, s_last, xs)
        s_new = jnp.moveaxis(s_nodes, 0, -1)
        return (s_new, s_last_new), s_new

    (_, _), states = jax.lax.scan(period, (s0, s0[..., -1]), jnp.moveaxis(u, 1, 0))
    return jnp.moveaxis(states, 0, 1)


@partial(jax.jit, static_argnames=("model",))
def _states_fast_p(model, p, u: jnp.ndarray, s0: jnp.ndarray) -> jnp.ndarray:
    """Period-scan path at traced per-lane device parameters ``p``."""

    def period(carry, u_k):
        s_prev, s_last = carry
        s_new = model.period_update_p(p, u_k, s_prev, s_last)
        return (s_new, s_new[..., -1]), s_new

    (_, _), states = jax.lax.scan(period, (s0, s0[..., -1]), jnp.moveaxis(u, 1, 0))
    return jnp.moveaxis(states, 0, 1)


def _kernel_states(model, j, mask, s0, *, block_s, return_final, state_dtype,
                   dev_params=None):
    """The Pallas ``dfr_scan`` over [B, K] inputs, one launch per device
    shard of B under an active mesh (parallel/sharding.over_batch_shards).
    ``dev_params`` leaves become [B] lanes, sharded with the batch."""
    from repro.kernels.dfr_scan import ops as dfr_ops
    from repro.parallel.sharding import over_batch_shards

    def scan(jb, m, s, p=None):
        return dfr_ops.dfr_scan(model, jb, m, s, block_s=block_s,
                                return_final=return_final,
                                out_dtype=state_dtype, params=p)

    args, batched = (j, mask, s0), (True, mask.ndim == 2, True)
    if dev_params is not None:
        args += (dfr_ops.lane_params(dev_params, j.shape[0]),)
        batched += (True,)
    return over_batch_shards(scan, args, batched)


def generate_states(
    model: NLModel,
    j: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    s0: jnp.ndarray | None = None,
    method: str = "fast",
    block_s: int | None = None,
    return_final: bool = False,
    state_dtype=None,
    dev_params=None,
):
    """DFR states for sample series ``j`` [..., K] -> [..., K, N].

    ``method``: "fast" (default), "ref" (sequential oracle) or "kernel"
    (Pallas; interpret-mode on CPU).  ``block_s`` sizes the kernel's sublane
    tile (None = smallest of {1, 2, 4, 8} covering the batch — see
    kernels/dfr_scan/ops.py); ignored by the jnp paths.

    ``dev_params`` threads a *traced* device operating-point pytree (e.g.
    ``devices.cmt.CMTSweepParams``; leaves scalar or [B], one grid point per
    batch lane) into the model's ``node_update_p``/``period_update_p``
    contract — how ``devices/sweep.py`` runs a whole (detuning × loss ×
    power) map as one program.  On the kernel path the leaves become per-lane
    [S, L] parameter tiles of ``dfr_scan`` (DESIGN.md §14).

    ``return_final=True`` additionally returns the final reservoir state
    [..., N] — feed it back as ``s0`` to resume the scan (train -> test
    continuation; chunked streaming over K).  On the kernel path this is the
    kernel's explicit VMEM-carry output rather than a slice of the state
    tensor, so a chunked caller never has to keep the full [..., K, N] block
    alive just to continue from its last period.

    ``state_dtype`` downcasts only the emitted state tensor (e.g. bf16 chunks
    for the streaming paths, halving chunk HBM traffic — DESIGN.md §9); the
    final-state carry and all in-scan compute stay in the input dtype, so
    chunked resume is unaffected by the chunk dtype.
    """
    jb, squeeze = _canon(j)
    n_nodes = int(mask.shape[-1])
    if s0 is None:
        s0b = init_state(model, (jb.shape[0],), n_nodes, dtype=jb.dtype)
    else:
        s0b = jnp.asarray(s0)
        if s0b.ndim == 1:
            s0b = jnp.broadcast_to(s0b[None], (jb.shape[0], n_nodes))

    if method == "kernel":
        out = _kernel_states(model, jb, mask, s0b, block_s=block_s,
                             return_final=return_final, state_dtype=state_dtype,
                             dev_params=dev_params)
        states, s_final = out if return_final else (out, None)
    else:
        u = masked_input(jb, mask)
        if method == "ref":
            states = (_states_ref(model, u, s0b) if dev_params is None
                      else _states_ref_p(model, dev_params, u, s0b))
        elif method == "fast":
            states = (_states_fast(model, u, s0b) if dev_params is None
                      else _states_fast_p(model, dev_params, u, s0b))
        else:
            raise ValueError(f"unknown method {method!r}")
        s_final = states[:, -1, :] if return_final else None
        if state_dtype is not None:
            states = states.astype(state_dtype)
    if squeeze:
        return (states[0], s_final[0]) if return_final else states[0]
    return (states, s_final) if return_final else states


def generate_channel_states(
    model: NLModel,
    j: jnp.ndarray,      # [R, K] — one series per wavelength channel
    masks: jnp.ndarray,  # [R, N] — one MLS mask per channel
    *,
    s0: jnp.ndarray | None = None,
    method: str = "fast",
    block_s: int | None = None,
    return_final: bool = False,
    state_dtype=None,
):
    """WDM ensemble states: per-channel masks over per-channel inputs.

    ``j`` [R, K] with ``masks`` [R, N] -> states [R, K, N]; the software
    analogue of R wavelength channels sharing one physical ring + delay
    loop (DESIGN.md §2/§9).  Same knob semantics as ``generate_states``:
    ``s0`` [R, N] resumes each channel's scan, ``return_final=True`` adds
    the [R, N] carry (the kernel's VMEM-flush output — a chunked caller
    never keeps the full [R, K, N] block alive), ``state_dtype`` downcasts
    only the emitted state tensor.

    ``method="kernel"`` rides the Pallas scan's per-lane mask path: each
    channel is a batch lane with its own [N] mask tile resident in VMEM, so
    all R channels run as ONE kernel launch.  The jnp paths vmap over
    channels.
    """
    j = jnp.asarray(j, jnp.float32)
    masks = jnp.asarray(masks, j.dtype)
    if j.ndim != 2 or masks.ndim != 2 or j.shape[0] != masks.shape[0]:
        raise ValueError(f"channels mismatch: j {j.shape} vs masks {masks.shape}")
    if s0 is None:
        s0 = jnp.zeros((j.shape[0], masks.shape[1]), j.dtype)
    s0 = jnp.asarray(s0, j.dtype)

    if method == "kernel":
        return _kernel_states(model, j, masks, s0, block_s=block_s,
                              return_final=return_final, state_dtype=state_dtype)

    def one(jr, mr, s0r):
        return generate_states(model, jr, mr, s0=s0r, method=method,
                               return_final=True, state_dtype=state_dtype)

    states, s_final = jax.vmap(one)(j, masks, s0)
    return (states, s_final) if return_final else states
