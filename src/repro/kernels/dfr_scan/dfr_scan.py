"""Pallas TPU kernel: fused masking + delayed-feedback reservoir scan.

One kernel evaluates the whole DFR evolution for a tile of batch lanes:
the masked input u = j·m (paper input layer), the per-node nonlinear update
(reservoir layer), and the τ-period feedback carry — with the reservoir
state resident in VMEM for the entire scan.  HBM traffic is one read of j
and one write of the states, instead of K·N round trips.

Layout (DESIGN.md §2): batch is the vector axis, tiled (S sublanes × 128
lanes) so every VPU op runs on full (8, 128) vregs; the node axis N lives in
VMEM rows; the period axis K is the innermost (sequential) grid dimension.
TPU grid order guarantees k advances fastest, so the VMEM scratch carries
s(t−τ) across periods of the same batch tile.

  grid = (B_tiles, K)
  j       [K, B_s, B_l]          block [1, S, L]    @ (k, b·S, 0)
  mask    [N, 1]                 block [N, 1]       (whole, every step)
       or [N, B_s, B_l]          block [N, S, L]    @ (0, b·S, 0)  (per-lane)
  s0      [N, B_s, B_l]          block [N, S, L]    @ (0, b·S, 0)
  params  P × [B_s, B_l]         block [S, L]       @ (b·S, 0)     (optional)
  out     [K, N, B_s, B_l]       block [1, N, S, L] @ (k, 0, b·S, 0)
  fin     [N, B_s, B_l]          block [N, S, L]    @ (0, b·S, 0)
  scratch s_prev [N, S, L] f32, s_last [S, L] f32

Two outputs: the per-period states AND the final reservoir state (the VMEM
``s_prev`` carry, flushed on the last period of each batch tile).  The final
state is what a *chunked* caller feeds back as ``s0`` of the next K-chunk —
for f32 I/O the resume is bit-exact, because the flush stores exactly the
f32 scratch values the uninterrupted scan would have kept in VMEM (DESIGN.md
§8).  The mask is either one [N, 1] vector broadcast across all batch lanes
(the paper's single-accelerator sweep — every lane shares the MLS mask) or a
per-lane [N, S, L] tile (WDM ensembles: each batch lane is a wavelength
channel with its own mask; pipeline/experiment.channel_states).

``params`` (optional) are per-lane device parameters (DESIGN.md §14): a
pytree of [S, L] tiles, one grid point of a device design-space sweep per
batch lane, handed to ``model.node_update_p``.  Their block index moves with
the batch tile only, so each tile is fetched once and stays in VMEM for all
K periods; they are operands, so new values compile nothing.  Without them
the kernel, its operands and its body are the static-model ones.

The node chain (θ coupling) is sequential by construction — the realised
branch bit of node i−1 feeds the value of node i (nonlinear.py docstring) —
so the inner loop is a ``fori_loop`` over N with dynamic row access into the
VMEM scratch; every step is elementwise on an [S, L] tile.

Compute is f32 in-kernel regardless of the I/O dtype (bf16 inputs are
upcast on load, downcast on store): the recurrence is a long product of
near-1 factors, where bf16 carries would accumulate error over K·N steps.
``out_dtype`` downcasts only the *emitted* state tensor (e.g. bf16 chunks
for the streaming path, halving the HBM write+readback traffic of each
chunk — DESIGN.md §9); the final-state carry always flushes in the input
dtype so chunked resume stays bit-exact in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES

LANES = 128


def _kernel(model, n_nodes, per_lane, params_tree,
            j_ref, mask_ref, s0_ref, *refs):
    n_params = 0 if params_tree is None else params_tree.num_leaves
    param_refs = refs[:n_params]
    out_ref, fin_ref, s_prev_ref, s_last_ref = refs[n_params:]
    k = pl.program_id(1)
    n_k = pl.num_programs(1)

    # First period of this batch tile: load the initial reservoir state.
    @pl.when(k == 0)
    def _init():
        s_prev_ref[...] = s0_ref[...].astype(jnp.float32)
        s_last_ref[...] = s0_ref[n_nodes - 1, :, :].astype(jnp.float32)

    j_k = j_ref[0, :, :].astype(jnp.float32)  # [S, L] — this period's sample
    if params_tree is None:
        update = model.node_update
    else:
        p = jax.tree.unflatten(params_tree, [r[...] for r in param_refs])

        def update(u_i, s_tau_i, s_left):
            return model.node_update_p(p, u_i, s_tau_i, s_left)

    def node(i, s_last):
        if per_lane:
            m_i = mask_ref[i, :, :].astype(jnp.float32)     # [S, L] tile
        else:
            m_i = mask_ref[i, 0]                            # lane-broadcast
        u_i = j_k * m_i                                 # input layer: u = j·m
        s_tau_i = s_prev_ref[i, :, :]                   # s(t−τ): same node, prev period
        s_i = update(u_i, s_tau_i, s_last)              # NL node (θ-chain via s_last)
        s_prev_ref[i, :, :] = s_i                       # becomes s(t−τ) for period k+1
        out_ref[0, i, :, :] = s_i.astype(out_ref.dtype)
        return s_i

    s_last = jax.lax.fori_loop(0, n_nodes, node, s_last_ref[...])
    s_last_ref[...] = s_last

    # Last period: flush the VMEM state carry — the resume point for the
    # next K-chunk (and the pipeline's train -> test continuation).
    @pl.when(k == n_k - 1)
    def _fin():
        fin_ref[...] = s_prev_ref[...].astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("model", "block_s", "interpret",
                                             "out_dtype"))
def dfr_scan_tiled(
    model,
    j: jnp.ndarray,      # [K, S_total, L]
    mask: jnp.ndarray,   # [N, 1] (broadcast) or [N, S_total, L] (per-lane)
    s0: jnp.ndarray,     # [N, S_total, L]
    *,
    block_s: int = 8,
    interpret: bool = False,
    out_dtype=None,      # state-tensor dtype (default: j.dtype); fin stays j.dtype
    params=None,         # pytree of [S_total, L] per-lane device parameters
) -> tuple[jnp.ndarray, jnp.ndarray]:  # ([K, N, S_total, L], [N, S_total, L])
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else j.dtype
    k_periods, s_total, lanes = j.shape
    n_nodes = mask.shape[0]
    if s_total % block_s:
        raise ValueError(f"S_total {s_total} not divisible by block_s {block_s}")
    # Multi-tile emitted blocks must start on the out dtype's min-tile
    # boundary: (8, 128) covers f32, but a bf16/int8 out block needs
    # (16/32, 128) sublane alignment — a sub-minimal block_s would place
    # tile b at sublane offset b·block_s, illegal for every odd b on real
    # Mosaic even though interpret mode happily computes it.  Single-tile
    # blocks (block spans the whole S axis, offset always 0) are exempt.
    min_sub = max(8, 32 // out_dtype.itemsize)
    if s_total > block_s and out_dtype.itemsize < 4 and block_s % min_sub:
        raise ValueError(
            f"out_dtype {out_dtype} needs block_s a multiple of {min_sub} "
            f"once the batch spans multiple tiles (S_total {s_total} > "
            f"block_s {block_s}); pick block_s={min_sub} or let "
            f"auto_block_s choose it")
    per_lane = mask.ndim == 3
    grid = (s_total // block_s, k_periods)

    if per_lane:
        mask_spec = pl.BlockSpec((n_nodes, block_s, lanes), lambda b, k: (0, b, 0))
    else:
        mask_spec = pl.BlockSpec((n_nodes, 1), lambda b, k: (0, 0))

    leaves, params_tree = (jax.tree.flatten(params) if params is not None
                           else ([], None))
    kernel = functools.partial(_kernel, model, n_nodes, per_lane, params_tree)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, lanes), lambda b, k: (k, b, 0)),
            mask_spec,
            pl.BlockSpec((n_nodes, block_s, lanes), lambda b, k: (0, b, 0)),
        ] + [pl.BlockSpec((block_s, lanes), lambda b, k: (b, 0))] * len(leaves),
        out_specs=[
            pl.BlockSpec((1, n_nodes, block_s, lanes), lambda b, k: (k, 0, b, 0)),
            pl.BlockSpec((n_nodes, block_s, lanes), lambda b, k: (0, b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_periods, n_nodes, s_total, lanes), out_dtype),
            jax.ShapeDtypeStruct((n_nodes, s_total, lanes), j.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_nodes, block_s, lanes), jnp.float32),
            pltpu.VMEM((block_s, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dfr_scan",
    )(j, mask, s0, *leaves)
