"""Pallas TPU kernel: streaming normal-equation accumulation for the readout.

Readout training (paper Section III.A.3) solves (XᵀX + λI)·w = Xᵀy over the
T×(N+1) reservoir-state matrix.  The physical accelerator streams states into
a sample memory; here the analogue is a single pass over the state stream
that accumulates the Gram matrix G = XᵀX and moment c = Xᵀy tile-by-tile on
the MXU, so the state matrix never has to be HBM-resident at once — T can be
arbitrarily long for a fixed F = N+1.

The grid carries a leading *batch* dimension so a whole sweep of B task
instances is one kernel launch (the pipeline's vmap axis), instead of a
sequential ``lax.map`` of B launches:

  grid = (B, I, J, T_tiles)   (T innermost: sequential accumulation)
  X  [B, T, F]   lhs block [1, block_t, block_f] @ (b, t, i)   (re-read per J)
  X  [B, T, F]   rhs block [1, block_t, block_f] @ (b, t, j)
  Y  [B, T, C]   block [1, block_t, C]           @ (b, t, 0)
  G  [B, F, F]   block [1, block_f, block_f]     @ (b, i, j)
  c  [B, F, C]   block [1, block_f, C]           @ (b, i, 0)  (accumulated at j == 0)

Accumulators live in VMEM scratch in f32 (MXU partials in f32 via
``preferred_element_type``) and are flushed to HBM on the last T step of each
(b, i, j) tile — the t == 0 re-zero makes the scratch per-instance, so batch
lanes never mix.  bf16/f32 inputs give identical G up to f32 accumulation
order.  The B = 1 wrapper ``gram_tiled`` serves the single-instance API.

``gram_tiled_batched_into`` is the *accumulate-into* variant (DESIGN.md §8):
two extra inputs carry running (G₀, c₀) stacks, aliased onto the outputs
(``input_output_aliases`` — the update is in-place in HBM), and the t == 0
step loads the VMEM scratch from them instead of zeroing.  Because each
chunk's partial products are added onto the running accumulator in exactly
the order an uninterrupted pass would use, folding a T-stream chunk-by-chunk
reproduces the one-shot result bit-for-bit whenever the chunk length is a
multiple of the T tile.  This is what lets a streaming caller fold
per-chunk state blocks into a running [B, F, F]/[B, F, C] Gram stack without
the full [B, T, F] state matrix ever existing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES

_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _dot_t(a, b):
    """aᵀ @ b over the T tile, f32 accumulation.  f32 operands ask for the
    full-f32 MXU contraction (bf16 passes would round the Gram statistics
    the eigh solve depends on); bf16 operands multiply natively."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dimension_numbers=(((0,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _kernel(n_t_tiles, has_init, *refs):
    if has_init:
        g0_ref, c0_ref, xl_ref, xr_ref, y_ref, g_ref, c_ref, g_acc, c_acc = refs
    else:
        xl_ref, xr_ref, y_ref, g_ref, c_ref, g_acc, c_acc = refs
        g0_ref = c0_ref = None
    t = pl.program_id(3)
    j = pl.program_id(2)

    # First T step of this (b, i, j) tile: seed the per-instance accumulator —
    # zeros for the one-shot kernel, the running G₀/c₀ block when folding a
    # chunk into a carried accumulator.
    @pl.when(t == 0)
    def _seed():
        if has_init:
            g_acc[...] = g0_ref[0]
            c_acc[...] = c0_ref[0]
        else:
            g_acc[...] = jnp.zeros_like(g_acc)
            c_acc[...] = jnp.zeros_like(c_acc)

    xl = xl_ref[0]
    g_acc[...] += _dot_t(xl, xr_ref[0])

    # bf16 state chunks keep the target stream f32 (it is O(B·T), not worth
    # rounding); dot_general needs homogeneous operands, so upcast the lhs
    # tile in VMEM — the HBM read already happened at the narrow dtype.
    @pl.when(j == 0)
    def _moment():
        xl_m = xl if xl.dtype == y_ref.dtype else xl.astype(y_ref.dtype)
        c_acc[...] += _dot_t(xl_m, y_ref[0])

    @pl.when(t == n_t_tiles - 1)
    def _flush_g():
        g_ref[0] = g_acc[...]

    # c's output block maps to (b, i, 0) for every j; only the j == 0 pass
    # accumulates it, so only that pass may flush it.
    @pl.when(jnp.logical_and(t == n_t_tiles - 1, j == 0))
    def _flush_c():
        c_ref[0] = c_acc[...]


def _specs(block_t, block_f, c_cols):
    in_specs = [
        pl.BlockSpec((1, block_t, block_f), lambda b, i, j, t: (b, t, i)),
        pl.BlockSpec((1, block_t, block_f), lambda b, i, j, t: (b, t, j)),
        pl.BlockSpec((1, block_t, c_cols), lambda b, i, j, t: (b, t, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, block_f, block_f), lambda b, i, j, t: (b, i, j)),
        pl.BlockSpec((1, block_f, c_cols), lambda b, i, j, t: (b, i, 0)),
    ]
    scratch = [
        pltpu.VMEM((block_f, block_f), jnp.float32),
        pltpu.VMEM((block_f, c_cols), jnp.float32),
    ]
    return in_specs, out_specs, scratch


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def gram_tiled_batched(
    x: jnp.ndarray,  # [B, T, F], T % block_t == 0, F % block_f == 0
    y: jnp.ndarray,  # [B, T, C]
    *,
    block_t: int = 512,
    block_f: int = 128,
    interpret: bool = False,
):
    batch, t_total, f_total = x.shape
    c_cols = y.shape[-1]
    grid = (batch, f_total // block_f, f_total // block_f, t_total // block_t)
    in_specs, out_specs, scratch = _specs(block_t, block_f, c_cols)

    kernel = functools.partial(_kernel, grid[3], False)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((batch, f_total, f_total), jnp.float32),
            jax.ShapeDtypeStruct((batch, f_total, c_cols), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ridge_gram",
    )(x, x, y)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def gram_tiled_batched_into(
    g0: jnp.ndarray,  # [B, F, F] f32 — running Gram stack (donated)
    c0: jnp.ndarray,  # [B, F, C] f32 — running moment stack (donated)
    x: jnp.ndarray,   # [B, T, F], T % block_t == 0, F % block_f == 0
    y: jnp.ndarray,   # [B, T, C]
    *,
    block_t: int = 512,
    block_f: int = 128,
    interpret: bool = False,
):
    """(G₀ + XᵀX, c₀ + XᵀY): fold one stream chunk into the running stats.

    The init stacks alias the outputs (in-place HBM update); each (b, i, j)
    tile reads its init block once (t == 0) before overwriting it on its
    last T step, so the aliasing is race-free under the sequential-T grid.
    """
    batch, t_total, f_total = x.shape
    c_cols = y.shape[-1]
    grid = (batch, f_total // block_f, f_total // block_f, t_total // block_t)
    in_specs, out_specs, scratch = _specs(block_t, block_f, c_cols)
    init_specs = [
        pl.BlockSpec((1, block_f, block_f), lambda b, i, j, t: (b, i, j)),
        pl.BlockSpec((1, block_f, c_cols), lambda b, i, j, t: (b, i, 0)),
    ]

    kernel = functools.partial(_kernel, grid[3], True)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=init_specs + in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((batch, f_total, f_total), jnp.float32),
            jax.ShapeDtypeStruct((batch, f_total, c_cols), jnp.float32),
        ],
        scratch_shapes=scratch,
        input_output_aliases={0: 0, 1: 1},
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ridge_gram_into",
    )(g0.astype(jnp.float32), c0.astype(jnp.float32), x, x, y)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f", "interpret"))
def gram_tiled(
    x: jnp.ndarray,  # [T, F], T % block_t == 0, F % block_f == 0
    y: jnp.ndarray,  # [T, C]
    *,
    block_t: int = 512,
    block_f: int = 128,
    interpret: bool = False,
):
    """Single-instance entry point: the batched kernel at B = 1."""
    g, c = gram_tiled_batched(x[None], y[None], block_t=block_t,
                              block_f=block_f, interpret=interpret)
    return g[0], c[0]
