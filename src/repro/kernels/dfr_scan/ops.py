"""Public jit'd wrapper for the DFR scan Pallas kernel.

Canonicalises [B, K] batches into the kernel's (S sublanes × 128 lanes)
tiling, pads the batch to a tile boundary, and restores [B, K, N] on the way
out.  On non-TPU backends the kernel runs in interpret mode (CPU-validated,
TPU-targeted); ``interpret`` can be forced either way.

``block_s`` sizes the sublane tile.  The default (``None``) picks the
smallest tile in {1, 2, 4, 8} that covers the batch, so small sweeps don't
pay for lanes they never use: a fixed block_s = 8 pads every batch to a
multiple of 1024 lanes (a B = 8 sweep would run 128× wasted reservoir work),
whereas auto-tiling pads B ≤ 128 to one 128-lane vreg row.

``mask`` is [N] (one mask broadcast across every batch lane — the paper's
sweep) or [B, N] (a mask per lane — WDM ensembles, where each lane is a
wavelength channel).  ``return_final=True`` additionally returns the final
reservoir state [B, N] straight from the kernel's VMEM carry: feeding it
back as ``s0`` of a following call resumes the scan bit-exactly for f32 I/O
(chunked streaming, train -> test continuation; DESIGN.md §8).

``params`` are per-lane device parameters (a pytree such as
``devices.cmt.CMTSweepParams`` with scalar or [B] leaves, DESIGN.md §14):
each leaf goes to the kernel as an [S, L] lane tile, the padded lanes
holding the model's own ``sweep_point()`` so that they stay finite.

Each dispatch is counted once per trace (``dispatch_counts``): lanes, lane
tiles, per-lane parameter leaves and the model's ``n_substeps``.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from .dfr_scan import LANES, dfr_scan_tiled

_BLOCK_S_CHOICES = (1, 2, 4, 8, 16, 32)
_DISPATCHES = collections.Counter()


def lane_params(params, b: int):
    """``params`` leaves (scalars or [b]) as the kernel's float32 [b] lanes."""
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(jnp.asarray(leaf, jnp.float32), (b,)), params)


def dispatch_counts() -> list[dict]:
    """The kernel's dispatches traced in this process, one dict per distinct
    layout with the number of traces (``traces``) that made it."""
    return [dict(key, traces=n) for key, n in _DISPATCHES.items()]


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def min_sublanes(dtype) -> int:
    """Minimum sublane count of a TPU vreg tile for ``dtype``.

    (8, 128) for 4-byte types, (16, 128) for 2-byte (bf16), (32, 128) for
    1-byte (int8/fp8) — the packing rule sublanes × itemsize = 32 bytes.
    A *multi-tile* block of this dtype must start on such a boundary; a
    block that spans the whole axis (single tile) is exempt, since Mosaic
    pads sub-minimal whole arrays internally.
    """
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def auto_block_s(batch: int, out_dtype=None) -> int:
    """Smallest sublane tile in {1, 2, 4, 8} whose (block_s, 128) tile covers
    ``batch``; once the batch spans multiple tiles, 8 (a full f32 vreg) — or
    the min tile of ``out_dtype`` when the *emitted* states are narrower than
    f32, so every multi-tile out block sits on a legal (16/32, 128) boundary
    instead of inheriting the f32 path's sub-minimal tile."""
    sublanes = -(-batch // LANES)
    for cand in _BLOCK_S_CHOICES[:4]:          # single-tile ladder: 1, 2, 4, 8
        if cand >= sublanes:
            return cand
    if out_dtype is not None and jnp.dtype(out_dtype).itemsize < 4:
        return min_sublanes(out_dtype)
    return 8


def padded_lanes(batch: int, block_s: int | None = None, out_dtype=None) -> int:
    """Total batch lanes (incl. padding) the kernel runs for ``batch``."""
    if block_s is None:
        block_s = auto_block_s(batch, out_dtype)
    tile = block_s * LANES
    return batch + (-batch % tile)


def dfr_scan(
    model,
    j: jnp.ndarray,      # [B, K]
    mask: jnp.ndarray,   # [N] (broadcast) or [B, N] (per-lane)
    s0: jnp.ndarray,     # [B, N]
    *,
    block_s: int | None = None,
    interpret: bool | None = None,
    return_final: bool = False,
    out_dtype=None,
    params=None,
):
    """States [B, K, N]; with ``return_final`` also the final state [B, N].

    ``out_dtype`` downcasts only the emitted state tensor (bf16 chunks for
    the streaming path); the final-state carry keeps the input dtype, so
    chunked resume stays bit-exact regardless of the chunk dtype.
    """
    if interpret is None:
        interpret = _auto_interpret()
    j = jnp.asarray(j)
    b, k_periods = j.shape
    mask = jnp.asarray(mask, j.dtype)
    n_nodes = int(mask.shape[-1])
    if mask.ndim == 2 and mask.shape[0] != b:
        raise ValueError(f"per-lane mask batch {mask.shape[0]} != j batch {b}")
    if block_s is None:
        block_s = auto_block_s(b, out_dtype)
    elif block_s not in _BLOCK_S_CHOICES:
        raise ValueError(f"block_s must be one of {_BLOCK_S_CHOICES}, got {block_s}")

    tile = block_s * LANES
    b_pad = -b % tile
    jp = jnp.pad(j, ((0, b_pad), (0, 0)))
    s0p = jnp.pad(jnp.asarray(s0, j.dtype), ((0, b_pad), (0, 0)))
    s_total = (b + b_pad) // LANES

    # [B, K] -> [K, S, L];  [B, N] -> [N, S, L]
    jt = jp.T.reshape(k_periods, s_total, LANES)
    s0t = s0p.T.reshape(n_nodes, s_total, LANES)
    if mask.ndim == 2:
        maskt = jnp.pad(mask, ((0, b_pad), (0, 0))).T.reshape(n_nodes, s_total, LANES)
    else:
        maskt = mask.reshape(n_nodes, 1)
    paramst = None
    if params is not None:
        # padded lanes run the model's own operating point, so stay finite
        paramst = jax.tree.map(
            lambda leaf, pad: jnp.pad(leaf, (0, b_pad), constant_values=pad)
            .reshape(s_total, LANES), lane_params(params, b), model.sweep_point())
    _DISPATCHES[(("lanes", b), ("padded_lanes", b + b_pad),
                 ("lane_tiles", s_total // block_s), ("block_s", block_s),
                 ("periods", k_periods), ("nodes", n_nodes),
                 ("param_leaves", len(jax.tree.leaves(params))),
                 ("n_substeps", getattr(model, "n_substeps", 1)))] += 1

    out, fin = dfr_scan_tiled(model, jt, maskt, s0t, block_s=block_s,
                              interpret=interpret, out_dtype=out_dtype,
                              params=paramst)
    # [K, N, S, L] -> [B, K, N];  [N, S, L] -> [B, N]
    out = out.reshape(k_periods, n_nodes, s_total * LANES)
    states = jnp.moveaxis(out, -1, 0)[:b]
    if not return_final:
        return states
    s_final = fin.reshape(n_nodes, s_total * LANES).T[:b]
    return states, s_final
