"""In-graph ridge readout: streaming Gram accumulation + GCV λ selection.

The host-side trainer (core/readout.py) solves the readout in float64 with a
numpy SVD — fine for one accelerator, useless for a jit/vmap sweep.  This
module is the pure-jax equivalent built on the *Gram* statistics

    G = XᵀX  [F, F],    c = Xᵀy  [F, C],    y2 = ‖y‖²

which are (a) streamable — the T×N state matrix never has to be resident,
(b) accumulable with the kernels/ridge_gram Pallas kernel, and (c) shardable:
``gram`` constrains the sample axis over the ("pod", "data") mesh axes via
parallel/sharding.maybe_shard, so under an active mesh each device reduces
its local shard of the state stream and GSPMD inserts the psum.

``fit_ridge_streaming`` takes (a) to its conclusion (DESIGN.md §8): one
jitted ``lax.scan`` over K-chunks drives the reservoir kernel and the
accumulate-into Gram kernel back to back, so the full per-instance state
matrix never exists in HBM — peak state memory is O(B·chunk·N) instead of
O(B·T·N), with washout handled by row masking, the bias column folded into
the chunk update, and digitiser noise applied as its expected Tikhonov
diagonal (``state_noise_mode="diagonal"``).

λ selection matches core/readout.py: generalised cross-validation

    GCV(λ) = T·‖y − ŷ_λ‖² / (T − dof(λ))²,   dof(λ) = Σ λᵢ/(λᵢ + λ′)

evaluated from the eigendecomposition G = QΛQᵀ (the λᵢ are the squared
singular values of X, so dof agrees with the host SVD path), with
λ′ = λ·tr(G)/F.  Everything — residual, dof, the winning weight vector — is
a function of (G, c, y2, T) only.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import ReservoirGraph, stage_link_drive, stage_states
from repro.core.reservoir import generate_channel_states, generate_states
from repro.parallel.sharding import maybe_shard, over_batch_shards

from . import qdwh_eigh, scopes

# The readout's f32 linear algebra asks for full f32 matmuls: at the default
# precision a TPU multiplies f32 operands in one bf16 pass, which loses ~3
# digits of the Gram statistics and of the eigenbasis products of the solve.
_F32 = jax.lax.Precision.HIGHEST


def with_bias(states: jnp.ndarray) -> jnp.ndarray:
    """Append the constant-1 bias feature: [..., T, N] -> [..., T, N + 1]."""
    ones = jnp.ones((*states.shape[:-1], 1), dtype=states.dtype)
    return jnp.concatenate([states, ones], axis=-1)


def gram(x: jnp.ndarray, y: jnp.ndarray, *, use_kernel: bool = False):
    """(G = XᵀX [F, F], c = Xᵀy [F, C]) in f32 from X [T, F], y [T, C].

    ``use_kernel=True`` accumulates with the Pallas streaming kernel
    (interpret mode off-TPU); the jnp path shards the sample axis.
    """
    if use_kernel:
        from repro.kernels.ridge_gram import ops as gram_ops

        return gram_ops.gram_accumulate(x, y)
    x32 = maybe_shard(x.astype(jnp.float32), ("pod", "data"))
    y32 = maybe_shard(y.astype(jnp.float32), ("pod", "data"))
    return (jnp.matmul(x32.T, x32, precision=_F32),
            jnp.matmul(x32.T, y32, precision=_F32))


# On the TPU an eigh of at most this many rows is XLA's Jacobi, natively
# batched, refined once (``_jacobi_refined``); a wider one is JAX's QDWH
# divide and conquer, which ``qdwh_eigh`` runs on fewer zeros
_JACOBI_ROWS = 256


def _lapack_eigh(g):
    return tuple(jnp.linalg.eigh(g))


def _round_robin(n: int) -> np.ndarray:
    """[n - 1, n] index orders for a cyclic Jacobi sweep over n (even)
    indices: round r pairs (order[2k], order[2k + 1]), n / 2 disjoint pairs,
    and the n - 1 rounds meet every pair once (the circle method)."""
    rounds = []
    for r in range(n - 1):
        ring = [0] + [1 + (i + r) % (n - 1) for i in range(n - 1)]
        rounds.append([ring[i] for k in range(n // 2) for i in (k, n - 1 - k)])
    return np.asarray(rounds, np.int32)


def _rotate_pairs(x, c, s, axis):
    """Rotate the adjacent pairs (2k, 2k + 1) of ``x`` along ``axis`` (-1
    or -2) by the Jacobi rotations (c, s): the columns of X J, or the rows
    of J^T X."""
    a, b = (x[..., 0::2], x[..., 1::2]) if axis == -1 else (x[..., 0::2, :], x[..., 1::2, :])
    pair = jnp.stack([c * a - s * b, s * a + c * b], axis=axis)
    return pair.reshape(x.shape)


def _jacobi_sweep(h, q):
    """One cyclic Jacobi sweep in float32 on the symmetric ``h`` [..., n, n]
    (n even), its rotations applied to the columns of ``q`` [..., m, n]:
    each round rotates n / 2 disjoint pairs (Golub & Van Loan, Alg. 8.5.1).
    On a nearly diagonal ``h`` the off-diagonal falls quadratically."""

    # a pair whose coupling is at rounding of the largest diagonal entry is
    # left alone: rotating it (a cluster of equal eigenvalues, say) moves
    # nothing above rounding and costs an ulp of orthogonality
    floor = jnp.finfo(h.dtype).eps * jnp.max(
        jnp.abs(jnp.diagonal(h, axis1=-2, axis2=-1)), axis=-1)[..., None]

    # unrolled over the rounds (F is static and at most _JACOBI_ROWS): h and
    # q are kept in the current round's index order, moved to the next
    # round's by one gather per axis, and put back in order at the end
    where = np.arange(h.shape[-1])
    for order in _round_robin(h.shape[-1]):
        move = np.argsort(where)[order]          # positions of ``order`` now
        h = jnp.take(jnp.take(h, move, axis=-2), move, axis=-1)
        q = jnp.take(q, move, axis=-1)
        where = order
        d = jnp.diagonal(h, axis1=-2, axis2=-1)
        hpp, hqq = d[..., 0::2], d[..., 1::2]
        hpq = jnp.diagonal(h[..., 0::2, 1::2], axis1=-2, axis2=-1)
        zero = jnp.abs(hpq) <= floor
        tau = (hqq - hpp) / (2 * jnp.where(zero, 1.0, hpq))
        t = jnp.where(tau >= 0, 1.0, -1.0) / (jnp.abs(tau) + jnp.sqrt(1 + tau * tau))
        t = jnp.where(zero, 0.0, t)
        c = 1 / jnp.sqrt(1 + t * t)
        s = t * c
        h = _rotate_pairs(h, c[..., :, None], s[..., :, None], -2)
        h = _rotate_pairs(h, c[..., None, :], s[..., None, :], -1)
        q = _rotate_pairs(q, c[..., None, :], s[..., None, :], -1)
    back = np.argsort(where)
    return jnp.take(jnp.take(h, back, axis=-2), back, axis=-1), jnp.take(q, back, axis=-1)


def _rayleigh_ritz(g, q):
    """One Rayleigh-Ritz step on an approximate eigenbasis ``q`` of ``g``:
    the Gram in that basis, H = Q^T G Q (nearly diagonal), diagonalised by
    one Jacobi sweep whose rotations turn Q; (eigenvalues ascending,
    eigenvectors).  Batched like ``jnp.linalg.eigh``.  From a basis ~50 eps
    off (as the TPU's Jacobi leaves the chan-eq Grams) one sweep reaches
    LAPACK's residual; a second changes nothing.

    XLA's Jacobi cannot do this step: it takes H as already converged and
    returns it untouched.  An odd F is padded by one decoupled index."""
    f = g.shape[-1]
    pad = f % 2
    with jax.named_scope(scopes.EIGH_REFINE):
        h = jnp.matmul(jnp.swapaxes(q, -1, -2),
                       jnp.matmul(g, q, precision=_F32), precision=_F32)
        widen = [(0, 0)] * (h.ndim - 2)
        h = jnp.pad(h, widen + [(0, pad), (0, pad)])
        h, q = _jacobi_sweep(h, jnp.pad(q, widen + [(0, 0), (0, pad)]))
        evals = jnp.diagonal(h, axis1=-2, axis2=-1)[..., :f]
        order = jnp.argsort(evals, axis=-1)
        return (jnp.take_along_axis(evals, order, axis=-1),
                jnp.take_along_axis(q[..., :f], order[..., None, :], axis=-1))


def _jacobi_refined(g):
    """XLA's Jacobi ``eigh`` of a Gram, then one Rayleigh-Ritz step.

    The TPU's Jacobi stops at a tolerance of 1e-6, which leaves residuals
    ||G Q - Q L|| of several 1e-6 of the largest eigenvalue, where LAPACK
    leaves a few eps; ``solve_gcv``'s 4 eps cut-off assumes the latter."""
    return _rayleigh_ritz(g, jnp.linalg.eigh(g)[1])


@jax.custom_batching.custom_vmap
def _eigh(g):
    """``jnp.linalg.eigh`` of one Gram [F, F]: (eigenvalues ascending,
    eigenvectors).  On the TPU, up to ``_JACOBI_ROWS`` XLA's Jacobi refined
    (``_jacobi_refined``), wider ``qdwh_eigh``'s, one matrix at a time
    under ``vmap``; elsewhere LAPACK."""
    tpu = _jacobi_refined if g.shape[-1] <= _JACOBI_ROWS else qdwh_eigh.eigh
    return jax.lax.platform_dependent(g, tpu=tpu, default=_lapack_eigh)


@_eigh.def_vmap
def _eigh_vmap(axis_size, in_batched, g):
    if not in_batched[0]:
        return _eigh(g), (False, False)
    if g.shape[-1] <= _JACOBI_ROWS:
        return jax.lax.platform_dependent(
            g, tpu=_jacobi_refined, default=jax.vmap(_lapack_eigh)), (True, True)
    # a map of ``_eigh``, so a vmap around this one maps again; under a mesh
    # each device maps its own shard of the batch
    return jax.lax.platform_dependent(
        g, tpu=lambda g: over_batch_shards(lambda g: jax.lax.map(_eigh, g), (g,), (True,)),
        default=jax.vmap(_lapack_eigh)), (True, True)


@scopes.scoped(scopes.SOLVE)
def solve_gcv(
    g: jnp.ndarray,        # [F, F]
    c: jnp.ndarray,        # [F, C]
    y2: jnp.ndarray,       # scalar ‖y‖²
    n_samples: int,
    lambdas: tuple[float, ...],
):
    """Ridge solve (G + λ·tr(G)/F·I)w = c with GCV-selected λ.

    Returns (w [F, C], lam_idx) — ``lam_idx`` indexes the winning entry of
    the static ``lambdas`` tuple.  A single-element tuple skips nothing but
    costs one extra reduction; the eigendecomposition dominates either way.
    On the TPU with F > 256 the eigendecomposition is ``qdwh_eigh``'s divide
    and conquer, one matrix at a time under ``vmap``, and at F <= 256 XLA's
    Jacobi refined by one Rayleigh-Ritz step (``_eigh``); else
    ``jnp.linalg.eigh``.
    """
    f = g.shape[0]
    g32 = g.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    with jax.named_scope(scopes.EIGH):
        evals, q = _eigh(g32)                    # λᵢ ascending; tiny negatives
    evals = jnp.maximum(evals, 0.0)              # from f32 round-off -> clamp
    qc = jnp.matmul(q.T, c32, precision=_F32)    # [F, C]
    # Rank truncation: eigenvalues below f32 noise are not signal — keeping
    # them poisons both w (1/λᵢ blow-up) and the residual (the stray qc
    # energy in a null direction enters as qc²/λ′).  The 4·eps·λmax cutoff
    # is calibrated on NARMA10: at F·eps real signal directions get dropped
    # (NRMSE 0.80 vs the host float64 path's 0.60), at 0 the null-space
    # noise explodes some instances.
    tol = evals[-1] * jnp.asarray(4 * jnp.finfo(jnp.float32).eps, jnp.float32)
    valid = evals > tol
    qc = jnp.where(valid[:, None], qc, 0.0)
    qc2 = jnp.sum(qc * qc, axis=1)               # [F]
    lamp = jnp.asarray(lambdas, jnp.float32) * (jnp.sum(evals) / f)  # [L]

    def per_lambda(lam):
        inv = jnp.where(valid, 1.0 / (evals + lam), 0.0)   # [F]
        w = jnp.matmul(q, qc * inv[:, None], precision=_F32)   # [F, C]
        dof = jnp.sum(evals * inv)
        # ‖y − ŷ‖² = ‖y‖² − Σᵢ qcᵢ²·(λᵢ + 2λ′)/(λᵢ + λ′)²  — evaluated in
        # the eigenbasis; the naive y2 − 2cᵀw + wᵀGw cancels catastrophically
        # in f32 once cond(G) approaches 1/eps.
        fit_energy = jnp.sum(qc2 * jnp.where(valid, (evals + 2.0 * lam) * inv * inv, 0.0))
        rss = jnp.maximum(y2 - fit_energy, 0.0)
        gcv = n_samples * rss / jnp.maximum(n_samples - dof, 1.0) ** 2
        return w, gcv

    ws, gcvs = jax.vmap(per_lambda)(lamp)        # [L, F, C], [L]
    idx = jnp.argmin(gcvs)
    return ws[idx], idx


@scopes.scoped(scopes.SOLVE)
def solve_gcv_svd(
    x: jnp.ndarray,        # [T, F]
    y: jnp.ndarray,        # [T, C]
    lambdas: tuple[float, ...],
):
    """GCV ridge from the SVD of X — the default in-graph solve.

    Works on X directly, so its conditioning is √cond(G): in f32 this
    matches the host float64 Gram path on every paper task, where the
    eigh-of-G route loses the small singular directions (cond squares).
    Use the Gram route (``solve_gcv``) only when X cannot be resident —
    streaming/kernel accumulation.
    """
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    u, s, vt = jnp.linalg.svd(x32, full_matrices=False)   # [T,F], [F], [F,F]
    uty = jnp.matmul(u.T, y32, precision=_F32)            # [F, C]
    uy2 = jnp.sum(uty * uty, axis=1)                      # [F]
    y2 = jnp.sum(y32 * y32)
    s2 = s * s
    n_samples = x.shape[0]
    lamp = jnp.asarray(lambdas, jnp.float32) * (jnp.sum(s2) / x.shape[1])

    def per_lambda(lam):
        shrink = s2 / (s2 + lam)                          # [F]
        w = jnp.matmul(vt.T, uty * (s / (s2 + lam))[:, None],
                       precision=_F32)                    # [F, C]
        dof = jnp.sum(shrink)
        rss = jnp.maximum(y2 - jnp.sum((2.0 * shrink - shrink * shrink) * uy2), 0.0)
        gcv = n_samples * rss / jnp.maximum(n_samples - dof, 1.0) ** 2
        return w, gcv

    ws, gcvs = jax.vmap(per_lambda)(lamp)
    idx = jnp.argmin(gcvs)
    return ws[idx], idx


@scopes.scoped(scopes.SOLVE)
def fit_ridge(
    states: jnp.ndarray,   # [T, N]
    targets: jnp.ndarray,  # [T] or [T, C]
    *,
    lambdas: tuple[float, ...] = (1e-6,),
    use_kernel: bool = False,
):
    """One-shot readout fit: states -> (w [N + 1, C], lam_idx).

    Pure jax; jit- and vmap-safe (``lambdas`` must be a static tuple).
    Default path is the SVD-of-X solve; ``use_kernel=True`` switches to the
    streaming Gram accumulation (Pallas kernel) + eigh solve, trading the
    last decade of λ-conditioning for never materialising X on device.
    """
    y = targets[:, None] if targets.ndim == 1 else targets
    x = with_bias(states)
    if use_kernel:
        g, c = gram(x, y.astype(x.dtype), use_kernel=True)
        y2 = jnp.sum(y.astype(jnp.float32) ** 2)
        return solve_gcv(g, c, y2, x.shape[0], tuple(lambdas))
    return solve_gcv_svd(x, y, tuple(lambdas))


@scopes.scoped(scopes.COLLECT)
def fit_ridge_batched(
    states: jnp.ndarray,   # [B, T, N]
    targets: jnp.ndarray,  # [B, T] or [B, T, C]
    *,
    lambdas: tuple[float, ...] = (1e-6,),
    use_kernel: bool = False,
    block_t: int = 512,
):
    """Batched readout fit: B instance fits -> (w [B, N + 1, C], lam_idx [B]).

    The default (SVD) path is just ``vmap(fit_ridge)``.  ``use_kernel=True``
    runs ONE batch-gridded Pallas ``gram_accumulate_batched`` launch over the
    whole instance stack (the kernel has no jax batching rule, so a naive
    vmap/``lax.map`` would serialise B launches) and vmaps the eigh/GCV solve
    over the resulting [B, F, F] Gram stack.  ``block_t`` sizes the kernel's
    T tile (sublane-aligned internally).
    """
    y = targets[..., None] if targets.ndim == 2 else targets
    lams = tuple(lambdas)
    if use_kernel:
        from repro.kernels.ridge_gram import ops as gram_ops

        x = with_bias(states)
        g, c = over_batch_shards(
            functools.partial(gram_ops.gram_accumulate_batched,
                              block_t=block_t),
            (x, y.astype(x.dtype)), (True, True))
        y32 = y.astype(jnp.float32)
        y2 = jnp.sum(y32 * y32, axis=(1, 2))
        n_samples = x.shape[1]
        return jax.vmap(lambda gb, cb, y2b: solve_gcv(gb, cb, y2b, n_samples, lams))(
            g, c, y2)
    return jax.vmap(functools.partial(fit_ridge, lambdas=lams))(states, y)


def guard_readout(w_new: jnp.ndarray, idx_new: jnp.ndarray,
                  w_last: jnp.ndarray, idx_last: jnp.ndarray):
    """Last-good-readout fallback for batched GCV solves (DESIGN.md §12).

    ``w_new`` [B, F, C] / ``idx_new`` [B] is a freshly solved readout batch;
    rows where the solve produced any non-finite weight keep
    (``w_last``, ``idx_last``) instead — an eigh that failed to converge or
    a fold that slipped an Inf past the upstream guards must degrade ONE
    row to its previous readout, never emit NaN predictions or poison the
    slab.  Pure ``jnp.where`` row selects: for finite rows the fallback is
    bitwise invisible, so guarded solves stay bit-identical to unguarded
    ones on healthy data (tests/test_robustness.py pins both properties).
    """
    ok = jnp.all(jnp.isfinite(w_new.reshape(w_new.shape[0], -1)), axis=1)
    w = jnp.where(ok[:, None, None], w_new, w_last)
    idx = jnp.where(ok, idx_new.astype(idx_last.dtype), idx_last)
    return w, idx


def apply_readout(states: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """y = [states, 1] @ w; squeezes a single output channel."""
    y = jnp.matmul(with_bias(states), w, precision=_F32)
    return y[..., 0] if y.shape[-1] == 1 else y


def _chunk_layout(k_total: int, chunk_k: int):
    """Static chunking of a K-long stream: (n_chunks, padded K)."""
    if chunk_k < 1:
        raise ValueError(f"chunk_k must be >= 1, got {chunk_k}")
    n_chunks = -(-k_total // chunk_k)
    return n_chunks, n_chunks * chunk_k


def _chunk_axis(x: jnp.ndarray, n_chunks: int, chunk_k: int) -> jnp.ndarray:
    """[B, Kp, ...] -> [n_chunks, B, chunk_k, ...] (zero-padded upstream)."""
    b = x.shape[0]
    return jnp.moveaxis(x.reshape(b, n_chunks, chunk_k, *x.shape[2:]), 1, 0)


def _canon_stream(j, targets):
    """Canonicalise a (j, targets) stream pair to ([B, K], [B, K, C])."""
    j = jnp.asarray(j, jnp.float32)
    if j.ndim == 1:
        j = j[None, :]
    y = jnp.asarray(targets, jnp.float32)
    if y.ndim == 1:
        y = y[None, :]
    if y.ndim == 2:
        y = y[..., None]
    if y.shape[:2] != j.shape:
        raise ValueError(f"targets {y.shape} do not match inputs {j.shape}")
    return j, y


@dataclasses.dataclass(frozen=True)
class _FoldPlan:
    """Static layout of one chunk -> Gram fold (shared by the streaming fits
    and the online-learning sessions, pipeline/session.py).

    ``fq`` is the feature-padded Gram side (kernel path: F rounded up to the
    block_f tile so the carried [B, Fp, Fp] stacks never pad per chunk);
    ``chunk_pt``/``eff_bt`` are the sublane-aligned T tile of the Pallas Gram
    kernel (16-row tiles for sub-f32 chunks).  The jnp path folds with a
    plain einsum and needs no padding.
    """

    f: int            # features = N + 1 (bias folded)
    fq: int           # feature-padded Gram side
    chunk_k: int      # periods per chunk
    chunk_pt: int     # T-tile-padded chunk length (kernel path)
    eff_bt: int       # effective Gram T tile (kernel path)
    block_f: int
    use_kernel: bool
    interpret: bool


def _plan_fold(f: int, chunk_k: int, *, use_kernel: bool, block_t: int,
               block_f: int, state_dtype) -> _FoldPlan:
    """Resolve the static fold layout for (F, chunk) under the chosen path."""
    interpret = jax.default_backend() != "tpu"
    if use_kernel:
        from repro.kernels.ridge_gram.ops import effective_block_t

        eff_bt = effective_block_t(chunk_k, block_t)
        sdt = jnp.dtype(state_dtype if state_dtype is not None else jnp.float32)
        if sdt.itemsize < 4:
            # sub-f32 chunks need a 16-row sublane tile (bf16 min tile is
            # (16, 128)); round the T tile up and let padding absorb it.
            eff_bt = -(-eff_bt // 16) * 16
        chunk_pt = chunk_k + (-chunk_k % eff_bt)
        fq = f + (-f % block_f)
    else:
        eff_bt, chunk_pt, fq = 0, chunk_k, f
    return _FoldPlan(f=f, fq=fq, chunk_k=chunk_k, chunk_pt=chunk_pt,
                     eff_bt=eff_bt, block_f=block_f, use_kernel=use_kernel,
                     interpret=interpret)


def _fold_chunk(plan: _FoldPlan, g, cvec, y2, x, yv, *, forgetting: float = 1.0):
    """Fold one washout/padding-masked chunk into the running statistics.

    ``x`` [B, chunk, F] (bias column appended, invalid rows zeroed), ``yv``
    [B, chunk, C] (invalid rows zeroed) update G [B, Fq, Fq], c [B, Fq, C]
    and ‖y‖² [B] — via the accumulate-into Pallas kernel or a plain einsum,
    per ``plan``.  ``forgetting`` < 1 applies RLS-style exponential decay:
    the *carried* statistics are scaled by λ before this chunk accumulates,
    so after n chunks chunk i carries weight λ^(n-1-i).  At λ = 1.0 the
    scaling inserts no ops at trace time — the fold is bit-identical to the
    historical (un-decayed) path, which tests/benchmarks pin bitwise.
    """
    if forgetting != 1.0:
        lam = jnp.float32(forgetting)
        g = g * lam
        cvec = cvec * lam
        y2 = y2 * lam
    y2 = y2 + jnp.sum(yv * yv, axis=(1, 2))
    if plan.use_kernel:
        from repro.kernels.ridge_gram.ridge_gram import gram_tiled_batched_into

        xq = jnp.pad(x, ((0, 0), (0, plan.chunk_pt - plan.chunk_k),
                         (0, plan.fq - plan.f)))
        yq = jnp.pad(yv, ((0, 0), (0, plan.chunk_pt - plan.chunk_k), (0, 0)))
        fold = functools.partial(gram_tiled_batched_into, block_t=plan.eff_bt,
                                 block_f=plan.block_f,
                                 interpret=plan.interpret)
        g, cvec = over_batch_shards(fold, (g, cvec, xq, yq), (True,) * 4)
    else:
        g = g + jnp.einsum("btf,btg->bfg", x, x, precision=_F32,
                           preferred_element_type=jnp.float32)
        cvec = cvec + jnp.einsum("btf,btc->bfc", x, yv, precision=_F32,
                                 preferred_element_type=jnp.float32)
    return g, cvec, y2


def _fit_streaming_core(
    states_fn,             # (j_chunk [B, chunk, ...], carry f32) -> (states, carry')
    n: int,                # feature nodes per instance (graph width)
    j: jnp.ndarray,        # [B, K] (or [B, K, ...]) canonicalised stream
    y: jnp.ndarray,        # [B, K, C] canonicalised targets
    *,
    washout: int,
    chunk_k: int,
    lambdas: tuple[float, ...],
    use_kernel: bool,
    block_t: int,
    block_f: int,
    noise_rel: float,
    state_dtype,
    s0,                    # carry pytree matching states_fn (None = dark)
    forgetting: float = 1.0,
    carry_layout: tuple[tuple[int, int], ...] | None = None,
):
    """The shared chunk-scan of both streaming fits (DESIGN.md §8/§9/§10).

    ``states_fn`` is the only degree of freedom between the single-mask fit
    (``fit_ridge_streaming``: one mask broadcast over B task instances) and
    the WDM fit (``fit_ridge_streaming_wdm``: per-channel masks, B = R
    wavelength channels) — everything downstream of state generation (washout
    row-masking, bias fold, Gram accumulation, noise-as-Tikhonov, the GCV
    solve) is identical, so it lives here once.

    ``state_dtype`` (e.g. bf16) applies to the emitted state *chunks* only:
    the reservoir carry between chunks stays f32 (resume is unaffected), the
    Gram/moment accumulators stay f32 (MXU partials via
    ``preferred_element_type``), and the target stream stays f32 — only the
    [B, chunk, F] block that round-trips through HBM per chunk narrows, which
    is where the traffic is.

    ``forgetting`` < 1 turns the fit into RLS-style exponential forgetting
    (DESIGN.md §10): the carried (G, c, ‖y‖²) are scaled by λ per chunk
    before the chunk accumulates, and the GCV solve sees the *effective*
    (decayed) sample count instead of T_fit.  λ = 1.0 adds no ops — the
    historical path, pinned bitwise by tests/test_serving.py.

    ``carry_layout`` generalises the reservoir carry from one [B, N] array to
    a pytree (DESIGN.md §13): a tuple of per-stage (L, N_s) entries declares
    the carry a matching tuple of [B, L, N_s] leaves AND how a feature row
    [B, n] slices back into per-stage carries (stage s occupies columns
    [Σ_{<s} L·N, …), loop-major within the stage) — which is what the
    mid-stream s_end extraction needs when the last real period is not at a
    chunk end.  ``None`` keeps the legacy single-array carry with identical
    traced ops, so existing fits stay bitwise.
    """
    b, k_total = j.shape[0], j.shape[1]
    f = n + 1
    c_cols = y.shape[-1]
    if k_total <= washout:
        raise ValueError(f"stream length {k_total} <= washout {washout}")
    if not 0.0 < forgetting <= 1.0:
        raise ValueError(f"forgetting must be in (0, 1], got {forgetting}")
    if noise_rel and forgetting != 1.0:
        raise ValueError(
            "noise_rel as an expected Tikhonov diagonal assumes un-decayed "
            "Gram statistics; forgetting < 1 is not supported with it")
    t_fit = k_total - washout
    n_chunks, k_padded = _chunk_layout(k_total, chunk_k)
    plan = _plan_fold(f, chunk_k, use_kernel=use_kernel, block_t=block_t,
                      block_f=block_f, state_dtype=state_dtype)
    fq = plan.fq

    jp = jnp.pad(j, ((0, 0), (0, k_padded - k_total))
                 + ((0, 0),) * (j.ndim - 2))
    yp = jnp.pad(y, ((0, 0), (0, k_padded - k_total), (0, 0)))
    if carry_layout is None:
        if s0 is None:
            s0 = jnp.zeros((b, n), jnp.float32)
        res0 = jnp.asarray(s0, jnp.float32)

        def carry_from_row(row):   # [B, n] f32 feature row IS the carry
            return row
    else:
        if s0 is None:
            s0 = tuple(jnp.zeros((b, lp, w), jnp.float32)
                       for lp, w in carry_layout)
        res0 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tuple(s0))
        offs, off = [], 0
        for lp, w in carry_layout:
            offs.append(off)
            off += lp * w
        if off != n:
            raise ValueError(f"carry_layout covers {off} features, expected {n}")

        def carry_from_row(row):   # [B, n] f32 -> tuple of [B, L, N_s]
            return tuple(
                jax.lax.dynamic_slice_in_dim(row, o, lp * w, axis=1)
                .reshape(b, lp, w)
                for o, (lp, w) in zip(offs, carry_layout))

    carry0 = (
        res0,                                  # running reservoir carry
        jnp.zeros((b, fq, fq), jnp.float32),   # G (feature-padded on kernel path)
        jnp.zeros((b, fq, c_cols), jnp.float32),
        jnp.zeros((b,), jnp.float32),          # ‖y‖² over the fit window
        jnp.zeros((b,), jnp.float32),          # Σ s   (noise σ estimate)
        jnp.zeros((b,), jnp.float32),          # Σ s²
        jnp.zeros((b,), jnp.float32),          # effective (decayed) samples
        res0,                                  # carry after period K - 1
    )
    xs = (_chunk_axis(jp, n_chunks, chunk_k),
          _chunk_axis(yp, n_chunks, chunk_k),
          jnp.arange(n_chunks, dtype=jnp.int32) * chunk_k)

    def body(carry, chunk):
        s, g, cvec, y2, ssum, ssq, tcnt, s_end = carry
        j_c, y_c, k_start = chunk
        states, s_next = states_fn(j_c, s)
        tidx = k_start + jnp.arange(chunk_k, dtype=jnp.int32)
        vfit = ((tidx >= washout) & (tidx < k_total)).astype(jnp.float32)

        x = jnp.concatenate(
            [states, jnp.ones((b, chunk_k, 1), states.dtype)], axis=-1)
        # washout/padding rows -> zero; keep the mask in the chunk dtype so a
        # bf16 chunk is not silently promoted back to f32 by the multiply
        x = x * vfit.astype(x.dtype)[None, :, None]
        yv = y_c * vfit[None, :, None]
        if noise_rel:
            sv = states.astype(jnp.float32) * vfit[None, :, None]
            ssum = ssum + jnp.sum(sv, axis=(1, 2))
            ssq = ssq + jnp.sum(sv * sv, axis=(1, 2))
        if forgetting != 1.0:
            tcnt = tcnt * jnp.float32(forgetting) + jnp.sum(vfit)

        g, cvec, y2 = _fold_chunk(plan, g, cvec, y2, x, yv,
                                  forgetting=forgetting)

        # State after period K - 1 (this chunk's padded tail, if any, keeps
        # evolving on zero input — the carry must come from the last *real*
        # period, not the end of the chunk).  When the last real period sits
        # exactly at the chunk end, prefer the f32 VMEM carry over the state
        # tensor: with bf16 chunks the tensor is rounded, the carry is not.
        in_chunk = (k_start <= k_total - 1) & (k_total - 1 < k_start + chunk_k)
        at_chunk_end = k_total - 1 == k_start + chunk_k - 1
        last_local = jnp.clip(k_total - 1 - k_start, 0, chunk_k - 1)
        row = jax.lax.dynamic_index_in_dim(states, last_local, axis=1,
                                           keepdims=False).astype(jnp.float32)
        s_k = carry_from_row(row)
        s_k = jax.tree.map(lambda nxt, sk: jnp.where(at_chunk_end, nxt, sk),
                           s_next, s_k)
        s_end = jax.tree.map(lambda sk, se: jnp.where(in_chunk, sk, se),
                             s_k, s_end)
        return (s_next, g, cvec, y2, ssum, ssq, tcnt, s_end), None

    (s_last, g, cvec, y2, ssum, ssq, tcnt, s_end), _ = jax.lax.scan(
        body, carry0, xs)
    del s_last

    if noise_rel:
        cnt = jnp.asarray(t_fit * n, jnp.float32)
        var = jnp.maximum(ssq / cnt - (ssum / cnt) ** 2, 0.0)
        sig2_t = (noise_rel ** 2) * var * t_fit       # σ²·T_fit per instance
        dn = jnp.arange(n)
        g = g.at[:, dn, dn].add(sig2_t[:, None])
    g = g[:, :f, :f]
    cvec = cvec[:, :f]

    lams = tuple(lambdas)
    if forgetting != 1.0:
        # decayed statistics -> decayed effective sample count in the GCV
        # score (Σ_i λ^(n-1-i)·valid_i, the standard RLS memory length)
        w, idx = jax.vmap(lambda gb, cb, y2b, nb: solve_gcv(
            gb, cb, y2b, nb, lams))(g, cvec, y2, tcnt)
    else:
        w, idx = jax.vmap(
            lambda gb, cb, y2b: solve_gcv(gb, cb, y2b, t_fit, lams))(g, cvec, y2)
    return w, idx, s_end


@scopes.scoped(scopes.COLLECT)
@functools.partial(jax.jit, static_argnames=(
    "model", "washout", "chunk_k", "lambdas", "state_method", "block_s",
    "use_kernel", "block_t", "block_f", "noise_rel", "state_dtype",
    "forgetting"))
def fit_ridge_streaming(
    model,
    mask: jnp.ndarray,     # [N]
    j: jnp.ndarray,        # [B, K] sample-and-held input stream
    targets: jnp.ndarray,  # [B, K] or [B, K, C]
    *,
    washout: int,
    chunk_k: int,
    lambdas: tuple[float, ...] = (1e-6,),
    state_method: str = "kernel",
    block_s: int | None = None,
    use_kernel: bool = True,
    block_t: int = 512,
    block_f: int = 128,
    noise_rel: float = 0.0,
    state_dtype=None,
    s0: jnp.ndarray | None = None,
    forgetting: float = 1.0,
    dev_params=None,
):
    """Streaming fused reservoir -> readout fit: states never fully resident.

    ONE ``lax.scan`` over ``ceil(K / chunk_k)`` chunks; each iteration runs
    the reservoir for ``chunk_k`` periods (resuming bit-exactly from the
    carried final state), masks washout/padding rows to zero, appends the
    bias column, and folds the chunk into running per-instance Gram stacks
    (G [B, F, F], c [B, F, C], F = N + 1) — via the accumulate-into Pallas
    kernel (``use_kernel=True``, carried in feature-padded [B, Fp, Fp] form
    so no per-chunk pad/slice copies of G) or a plain einsum.  Peak live
    state memory is O(B·chunk_k·N); the [B, K, N] state tensor of the
    materialized path never exists.  ``state_dtype`` (e.g. ``"bfloat16"``)
    narrows the emitted state chunks, halving their HBM round-trip; carry
    and accumulators stay f32 (DESIGN.md §9 bounds the accuracy cost).

    The solve is necessarily the Gram/eigh route (``solve_gcv``): running
    (G, c, ‖y‖²) statistics are all a streaming fit ever holds, and the
    better-conditioned SVD-of-X solve needs X resident.  Parity targets are
    therefore the materialized *Gram* fit (``fit_ridge_batched(use_kernel=
    True)``); vs the SVD default the last decade of λ-conditioning can
    differ (see ``solve_gcv_svd``).

    ``noise_rel`` > 0 applies the digitiser noise of the materialized path
    in expectation, without a second pass over the stream: for i.i.d. state
    noise ε with σ = noise_rel·std(states over the fit window),

        E[(X+ε)ᵀ(X+ε)] = XᵀX + σ²·T_fit·I,   E[(X+ε)ᵀy] = Xᵀy,

    so the fit adds σ²·T_fit to the N state-feature diagonal entries of G
    (not the bias), with σ estimated from in-scan sum/sum-of-squares
    accumulators over the same fit window.  This is
    ``ExperimentConfig.state_noise_mode="diagonal"``; the sampled-noise path
    stays available on the unfused route.

    ``forgetting`` < 1 applies RLS-style exponential forgetting (DESIGN.md
    §10): chunk i of n carries weight λ^(n-1-i) in the Gram statistics, so
    the fit tracks a drifting stream (online channel equalisation, device
    operating-point drift) instead of averaging over its whole history.
    λ = 1.0 is bit-identical to the un-decayed fit.

    Returns ``(w [B, F, C], lam_idx [B], s_end [B, N])`` where ``s_end`` is
    the reservoir state after period K - 1 (the train -> test carry), exact
    even when K is not a multiple of ``chunk_k`` — except that with a
    sub-f32 ``state_dtype`` AND a ragged tail (K % chunk_k != 0) the carry
    is read from the rounded state chunk (the f32 VMEM carry describes the
    chunk *end*, which is past period K - 1); chunk-aligned K keeps it
    f32-exact (DESIGN.md §9).

    ``dev_params`` (a traced device operating-point pytree, e.g.
    ``devices.cmt.CMTSweepParams`` with [B] leaves) threads per-lane swept
    device parameters into state generation — an *operand*, so a design-
    space sweep over it reuses this compiled program (DESIGN.md §14).  On
    the kernel path they are ``dfr_scan``'s per-lane parameter tiles.
    """
    j, y = _canon_stream(j, targets)

    def states_fn(j_c, s):
        return generate_states(model, j_c, mask, s0=s, method=state_method,
                               block_s=block_s, return_final=True,
                               state_dtype=state_dtype,
                               dev_params=dev_params)

    return _fit_streaming_core(
        states_fn, int(mask.shape[-1]), j, y, washout=washout, chunk_k=chunk_k,
        lambdas=lambdas, use_kernel=use_kernel, block_t=block_t,
        block_f=block_f, noise_rel=noise_rel, state_dtype=state_dtype, s0=s0,
        forgetting=forgetting)


@scopes.scoped(scopes.COLLECT)
@functools.partial(jax.jit, static_argnames=(
    "model", "washout", "chunk_k", "lambdas", "state_method", "block_s",
    "use_kernel", "block_t", "block_f", "noise_rel", "state_dtype",
    "forgetting"))
def fit_ridge_streaming_wdm(
    model,
    masks: jnp.ndarray,    # [R, N] — one MLS mask per wavelength channel
    j: jnp.ndarray,        # [R, K] — one sample-and-held stream per channel
    targets: jnp.ndarray,  # [R, K] or [R, K, C]
    *,
    washout: int,
    chunk_k: int,
    lambdas: tuple[float, ...] = (1e-6,),
    state_method: str = "kernel",
    block_s: int | None = None,
    use_kernel: bool = True,
    block_t: int = 512,
    block_f: int = 128,
    noise_rel: float = 0.0,
    state_dtype=None,
    s0: jnp.ndarray | None = None,
    forgetting: float = 1.0,
):
    """Streaming readout fit for a WDM ensemble: per-channel masks, one scan.

    The WDM workload (paper Section VI; DESIGN.md §9) is R microring
    wavelength channels sharing one delay loop — software-side, R reservoirs
    with *different* masks over *different* input streams.  This is the
    ``fit_ridge_streaming`` chunk scan with the per-lane-mask reservoir
    kernel in the driver's seat: each chunk runs all R channels as ONE
    Pallas launch (``generate_channel_states(method="kernel")`` — channels
    are batch lanes with their own [N] mask tiles in VMEM) and folds into
    per-channel Gram stacks G [R, F, F] / c [R, F, C] via the accumulate-into
    kernel, followed by one vmapped GCV solve.  Peak live state memory is
    O(R·chunk_k·N); the [R, K, N] channel-state tensor of the materialized
    ``generate_channel_states`` path never exists — which is what lets long
    WDM streams (K ≫ chunk) scale past HBM.

    All other knob semantics (``noise_rel`` as expected Tikhonov diagonal,
    ``state_dtype`` bf16 chunks, kernel/einsum Gram accumulation,
    ``forgetting`` as per-chunk RLS decay) match ``fit_ridge_streaming``.
    Returns ``(w [R, F, C], lam_idx [R], s_end [R, N])`` with ``s_end`` the
    per-channel train -> test carry (same exactness caveat for sub-f32
    chunks with a ragged tail).
    """
    j, y = _canon_stream(j, targets)
    if masks.ndim != 2 or masks.shape[0] != j.shape[0]:
        raise ValueError(f"channels mismatch: j {j.shape} vs masks {masks.shape}")

    def states_fn(j_c, s):
        return generate_channel_states(model, j_c, masks, s0=s,
                                       method=state_method, block_s=block_s,
                                       return_final=True,
                                       state_dtype=state_dtype)

    return _fit_streaming_core(
        states_fn, int(masks.shape[-1]), j, y, washout=washout,
        chunk_k=chunk_k, lambdas=lambdas, use_kernel=use_kernel,
        block_t=block_t, block_f=block_f, noise_rel=noise_rel,
        state_dtype=state_dtype, s0=s0, forgetting=forgetting)


def composed_chunk_states_fn(graph: ReservoirGraph, masks, *,
                             state_method: str = "kernel",
                             block_s: int | None = None,
                             state_dtype=None):
    """The per-chunk transformer of a reservoir graph (DESIGN.md §13).

    Returns ``states_fn(j_chunk [B, chunk], carries) -> (features
    [B, chunk, graph.width], carries')`` with ``carries`` a tuple of
    per-stage [B, L, N_s] f32 arrays (``graph.carry_layout``): each stage
    runs over the *chunk* (loops folded into batch lanes — one Pallas launch
    per stage), its linked drive feeds the next stage inside the SAME scan
    step, and only chunk-sized feature blocks ever exist — no stage
    materialises a full-K [B, K, L·N] tensor.  Shared between the composed
    streaming fit below and the composed streaming eval
    (pipeline/experiment.py), so train and test trace identical stage ops.
    """
    masks = tuple(masks)
    if len(masks) != graph.depth:
        raise ValueError(f"expected {graph.depth} stage mask stacks, "
                         f"got {len(masks)}")
    depth = graph.depth

    def states_fn(j_c, carries):
        feats, new_c = [], []
        drive = j_c
        for i, stage in enumerate(graph.stages):
            f_i, c_i = stage_states(stage, drive, masks[i], carries[i],
                                    method=state_method, block_s=block_s,
                                    state_dtype=state_dtype)
            feats.append(f_i)
            new_c.append(c_i)
            if i + 1 < depth:
                drive = stage_link_drive(stage, f_i)
        states = feats[0] if depth == 1 else jnp.concatenate(feats, axis=-1)
        return states, tuple(new_c)

    return states_fn


@scopes.scoped(scopes.COLLECT)
@functools.partial(jax.jit, static_argnames=(
    "graph", "washout", "chunk_k", "lambdas", "state_method", "block_s",
    "use_kernel", "block_t", "block_f", "noise_rel", "state_dtype",
    "forgetting"))
def fit_ridge_streaming_composed(
    graph: ReservoirGraph,
    masks,                 # tuple of per-stage [L, N] / [B, L, N] mask stacks
    j: jnp.ndarray,        # [B, K] stage-0 sample-and-held input stream
    targets: jnp.ndarray,  # [B, K] or [B, K, C]
    *,
    washout: int,
    chunk_k: int,
    lambdas: tuple[float, ...] = (1e-6,),
    state_method: str = "kernel",
    block_s: int | None = None,
    use_kernel: bool = True,
    block_t: int = 512,
    block_f: int = 128,
    noise_rel: float = 0.0,
    state_dtype=None,
    s0=None,               # tuple of per-stage [B, L, N] carries
    forgetting: float = 1.0,
):
    """Streaming readout fit over a composed reservoir graph (DESIGN.md §13).

    The ``fit_ridge_streaming`` chunk scan with the whole stage *chain* in
    the driver's seat: each scan step runs every stage over the chunk
    (stage k + 1 driven by stage k's linked output, computed in-step), folds
    the concatenated [B, chunk, graph.width] feature block into per-instance
    Gram stacks, and carries the per-stage reservoir states as a tuple —
    threaded independently, so the chain resumes bit-exactly at any chunk
    split.  Peak live state memory is O(B·chunk·width); no stage ever holds
    a full-K block (``repro.analysis`` NoStateTensor pins this per stage).

    A depth-1/loops-1 graph is the legacy fit, bit for bit: the stage calls
    ``generate_states`` literally and the single-element concat is skipped,
    so ``w``/``lam_idx`` match ``fit_ridge_streaming`` bitwise (the carry
    just gains the [B, 1, N] stage axis).  Knob semantics (``noise_rel``,
    ``state_dtype``, ``forgetting``, kernel/einsum Gram) are inherited
    unchanged from ``fit_ridge_streaming``.

    Returns ``(w [B, F, C], lam_idx [B], s_end)`` with F = graph.width + 1
    and ``s_end`` the per-stage carry tuple after period K - 1 — feed it to
    the composed eval (or back in as ``s0``) as the train -> test carry.
    """
    j, y = _canon_stream(j, targets)
    states_fn = composed_chunk_states_fn(graph, masks,
                                         state_method=state_method,
                                         block_s=block_s,
                                         state_dtype=state_dtype)
    return _fit_streaming_core(
        states_fn, graph.width, j, y, washout=washout, chunk_k=chunk_k,
        lambdas=lambdas, use_kernel=use_kernel, block_t=block_t,
        block_f=block_f, noise_rel=noise_rel, state_dtype=state_dtype,
        s0=None if s0 is None else tuple(s0), forgetting=forgetting,
        carry_layout=graph.carry_layout)


@scopes.scoped(scopes.COLLECT)
@functools.partial(jax.jit, static_argnames=(
    "model", "washout", "chunk_k", "lambdas", "state_method", "block_s",
    "use_kernel", "block_t", "block_f", "noise_rel", "state_dtype",
    "forgetting"))
def fit_ridge_streaming_shared(
    model,
    masks: jnp.ndarray,    # [R, N] — one MLS mask per wavelength channel
    j: jnp.ndarray,        # [R, K] — one sample-and-held stream per channel
    targets: jnp.ndarray,  # [K] or [K, C] — ONE target for the ensemble
    *,
    washout: int,
    chunk_k: int,
    lambdas: tuple[float, ...] = (1e-6,),
    state_method: str = "kernel",
    block_s: int | None = None,
    use_kernel: bool = True,
    block_t: int = 512,
    block_f: int = 128,
    noise_rel: float = 0.0,
    state_dtype=None,
    s0: jnp.ndarray | None = None,  # [R, N]
    forgetting: float = 1.0,
):
    """Shared-readout WDM fit: ONE readout over all R channels' features.

    ``fit_ridge_streaming_wdm`` trains R independent readouts — R separate
    [F, F] Grams, each channel predicting its own target.  Here the R
    channels are treated as ONE wide reservoir observing one task: per
    period the readout sees the concatenation of every channel's N node
    states (feature r·N + i = channel r, node i), so the single Gram is
    [R·N + 1, R·N + 1] and its off-diagonal blocks carry the *cross-channel*
    state correlations the per-channel fits discard.  This is the
    series/parallel-coupled-MR readout of arXiv:2308.15902 mapped onto the
    WDM hardware: same photonic ensemble, richer (and R× larger) linear
    readout, one target stream.

    Streaming shape: the channel axis rides the chunk scan as a trailing
    input dim (stream [1, K, R]), each chunk runs all R channels as ONE
    per-lane-mask kernel launch, and the features fold into a single Gram —
    peak state memory O(R·chunk·N), the [K, R·N] feature matrix never
    resident.  Carry layout is one ((R, N),) entry, so mid-chunk s_end
    extraction reshapes a feature row back to [R, N] per channel.

    Returns ``(w [F, C], lam_idx, s_end [R, N])`` — one weight vector and
    one λ for the whole ensemble, per-channel train -> test carry.
    """
    masks = jnp.asarray(masks)
    if masks.ndim != 2:
        raise ValueError(f"masks must be [R, N], got {masks.shape}")
    r, n_nodes = masks.shape
    j = jnp.asarray(j, jnp.float32)
    if j.ndim != 2 or j.shape[0] != r:
        raise ValueError(f"channels mismatch: j {j.shape} vs masks {masks.shape}")
    y = jnp.asarray(targets, jnp.float32)
    if y.ndim == 1:
        y = y[:, None]
    if y.ndim != 2 or y.shape[0] != j.shape[1]:
        raise ValueError(f"targets {y.shape} do not match stream length "
                         f"{j.shape[1]}")
    j_core = jnp.moveaxis(j, 0, 1)[None]       # [1, K, R]
    y_core = y[None]                           # [1, K, C]

    def states_fn(j_c, carries):               # j_c [1, chunk, R]
        s = carries[0]                         # [1, R, N]
        states, s_next = generate_channel_states(
            model, j_c[0].T, masks, s0=s[0], method=state_method,
            block_s=block_s, return_final=True, state_dtype=state_dtype)
        feats = jnp.moveaxis(states, 0, 1).reshape(
            j_c.shape[1], r * n_nodes)[None]   # [1, chunk, R·N]
        return feats, (s_next[None],)

    w, idx, s_end = _fit_streaming_core(
        states_fn, r * n_nodes, j_core, y_core, washout=washout,
        chunk_k=chunk_k, lambdas=lambdas, use_kernel=use_kernel,
        block_t=block_t, block_f=block_f, noise_rel=noise_rel,
        state_dtype=state_dtype,
        s0=None if s0 is None else (jnp.asarray(s0, jnp.float32)[None],),
        forgetting=forgetting, carry_layout=((r, n_nodes),))
    return w[0], idx[0], s_end[0][0]
