"""Spectral divide-and-conquer ``eigh`` of one symmetric matrix by QDWH.

On the TPU, ``jnp.linalg.eigh`` of a matrix wider than 256 rows is JAX's
QDWH-based spectral divide and conquer (Nakatsukasa & Higham 2013,
``jax/_src/tpu/linalg/eigh.py`` and ``qdwh.py``).  This module is that
algorithm (DESIGN.md §15):

* the same split point (the median of a block's diagonal), the same QDWH
  polar iteration (two QR-based, two Cholesky-based steps, then Halley
  until converged), the same projector subspace iteration, the same
  stopping tests ("nearly diagonal", "tiny"), the same bucket sizes
  (901 -> 480 -> leaves of at most 256 at F = 901) and float32 matmuls;
* the pending blocks are kept per offset (``pend[o]`` = size of the block
  starting at row o).  Buckets are visited largest first; each step splits
  one pending block of the bucket and writes both halves back.  A half too
  large for the smaller bucket (a lopsided split) stays in this bucket for
  the next step, as JAX's agenda would file it.

Two steps do less work than JAX's for the same result.  The subspace
iteration runs on as many columns as the smaller side of the split has
rows (``widths``), not on all of them: the other columns are zero, and a
zero column's Householder reflector is the identity.  Leaves are
decomposed by size class (``_LEAF_SIZES``), up to ``_LEAVES_PER_STEP`` in
one Jacobi call padded to the class's size, not always to 256: Jacobi's
rotations leave a block's zero padding alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import scopes

_EPS = float(np.finfo(np.float32).eps)
# QDWH: at most this many polar iterations (JAX's default), and the QR-based
# iterations run while the coefficient c exceeds the Cholesky cut-off
_QDWH_MAX_ITERS = 10
_CHOLESKY_CUTOFF = 100

# leaves decomposed in one Jacobi call, and the size classes of the leaves
# (up to the leaf size of the divide and conquer)
_LEAVES_PER_STEP = 4
_LEAF_SIZES = (32, 64, 128)


# --------------------------------------------------------------- helpers


def _mask(x, dims, alternative=0):
    """``x`` with every entry outside the dynamic shape ``dims`` replaced."""
    mask = None
    for i, d in enumerate(dims):
        if d is not None:
            m = lax.broadcasted_iota(np.int32, x.shape, i) < d
            mask = m if mask is None else mask & m
    return x if mask is None else jnp.where(mask, x, alternative)


def _put(ws, update, start, dims):
    """Write the ``dims`` corner of ``update`` into ``ws`` at ``start``; the
    rest of the window keeps what ``ws`` held (``ws`` is padded, so the
    window never clamps)."""
    start = tuple(jnp.asarray(s, np.int32) for s in start)
    old = lax.dynamic_slice(ws, start, update.shape)
    return lax.dynamic_update_slice(ws, _mask(update, dims, old), start)


def _take(ws, start, size, dims):
    """The ``size`` window of ``ws`` at ``start``, masked to ``dims``."""
    start = tuple(jnp.asarray(s, np.int32) for s in start)
    return _mask(lax.dynamic_slice(ws, start, size), dims)


def buckets(f: int, termination_size: int = 256) -> list[int]:
    """Padded block sizes of the divide and conquer, ascending: JAX's
    schedule N, round_up(N / 1.98, 32), halving, down to the leaf size."""
    cutoff = min(f, termination_size)
    out = [cutoff]
    if f > termination_size:
        out.append(f)
        i = int(f / 1.98)
        while i > cutoff:
            out.append(-(-i // 32) * 32)
            i //= 2
    return sorted(out)


# ------------------------------------------------------------------ QDWH


def _qdwh_coefficients():
    """JAX's static QDWH schedule for float32: (QR params, Cholesky params)."""
    def qr_params(a, b, c):
        e = b / c
        return ((a - e) / c ** 0.5, c ** 0.5, e)

    def chol_params(a, b, c):
        e = b / c
        return (a - e, c, e)

    qr, chol = [], []
    low, tol_l, k = _EPS, 10.0 * _EPS / 2.0, 0
    while low + tol_l < 1 and k < _QDWH_MAX_ITERS:
        k += 1
        l2 = low * low
        dd = (4 * (1 / l2 - 1) / l2) ** (1 / 3)
        sqd = (1.0 + dd) ** 0.5
        a = sqd + (2 - dd + 2 * (2 - l2) / (l2 * sqd)) ** 0.5
        b = (a - 1) ** 2 / 4
        c = a + b - 1
        low = low * (a + b * l2) / (1 + c * l2)
        (qr if c > _CHOLESKY_CUTOFF else chol).append(
            qr_params(a, b, c) if c > _CHOLESKY_CUTOFF else chol_params(a, b, c))
    return qr, chol


def _use_qr(u, n, params):
    """One QR-based QDWH step on ``u`` [N, N] of dynamic size (n, n)."""
    a_minus_e_by_sqrt_c, sqrt_c, e = params
    size = u.shape[0]
    eye = jnp.eye(size, dtype=u.dtype)
    y = lax.dynamic_update_slice_in_dim(
        jnp.pad(sqrt_c * u, ((0, size), (0, 0))), eye, n, axis=0)
    q, _ = lax.linalg.qr(y, full_matrices=False)
    q1 = _mask(q[:size], (n, n))
    q2 = _mask(lax.dynamic_slice_in_dim(q, n, size, axis=0), (n, n)).T
    return e * u + a_minus_e_by_sqrt_c * (q1 @ q2)


def _use_cholesky(u, n, params):
    """One Cholesky-based QDWH step on ``u`` [N, N] of dynamic size (n, n)."""
    a_minus_e, c, e = params
    size = u.shape[0]
    eye = jnp.eye(size, dtype=u.dtype)
    x = _mask(c * (u.T @ u) + eye, (n, n), eye)
    y = lax.linalg.cholesky(x, symmetrize_input=False)
    z = lax.linalg.triangular_solve(y, u.T, left_side=True, lower=True)
    z = lax.linalg.triangular_solve(y, z, left_side=True, lower=True,
                                    transpose_a=True).T
    return e * u + a_minus_e * z


def _qdwh(x, n):
    """Unitary polar factor of the Hermitian ``x`` [N, N] (dynamic size n)
    by QDWH, and the number of iterations it took."""
    x = _mask(x, (n, n))
    one_norm = jnp.linalg.norm(x, ord=1)
    inf_norm = jnp.linalg.norm(x, ord=np.inf)
    alpha_inverse = lax.rsqrt(one_norm) * lax.rsqrt(inf_norm)
    alpha_inverse = jnp.where(one_norm == 0, 1, alpha_inverse)
    u = x * alpha_inverse.astype(x.dtype)
    tol_norm = jnp.cbrt(jnp.float32(10.0 * _EPS / 2.0))
    qr_coefs, chol_coefs = _qdwh_coefficients()

    def iterate(u, coefs, update):
        table = jnp.asarray(coefs, x.dtype)

        def body(i, carry):
            u_prev, _ = carry
            u_new = update(u_prev, n, tuple(table[i]))
            return u_new, jnp.linalg.norm(u_new - u_prev) > tol_norm

        return lax.fori_loop(0, len(coefs), body, (u, jnp.bool_(True)))

    u, _ = iterate(u, qr_coefs, _use_qr)
    u, not_converged = iterate(u, chol_coefs, _use_cholesky)

    # l has converged; Halley's method (a, b, c -> 3, 1, 3) until u has too
    halley = (3.0 - 1.0 / 3.0, 3.0, 1.0 / 3.0)

    def cond(state):
        k, _, not_converged = state
        return not_converged & (k < _QDWH_MAX_ITERS)

    def body(state):
        k, u_prev, _ = state
        u_new = _use_cholesky(u_prev, n, halley)
        return k + 1, u_new, jnp.linalg.norm(u_new - u_prev) > tol_norm

    iters, u, _ = lax.while_loop(
        cond, body, (jnp.int32(len(qr_coefs) + len(chol_coefs)), u, not_converged))
    # Newton-Schulz refinement
    u = 1.5 * u - 0.5 * u @ (u.T @ u)
    return u, iters


# ------------------------------------------------------- spectral split


def _projector_subspace(p, h, n, rank, width, maxiter=2):
    """Isometries onto the range (V1 [N, width], ``rank`` columns) and the
    null space (V2 [N, N], n - rank columns) of the rank-``rank`` projector
    ``p``, by subspace iteration from its largest columns.

    JAX runs the iteration on all N columns, of which only ``rank`` are not
    zero; a zero column's Householder reflector is the identity, so the QR
    of the first ``width`` >= rank columns gives the same complete Q."""
    size = p.shape[0]
    neg_norms = _mask(-jnp.linalg.norm(p, axis=1), (n,), np.nan)
    x = _mask(p[:, jnp.argsort(neg_norms)[:width]], (n, rank))
    thresh = 10.0 * _EPS * jnp.linalg.norm(h)

    def after_matmul(x):
        q, _ = jnp.linalg.qr(x, mode="complete")
        v1 = _mask(q[:, :width], (n, rank))
        v2 = lax.dynamic_slice_in_dim(jnp.pad(q, ((0, 0), (0, size))), rank,
                                      size, axis=1)
        v2 = _mask(v2, (n, n - rank))
        return v1, v2, jnp.linalg.norm(v2.T @ (h @ v1))

    def cond(state):
        _, _, j, error = state
        return (j < maxiter) & (error > thresh)

    def body(state):
        v1, _, j, _ = state
        v1, v2, error = after_matmul(p @ v1)
        return v1, v2, j + 1, error

    v1, v2, error = after_matmul(x)
    v1, v2, _, _ = lax.while_loop(cond, body, (v1, v2, jnp.int32(1), error))
    return v1, v2


def _polar_projector(h, b, split_point):
    """QDWH on the block ``h`` [B, B] (dynamic size b) shifted by
    ``split_point``: (the projector onto the side of smaller rank, that
    rank, whether it is the upper side, the lower side's rank, QDWH
    iterations)."""
    size = h.shape[0]
    eye = jnp.eye(size, dtype=h.dtype)
    u, iters = _qdwh(h - split_point * eye, b)
    eye_b = _mask(eye, (b, b))
    p_minus = -0.5 * (u - eye_b)
    rank_minus = jnp.round(jnp.trace(p_minus)).astype(np.int32)
    p_plus = 0.5 * (u + eye_b)
    rank_plus = b - rank_minus
    # the subspace iteration runs on the projector of smaller rank
    swap = rank_plus < rank_minus
    return (jnp.where(swap, p_plus, p_minus), jnp.where(swap, rank_plus, rank_minus),
            swap, rank_minus, iters)


def _split_halves(p, h, v0, b, rank, width):
    """Both halves of the block ``h`` split by the projector ``p``: the
    smaller (H [width, width], V0 V [N, width], ``rank`` rows) and the
    larger (H [B, B], V0 V [N, B], b - rank rows)."""
    v_small, v_big = _projector_subspace(p, h, b, rank, width)
    return ((v_small.T @ h) @ v_small, v0 @ v_small,
            (v_big.T @ h) @ v_big, v0 @ v_big)


def widths(bucket: int) -> list[int]:
    """Column widths of the subspace iteration in a bucket, ascending: the
    smaller side of a split has at most half the block's rows."""
    half = -(-bucket // 2)
    return sorted({min(w, half) for w in (32, 128, half)})


# ----------------------------------------------------------------- leaves


def _jacobi(h):
    """The TPU leaf: Jacobi ``eigh`` in place (unsorted), as JAX's base case;
    the zero padding stays where it was."""
    vecs, vals = lax.linalg.eigh(
        h, sort_eigenvalues=False,
        implementation=lax.linalg.EighImplementation.JACOBI)
    return vals, vecs


def _sorted_leaf(h, b):
    """Elsewhere the library ``eigh`` sorts, so pad the diagonal past b with
    a value above every eigenvalue: the padding then sorts last."""
    size = h.shape[-1]
    big = 2.0 * jnp.linalg.norm(h, axis=(-2, -1)) + 1.0
    pad = (jnp.arange(size) >= b[:, None]).astype(h.dtype) * big[:, None]
    vecs, vals = lax.linalg.eigh(h + pad[:, :, None] * jnp.eye(size, dtype=h.dtype))
    return vals, vecs


def _leaf_eigh(h, b):
    """Eigenpairs of a stack of blocks ``h`` [S, T, T] of sizes ``b`` [S],
    the first b of each in its first b entries."""
    return lax.platform_dependent(h, b, tpu=lambda h, b: _jacobi(h),
                                  default=_sorted_leaf)


# --------------------------------------------------------------- the work


def decompose(h, termination_size: int = 256):
    """Eigendecomposition of one symmetric matrix ``h`` [N, N]:
    (eigenvalues [N] ascending, eigenvectors [N, N] in columns, stats).
    ``termination_size`` is the leaf size of the divide and conquer (the
    TPU's: 256); ``stats`` counts the splits and their QDWH iterations."""
    size = h.shape[0]
    dt = h.dtype
    sizes = buckets(size, termination_size)
    leaf = sizes[0]
    offs = lax.iota(np.int32, size)
    h = (h + h.T) / 2

    # block workspace: the pending block at offset o lives in rows [o, o+b),
    # columns [0, b); eigenvalues end in column 0.  Padded by N rows (and
    # the eigenvectors by N columns) so no window clamps.
    blocks = jnp.pad(h, ((0, size), (0, 0)))
    vecs = jnp.pad(jnp.eye(size, dtype=dt), ((0, 0), (0, size)))
    pend = jnp.zeros((size,), np.int32).at[0].set(size)
    h0_norm = jnp.linalg.norm(h)
    stats = dict(splits=jnp.int32(0), qdwh_iters=jnp.int32(0))

    def recursive_step(bucket, lo, carry):
        blocks, vecs, pend, stats = carry
        o = jnp.argmax((pend > lo) & (pend <= bucket)).astype(np.int32)
        b = pend[o]
        pend = jnp.where(offs == o, 0, pend)
        hb = _take(blocks, (o, 0), (bucket, bucket), (b, b))
        norm = jnp.linalg.norm(hb)
        diag = jnp.diagonal(hb)
        nearly_diagonal = jnp.linalg.norm(hb - jnp.diag(diag)) <= 5 * _EPS * norm
        tiny = norm < _EPS * h0_norm

        def stop(blocks, vecs, pend, stats):
            # a nearly diagonal or tiny block: its diagonal is its eigenvalues
            return _put(blocks, diag[:, None], (o, 0), (b, 1)), vecs, pend, stats

        def split(blocks, vecs, pend, stats):
            v0 = _take(vecs, (0, o), (size, bucket), (size, b))
            split_point = jnp.nanmedian(
                jnp.where(jnp.arange(bucket) < b, diag, np.nan)).astype(dt)
            proj, rank, swap, rank_minus, iters = _polar_projector(hb, b, split_point)
            rest = b - rank
            # the smaller half is the lower one unless swapped
            small_at = jnp.where(swap, o + rest, o)
            big_at = jnp.where(swap, o, o + rank)

            def halves(width, blocks, vecs):
                h_small, v_small, h_big, v_big = _split_halves(proj, hb, v0, b, rank,
                                                               width)
                blocks = _put(blocks, h_small, (small_at, 0), (rank, rank))
                blocks = _put(blocks, h_big, (big_at, 0), (rest, rest))
                vecs = _put(vecs, v_small, (0, small_at), (size, rank))
                vecs = _put(vecs, v_big, (0, big_at), (size, rest))
                return blocks, vecs

            ws = widths(bucket)
            blocks, vecs = lax.switch(
                jnp.searchsorted(jnp.asarray(ws), rank),
                [functools.partial(halves, w) for w in ws], blocks, vecs)
            upper = b - rank_minus
            pend = jnp.where(offs == o, rank_minus, pend)
            pend = jnp.where((offs == o + rank_minus) & (upper > 0), upper, pend)
            stats = dict(splits=stats["splits"] + 1,
                         qdwh_iters=stats["qdwh_iters"] + iters)
            return blocks, vecs, pend, stats

        with jax.named_scope(scopes.EIGH_SPLIT):
            return lax.cond(nearly_diagonal | tiny, stop, split,
                            blocks, vecs, pend, stats)

    per_step = min(_LEAVES_PER_STEP, -(-size // leaf))
    leaf_sizes = sorted({min(s, leaf) for s in _LEAF_SIZES} | {leaf})

    def leaf_step(pad, lo, carry):
        blocks, vecs, pend, stats = carry
        sel = (pend > lo) & (pend <= pad)
        nth = jnp.cumsum(sel)
        picks = [sel & (nth == j + 1) for j in range(per_step)]
        o = jnp.stack([jnp.argmax(p) for p in picks]).astype(np.int32)   # [m]
        b = jnp.where(jnp.stack([jnp.any(p) for p in picks]), pend[o], 0)
        # one slice each: a vmapped slice would be a gather, which the
        # compiler turns into a loop of its own
        hl = jnp.stack([_take(blocks, (o[j], 0), (pad, pad), (b[j], b[j]))
                        for j in range(per_step)])
        v0 = jnp.stack([_take(vecs, (0, o[j]), (size, pad), (size, b[j]))
                        for j in range(per_step)])
        vals, z = _leaf_eigh(hl, b)
        vals = _mask(vals, (None, b[:, None]))
        z = _mask(z, (None, b[:, None, None], b[:, None, None]))
        vz = v0 @ z                                              # [m, N, pad]
        for j in range(per_step):
            blocks = _put(blocks, vals[j, :, None], (o[j], 0), (b[j], 1))
            vecs = _put(vecs, vz[j], (0, o[j]), (size, b[j]))
        return blocks, vecs, jnp.where(sel & (nth <= per_step), 0, pend), stats

    def pending(lo, hi):
        return lambda carry: jnp.any((carry[2] > lo) & (carry[2] <= hi))

    carry = (blocks, vecs, pend, stats)
    with jax.default_matmul_precision("float32"):
        for bucket, lo in reversed(list(zip(sizes[1:], sizes[:-1]))):
            carry = lax.while_loop(pending(lo, bucket),
                                   functools.partial(recursive_step, bucket, lo), carry)
        with jax.named_scope(scopes.EIGH_LEAF):
            for pad, lo in zip(leaf_sizes, [0] + leaf_sizes[:-1]):
                carry = lax.while_loop(pending(lo, pad),
                                       functools.partial(leaf_step, pad, lo), carry)
    blocks, vecs, _, stats = carry
    vals = blocks[:size, 0]
    order = jnp.argsort(vals)
    return vals[order], vecs[:, order], stats


def eigh(h, *, termination_size: int = 256):
    """Like ``jnp.linalg.eigh`` of one symmetric matrix ``h`` [N, N]:
    (eigenvalues [N] ascending, eigenvectors [N, N] in columns)."""
    return decompose(h, termination_size)[:2]
