"""The QDWH divide-and-conquer ``eigh`` (``pipeline/qdwh_eigh``) and its
dispatch.

``qdwh_eigh`` is JAX's TPU divide and conquer for one matrix.  Here it runs
on the CPU with a reduced leaf size, so two or three levels of splits run,
and is checked against float64 ``numpy`` on the spectra the GCV solve
meets: random SPD, a NARMA10-like Gram (bias column, large common mode, so
the first split is lopsided), a rank-deficient Gram, clustered
eigenvalues, and one large eigenvalue over a flat rest.

The dispatch (``ridge._eigh``) is checked by lowering ``vmap(solve_gcv)``
for the TPU from the CPU: at F = 901 the decomposition is ``qdwh_eigh``'s
and not JAX's agenda; at F = 31 it is the native Jacobi refined by one
Rayleigh-Ritz step (``eigh_refine``); on the CPU it is LAPACK's, bit for
bit.
"""

import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.pipeline import qdwh_eigh, ridge, scopes

LEAF = 128                  # leaf size: buckets [128, 160, 300] at F = 300
EPS = float(np.finfo(np.float32).eps)
KINDS = ("spd", "narma_gram", "rank_deficient", "clustered", "lopsided")
LAMBDAS = (1e-10, 1e-8, 1e-6, 1e-4, 1e-2)


def _rotate(rng, vals):
    q, _ = np.linalg.qr(rng.standard_normal((len(vals), len(vals))))
    return (q * vals) @ q.T


def _matrix(kind: str, f: int, rng) -> np.ndarray:
    if kind == "spd":
        a = rng.standard_normal((f, f + 8))
        h = a @ a.T / f
    elif kind == "narma_gram":
        # states near a common level, correlated along the chain, plus bias
        t = 2 * f
        x = 0.6 + 0.05 * np.cumsum(rng.standard_normal((t, f - 1)), axis=1) / np.sqrt(f)
        xb = np.concatenate([x, np.ones((t, 1))], axis=1)
        h = xb.T @ xb + 1e-4 * np.eye(f)
    elif kind == "rank_deficient":
        x = rng.standard_normal((f // 3, f))
        h = x.T @ x
    elif kind == "clustered":
        vals = np.repeat([1.0, 2.0, 4.0], -(-f // 3))[:f] + 1e-6 * rng.standard_normal(f)
        h = _rotate(rng, vals)
    elif kind == "lopsided":
        # the diagonal's median lies above every eigenvalue but the largest
        vals = np.concatenate([rng.uniform(0.0, 1.0, f - 1), [10.0 * f]])
        h = _rotate(rng, vals)
    else:
        raise ValueError(kind)
    return ((h + h.T) / 2).astype(np.float32)


def _batch(b: int, f: int, seed: int = 0):
    rng = np.random.default_rng([seed, b, f])
    kinds = [KINDS[(i + b) % len(KINDS)] for i in range(b)]
    return kinds, np.stack([_matrix(k, f, rng) for k in kinds])


_RUNS: dict = {}


def _decomposed(b: int, f: int):
    """(kinds, h, eigenvalues, eigenvectors, stats) of one batch, once."""
    if (b, f) not in _RUNS:
        kinds, h = _batch(b, f)
        vals, vecs, stats = jax.jit(lambda h: jax.lax.map(
            lambda m: qdwh_eigh.decompose(m, LEAF), h))(jnp.asarray(h))
        _RUNS[b, f] = (kinds, h, np.asarray(vals), np.asarray(vecs),
                       jax.tree.map(np.asarray, stats))
    return _RUNS[b, f]


@pytest.mark.parametrize("f", [300])
@pytest.mark.parametrize("b", [1, 3, 8])
def test_eigh_matches_float64(b, f):
    kinds, h, vals, vecs, _ = _decomposed(b, f)
    for i, kind in enumerate(kinds):
        h64 = h[i].astype(np.float64)
        ref = np.linalg.eigvalsh(h64)
        scale = np.max(np.abs(ref))
        assert np.all(np.diff(vals[i]) >= 0), kind
        np.testing.assert_allclose(vals[i], ref, rtol=0, atol=200 * EPS * scale,
                                   err_msg=kind)
        q = vecs[i].astype(np.float64)
        np.testing.assert_allclose(q.T @ q, np.eye(f), rtol=0, atol=200 * EPS,
                                   err_msg=kind)
        resid = h64 @ q - q * vals[i]
        assert np.max(np.abs(resid)) <= 200 * EPS * scale, kind


def test_eigh_levels_and_stats():
    """Two or more levels ran, and a lopsided first split sent its large
    half back to the top bucket."""
    assert qdwh_eigh.buckets(300, LEAF) == [128, 160, 300]
    assert qdwh_eigh.buckets(901) == [256, 480, 901]
    assert qdwh_eigh.widths(901) == [32, 128, 451]
    kinds, _, _, _, stats = _decomposed(8, 300)
    splits = dict(zip(kinds, stats["splits"]))
    assert all(n >= 2 for k, n in splits.items() if k in ("spd", "narma_gram"))
    # at least 4 QDWH iterations a split (2 QR-based, 2 Cholesky-based)
    assert np.all(stats["qdwh_iters"] >= 4 * stats["splits"])
    # the Gram alone: its common mode splits off one row and leaves F - 1,
    # so the top bucket takes a second step
    kinds, _, _, _, one = _decomposed(1, 300)
    assert kinds == ["narma_gram"] and one["splits"][0] >= 2


def _narma_stats(b, f, seed):
    rng = np.random.default_rng(seed)
    t = 2 * f
    x = 0.6 + 0.05 * np.cumsum(rng.standard_normal((b, t, f - 1)), axis=2) / np.sqrt(f)
    y = x[:, :, 3] * 0.7 - x[:, :, 9] * 0.2 + 0.01 * rng.standard_normal((b, t))
    xb = np.concatenate([x, np.ones((b, t, 1))], axis=2)
    g = np.einsum("btf,btg->bfg", xb, xb)
    # a noise floor, as the fit's state-noise diagonal, above f32's cut-off
    g += 1e-3 * np.trace(g, axis1=1, axis2=2)[:, None, None] / f * np.eye(f)
    g = g.astype(np.float32)
    c = np.einsum("btf,bt->bf", xb, y)[..., None].astype(np.float32)
    return g, c, np.sum(y * y, axis=1).astype(np.float32), t


def test_solve_gcv_same_answer_on_both_eigh_paths():
    g, c, y2, t = _narma_stats(3, 300, 11)
    # one matrix at a time, as on the TPU; the CPU's eigh swapped for the port
    def solve(*a):
        return jax.lax.map(lambda a: ridge.solve_gcv(*a, t, LAMBDAS), a)

    w_xla, i_xla = jax.jit(solve)(g, c, y2)
    with mock.patch.object(ridge, "_lapack_eigh", qdwh_eigh.eigh):
        w_b, i_b = jax.jit(lambda *a: solve(*a))(g, c, y2)
    np.testing.assert_array_equal(np.asarray(i_b), np.asarray(i_xla))
    w_xla, w_b = np.asarray(w_xla), np.asarray(w_b)
    # the fitted answers of both readouts on the training Gram agree
    fit = np.einsum("bfg,bgc->bfc", g.astype(np.float64), w_b - w_xla)
    ref = np.einsum("bfg,bgc->bfc", g.astype(np.float64), w_xla)
    assert np.max(np.abs(fit)) <= 1e-3 * np.max(np.abs(ref))


# ------------------------------------------------------------ lowering


def _tpu_text(b, f, debug_info=False):
    jax.clear_caches()          # the dispatch is traced anew under each patch
    shapes = (jax.ShapeDtypeStruct((b, f, f), jnp.float32),
              jax.ShapeDtypeStruct((b, f, 1), jnp.float32),
              jax.ShapeDtypeStruct((b,), jnp.float32))
    fn = jax.vmap(lambda g, c, y2: ridge.solve_gcv(g, c, y2, 1000, LAMBDAS))
    lowered = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))
    return lowered.as_text(debug_info=debug_info)


def _qr_operands(text: str) -> set:
    return set(re.findall(r"@Qr\(%[\w.]+\) \{[^}]*\} : \((tensor<[^>]*>)", text))


def test_lowering_tpu_f901_takes_the_qdwh_path():
    text = _tpu_text(8, 901, debug_info=True)
    assert scopes.EIGH_SPLIT in text and scopes.EIGH_LEAF in text
    assert "dfrc.eigh/" in text and "_eigh_work" not in text
    assert scopes.EIGH_REFINE not in text
    # one matrix at a time: the polar iteration's QR is of [2F, F]
    assert "tensor<1802x901xf32>" in _qr_operands(text)
    # XLA's own path: JAX's agenda
    with mock.patch.object(ridge, "_JACOBI_ROWS", 10**6):
        xla = _tpu_text(8, 901, debug_info=True)
    assert "_eigh_work" in xla and scopes.EIGH_SPLIT not in xla


def test_lowering_small_f_and_cpu_unchanged():
    # F <= 256: the native batched Jacobi once, then the refinement's Jacobi
    # sweep on the Gram in its basis (rotations, no second Eigh)
    debug = _tpu_text(16, 31, True)
    assert debug.count("@Eigh") == 1 and scopes.EIGH_SPLIT not in debug
    assert scopes.EIGH_REFINE in debug and "dfrc.eigh/" in debug
    # the CPU keeps LAPACK, bit for bit, and lowers no QDWH path
    g = jnp.asarray(_batch(3, 300)[1])
    ours = jax.jit(jax.vmap(ridge._eigh))(g)
    plain = jax.jit(jax.vmap(jnp.linalg.eigh))(g)
    for a, p in zip(ours, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))
    cpu = jax.jit(jax.vmap(ridge._eigh)).lower(g).as_text(debug_info=True)
    assert scopes.EIGH_SPLIT not in cpu and "syevd" in cpu


@pytest.mark.parametrize("kind", ["narma_gram", "rank_deficient", "clustered"])
def test_rayleigh_ritz_step_matches_float64(kind):
    """The refinement of the F <= 256 eigh on the TPU (``_jacobi_refined``),
    here on the CPU's eigh: from a basis rotated ~3e-6 off, as the TPU's
    Jacobi leaves it (some 40-60 eps of residual), one Rayleigh-Ritz step
    gives eigenvalues, orthogonality and residuals at LAPACK's own levels
    (up to 10 eps of the largest eigenvalue on these spectra)."""
    import scipy.linalg

    f = 31
    rng = np.random.default_rng([f, KINDS.index(kind)])
    h = _matrix(kind, f, rng)
    h64 = h.astype(np.float64)
    ref, q64 = np.linalg.eigh(h64)
    scale = np.max(np.abs(ref))
    a = 2e-6 * rng.standard_normal((f, f))
    q_off = (q64 @ scipy.linalg.expm(a - a.T)).astype(np.float32)
    off = h64 @ q_off - q_off * (np.diag(q_off.T @ h64 @ q_off))
    assert np.max(np.abs(off)) > 30 * EPS * scale
    vals, q = (np.asarray(x, np.float64)
               for x in jax.jit(ridge._rayleigh_ritz)(jnp.asarray(h), jnp.asarray(q_off)))
    np.testing.assert_allclose(vals, ref, rtol=0, atol=16 * EPS * scale)
    np.testing.assert_allclose(q.T @ q, np.eye(f), rtol=0, atol=16 * EPS)
    assert np.max(np.linalg.norm(h64 @ q - q * vals, axis=0)) <= 16 * EPS * scale
    # the whole refined eigh, as on the TPU, agrees with LAPACK's own
    vals_r, _ = jax.jit(ridge._jacobi_refined)(jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(vals_r), ref, rtol=0, atol=16 * EPS * scale)
