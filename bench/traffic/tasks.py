"""The benchmark's own copies of the task generators (paper Section V).

Kept here so that a change to the program's ``repro.core.tasks`` or
``repro.launch.serve_dfr`` cannot change the benchmark's inputs.
``bench/tests/test_traffic.py`` shows that these give arrays identical to
the program's generators.

* ``narma10`` / ``narma10_batch`` -- NARMA10, paper Eq. (10).  The batch form
  runs the recursion for many seeds at once, in the same floating-point
  order, and redraws a diverging seed with the scalar form.
* ``channel_equalization`` -- 4-PAM symbols through the Jaeger & Haas
  ISI + cubic channel with AWGN, paper Eq. (11-12).
* ``chan_eq_streams`` -- the serving loop's streams: one link per stream,
  cut to whole chunks, inputs mapped to [0, 1].
* ``make_mask`` -- the MLS input mask (Appeltant et al., paper ref. 25).
"""

from __future__ import annotations

import numpy as np

_NARMA_DIVERGENCE_BOUND = 10.0
_NARMA_MAX_REDRAWS = 16
_NARMA_WARM = 50

SYMBOLS = np.array([-3.0, -1.0, 1.0, 3.0])

# q(n) = sum_off w_off * d(n + off), taps n+2 .. n-7 (paper Eq. (11))
_CHAN_EQ_TAPS = {2: 0.08, 1: -0.12, 0: 1.0, -1: 0.18, -2: -0.1, -3: 0.09,
                 -4: -0.05, -5: 0.04, -6: 0.03, -7: 0.01}


def _narma_recursion(i: np.ndarray) -> np.ndarray:
    """Eq. (10) over the last axis of ``i`` ([T] or [B, T]).  A row whose
    |y| passes the divergence bound is set to inf from there on."""
    y = np.zeros(i.shape)
    n = i.shape[-1]
    alive = np.ones(i.shape[:-1], bool)
    with np.errstate(over="ignore", invalid="ignore"):
        for k in range(9, n - 1):
            w = y[..., k - 9:k + 1]
            # the pairwise order numpy's sum uses for ten contiguous values
            s = (((w[..., 0] + w[..., 1]) + (w[..., 2] + w[..., 3]))
                 + ((w[..., 4] + w[..., 5]) + (w[..., 6] + w[..., 7])))
            s = s + w[..., 8]
            s = s + w[..., 9]
            yk = y[..., k]
            y[..., k + 1] = (0.3 * yk + 0.05 * yk * s
                             + 1.5 * i[..., k] * i[..., k - 9] + 0.1)
            bad = ~np.isfinite(y[..., k + 1]) | (
                np.abs(y[..., k + 1]) > _NARMA_DIVERGENCE_BOUND)
            if np.any(bad & alive):
                alive = alive & ~bad
                y[..., k + 1:][~alive] = np.inf
    return y


def _narma_draw(seed: int, attempt: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed if attempt == 0 else (seed, attempt))
    return rng.uniform(0.0, 0.5, size=n)


def _narma_split(i, y, n_samples, train_frac):
    i, y = i[..., _NARMA_WARM:], y[..., _NARMA_WARM:]
    split = int(n_samples * train_frac)
    return (i[..., :split], y[..., :split], i[..., split:], y[..., split:])


def narma10(n_samples: int = 2000, *, train_frac: float = 0.5, seed: int = 0):
    """One NARMA10 instance: (train in, train target, test in, test target)."""
    n = n_samples + _NARMA_WARM
    for attempt in range(_NARMA_MAX_REDRAWS):
        i = _narma_draw(seed, attempt, n)
        y = _narma_recursion(i)
        if np.isfinite(y).all():
            return _narma_split(i, y, n_samples, train_frac)
    raise RuntimeError(f"narma10(seed={seed}) diverged {_NARMA_MAX_REDRAWS} times")


def narma10_batch(seeds, n_samples: int = 2000, *, train_frac: float = 0.5):
    """``narma10`` for every seed, stacked: four [B, T] float64 arrays."""
    seeds = [int(s) for s in seeds]
    n = n_samples + _NARMA_WARM
    i = np.stack([_narma_draw(s, 0, n) for s in seeds])
    y = _narma_recursion(i)
    for r in np.flatnonzero(~np.isfinite(y).all(axis=1)):
        for attempt in range(1, _NARMA_MAX_REDRAWS):
            i[r] = _narma_draw(seeds[r], attempt, n)
            y[r] = _narma_recursion(i[r])
            if np.isfinite(y[r]).all():
                break
        else:
            raise RuntimeError(f"narma10(seed={seeds[r]}) diverged")
    return _narma_split(i, y, n_samples, train_frac)


def _chan_eq_clean(d: np.ndarray) -> np.ndarray:
    q = np.zeros(d.shape[0])
    for off, w in _CHAN_EQ_TAPS.items():
        q += w * np.roll(d, -off)
    return q + 0.036 * q**2 - 0.011 * q**3


def channel_equalization(n_symbols: int = 9000, *, snr_db: float = 24.0,
                         train_frac: float = 6000 / 9000, seed: int = 0):
    """One link: (train in, train target, test in, test target)."""
    rng = np.random.default_rng(seed)
    pad = 16
    n = n_symbols + 2 * pad
    d = rng.choice(SYMBOLS, size=n)
    x = _chan_eq_clean(d)
    noise_p = np.mean(x**2) / (10.0 ** (snr_db / 10.0))
    x = x + rng.normal(0.0, np.sqrt(noise_p), size=n)
    d, x = d[pad:-pad], x[pad:-pad]
    split = int(n_symbols * train_frac)
    return x[:split], d[:split], x[split:], d[split:]


def chan_eq_streams(n: int, stream_len: int, chunk: int, *,
                    snr_db: float = 24.0, seed: int = 0):
    """``n`` serving streams ``(j, y)`` (float32), one link each, cut to
    whole chunks, inputs mapped to [0, 1] per stream."""
    k = (stream_len // chunk) * chunk
    out = []
    for r in range(n):
        x, d, _, _ = channel_equalization(max(k, 64), snr_db=snr_db,
                                          train_frac=0.999, seed=seed + r)
        x = np.asarray(x[:k], np.float32)
        x = (x - x.min()) / (x.max() - x.min() + 1e-12)
        out.append((x, np.asarray(d[:k], np.float32)))
    return out


_PRIMITIVE_TAPS = {
    2: (2, 1), 3: (3, 2), 4: (4, 3), 5: (5, 3), 6: (6, 5), 7: (7, 6),
    8: (8, 6, 5, 4), 9: (9, 5), 10: (10, 7), 11: (11, 9), 12: (12, 11, 10, 4),
    13: (13, 12, 11, 8), 14: (14, 13, 12, 2), 15: (15, 14), 16: (16, 15, 13, 4),
}


def _mls(m: int, init_state: int) -> np.ndarray:
    """One period of a maximum-length +-1 sequence (Galois LFSR)."""
    poly = 0
    for t in _PRIMITIVE_TAPS[m]:
        poly |= 1 << (t - 1)
    state = init_state
    out = np.empty(2**m - 1, dtype=np.int8)
    for i in range(out.shape[0]):
        lsb = state & 1
        out[i] = 1 if lsb else -1
        state >>= 1
        if lsb:
            state ^= poly
    return out


def make_mask(n_nodes: int, *, levels=(0.0, 1.0), seed: int = 1) -> np.ndarray:
    """Binary MLS mask [N] (float32) with values ``levels``; ``seed``
    picks the register's start state and a rotation."""
    m = 2
    while 2**m - 1 < n_nodes:
        m += 1
    seq = _mls(m, (seed % (2**m - 1)) + 1)
    seq = np.roll(seq, seed // (2**m - 1))[:n_nodes]
    lo, hi = levels
    return np.where(seq > 0, hi, lo).astype(np.float32)
