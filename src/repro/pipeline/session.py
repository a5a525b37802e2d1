"""Online-learning DFR sessions: per-stream adaptive readouts (DESIGN.md §10).

The paper's "98% faster training" pitch rests on the readout being a tiny
linear solve ([F, C] per stream) over a shared photonic reservoir — exactly
the shape where *per-user adaptive* readouts are nearly free at serving
scale.  This module packages the streaming-fit machinery (pipeline/ridge:
``dfr_scan`` ``s0``/``return_final`` carry + accumulate-into Gram folds) as
an online-update engine:

* ``SessionState`` — one pytree holding everything a live stream needs to
  resume mid-flight: the reservoir carry ``s`` (the DFR analogue of a KV
  cache), the running (optionally λ-decayed) Gram/moment statistics, the
  current readout, and the per-session period counter that tracks the
  washout phase.  All leaves carry a leading batch axis, so one state
  object IS a continuously-batched slab of B independent sessions.
* ``session_init / session_update / session_predict / session_step`` — pure,
  jit-once step functions over that pytree.  ``session_step`` is the serving
  tick: ONE reservoir pass per chunk shared by predict (with the readout
  solved from *earlier* data) and update (fold the chunk into the Gram,
  optionally re-solve).  Because they are pure pytree -> pytree maps they
  compose with ``jax.vmap``/``jax.jit``/donation, and the batch axis shards
  over the ("pod", "data") mesh axes like every other pipeline batch.
* **RLS forgetting** (``SessionConfig.forgetting`` = λ < 1) — the carried
  Gram is scaled by λ per chunk before the chunk accumulates, so the readout
  tracks link/device drift instead of averaging over the whole session
  history; λ = 1.0 folds bit-identically to ``fit_ridge_streaming``.
* **Amortised solves** (``refresh_every``) — folding a chunk is one Gram
  accumulate (cheap, streaming); *solving* is an eigh + GCV grid (the
  expensive part).  The ``refresh`` flag of ``session_update``/
  ``session_step`` is static, so a server re-solves every ``refresh_every``
  ticks and pays the eigh 1/refresh_every as often, with exactly two
  compiled step variants (fold-only, fold+solve).
* **In-graph health masking** (``SessionConfig.guard``, DESIGN.md §12) —
  one non-finite tick would otherwise poison a slot *permanently*: NaN in
  the reservoir carry propagates to every later chunk, NaN in the Gram
  survives every later fold (λ·NaN + X = NaN).  The serving tick therefore
  ends with a per-row finite check over everything the row carries forward
  (carry, Gram/moments, readout, prediction); rows that fail are reset
  in-graph (the quarantine), flagged in ``SessionState.quarantined`` and
  counted in ``SessionState.poison`` — all traced ops, no host round-trip,
  still exactly two compiled step variants.  The GCV solve additionally
  falls back to the row's last-good readout when the fresh solve comes
  back non-finite (``pipeline/ridge.guard_readout``).  For healthy rows
  every guard is a ``select`` of the identical value, so the guarded step
  stays *bitwise* equal to the unguarded one on clean data.

The serving loop built on top lives in ``launch/serve_dfr.py``; the fault
models the guards are validated against live in ``repro.robustness``.  The
invariants (λ = 1.0 bitwise parity with the one-shot streaming fit,
chunk-split independence, quarantine isolation) are pinned by
tests/test_serving.py, tests/test_robustness.py and the hypothesis property
suite (tests/test_properties.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.nonlinear import NLModel, SiliconMR
from repro.core.reservoir import generate_states

from .ridge import _fold_chunk, _plan_fold, guard_readout, solve_gcv, with_bias


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    """Static (hashable) configuration of an online-learning session batch.

    Mirrors the streaming knobs of ``ExperimentConfig`` (washout, λ grid,
    state method, kernel tiling) plus the online-only ones: ``chunk_k`` is
    the periods-per-tick granularity (static — one compiled step program),
    ``forgetting`` the RLS decay per chunk, ``refresh_every`` the re-solve
    cadence a server should drive (the session functions themselves take the
    decision as the static ``refresh`` flag; this field is the policy knob
    ``launch/serve_dfr.py`` and the benchmark read).
    """

    model: NLModel = dataclasses.field(default_factory=SiliconMR)
    n_nodes: int = 100
    n_channels: int = 1            # C output channels of the readout
    washout: int = 50
    ridge_l2: tuple[float, ...] = (1e-6,)
    chunk_k: int = 32
    forgetting: float = 1.0
    refresh_every: int = 1
    state_method: str = "fast"     # "fast" | "ref" | "kernel"
    use_kernel: bool = False       # Gram fold via the Pallas kernel
    block_s: int | None = None
    block_t: int = 512
    block_f: int = 128
    state_dtype: str | None = None  # sub-f32 emitted state chunks (DESIGN.md §9)
    guard: bool = True             # in-graph health masking (DESIGN.md §12)

    def __post_init__(self):
        if not isinstance(self.ridge_l2, tuple):
            object.__setattr__(self, "ridge_l2",
                               tuple(float(v) for v in self.ridge_l2))
        if not 0.0 < self.forgetting <= 1.0:
            raise ValueError(f"forgetting must be in (0, 1], got {self.forgetting}")
        if self.chunk_k < 1:
            raise ValueError(f"chunk_k must be >= 1, got {self.chunk_k}")
        if self.refresh_every < 1:
            raise ValueError(
                f"refresh_every must be >= 1, got {self.refresh_every}")

    @property
    def features(self) -> int:
        """Readout features F = N + 1 (bias folded)."""
        return self.n_nodes + 1

    @property
    def fold_plan(self):
        return _plan_fold(self.features, self.chunk_k,
                          use_kernel=self.use_kernel, block_t=self.block_t,
                          block_f=self.block_f, state_dtype=self.state_dtype)


class SessionState(NamedTuple):
    """Everything a batch of B live DFR streams needs to resume mid-flight.

    A NamedTuple, hence a pytree: jit/vmap/donate/shard-transparent.  The
    Gram block is carried feature-padded ([B, Fq, Fq], Fq = F rounded to the
    kernel's block_f tile) for the same reason ``fit_ridge_streaming``
    carries it padded — the accumulate-into kernel then never pads or
    slices G per chunk (DESIGN.md §8/§10).  The health leaves
    (``quarantined``/``poison``, DESIGN.md §12) are [B] bookkeeping only —
    no per-period axis ever enters the state, so the serving memory
    contracts are unchanged by the guards.
    """

    s: jnp.ndarray         # [B, N]  f32 — reservoir carry (resume point)
    g: jnp.ndarray         # [B, Fq, Fq] f32 — running (λ-decayed) Gram
    c: jnp.ndarray         # [B, Fq, C] f32 — running Xᵀy moment
    y2: jnp.ndarray        # [B] f32 — running (λ-decayed) ‖y‖²
    tcnt: jnp.ndarray      # [B] f32 — effective (λ-decayed) sample count
    w: jnp.ndarray         # [B, F, C] f32 — current readout (zeros until solved)
    lam_idx: jnp.ndarray   # [B] i32 — GCV-selected λ index of that readout
    step: jnp.ndarray      # [B] i32 — periods consumed (washout phase tracker)
    quarantined: jnp.ndarray  # [B] bool — row reset by the health guard THIS tick
    poison: jnp.ndarray    # [B] i32 — quarantine events since the slot was reset

    @property
    def batch(self) -> int:
        return self.s.shape[0]


@functools.partial(jax.jit, static_argnames=("cfg", "batch"))
def session_init(cfg: SessionConfig, batch: int) -> SessionState:
    """Fresh (dark-reservoir, empty-statistics) state for ``batch`` streams."""
    f, fq, c = cfg.features, cfg.fold_plan.fq, cfg.n_channels
    return SessionState(
        s=jnp.zeros((batch, cfg.n_nodes), jnp.float32),
        g=jnp.zeros((batch, fq, fq), jnp.float32),
        c=jnp.zeros((batch, fq, c), jnp.float32),
        y2=jnp.zeros((batch,), jnp.float32),
        tcnt=jnp.zeros((batch,), jnp.float32),
        w=jnp.zeros((batch, f, c), jnp.float32),
        lam_idx=jnp.zeros((batch,), jnp.int32),
        step=jnp.zeros((batch,), jnp.int32),
        quarantined=jnp.zeros((batch,), bool),
        poison=jnp.zeros((batch,), jnp.int32),
    )


def session_reset(state: SessionState, rows: jnp.ndarray) -> SessionState:
    """Zero the per-session leaves where ``rows`` [B] is True.

    The continuous-batching primitive: a finished stream's slot is handed to
    a newly arrived request by resetting that row in-graph — no host-side
    state surgery, no recompilation (``rows`` is a traced operand).
    """
    rows = jnp.asarray(rows, bool)

    def zero_rows(leaf):
        mask = rows.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.where(mask, jnp.zeros_like(leaf), leaf)

    return SessionState(*(zero_rows(leaf) for leaf in state))


def _rows_finite(*arrays) -> jnp.ndarray:
    """[B] bool — True where every entry of every array's row is finite."""
    ok = None
    for a in arrays:
        fin = jnp.all(jnp.isfinite(a.reshape(a.shape[0], -1)), axis=1)
        ok = fin if ok is None else ok & fin
    return ok


def session_health(state: SessionState,
                   y_hat: jnp.ndarray | None = None) -> jnp.ndarray:
    """[B] bool — per-row finite check of everything a row carries forward.

    A row is healthy iff its reservoir carry, Gram/moment statistics, and
    readout are all finite (plus this tick's prediction when given).  One
    NaN/Inf anywhere marks the row: NaN in the carry re-poisons every later
    chunk, NaN in G survives every later fold, NaN in w corrupts every
    later prediction — so the check is over the *persisted* leaves, which
    is both necessary and sufficient to catch a poisoned slot at the tick
    it happens.
    """
    arrays = [state.s, state.g, state.c, state.y2, state.w]
    if y_hat is not None:
        arrays.append(y_hat)
    return _rows_finite(*arrays)


def _quarantine(state: SessionState, y_hat: jnp.ndarray):
    """In-graph slot quarantine (DESIGN.md §12).

    Rows whose post-fold state or prediction went non-finite are reset to
    the dark-reservoir/empty-statistics state *inside the compiled step*
    (``jnp.where`` per leaf — the same mechanism as ``session_reset``), so
    one poisoned stream never contaminates its slab neighbours or any later
    tick of its own slot.  The reset restarts the row's period counter, so
    washout re-applies and the slot re-converges from clean data.  The
    row's prediction is zeroed (never emit NaN to the host); the event is
    flagged in ``quarantined`` and counted in ``poison``.  Healthy rows
    pass through as selects of the identical value — bitwise a no-op.
    """
    bad = ~session_health(state, y_hat)

    def scrub(leaf):
        m = bad.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.zeros_like(leaf), leaf)

    state = SessionState(
        s=scrub(state.s), g=scrub(state.g), c=scrub(state.c),
        y2=scrub(state.y2), tcnt=scrub(state.tcnt), w=scrub(state.w),
        lam_idx=scrub(state.lam_idx), step=scrub(state.step),
        quarantined=bad,
        poison=state.poison + bad.astype(jnp.int32))
    y_hat = jnp.where(bad[:, None, None], jnp.zeros_like(y_hat), y_hat)
    return y_hat, state


def _valid_mask(cfg: SessionConfig, step: jnp.ndarray,
                n_valid: jnp.ndarray | None) -> jnp.ndarray:
    """[B, chunk] f32 fit mask: past washout AND inside the valid prefix."""
    tidx = step[:, None] + jnp.arange(cfg.chunk_k, dtype=jnp.int32)[None, :]
    vfit = tidx >= cfg.washout
    if n_valid is not None:
        local = jnp.arange(cfg.chunk_k, dtype=jnp.int32)[None, :]
        vfit = vfit & (local < jnp.asarray(n_valid, jnp.int32)[:, None])
    return vfit.astype(jnp.float32)


def _canon_chunk_targets(cfg: SessionConfig, y_chunk: jnp.ndarray) -> jnp.ndarray:
    y = jnp.asarray(y_chunk, jnp.float32)
    if y.ndim == 2:
        y = y[..., None]
    if y.shape[-1] != cfg.n_channels:
        raise ValueError(
            f"targets carry {y.shape[-1]} channels, config says {cfg.n_channels}")
    return y


def _gen_chunk(cfg: SessionConfig, mask, j_chunk, s):
    return generate_states(cfg.model, j_chunk, mask, s0=s,
                           method=cfg.state_method, block_s=cfg.block_s,
                           return_final=True, state_dtype=cfg.state_dtype)


def _fold(cfg: SessionConfig, state: SessionState, states, y3, vfit,
          s_next) -> SessionState:
    """Fold one chunk of states into the running statistics (no solve)."""
    x = jnp.concatenate(
        [states, jnp.ones((*states.shape[:2], 1), states.dtype)], axis=-1)
    x = x * vfit.astype(x.dtype)[:, :, None]
    yv = y3 * vfit[:, :, None]
    lam = cfg.forgetting
    tcnt = state.tcnt + jnp.sum(vfit, axis=1) if lam == 1.0 else (
        state.tcnt * jnp.float32(lam) + jnp.sum(vfit, axis=1))
    g, cvec, y2 = _fold_chunk(cfg.fold_plan, state.g, state.c, state.y2,
                              x, yv, forgetting=lam)
    return state._replace(s=s_next, g=g, c=cvec, y2=y2, tcnt=tcnt,
                          step=state.step + jnp.int32(cfg.chunk_k))


def _solve(cfg: SessionConfig, state: SessionState) -> SessionState:
    """Re-solve the readout from the current statistics (the eigh+GCV pass).

    Under ``cfg.guard`` a row whose fresh solve comes back non-finite keeps
    its last-good readout (``pipeline/ridge.guard_readout``) — the running
    statistics are untouched, so the next refresh retries; rows whose
    *statistics* are poisoned are handled upstream by the quarantine.
    """
    f = cfg.features
    g = state.g[:, :f, :f]
    cvec = state.c[:, :f]
    lams = cfg.ridge_l2
    w, idx = jax.vmap(lambda gb, cb, y2b, nb: solve_gcv(
        gb, cb, y2b, nb, lams))(g, cvec, state.y2, state.tcnt)
    idx = idx.astype(jnp.int32)
    if cfg.guard:
        w, idx = guard_readout(w, idx, state.w, state.lam_idx)
    return state._replace(w=w, lam_idx=idx)


@functools.partial(jax.jit, static_argnames=("cfg", "refresh"))
def session_update(cfg: SessionConfig, mask: jnp.ndarray, state: SessionState,
                   j_chunk: jnp.ndarray, y_chunk: jnp.ndarray, *,
                   refresh: bool = False,
                   n_valid: jnp.ndarray | None = None) -> SessionState:
    """Advance B sessions by one chunk of observed (input, target) pairs.

    ``j_chunk`` [B, chunk_k], ``y_chunk`` [B, chunk_k] or [B, chunk_k, C].
    Runs the reservoir from each session's carry, masks washout rows (per
    session, via the ``step`` counter) and rows past ``n_valid`` (ragged
    stream tails), folds the chunk into the λ-decayed Gram statistics, and —
    when ``refresh`` (static) is True — re-solves the readout.  With
    ``forgetting=1.0`` and aligned chunks the folded statistics and solved
    readout are bit-identical to ``fit_ridge_streaming`` over the
    concatenated stream (tests/test_serving.py pins this).
    """
    y3 = _canon_chunk_targets(cfg, y_chunk)
    states, s_next = _gen_chunk(cfg, mask, j_chunk, state.s)
    vfit = _valid_mask(cfg, state.step, n_valid)
    state = _fold(cfg, state, states, y3, vfit, s_next)
    return _solve(cfg, state) if refresh else state


@functools.partial(jax.jit, static_argnames=("cfg",))
def session_predict(cfg: SessionConfig, mask: jnp.ndarray, state: SessionState,
                    j_chunk: jnp.ndarray):
    """Inference-only chunk: advance the reservoir, apply the current readout.

    Returns (y_hat [B, chunk_k, C], state') — the Gram statistics are left
    untouched (nothing is learned), but the reservoir carry and period
    counter advance so a later ``session_update`` resumes correctly.
    """
    states, s_next = _gen_chunk(cfg, mask, j_chunk, state.s)
    y_hat = jnp.einsum("btf,bfc->btc", with_bias(states), state.w,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    return y_hat, state._replace(s=s_next,
                                 step=state.step + jnp.int32(cfg.chunk_k))


def _session_step(cfg: SessionConfig, mask: jnp.ndarray, state: SessionState,
                  j_chunk: jnp.ndarray, y_chunk: jnp.ndarray, *,
                  refresh: bool = False,
                  n_valid: jnp.ndarray | None = None,
                  reset: jnp.ndarray | None = None):
    """The serving tick: predict-then-update with ONE reservoir pass.

    Optionally resets the rows flagged in ``reset`` [B] first (slots handed
    to newly arrived requests), then evaluates the chunk's states once and
    uses them for both the prediction (with the readout solved from earlier
    data — honest online inference) and the Gram fold.  ``refresh`` is
    static: a server calls the fold+solve variant every
    ``cfg.refresh_every``-th tick and the fold-only variant otherwise, so
    exactly two step programs are ever compiled — the health guard is part
    of both, not a third variant.

    Under ``cfg.guard`` (default) the tick ends with the in-graph
    quarantine: rows whose carry/Gram/readout/prediction went non-finite
    are reset in place, their prediction zeroed, ``quarantined`` flagged
    and ``poison`` incremented (DESIGN.md §12).  On clean data the guard
    is bitwise invisible.

    Returns (y_hat [B, chunk_k, C], new state).
    """
    if reset is not None:
        state = session_reset(state, reset)
    y3 = _canon_chunk_targets(cfg, y_chunk)
    states, s_next = _gen_chunk(cfg, mask, j_chunk, state.s)
    y_hat = jnp.einsum("btf,bfc->btc", with_bias(states), state.w,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    vfit = _valid_mask(cfg, state.step, n_valid)
    state = _fold(cfg, state, states, y3, vfit, s_next)
    if refresh:
        state = _solve(cfg, state)
    if cfg.guard:
        y_hat, state = _quarantine(state, y_hat)
    return y_hat, state


# The public step is jit-per-(cfg, refresh); ``_session_step`` stays
# importable for callers that re-jit with their own options — the serving
# loop (launch/serve_dfr.py) wraps it with donate_argnums so the session
# slab is updated in place across ticks.
session_step = functools.partial(jax.jit,
                                 static_argnames=("cfg", "refresh"))(_session_step)


@functools.partial(jax.jit, static_argnames=("cfg",))
def session_solve(cfg: SessionConfig, state: SessionState) -> SessionState:
    """Re-solve the readout now, regardless of cadence."""
    return _solve(cfg, state)
