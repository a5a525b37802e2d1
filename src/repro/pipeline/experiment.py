"""Jit-end-to-end DFRC experiment pipeline.

One compiled function runs the paper's whole claims path — input layer
(normalise + sample-and-hold + MLS mask), reservoir layer (``ref`` / ``fast``
/ ``kernel`` state generation), output layer (streaming-Gram ridge readout
with GCV λ selection) and the evaluation metrics — *batched over task
instances*.  Where the host-side ``DFRCAccelerator`` runs one accelerator on
one series with numpy in the loop, ``Experiment.run`` takes ``[B, T]`` input
stacks (B independent task instances: seeds, SNR points, hyperparameter
draws, WDM channels) and produces per-instance predictions and metrics from
a single jit call, so a sweep compiles once and runs as one XLA program.

Scaling hooks:

* the instance axis is constrained over the ("pod", "data") mesh axes via
  parallel/sharding.maybe_shard — under an active mesh (compat.use_mesh) the
  sweep shards across devices with no code change;
* the Gram accumulation inside the readout fit can run through the
  kernels/ridge_gram Pallas kernel (``readout_use_kernel=True``), and the
  reservoir through kernels/dfr_scan (``state_method="kernel"``);
* ``stream_chunk_k`` switches the whole run onto the streaming fused path
  (DESIGN.md §8): train fit and test evaluation scan over K-chunks with the
  reservoir state carried between chunks and per-chunk states folded into
  running Gram / error accumulators, so peak device memory for the run is
  O(B·chunk·N) instead of O(B·T·N);
* ``channel_states`` evaluates per-channel (mask, input) pairs for
  WDM-multiplexed reservoir ensembles (examples/wdm_scaling.py) — on the
  kernel path via the per-lane mask tiling, still one Pallas launch.

Numerics note: the readout solve is f32 on device (eigh of the Gram matrix),
versus the host trainer's float64 SVD; on the paper's tasks the resulting
NRMSE/SER differences are within the run-to-run seed spread, and the
regression tests (tests/test_pipeline.py) pin thresholds on this path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import ReservoirGraph, ReservoirStage, build_stage_masks
from repro.core.masking import make_mask, sample_and_hold
from repro.core.metrics import VAR_EPS
from repro.core.nonlinear import NLModel, SiliconMR
from repro.core.reservoir import generate_channel_states, generate_states
from repro.core.tasks import SYMBOLS
from repro.parallel.sharding import maybe_shard

from . import scopes
from .ridge import (apply_readout, composed_chunk_states_fn, fit_ridge_batched,
                    fit_ridge_streaming, fit_ridge_streaming_composed,
                    fit_ridge_streaming_shared, fit_ridge_streaming_wdm,
                    with_bias)

_SYMBOLS = tuple(float(s) for s in SYMBOLS)


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """Static (hashable) configuration of one batched DFRC experiment.

    Field semantics mirror core/accelerator.DFRCConfig — see there for the
    physics rationale of each knob; differences are noted inline.
    """

    model: NLModel = dataclasses.field(default_factory=SiliconMR)
    n_nodes: int = 900
    mask_levels: tuple[float, float] = (0.0, 1.0)
    mask_seed: int = 1
    input_gain: float = 1.0
    normalize_input: bool = True   # per-instance affine map to [0, 1]
    washout: int = 50
    ridge_l2: tuple[float, ...] = (1e-6,)   # always a tuple here (GCV-selected)
    state_noise_rel: float = 0.003
    noise_seed: int = 0
    state_method: str = "fast"     # "fast" | "ref" | "kernel"
    readout_use_kernel: bool = False
    quantize: bool = False
    # Streaming fused path (DESIGN.md §8): a chunk length in periods switches
    # the whole run onto pipeline/ridge.fit_ridge_streaming + chunked test
    # evaluation — the full [B, T, N] state tensor never exists in HBM; peak
    # state memory is O(B·stream_chunk_k·N).  NOTE the readout solve is then
    # always the Gram/eigh route (G is all a streaming fit ever has —
    # SVD-of-X needs X resident), regardless of ``readout_use_kernel``,
    # which only picks HOW G accumulates (Pallas kernel vs einsum).  Parity
    # is therefore stated vs the materialized *Gram* path; vs the unfused
    # SVD default the last decade of λ-conditioning can differ (ridge.py
    # ``solve_gcv_svd`` note).  ``state_noise_mode`` picks how digitiser
    # noise enters the readout fit:
    #   "sampled"  — materialize state noise and add it (unfused route only;
    #                needs the state tensor, so incompatible with streaming),
    #   "diagonal" — add the expected Gram of the noise, σ²·T_fit·I, to the
    #                state block of G (single-pass; the streaming route).
    stream_chunk_k: int | None = None
    state_noise_mode: str = "sampled"
    # Streaming state-chunk dtype (DESIGN.md §9): "bfloat16" halves the HBM
    # round-trip of every [B, chunk, N] state block on both streaming scans
    # (fit and eval).  The chunk-to-chunk carry, targets and Gram
    # accumulators stay f32, so the scan itself resumes exactly; the emitted
    # chunks are rounded, which makes parity vs f32 chunks looser (documented
    # bounds, tests/benchmark) and rounds the train -> test carry too when
    # the train length is not chunk-aligned (ridge.fit_ridge_streaming note).
    stream_state_dtype: str = "float32"
    # collect_y_pred=False switches the evaluation to metrics-only: the
    # per-chunk predictions are never stacked back into a [B, T_test, C]
    # block, so a long streamed test set costs O(B·chunk) instead of O(B·T)
    # — ExperimentResult.y_pred is then None.  Default True for API compat.
    collect_y_pred: bool = True
    # Pallas tiling knobs (only read by the kernel paths):
    #   kernel_block_s — dfr_scan sublane tile; None = smallest of {1, 2, 4, 8}
    #     covering the batch (a B ≤ 128 sweep pads to 128 lanes, not 1024).
    #   readout_block_t — ridge_gram T tile (sublane-aligned internally).
    kernel_block_s: int | None = None
    readout_block_t: int = 512
    # Composed reservoir graph (DESIGN.md §13): a core.graph.ReservoirGraph
    # (or a single ReservoirStage, auto-chained) replaces the single delay
    # loop — deep/cascaded stages and multi-loop stages run as a per-chunk
    # stage chain inside the streaming scans, readout features the
    # concatenation of every stage's nodes (width = topology.width).  The
    # composed path is streaming-ONLY (requires ``stream_chunk_k``): chunk
    # chaining is what keeps every stage at O(B·chunk·L·N) instead of a
    # full-T block per stage, and the materialized fallback would defeat
    # exactly that.  ``n_nodes``/``mask_seed``/``mask_levels`` are ignored in
    # favour of the per-stage settings; a depth-1/loops-1 topology reproduces
    # the legacy single-reservoir fit bit for bit.
    topology: ReservoirGraph | None = None

    def __post_init__(self):
        if not isinstance(self.ridge_l2, tuple):
            object.__setattr__(self, "ridge_l2", _as_tuple(self.ridge_l2))
        if isinstance(self.topology, ReservoirStage):
            object.__setattr__(self, "topology",
                               ReservoirGraph(stages=(self.topology,)))
        if self.topology is not None:
            if not isinstance(self.topology, ReservoirGraph):
                raise TypeError(f"topology must be a ReservoirGraph or "
                                f"ReservoirStage, got {self.topology!r}")
            if self.stream_chunk_k is None:
                raise ValueError(
                    "a composed topology runs streaming-only (per-chunk stage "
                    "chaining is its memory contract); set stream_chunk_k")
        if self.state_noise_mode not in ("sampled", "diagonal"):
            raise ValueError(f"unknown state_noise_mode {self.state_noise_mode!r}")
        if self.stream_state_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown stream_state_dtype {self.stream_state_dtype!r} "
                "(expected 'float32' or 'bfloat16')")
        if self.stream_state_dtype != "float32" and self.stream_chunk_k is None:
            raise ValueError(
                "stream_state_dtype narrows the *streaming* state chunks; "
                "set stream_chunk_k (the materialized path keeps f32 states)")
        if self.state_noise_rel:
            if self.stream_chunk_k is not None and self.state_noise_mode != "diagonal":
                raise ValueError(
                    "the streaming path cannot materialize sampled state noise; "
                    "set state_noise_mode='diagonal' (noise as its expected "
                    "Tikhonov diagonal) or state_noise_rel=0")
            if self.stream_chunk_k is None and self.state_noise_mode == "diagonal":
                raise ValueError(
                    "state_noise_mode='diagonal' is the streaming-path noise "
                    "model (set stream_chunk_k); the unfused route keeps the "
                    "sampled-noise path")

    @property
    def _stream_state_dtype_arg(self) -> str | None:
        """stream_state_dtype as the kernels' ``state_dtype`` argument."""
        return None if self.stream_state_dtype == "float32" else self.stream_state_dtype

    @classmethod
    def from_dfrc(cls, cfg) -> "ExperimentConfig":
        """Lift a core DFRCConfig onto the batched pipeline.

        The pipeline's readout is always the ridge/GCV path (the paper's
        pinv is the λ→0 limit; core/readout.py keeps the exact pinv for the
        faithfulness benchmarks).
        """
        return cls(
            model=cfg.model,
            n_nodes=cfg.n_nodes,
            mask_levels=tuple(cfg.mask_levels),
            mask_seed=cfg.mask_seed,
            input_gain=cfg.input_gain,
            normalize_input=cfg.normalize_input,
            washout=cfg.washout,
            ridge_l2=_as_tuple(cfg.ridge_l2),
            state_noise_rel=cfg.state_noise_rel,
            noise_seed=cfg.noise_seed,
            state_method=cfg.state_method,
            quantize=cfg.quantize,
        )


def _as_tuple(l2) -> tuple[float, ...]:
    return tuple(float(v) for v in l2) if isinstance(l2, (tuple, list)) else (float(l2),)


@dataclasses.dataclass(frozen=True)
class ExperimentResult:
    """Per-instance outputs of one Experiment.run call (host numpy arrays).

    Single-channel targets (the common case) keep the historical 2-D shapes;
    C > 1 output channels add a trailing channel axis instead of being
    silently dropped.  ``y_pred`` is None when the run was metrics-only
    (``collect_y_pred=False``): the streamed evaluation then never stacks
    the per-chunk predictions back into a [B, T_test, C] block.
    """

    y_pred: np.ndarray | None  # [B, T_test] (or [B, T_test, C]); quantized iff cfg.quantize
    nrmse: np.ndarray       # [B]  (mean of per-channel NRMSEs for C > 1)
    ser: np.ndarray         # [B]  (vs 4-PAM quantized predictions)
    lam: np.ndarray         # [B]  selected ridge λ per instance
    readout_w: np.ndarray   # [B, N + 1] (or [B, N + 1, C])

    @property
    def batch(self) -> int:
        return self.nrmse.shape[0]


def _canon_batch(x, name: str) -> jnp.ndarray:
    x = jnp.asarray(x, jnp.float32)
    if x.ndim == 1:
        return x[None, :]
    if x.ndim == 2:
        return x
    raise ValueError(f"{name} must be [T] or [B, T], got {x.shape}")


def _canon_targets(x, name: str, inputs: jnp.ndarray) -> jnp.ndarray:
    """Targets matching ``inputs`` [B, T]: returns [B, T] or [B, T, C].

    A trailing channel axis is kept only for C > 1 ([B, T, 1] squeezes to
    [B, T]), so single-channel results keep their historical shapes.
    """
    x = jnp.asarray(x, jnp.float32)
    b, t = inputs.shape
    if x.ndim == 1:
        x = x[None, :]
    elif x.ndim == 2 and b == 1 and x.shape != (b, t) and x.shape[0] == t:
        x = x[None, :, :]            # [T, C] with 1-D inputs
    if x.ndim == 3 and x.shape[-1] == 1:
        x = x[..., 0]
    if x.shape[:2] != (b, t):
        raise ValueError(f"{name} shape {x.shape} does not match inputs ({b}, {t})")
    return x


def _quantize(y: jnp.ndarray) -> jnp.ndarray:
    sym = jnp.asarray(_SYMBOLS, y.dtype)
    return sym[jnp.argmin(jnp.abs(y[..., None] - sym), axis=-1)]


def _gen_states(cfg: ExperimentConfig, mask, j, *, wdm: bool, s0=None,
                return_final: bool = False, state_dtype=None,
                dev_params=None):
    """State generation for both workloads: ``mask`` is [N] broadcast over B
    task instances (the paper's sweep) or, with ``wdm=True``, [R, N] per-lane
    masks (one wavelength channel per batch row — DESIGN.md §9).

    ``dev_params`` threads traced per-lane device parameters into the model
    (device design-space sweeps, DESIGN.md §14) — single-mask workloads only;
    the WDM per-channel-mask path keeps the static-model contract."""
    if wdm:
        if dev_params is not None:
            raise NotImplementedError(
                "dev_params sweeps use the single-mask workload; per-channel "
                "WDM masks with per-lane device parameters are not supported")
        gen = generate_channel_states
        return gen(cfg.model, j, mask, s0=s0, method=cfg.state_method,
                   block_s=cfg.kernel_block_s, return_final=return_final,
                   state_dtype=state_dtype)
    return generate_states(cfg.model, j, mask, s0=s0, method=cfg.state_method,
                           block_s=cfg.kernel_block_s,
                           return_final=return_final,
                           state_dtype=state_dtype, dev_params=dev_params)


@scopes.scoped(scopes.EVAL)
def _eval_streaming(cfg: ExperimentConfig, mask, j_te, te_tg3, w_fit, s0, *,
                    wdm: bool = False, states_fn=None, dev_params=None):
    """Chunked test evaluation: states per chunk, running error accumulators.

    ``te_tg3`` [B, T, C].  Returns (y_raw [B, T, C] or None, acc) where acc
    packs the running error statistics (err2 = Σ_t (ŷ − y)², the 4-PAM
    symbol-mismatch count, and target Σy/Σy² for the variance), all
    accumulated inside the chunk scan so neither a [B, T, N] state block nor
    any other full-stream reduction is resident (DESIGN.md §8) — the target
    variance in particular is derived from the in-scan moments, not a
    ``jnp.var`` pass over the full target block.  With
    ``cfg.collect_y_pred=False`` the per-chunk predictions are consumed by
    the accumulators and dropped — the scan stacks nothing, so the O(B·T·C)
    prediction block never exists either (metrics-only mode).

    ``states_fn`` overrides the per-chunk state producer (a ``(j_chunk,
    carry) -> (features, carry')`` transformer; ``s0`` then a matching carry
    pytree) — the composed-graph and shared-readout paths pass theirs so
    test evaluation traces the exact stage ops the fit traced; ``None``
    keeps the legacy mask/``wdm`` path with identical traced ops.
    """
    from .ridge import _chunk_axis, _chunk_layout

    b, t_total = j_te.shape[0], j_te.shape[1]
    c_cols = te_tg3.shape[-1]
    chunk_k = cfg.stream_chunk_k
    n_chunks, t_padded = _chunk_layout(t_total, chunk_k)
    jp = jnp.pad(j_te, ((0, 0), (0, t_padded - t_total))
                 + ((0, 0),) * (j_te.ndim - 2))
    yp = jnp.pad(te_tg3, ((0, 0), (0, t_padded - t_total), (0, 0)))

    # Variance accumulators are *shifted* by the stream's first sample: the
    # single-pass E[y²] − E[y]² identity cancels catastrophically in f32
    # when |mean| ≫ std (e.g. a narrow signal riding a large offset), but
    # applied to d = y − y[0] the cancellation is against ~std², not mean².
    # y[0] is one [B, C] gather, not a full-stream pass.
    shift = te_tg3[:, 0, :]                          # [B, C]
    carry0 = (jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), s0),
              jnp.zeros((b, c_cols), jnp.float32),   # Σ (ŷ − y)²
              jnp.zeros((b,), jnp.float32),          # symbol mismatches
              jnp.zeros((b, c_cols), jnp.float32),   # Σ (y − y₀)
              jnp.zeros((b, c_cols), jnp.float32))   # Σ (y − y₀)²
    xs = (_chunk_axis(jp, n_chunks, chunk_k),
          _chunk_axis(yp, n_chunks, chunk_k),
          jnp.arange(n_chunks, dtype=jnp.int32) * chunk_k)

    def body(carry, chunk):
        s, err2, ser_cnt, y_sum, y_sq = carry
        j_c, y_c, t_start = chunk
        if states_fn is not None:
            states, s = states_fn(j_c, s)
        else:
            states, s = _gen_states(cfg, mask, j_c, wdm=wdm, s0=s,
                                    return_final=True,
                                    state_dtype=cfg._stream_state_dtype_arg,
                                    dev_params=dev_params)
        y_hat = jnp.einsum("btf,bfc->btc", with_bias(states), w_fit,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        tidx = t_start + jnp.arange(chunk_k, dtype=jnp.int32)
        valid = (tidx < t_total).astype(jnp.float32)[None, :, None]
        err = (y_hat - y_c) * valid
        err2 = err2 + jnp.sum(err * err, axis=1)
        mism = (_quantize(y_hat) != _quantize(y_c)) & (valid > 0)
        ser_cnt = ser_cnt + jnp.sum(mism.astype(jnp.float32), axis=(1, 2))
        yv = (y_c - shift[:, None, :]) * valid
        y_sum = y_sum + jnp.sum(yv, axis=1)
        y_sq = y_sq + jnp.sum(yv * yv, axis=1)
        return (s, err2, ser_cnt, y_sum, y_sq), (
            y_hat if cfg.collect_y_pred else None)

    (_, *acc), y_chunks = jax.lax.scan(body, carry0, xs)
    if not cfg.collect_y_pred:
        return None, acc
    y_raw = jnp.moveaxis(y_chunks, 0, 1).reshape(b, t_padded, c_cols)[:, :t_total]
    return y_raw, acc


@scopes.scoped(scopes.EVAL)
def _streaming_metrics(acc, t_test: int, *, channel_axis: bool):
    """NRMSE/SER from the running accumulators — same conventions as the
    materialized path: per-channel NRMSE (that channel's variance, computed
    from the in-scan shifted Σ(y−y₀)/Σ(y−y₀)² moments — variance is
    shift-invariant) then channel-mean; SER over quantized-vs-quantized
    symbols."""
    err2, ser_cnt, y_sum, y_sq = acc
    mean = y_sum / t_test
    var = jnp.maximum(y_sq / t_test - mean * mean, 0.0)   # [B, C]
    nrmse_ch = jnp.sqrt((err2 / t_test) / (var + VAR_EPS))
    nrmse = jnp.mean(nrmse_ch, axis=-1) if channel_axis else nrmse_ch[:, 0]
    ser = ser_cnt / (t_test * err2.shape[-1])
    return nrmse, ser


def _input_layer(cfg: ExperimentConfig, tr_in, te_in):
    """The input layer: per-instance normalisation (fitted on the train
    segment), sample-and-hold and gain -> the drives (j_tr, j_te) [B, T*]."""
    with jax.named_scope(scopes.INPUT):
        if cfg.normalize_input:
            lo = jnp.min(tr_in, axis=1, keepdims=True)
            scale = 1.0 / (jnp.max(tr_in, axis=1, keepdims=True) - lo + 1e-12)
        else:
            lo, scale = 0.0, 1.0
        j_tr = sample_and_hold((tr_in - lo) * scale * cfg.input_gain)
        j_te = sample_and_hold((te_in - lo) * scale * cfg.input_gain)
        return maybe_shard(j_tr, ("pod", "data")), maybe_shard(j_te, ("pod", "data"))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _pipeline_states(cfg: ExperimentConfig, mask, tr_in, te_in, dev_params=None):
    """The states ``_run_pipeline`` fits and evaluates its readouts on:
    train from the dark state, test resumed from the train segment's end,
    in the streamed chunks' dtype."""
    j_tr, j_te = _input_layer(cfg, tr_in, te_in)
    dtype = cfg._stream_state_dtype_arg if cfg.stream_chunk_k is not None else None
    st_tr, s_end = _gen_states(cfg, mask, j_tr, wdm=False, return_final=True,
                               state_dtype=dtype, dev_params=dev_params)
    st_te = _gen_states(cfg, mask, j_te, wdm=False, s0=s_end,
                        state_dtype=dtype, dev_params=dev_params)
    return st_tr, st_te


@functools.partial(jax.jit, static_argnames=("cfg", "wdm", "shared"))
def _run_pipeline(cfg: ExperimentConfig, mask, tr_in, tr_tg, te_in, te_tg,
                  wdm: bool = False, shared: bool = False, dev_params=None):
    """The whole experiment as one XLA program.  All arrays [B, T*].

    ``dev_params`` (an *operand* pytree, e.g. ``devices.cmt.CMTSweepParams``
    with per-lane [B] leaves) sweeps the device operating point across batch
    lanes without retracing: same cfg + same shapes + new parameter VALUES
    reuse the compiled program (DESIGN.md §14).  Single-mask workloads only
    (``wdm``/``shared``/``topology`` keep the static-model contract); the
    ``None`` default adds no operands, so legacy call sites trace the exact
    program they always did.

    ``wdm=True`` runs the WDM ensemble workload: the batch axis is R
    wavelength channels and ``mask`` is a per-channel [R, N] stack — state
    generation swaps to the per-lane-mask path (``generate_channel_states``,
    one Pallas launch for all channels) and the streamed fit to
    ``fit_ridge_streaming_wdm``; everything else (input layer, readout
    solve, metrics) is the same program.

    ``shared=True`` (with ``wdm=True``) is the shared-readout WDM mode:
    ONE readout over the concatenation of all R channels' states
    (``fit_ridge_streaming_shared``), targets [1, K(, C)] — one task for
    the ensemble.  ``cfg.topology`` switches the streaming branch onto the
    composed stage-chain fit/eval (``mask`` then the per-stage mask-stack
    tuple); both are streaming-only (enforced at config construction).
    """
    j_tr, j_te = _input_layer(cfg, tr_in, te_in)

    if cfg.stream_chunk_k is not None:
        # -- streaming fused path (DESIGN.md §8/§9/§13): reservoir chunks
        # feed the accumulate-into Gram kernel inside ONE lax.scan; test
        # evaluation streams too.  The [B, T, N] state tensor never exists.
        noise_rel = (cfg.state_noise_rel
                     if cfg.state_noise_mode == "diagonal" else 0.0)
        kw = dict(washout=cfg.washout, chunk_k=cfg.stream_chunk_k,
                  lambdas=cfg.ridge_l2, state_method=cfg.state_method,
                  block_s=cfg.kernel_block_s,
                  use_kernel=cfg.readout_use_kernel,
                  block_t=cfg.readout_block_t,
                  state_dtype=cfg._stream_state_dtype_arg,
                  noise_rel=noise_rel)
        te_tg3 = te_tg[..., None] if te_tg.ndim == 2 else te_tg
        if cfg.topology is not None:
            # composed stage chain: fit and eval share ONE per-chunk
            # transformer, so test states trace the exact stage ops the
            # Gram accumulation saw (pipeline/ridge.composed_chunk_states_fn)
            w_fit, lam_idx, s_carry = fit_ridge_streaming_composed(
                cfg.topology, mask, j_tr, tr_tg, **kw)
            eval_fn = composed_chunk_states_fn(
                cfg.topology, mask, state_method=cfg.state_method,
                block_s=cfg.kernel_block_s,
                state_dtype=cfg._stream_state_dtype_arg)
            y_raw3, acc = _eval_streaming(cfg, mask, j_te, te_tg3,
                                          w_fit, s_carry, states_fn=eval_fn)
        elif shared:
            # shared-readout WDM: one [R·N + 1] readout, channel axis rides
            # the chunk scan as a trailing input dim (B = 1 for the Gram)
            r, n_nodes = mask.shape
            w_1, lam_1, s_1 = fit_ridge_streaming_shared(
                cfg.model, mask, j_tr, tr_tg[0], **kw)
            w_fit, lam_idx = w_1[None], lam_1[None]

            def eval_fn(j_c, carries):         # j_c [1, chunk, R]
                states, s_next = _gen_states(
                    cfg, mask, j_c[0].T, wdm=True, s0=carries[0][0],
                    return_final=True,
                    state_dtype=cfg._stream_state_dtype_arg)
                feats = jnp.moveaxis(states, 0, 1).reshape(
                    j_c.shape[1], r * n_nodes)[None]
                return feats, (s_next[None],)

            with jax.named_scope(scopes.EVAL):
                y_raw3, acc = _eval_streaming(
                    cfg, mask, jnp.moveaxis(j_te, 0, 1)[None], te_tg3,
                    w_fit, (s_1[None],), states_fn=eval_fn)
        elif dev_params is not None:
            w_fit, lam_idx, s_carry = fit_ridge_streaming(
                cfg.model, mask, j_tr, tr_tg, dev_params=dev_params, **kw)
            y_raw3, acc = _eval_streaming(cfg, mask, j_te, te_tg3,
                                          w_fit, s_carry,
                                          dev_params=dev_params)
        else:
            fit = fit_ridge_streaming_wdm if wdm else fit_ridge_streaming
            w_fit, lam_idx, s_carry = fit(cfg.model, mask, j_tr, tr_tg, **kw)
            y_raw3, acc = _eval_streaming(cfg, mask, j_te, te_tg3,
                                          w_fit, s_carry, wdm=wdm)
        with jax.named_scope(scopes.EVAL):
            nrmse, ser = _streaming_metrics(acc, te_tg3.shape[1],
                                            channel_axis=te_tg.ndim == 3)
            lam = jnp.asarray(cfg.ridge_l2, jnp.float32)[lam_idx]
            if y_raw3 is None:
                return None, nrmse, ser, lam, w_fit
            y_raw = y_raw3 if te_tg.ndim == 3 else y_raw3[..., 0]
            y_out = _quantize(y_raw) if cfg.quantize else y_raw
            return y_out, nrmse, ser, lam, w_fit

    with jax.named_scope(scopes.COLLECT):
        # -- reservoir layer: batched state generation, carry train -> test --
        st_tr, s_carry = _gen_states(cfg, mask, j_tr, wdm=wdm,
                                     return_final=True, dev_params=dev_params)
        st_te = _gen_states(cfg, mask, j_te, wdm=wdm, s0=s_carry,
                            dev_params=dev_params)
        st_tr = maybe_shard(st_tr, ("pod", "data"))
        st_te = maybe_shard(st_te, ("pod", "data"))

        # -- output layer: digitiser noise + per-instance ridge/GCV fit ------
        w = cfg.washout
        st_fit = st_tr[:, w:]
        y_fit = tr_tg[:, w:]
        if cfg.state_noise_rel:
            sigma = cfg.state_noise_rel * jnp.std(st_fit, axis=(1, 2), keepdims=True)
            noise = jax.random.normal(jax.random.PRNGKey(cfg.noise_seed),
                                      st_fit.shape, st_fit.dtype)
            st_fit = st_fit + sigma * noise

        # Kernel path: ONE batch-gridded pallas_call over the instance stack
        # (ridge.fit_ridge_batched); jnp path: vmapped SVD solve.
        w_fit, lam_idx = fit_ridge_batched(
            st_fit, y_fit, lambdas=cfg.ridge_l2,
            use_kernel=cfg.readout_use_kernel, block_t=cfg.readout_block_t)

    # -- evaluation -----------------------------------------------------------
    return _materialized_eval(cfg, st_te, te_tg, w_fit, lam_idx)


@scopes.scoped(scopes.EVAL)
def _materialized_eval(cfg: ExperimentConfig, st_te, te_tg, w_fit, lam_idx):
    """Readout on the materialised test states, then NRMSE/SER."""
    y_raw = jax.vmap(apply_readout)(st_te, w_fit)      # [B, T_test(, C)]
    y_sym = _quantize(y_raw)
    inst_axes = tuple(range(1, y_raw.ndim))            # all but the batch axis
    err = y_raw - te_tg
    # NRMSE per channel (normalised by that channel's variance, reduced over
    # T only), then channel-mean — a pooled T×C reduction would let a
    # high-variance channel mask total failure on a low-variance one.
    var = jnp.var(te_tg, axis=1)                       # [B(, C)]
    nrmse_ch = jnp.sqrt(jnp.mean(err * err, axis=1) / (var + VAR_EPS))
    nrmse = nrmse_ch if nrmse_ch.ndim == 1 else jnp.mean(nrmse_ch, axis=-1)
    # SER on quantized-vs-quantized symbols: targets that round-tripped
    # through a wider dtype (f64 task gen -> f32 canon) may sit eps off the
    # nominal 4-PAM levels; raw float equality would count those as errors.
    ser = jnp.mean((y_sym != _quantize(te_tg)).astype(jnp.float32), axis=inst_axes)
    lam = jnp.asarray(cfg.ridge_l2, jnp.float32)[lam_idx]
    y_out = y_sym if cfg.quantize else y_raw
    if not cfg.collect_y_pred:
        return None, nrmse, ser, lam, w_fit
    return y_out, nrmse, ser, lam, w_fit


@jax.jit
def _end_to_end(outs):
    """Outputs of one dtype end to end in one flat array."""
    return jnp.concatenate([jnp.ravel(x) for x in outs])


def _pack_result(y, nrmse, ser, lam, w) -> ExperimentResult:
    """Device outputs -> host ExperimentResult (shared by both experiments),
    fetched in one device-to-host transfer: outputs of one dtype are laid
    end to end on the device first, so the host waits once a call."""
    outs = [x for x in (y, nrmse, ser, lam, w) if x is not None]
    if len({x.dtype for x in outs}) == 1:
        flat = np.asarray(_end_to_end(outs))
        cuts = np.cumsum([x.size for x in outs])[:-1]
        host = [a.reshape(x.shape) for a, x in zip(np.split(flat, cuts), outs)]
    else:
        host = jax.device_get(outs)
    if y is not None:
        y, *host = host
    nrmse, ser, lam, w = host
    # w is [B, N + 1, C]; drop the channel axis only when there is a
    # single output channel (C > 1 used to be silently truncated here).
    w = np.asarray(w)
    if w.shape[-1] == 1:
        w = w[..., 0]
    return ExperimentResult(
        y_pred=None if y is None else np.asarray(y),
        nrmse=np.asarray(nrmse), ser=np.asarray(ser),
        lam=np.asarray(lam), readout_w=w)


def _check_lanes(dev_params, b: int) -> None:
    """``dev_params`` leaves must be scalars or [B], one value per lane
    (the kernel wrapper makes them float32 lanes, ``dfr_scan.ops``)."""
    for leaf in jax.tree.leaves(dev_params):
        shape = jnp.shape(leaf)
        if len(shape) > 1 or (len(shape) == 1 and shape[0] != b):
            raise ValueError(
                f"dev_params leaves must be scalars or [{b}] "
                f"(one value per batch lane), got shape {shape}")


class Experiment:
    """Batched DFRC experiment: one jit call for fit + predict + metrics.

    >>> exp = Experiment(ExperimentConfig(model=SiliconMR(), n_nodes=200))
    >>> res = exp.run(tr_in, tr_tg, te_in, te_tg)   # arrays [B, T] (or [T])
    >>> res.nrmse                                    # [B]

    The compiled program is cached per (config, input shapes) by jax.jit;
    re-running with new data of the same shape does not recompile.
    """

    def __init__(self, config: ExperimentConfig):
        self.config = config
        if config.topology is not None:
            # per-stage mask stacks (tuple of [L, N]) replace the single mask
            self.mask = build_stage_masks(config.topology)
        else:
            self.mask = make_mask(config.n_nodes, levels=config.mask_levels,
                                  seed=config.mask_seed)

    def run(self, inputs_train, targets_train, inputs_test, targets_test,
            *, dev_params=None) -> ExperimentResult:
        """Fit readouts and evaluate, one task instance per batch row.

        Inputs are [B, T] (or [T], treated as B = 1); targets may carry a
        trailing channel axis ([B, T, C]) for multi-output readouts.
        Train/test lengths may differ; all instances in a batch share shapes
        (stack equal-length series; pad/trim upstream otherwise).

        ``dev_params`` sweeps the device operating point across the batch
        lanes (a traced pytree, e.g. ``devices.cmt.CMTSweepParams``; leaves
        scalar or [B]) — the design-space-exploration hook (DESIGN.md §14):
        every lane runs the same compiled program at its own device point,
        and re-running with new parameter values recompiles nothing.

        Under a profiler trace the call shows as three host spans
        (``scopes.HOST_SPANS``): ``dfrc.prepare``, ``dfrc.dispatch`` and
        ``dfrc.fetch``, the last holding the wait for the device; inside
        ``dfrc.prepare``, ``dfrc.sweep_params`` checks the parameter lanes.
        """
        with jax.profiler.TraceAnnotation(scopes.PREPARE):
            operands = self._operands(inputs_train, targets_train,
                                      inputs_test, targets_test, dev_params)
        with jax.profiler.TraceAnnotation(scopes.DISPATCH):
            out = _run_pipeline(self.config, self.mask, *operands,
                                dev_params=dev_params)
        with jax.profiler.TraceAnnotation(scopes.FETCH):
            return _pack_result(*out)

    def lowered(self, inputs_train, targets_train, inputs_test, targets_test,
                *, dev_params=None) -> jax.stages.Lowered:
        """The program ``run`` calls for these inputs, lowered: its
        ``compile()`` is the executable, whose ``as_text()`` is the optimised
        HLO with each instruction's ``dfrc.*`` scope in its ``op_name`` and
        whose ``memory_analysis()`` gives the device memory it takes.  Same
        shapes as a ``run`` call: the compile is a cache hit."""
        operands = self._operands(inputs_train, targets_train, inputs_test,
                                  targets_test, dev_params)
        return _run_pipeline.lower(self.config, self.mask, *operands,
                                   dev_params=dev_params)

    def states(self, inputs_train, inputs_test, *, dev_params=None):
        """The reservoir states the readouts of ``run`` are fitted and
        evaluated on, for the same inputs and ``dev_params``: (train
        [B, T_train, N], washout included; test [B, T_test, N]), through
        the same input layer and state method (DESIGN.md §14)."""
        if self.config.topology is not None:
            raise ValueError("states() reads the single-loop workload")
        tr_in = _canon_batch(inputs_train, "inputs_train")
        te_in = _canon_batch(inputs_test, "inputs_test")
        if dev_params is not None:
            _check_lanes(dev_params, tr_in.shape[0])
        return _pipeline_states(self.config, self.mask, tr_in, te_in, dev_params)

    def _operands(self, inputs_train, targets_train, inputs_test, targets_test,
                  dev_params):
        """Canonicalised device operands (tr_in, tr_tg, te_in, te_tg)."""
        tr_in = _canon_batch(inputs_train, "inputs_train")
        te_in = _canon_batch(inputs_test, "inputs_test")
        tr_tg = _canon_targets(targets_train, "targets_train", tr_in)
        te_tg = _canon_targets(targets_test, "targets_test", te_in)
        if tr_in.shape[0] != te_in.shape[0] or tr_tg.ndim != te_tg.ndim or (
                tr_tg.ndim == 3 and tr_tg.shape[-1] != te_tg.shape[-1]):
            raise ValueError(
                f"inconsistent batch shapes: train {tr_in.shape}/{tr_tg.shape}, "
                f"test {te_in.shape}/{te_tg.shape}")
        if dev_params is not None:
            if self.config.topology is not None:
                raise ValueError(
                    "dev_params with a composed topology is not supported; "
                    "sweep the single-loop workload")
            with jax.profiler.TraceAnnotation(scopes.SWEEP_PARAMS):
                _check_lanes(dev_params, tr_in.shape[0])
        return tr_in, tr_tg, te_in, te_tg

    def run_dataset(self, ds) -> ExperimentResult:
        """Convenience for a core.tasks Dataset (single instance, B = 1)."""
        return self.run(ds.inputs_train, ds.targets_train,
                        ds.inputs_test, ds.targets_test)


@functools.partial(jax.jit, static_argnames=("model", "method", "block_s",
                                             "return_final", "state_dtype"))
def channel_states(model: NLModel, j: jnp.ndarray, masks: jnp.ndarray, *,
                   s0: jnp.ndarray | None = None, method: str = "fast",
                   block_s: int | None = None, return_final: bool = False,
                   state_dtype=None):
    """WDM ensemble states: per-channel masks over per-channel inputs.

    ``j`` [R, K] (one series per wavelength channel), ``masks`` [R, N] ->
    states [R, K, N].  ``s0`` [R, N] carries each channel's reservoir state
    across calls (train -> test).  One program evaluates all R channels in
    parallel — the software analogue of R wavelengths sharing the physical
    ring.

    Jitted wrapper over ``core.reservoir.generate_channel_states`` with full
    ``generate_states`` knob parity (DESIGN.md §9): ``return_final=True``
    adds the [R, N] carry (on the kernel path the VMEM-flush output, so a
    chunked caller never keeps the full [R, K, N] block alive just to
    resume), ``state_dtype`` narrows the emitted state tensor (bf16 chunks).

    ``method="kernel"`` rides the Pallas scan's per-lane mask path: each
    wavelength channel is a batch lane with its own [N] mask tile resident
    in VMEM (kernels/dfr_scan per-lane BlockSpec), so all R channels still
    run as ONE kernel launch — no per-channel vmap over ``pallas_call``.
    The jnp paths ("fast"/"ref") vmap over channels as before.
    """
    return generate_channel_states(model, j, masks, s0=s0, method=method,
                                   block_s=block_s, return_final=return_final,
                                   state_dtype=state_dtype)


class WDMExperiment:
    """WDM ensemble experiment: R wavelength channels, one delay loop.

    The chip-scale scaling scenario of the paper (Section VI): R microring
    wavelength channels share one physical delay loop, each carrying an
    independent input stream against its own MLS mask, each with its own
    readout — R× the throughput of one accelerator at constant optical
    hardware.  Software-side this is ``Experiment`` with the batch axis
    reinterpreted as channels and a per-channel [R, N] mask stack
    (DESIGN.md §9); with ``config.stream_chunk_k`` set, the run streams:
    the fit is ``fit_ridge_streaming_wdm`` (ONE chunk scan, per-channel
    Gram stacks, no [R, K, N] state tensor ever resident) and the test
    evaluation runs chunked with running NRMSE/SER accumulators — long WDM
    streams (K ≫ chunk) no longer fall back to O(R·K·N) memory.

    >>> cfg = ExperimentConfig(n_nodes=100, stream_chunk_k=512)
    >>> res = WDMExperiment(cfg, n_channels=16).run(tr_in, tr_tg, te_in, te_tg)
    >>> res.nrmse                                    # [R] — per channel

    Channel masks default to ``make_mask(n_nodes, seed=mask_seed + r)``;
    pass ``masks`` [R, N] to override.

    ``shared_readout=True`` switches to the shared-readout mode (DESIGN.md
    §13): the R channels observe ONE task (targets [K(, C)], one stream for
    the ensemble, inputs still [R, K] — e.g. R delayed/transformed views of
    one signal) and the fit trains a single [R·N + 1] readout over the
    concatenation of every channel's states, whose Gram carries the
    cross-channel correlation blocks the per-channel fits discard
    (``fit_ridge_streaming_shared``).  Result arrays are then ensemble-level
    (B = 1): ``nrmse``/``ser``/``lam`` [1], ``readout_w`` [1, R·N + 1(, C)].
    Streaming-only, like every composed mode.

    ``config.topology`` (per-channel composed graphs) builds per-stage
    [R, L, N] mask stacks — channel r, loop l seeded ``mask_seed + r·L + l``
    — and runs the composed streaming fit with channels as instances.
    """

    def __init__(self, config: ExperimentConfig, n_channels: int, *,
                 masks: jnp.ndarray | None = None,
                 shared_readout: bool = False):
        if n_channels < 1:
            raise ValueError(f"n_channels must be >= 1, got {n_channels}")
        self.config = config
        self.n_channels = n_channels
        self.shared_readout = shared_readout
        if shared_readout and config.stream_chunk_k is None:
            raise ValueError(
                "shared_readout accumulates ONE cross-channel Gram on the "
                "streaming path; set stream_chunk_k")
        if shared_readout and config.topology is not None:
            raise ValueError(
                "shared_readout with a composed topology is not supported; "
                "pick one readout generalisation per run")
        if config.topology is not None:
            if masks is not None:
                raise ValueError("with config.topology the per-stage mask "
                                 "stacks are derived; masks= is not accepted")
            self.masks = build_stage_masks(config.topology,
                                           channels=n_channels)
            return
        if masks is None:
            masks = jnp.stack([
                make_mask(config.n_nodes, levels=config.mask_levels,
                          seed=config.mask_seed + r)
                for r in range(n_channels)])
        else:
            masks = jnp.asarray(masks, jnp.float32)
        if masks.shape != (n_channels, config.n_nodes):
            raise ValueError(
                f"masks {masks.shape} do not match (R, N) = "
                f"({n_channels}, {config.n_nodes})")
        self.masks = masks

    def run(self, inputs_train, targets_train, inputs_test, targets_test) -> ExperimentResult:
        """Fit per-channel readouts and evaluate, one channel per batch row.

        Inputs are [R, K] (R = ``n_channels``); targets may carry a trailing
        output-channel axis ([R, K, C]).  Result arrays are per wavelength
        channel: ``nrmse``/``ser``/``lam`` [R], ``readout_w`` [R, N + 1(, C)].
        With ``shared_readout=True`` targets are ONE stream ([K] or [K, C])
        and results are ensemble-level (see class docstring).
        """
        tr_in = _canon_batch(inputs_train, "inputs_train")
        te_in = _canon_batch(inputs_test, "inputs_test")
        if tr_in.shape[0] != self.n_channels or te_in.shape[0] != self.n_channels:
            raise ValueError(
                f"expected {self.n_channels} channel rows, got train "
                f"{tr_in.shape} / test {te_in.shape}")
        if self.shared_readout:
            # one target stream for the whole ensemble -> canon against a
            # B = 1 view of the stream length
            tr_tg = _canon_targets(targets_train, "targets_train", tr_in[:1])
            te_tg = _canon_targets(targets_test, "targets_test", te_in[:1])
        else:
            tr_tg = _canon_targets(targets_train, "targets_train", tr_in)
            te_tg = _canon_targets(targets_test, "targets_test", te_in)
        if tr_tg.ndim != te_tg.ndim or (
                tr_tg.ndim == 3 and tr_tg.shape[-1] != te_tg.shape[-1]):
            raise ValueError(
                f"inconsistent target shapes: train {tr_tg.shape}, "
                f"test {te_tg.shape}")
        y, nrmse, ser, lam, w = _run_pipeline(
            self.config, self.masks, tr_in, tr_tg, te_in, te_tg, wdm=True,
            shared=self.shared_readout)
        return _pack_result(y, nrmse, ser, lam, w)
