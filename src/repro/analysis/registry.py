"""Entry-point registry: every compiled hot path, with its contract set.

Each entry point is a builder that constructs the callable on *tiny* shapes
(tracing cost only — nothing executes) and declares the rules the program
must satisfy.  `python -m repro.analysis` traces them all; tests and CI
treat a violation as a broken structural claim, the same way a failing
parity test is a broken numerical claim.

Registering a new entry point (DESIGN.md §11): write a builder that closes
over static config and returns ``(Program, rules)``, decorate it with
``@register(name, description)``.  Keep shapes minimal — the walker scales
with program size, and the properties being checked are shape-generic.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .rules import (DonationHonored, MaxPallasCalls, MaxScans, NoDtypeAbove,
                    NoHostCallback, NoSilentUpcast, NoStateTensor, Program,
                    VmemBudget)

# Tiny trace shapes shared by the pipeline entries.
_B, _N, _T_TR, _T_TE, _CHUNK, _W0 = 2, 16, 96, 64, 32, 16
_LAMS = (1e-6, 1e-4)


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    name: str
    description: str
    build: object          # () -> (Program, tuple[Rule, ...])


ENTRY_POINTS = {}


def register(name: str, description: str):
    def deco(fn):
        ENTRY_POINTS[name] = EntryPoint(name, description, fn)
        return fn
    return deco


def _padded_f(n_nodes: int) -> int:
    """Feature count padded to the Gram kernel's 128-lane tile."""
    return -(-(n_nodes + 1) // 128) * 128


def _experiment_setup(**cfg_kw):
    from repro.pipeline import Experiment, ExperimentConfig
    from repro.core import SiliconMR
    base = dict(model=SiliconMR(), n_nodes=_N, washout=_W0, ridge_l2=_LAMS,
                state_noise_rel=0.0)
    base.update(cfg_kw)
    cfg = ExperimentConfig(**base)
    mask = Experiment(cfg).mask
    args = (jnp.zeros((_B, _T_TR), jnp.float32),
            jnp.zeros((_B, _T_TR), jnp.float32),
            jnp.zeros((_B, _T_TE), jnp.float32),
            jnp.zeros((_B, _T_TE), jnp.float32))
    return cfg, mask, args


def _pipeline_program(name, **cfg_kw):
    from repro.pipeline.experiment import _run_pipeline
    cfg, mask, args = _experiment_setup(**cfg_kw)
    return Program(lambda a, b, c, d: _run_pipeline(cfg, mask, a, b, c, d),
                   args, name=name)


@register("experiment_ref",
          "Experiment pipeline, reference reservoir, jnp readout")
def _experiment_ref():
    prog = _pipeline_program("experiment_ref", state_method="ref",
                             readout_use_kernel=False)
    return prog, (NoHostCallback(), NoDtypeAbove("float32"),
                  MaxPallasCalls(0))


@register("experiment_fast",
          "Experiment pipeline, vectorised jnp reservoir, jnp readout")
def _experiment_fast():
    prog = _pipeline_program("experiment_fast", state_method="fast",
                             readout_use_kernel=False)
    return prog, (NoHostCallback(), NoDtypeAbove("float32"),
                  MaxPallasCalls(0))


@register("experiment_kernel",
          "Experiment pipeline, materialized Pallas path (dfr_scan + Gram)")
def _experiment_kernel():
    prog = _pipeline_program("experiment_kernel", state_method="kernel",
                             readout_use_kernel=True)
    # train dfr_scan + test dfr_scan + one batched Gram launch
    return prog, (NoHostCallback(), NoDtypeAbove("float32"),
                  MaxPallasCalls(3), VmemBudget())


@register("experiment_streaming",
          "Experiment pipeline, streamed fit + eval (no [B,T,N] tensor)")
def _experiment_streaming():
    prog = _pipeline_program("experiment_streaming", state_method="kernel",
                             readout_use_kernel=True, stream_chunk_k=_CHUNK)
    rules = (NoHostCallback(), NoDtypeAbove("float32"),
             MaxScans(2),               # one fit scan + one eval scan
             VmemBudget(),
             NoStateTensor(_T_TR, _B * _T_TR * _N, what="train state tensor"),
             NoStateTensor(_T_TE, _B * _T_TE * _N, what="test state tensor"))
    return prog, rules


def _streaming_fit_program(name, *, wdm=False, state_dtype=None):
    from repro.core import SiliconMR, make_mask
    from repro.pipeline import fit_ridge_streaming, fit_ridge_streaming_wdm
    model = SiliconMR()
    kw = dict(washout=_W0, chunk_k=_CHUNK, lambdas=_LAMS,
              state_method="kernel", use_kernel=True)
    if state_dtype is not None:
        kw["state_dtype"] = state_dtype
    j = jnp.zeros((_B, _T_TR), jnp.float32)
    y = jnp.zeros((_B, _T_TR), jnp.float32)
    if wdm:
        masks = jnp.stack([make_mask(_N, seed=30 + i) for i in range(_B)])
        fn = lambda jj, yy: fit_ridge_streaming_wdm(model, masks, jj, yy, **kw)
    else:
        mask = make_mask(_N, seed=1)
        fn = lambda jj, yy: fit_ridge_streaming(model, mask, jj, yy, **kw)
    return Program(fn, (j, y), name=name)


def _streaming_fit_rules():
    return (NoHostCallback(), NoDtypeAbove("float32"),
            MaxScans(1), MaxPallasCalls(2),        # dfr_scan + Gram per chunk
            VmemBudget(),
            NoStateTensor(_T_TR, _B * _T_TR * _N, what="full-stream tensor"),
            DonationHonored(min_pallas_aliases=2))  # accumulate-into Gram


@register("fit_ridge_streaming",
          "Streamed ridge fit: ONE chunk scan, accumulate-into Gram")
def _fit_ridge_streaming():
    return (_streaming_fit_program("fit_ridge_streaming"),
            _streaming_fit_rules())


@register("fit_ridge_streaming_bf16",
          "Streamed ridge fit with bf16 state chunks (no silent f32 chunk)")
def _fit_ridge_streaming_bf16():
    from repro.kernels.dfr_scan import padded_lanes
    prog = _streaming_fit_program("fit_ridge_streaming_bf16",
                                  state_dtype="bfloat16")
    # The f32 final-state carry [B, N] and the lane-padded input chunk
    # (O(B_pad·chunk), no node axis) are legitimate; a wide block at
    # state-chunk scale — padded-batch × chunk × nodes — is not.
    floor = padded_lanes(_B) * _CHUNK * _N
    rules = _streaming_fit_rules() + (
        NoSilentUpcast(_CHUNK, floor),)
    return prog, rules


@register("fit_ridge_streaming_wdm",
          "WDM streamed fit: all channels in ONE launch pair per chunk")
def _fit_ridge_streaming_wdm():
    return (_streaming_fit_program("fit_ridge_streaming_wdm", wdm=True),
            _streaming_fit_rules())


# Device-physics entries (DESIGN.md §14): the CMT cavity's sub-stepped tick
# integration must hold the SAME structural contracts as the closed-form
# models — the substeps unroll inside the node update, so every rule that
# held for SiliconMR must hold verbatim with MRCavityCMT swapped in.
def _cmt_model():
    from repro.core import SiliconMR
    from repro.devices import calibrated_twin
    return calibrated_twin(SiliconMR(), power_mw=1.0)


@register("experiment_cmt_kernel",
          "CMT-cavity pipeline through the Pallas dfr_scan (substeps in-tile)")
def _experiment_cmt_kernel():
    prog = _pipeline_program("experiment_cmt_kernel", model=_cmt_model(),
                             state_method="kernel", readout_use_kernel=True)
    # identical launch budget to experiment_kernel: the substep loop unrolls
    # inside the node update — richer physics may not add launches
    return prog, (NoHostCallback(), NoDtypeAbove("float32"),
                  MaxPallasCalls(3), VmemBudget())


def _device_sweep_program(name, *, state_dtype="float32", use_kernel=False):
    from repro.devices import CMTSweepParams
    from repro.pipeline.experiment import _run_pipeline
    cfg, mask, args = _experiment_setup(
        model=_cmt_model(), state_method="fast", stream_chunk_k=_CHUNK,
        stream_state_dtype=state_dtype, readout_use_kernel=use_kernel)
    lanes = (jnp.zeros((_B,), jnp.float32),    # detune
             jnp.ones((_B,), jnp.float32),     # loss_scale
             jnp.ones((_B,), jnp.float32))     # power
    fn = lambda a, b, c, d, pd, pl, pp: _run_pipeline(
        cfg, mask, a, b, c, d, dev_params=CMTSweepParams(pd, pl, pp))
    return Program(fn, args + lanes, name=name)


# These swept maps run on the jnp fast path, so the scan budget is its true
# nesting: fit and eval each run the chunk scan -> per-chunk period scan ->
# in-period node chain scan.
_SWEEP_SCANS = 6


@register("device_sweep",
          "Swept-params CMT robustness map: grid as lanes, ONE streamed trace")
def _device_sweep():
    prog = _device_sweep_program("device_sweep")
    rules = (NoHostCallback(), NoDtypeAbove("float32"),
             MaxScans(_SWEEP_SCANS),
             MaxPallasCalls(0),         # jnp state + einsum Gram throughout
             NoStateTensor(_T_TR, _B * _T_TR * _N, what="train state tensor"),
             NoStateTensor(_T_TE, _B * _T_TE * _N, what="test state tensor"))
    return prog, rules


@register("device_sweep_bf16",
          "Swept CMT map, bf16 state chunks (no silent f32 chunk upcast)")
def _device_sweep_bf16():
    prog = _device_sweep_program("device_sweep_bf16", state_dtype="bfloat16",
                                 use_kernel=True)
    # In-scan state *compute* is f32 by design on the jnp path (only the
    # emitted chunk narrows — generate_states docstring), so the [B, chunk,
    # N] block and its period-scan transpose are declared benign.  Anything
    # else wide at chunk scale — e.g. a silently re-promoted [B, chunk, N+1]
    # feature block downstream of the bf16 chunk — still trips.
    benign = ((_B, _CHUNK, _N), (_CHUNK, _B, _N))
    rules = (NoHostCallback(), NoDtypeAbove("float32"),
             MaxScans(_SWEEP_SCANS), VmemBudget(),
             NoStateTensor(_T_TR, _B * _T_TR * _N, what="train state tensor"),
             NoStateTensor(_T_TE, _B * _T_TE * _N, what="test state tensor"),
             NoSilentUpcast(_CHUNK, _B * _CHUNK * _N, benign_shapes=benign))
    return prog, rules


# Composed-graph trace shapes: a depth-3 chain whose smallest stage sets the
# NoStateTensor floor — ANY stage materializing its full-T [B·L, T, N] block
# (the smallest is _B·_T_TR·8 elements) trips the rule, while the O(B·T)
# input/target streams stay well under it.
def _trace_graph(depth: int):
    from repro.core import ReservoirStage, SiliconMR, chain
    stages = [ReservoirStage(model=SiliconMR(), n_nodes=_N, loops=2,
                             mask_seed=1),
              ReservoirStage(model=SiliconMR(), n_nodes=_N, mask_seed=7),
              ReservoirStage(model=SiliconMR(), n_nodes=8, mask_seed=13,
                             link="sin2")]
    return chain(*stages[-depth:])


@register("fit_ridge_streaming_composed",
          "Composed depth-3 streamed fit: stage chain per chunk, ONE scan")
def _fit_ridge_streaming_composed():
    from repro.core import build_stage_masks
    from repro.pipeline import fit_ridge_streaming_composed
    graph = _trace_graph(3)
    masks = build_stage_masks(graph)
    kw = dict(washout=_W0, chunk_k=_CHUNK, lambdas=_LAMS,
              state_method="kernel", use_kernel=True)
    j = jnp.zeros((_B, _T_TR), jnp.float32)
    prog = Program(lambda jj, yy: fit_ridge_streaming_composed(
        graph, masks, jj, yy, **kw), (j, j),
        name="fit_ridge_streaming_composed")
    w_min = min(st.n_nodes for st in graph.stages)
    rules = (NoHostCallback(), NoDtypeAbove("float32"),
             MaxScans(1),                          # the whole chain, one scan
             MaxPallasCalls(graph.depth + 1),      # one dfr_scan/stage + Gram
             VmemBudget(),
             NoStateTensor(_T_TR, _B * _T_TR * w_min,
                           what="full-stream stage tensor"),
             DonationHonored(min_pallas_aliases=2))
    return prog, rules


@register("fit_ridge_streaming_shared",
          "Shared-readout WDM fit: one cross-channel Gram, ONE launch pair")
def _fit_ridge_streaming_shared():
    from repro.core import SiliconMR, make_mask
    from repro.pipeline import fit_ridge_streaming_shared
    model = SiliconMR()
    masks = jnp.stack([make_mask(_N, seed=40 + i) for i in range(_B)])
    kw = dict(washout=_W0, chunk_k=_CHUNK, lambdas=_LAMS,
              state_method="kernel", use_kernel=True)
    j = jnp.zeros((_B, _T_TR), jnp.float32)
    y = jnp.zeros((_T_TR,), jnp.float32)
    prog = Program(lambda jj, yy: fit_ridge_streaming_shared(
        model, masks, jj, yy, **kw), (j, y),
        name="fit_ridge_streaming_shared")
    return prog, _streaming_fit_rules()


@register("experiment_composed",
          "Depth-2 composed Experiment: streamed fit + eval, no stage tensor")
def _experiment_composed():
    graph = _trace_graph(2)
    prog = _pipeline_program("experiment_composed", state_method="kernel",
                             readout_use_kernel=True, stream_chunk_k=_CHUNK,
                             topology=graph)
    w_min = min(st.n_nodes for st in graph.stages)
    rules = (NoHostCallback(), NoDtypeAbove("float32"),
             MaxScans(2),                          # one fit scan + one eval scan
             # fit: one dfr_scan per stage + Gram; eval: one dfr_scan per stage
             MaxPallasCalls(2 * graph.depth + 1),
             VmemBudget(),
             NoStateTensor(_T_TR, _B * _T_TR * w_min,
                           what="train stage tensor"),
             NoStateTensor(_T_TE, _B * _T_TE * w_min,
                           what="test stage tensor"))
    return prog, rules


def _session_program(name, *, refresh, donate=False, **cfg_kw):
    from repro.core import make_mask
    from repro.pipeline.session import (SessionConfig, _session_step,
                                        session_init)
    cfg = SessionConfig(n_nodes=_N, chunk_k=_CHUNK, **cfg_kw)
    mask = make_mask(cfg.n_nodes, seed=0)
    state = session_init(cfg, _B)
    z = jnp.zeros((_B, _CHUNK), jnp.float32)
    fn = lambda st, jc, yc: _session_step(cfg, mask, st, jc, yc,
                                          refresh=refresh)
    return Program(fn, (state, z, z), name=name,
                   donate_argnums=(0,) if donate else ())


_SESSION_RULES = (NoHostCallback(), NoDtypeAbove("float32"),
                  NoStateTensor(4096, _B * 4096 * _N,
                                what="full-stream tensor"))


@register("session_step", "Online session tick (carry + Gram fold)")
def _session_step_entry():
    return _session_program("session_step", refresh=False), _SESSION_RULES


@register("session_step_refresh",
          "Online session tick with in-graph weight refresh (GCV solve)")
def _session_step_refresh():
    return (_session_program("session_step_refresh", refresh=True),
            _SESSION_RULES)


@register("session_step_kernel",
          "Online session tick on the Pallas path (one launch pair)")
def _session_step_kernel():
    prog = _session_program("session_step_kernel", refresh=False,
                            state_method="kernel", use_kernel=True)
    return prog, _SESSION_RULES + (MaxPallasCalls(2), VmemBudget(),
                                   DonationHonored(min_pallas_aliases=2))


@register("serve_dfr_step",
          "DFRServer donated step: the SessionState slab updates in place")
def _serve_dfr_step():
    prog = _session_program("serve_dfr_step", refresh=True, donate=True,
                            forgetting=0.99)
    # All 10 SessionState leaves (incl. the quarantined/poison health
    # bookkeeping) must come back donated in the lowered program — a
    # silently dropped donation doubles the serving slab.
    return prog, _SESSION_RULES + (DonationHonored(),)


def _faulted_program(name, *, refresh, donate=False, **cfg_kw):
    from repro.core import make_mask
    from repro.pipeline.session import SessionConfig, session_init
    from repro.robustness.faults import faulty_session_step, no_faults
    cfg = SessionConfig(n_nodes=_N, chunk_k=_CHUNK, **cfg_kw)
    mask = make_mask(cfg.n_nodes, seed=0)
    state = session_init(cfg, _B)
    spec = no_faults(_B)
    z = jnp.zeros((_B, _CHUNK), jnp.float32)
    tick = jnp.int32(0)
    fn = lambda sp, st, jc, yc, t: faulty_session_step(
        cfg, mask, sp, st, jc, yc, t, refresh=refresh)
    return Program(fn, (spec, state, z, z, tick), name=name,
                   donate_argnums=(1,) if donate else ())


@register("session_step_faulted",
          "Fault-injected session tick: injections + quarantine in-graph")
def _session_step_faulted():
    # Same contract set as the clean tick: fault models are traced operand
    # transforms (repro.robustness), never host callbacks or new tensors.
    # The uint32 PRNG key is integer data — NoDtypeAbove only constrains
    # inexact dtypes.
    return (_faulted_program("session_step_faulted", refresh=True),
            _SESSION_RULES)


@register("session_step_faulted_kernel",
          "Fault-injected session tick, Pallas path (still one launch pair)")
def _session_step_faulted_kernel():
    prog = _faulted_program("session_step_faulted_kernel", refresh=False,
                            donate=True, state_method="kernel",
                            use_kernel=True)
    return prog, _SESSION_RULES + (MaxPallasCalls(2), VmemBudget(),
                                   DonationHonored(min_pallas_aliases=2))


@register("reservoir_lm_train_step",
          "reservoir_lm train step (grad-accum scan, donated TrainState)")
def _reservoir_lm_train_step():
    from repro.configs import smoke_config
    from repro.optim import AdamWConfig
    from repro.runtime.steps import init_train_state, train_step
    cfg = smoke_config("reservoir_lm")
    opt = AdamWConfig()
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    b, s = 2 * max(1, cfg.microbatches), 16
    batch = {"tokens": jnp.zeros((b, s), jnp.int32),
             "labels": jnp.zeros((b, s), jnp.int32)}
    prog = Program(lambda st, bt: train_step(cfg, opt, st, bt),
                   (state, batch), name="reservoir_lm_train_step",
                   donate_argnums=(0,))
    return prog, (NoHostCallback(), NoDtypeAbove("float32"),
                  MaxPallasCalls(0), DonationHonored())


def seeded_violation_entry() -> EntryPoint:
    """A deliberately violating entry (materialized [B, T, N] state tensor
    under `NoStateTensor`) — CI runs it to prove the gate exits nonzero."""
    def build():
        from repro.core import SiliconMR, make_mask
        from repro.core.reservoir import generate_states
        from repro.pipeline import fit_ridge_batched
        model = SiliconMR()
        mask = make_mask(_N, seed=1)

        def fit(j, y):
            st = generate_states(model, j, mask, method="fast")
            return fit_ridge_batched(st[:, _W0:], y[:, _W0:], lambdas=_LAMS,
                                     use_kernel=False)

        j = jnp.zeros((_B, _T_TR), jnp.float32)
        prog = Program(fit, (j, j), name="seeded_violation")
        return prog, (NoStateTensor(_T_TR, _B * _T_TR * _N),)
    return EntryPoint("seeded_violation",
                      "Deliberate NoStateTensor violation (gate self-test)",
                      build)


def entry_point_names() -> list:
    return sorted(ENTRY_POINTS)


def get_entry_points(names=None, *, include_seeded=False) -> list:
    """Resolve ``names`` (None = all registered) to EntryPoint objects."""
    eps = dict(ENTRY_POINTS)
    if include_seeded:
        seeded = seeded_violation_entry()
        eps[seeded.name] = seeded
    if names is None:
        return [eps[n] for n in sorted(eps)]
    missing = [n for n in names if n not in eps]
    if missing:
        raise KeyError(f"unknown entry point(s) {missing}; "
                       f"known: {sorted(eps)}")
    return [eps[n] for n in names]
