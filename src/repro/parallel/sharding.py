"""Logical-axis -> mesh-axis sharding rules (DESIGN.md §5).

Strategies (per-arch, chosen by divisibility — recorded in each config):

  fsdp_tp     hybrid ZeRO-3 × tensor parallel: "embed"-class dims shard over
              the data axis (params gathered on use), "heads"/"mlp"/"vocab"
              dims over the model axis (Megatron TP).  Any rule whose mesh
              axis does not divide the dim falls back to replication
              (e.g. 8 kv heads on a 16-way model axis).
  fsdp        as fsdp_tp, plus: when TP found nothing to shard on the model
              axis, the largest eligible dim also shards over "model"
              (full ZeRO-3 over data×model) — used by starcoder2 (24 H) and
              xlstm (4 H), whose head counts don't divide 16.
  fsdp_tp_ep  fsdp_tp with the "expert" axis on "model" (expert parallelism);
              same table — listed separately for config clarity.

Batch shards over ("pod", "data") everywhere; long_500k (batch 1) shards the
KV-cache sequence axis over "data" instead (sequence parallelism for
decode).
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import get_abstract_mesh
from repro.models import param_logical_axes

# Candidate mesh axes per logical axis, in preference order.
_TABLE = {
    "vocab": ("model",),
    "embed": ("data",),
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "expert": ("model",),
    "ctx": ("data",),
    "hd": (),
    "layers": (),
    "nodes": (),
    None: (),
}

# Logical axes eligible for the pure-FSDP fallback shard over "model".
_FSDP_FALLBACK = ("embed", "vocab", "mlp", "ctx")


def _axis_size(mesh, name) -> int:
    return mesh.shape[name] if name in mesh.shape else 0


def spec_for(axes: tuple, shape: tuple, mesh, strategy: str) -> P:
    """PartitionSpec for one param leaf given its logical axes and shape."""
    used: set[str] = set()
    entries: list = []
    for dim, logical in zip(shape, axes):
        chosen = None
        for cand in _TABLE.get(logical, ()):
            size = _axis_size(mesh, cand)
            if size and cand not in used and dim % size == 0:
                chosen = cand
                used.add(cand)
                break
        entries.append(chosen)

    if strategy in ("fsdp", "zero3") and "model" not in used:
        # Full ZeRO-3: fold "model" into the largest eligible dim.
        best = None
        for i, (dim, logical) in enumerate(zip(shape, axes)):
            if logical in _FSDP_FALLBACK and dim % _axis_size(mesh, "model") == 0:
                if best is None or dim > shape[best]:
                    best = i
        if best is not None:
            prev = entries[best]
            entries[best] = (
                (prev, "model") if isinstance(prev, str) else "model"
            )
    return P(*entries)


def param_pspecs(cfg, mesh):
    """PartitionSpec pytree matching init_params(cfg, ...) structure."""
    axes_tree = param_logical_axes(cfg)
    strategy = cfg.strategy

    def leaf_spec(axes, shape):
        return spec_for(axes, shape, mesh, strategy)

    # axes_tree leaves are tuples; we need shapes -> use eval_shape of init.
    from repro.models import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    def walk(ax, sh):
        if isinstance(ax, tuple) and not isinstance(sh, tuple):
            # leaf: ax is the axes tuple, sh a ShapeDtypeStruct
            return leaf_spec(ax, sh.shape)
        if isinstance(ax, dict):
            return {k: walk(ax[k], sh[k]) for k in ax}
        if isinstance(ax, tuple):
            return tuple(walk(a, s) for a, s in zip(ax, sh))
        raise TypeError(type(ax))

    return walk(axes_tree, shapes)


def batch_axes(mesh, *, strategy: str = "fsdp_tp", batch: int | None = None) -> tuple:
    """Mesh axes the batch dim shards over.

    zero3 spreads the batch over every axis that divides it (the model axis
    carries data parallelism instead of TP — per-token activation
    all-reduces disappear in exchange for per-microbatch param gathers).
    """
    cands = ("pod", "data", "model") if strategy == "zero3" else ("pod", "data")
    axes: list[str] = []
    size = 1
    for a in cands:
        if a not in mesh.shape:
            continue
        if batch is not None and batch % (size * mesh.shape[a]):
            continue
        axes.append(a)
        size *= mesh.shape[a]
    return tuple(axes)


def batch_pspec(mesh, rank: int = 2, *, strategy: str = "fsdp_tp", batch: int | None = None) -> P:
    return P(batch_axes(mesh, strategy=strategy, batch=batch), *([None] * (rank - 1)))


def data_pspecs(cfg, mesh, specs: dict) -> dict:
    """Shardings for a train/prefill input-spec dict (tokens/labels/context)."""
    out = {}
    for k, v in specs.items():
        if k == "cache":
            out[k] = cache_pspecs(cfg, mesh, v)
        else:
            out[k] = batch_pspec(mesh, rank=len(v.shape),
                                 strategy=cfg.strategy, batch=v.shape[0])
    return out


def cache_pspecs(cfg, mesh, cache_shapes):
    """Sharding specs mirroring init_cache structure.

    Batch shards over ("pod","data") when it divides; otherwise (long_500k,
    batch 1) the attention-cache *sequence* axis shards over "data" and
    recurrent-state inner dims shard over "model" where divisible.
    """
    b_axes = batch_axes(mesh)
    b_size = 1
    for a in b_axes:
        b_size *= mesh.shape[a]
    kinds = [blk.mixer for blk in cfg.unit]

    # cache_shapes: {"pos": ..., "units": tuple per position}
    batch = None
    for leaf in jax.tree.leaves(cache_shapes["units"]):
        batch = leaf.shape[1]
        break
    shard_batch = batch is not None and batch % b_size == 0

    def b_ax():
        return b_axes if shard_batch else None

    model = _axis_size(mesh, "model")
    data = _axis_size(mesh, "data")

    def seq_ax(s):
        return "data" if (not shard_batch and data and s % data == 0) else None

    def inner_ax(d):
        return "model" if (model and d % model == 0) else None

    units_specs = []
    for kind, unit_cache in zip(kinds, cache_shapes["units"]):
        if kind in ("attn", "cross_attn"):
            k_sh = unit_cache[0].shape  # [U, B, S, KV, hd]
            kv_ax = "model" if (model and k_sh[3] % model == 0) else None
            # Sequence axis takes whatever is left: "model" when kv heads
            # don't divide it (kv replication would hold the full cache per
            # device — 38 GiB at granite decode_32k), and "data" too when
            # the batch can't shard (long_500k, batch 1).
            s_axes = []
            if not shard_batch:
                s_axes.append("data")
            if kv_ax is None and model:
                s_axes.append("model")
            s_div = 1
            for a in s_axes:
                s_div *= mesh.shape[a]
            s_entry = tuple(s_axes) if (s_axes and k_sh[2] % s_div == 0) else None
            spec = P(None, b_ax(), s_entry, kv_ax, None)
            units_specs.append((spec, spec))
        elif kind == "mamba":
            conv_sh, h_sh = unit_cache[0].shape, unit_cache[1].shape
            units_specs.append(
                (
                    P(None, b_ax(), None, inner_ax(conv_sh[3])),
                    P(None, b_ax(), inner_ax(h_sh[2]), None),
                )
            )
        elif kind == "mlstm":
            conv_sh, c_sh, n_sh, m_sh = (u.shape for u in unit_cache)
            units_specs.append(
                (
                    P(None, b_ax(), None, inner_ax(conv_sh[3])),
                    P(None, b_ax(), None, inner_ax(c_sh[3]), None),
                    P(None, b_ax(), None, inner_ax(n_sh[3])),
                    P(None, b_ax(), None),
                )
            )
        elif kind == "slstm":
            units_specs.append(
                (
                    P(None, b_ax(), inner_ax(unit_cache[0].shape[2])),
                    P(None, b_ax(), inner_ax(unit_cache[1].shape[2])),
                    P(None, b_ax(), None),
                    P(None, b_ax(), inner_ax(unit_cache[3].shape[2])),
                )
            )
        elif kind == "reservoir":
            units_specs.append(
                (P(None, b_ax(), None, None), P(None, b_ax(), None))
            )
        else:
            raise ValueError(kind)
    return {"pos": P(), "units": tuple(units_specs)}


def named(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def maybe_shard(x, *spec_entries):
    """with_sharding_constraint that degrades to a no-op when no mesh is
    active or the named axes aren't in the mesh (smoke tests, single device).

    Entries may be axis names, tuples of axis names, or None; names missing
    from the active mesh are dropped from the constraint.
    """
    mesh = get_abstract_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def keep(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    entries = [keep(e) for e in spec_entries]
    entries += [None] * (x.ndim - len(entries))
    return jax.lax.with_sharding_constraint(x, P(*entries))


def over_batch_shards(fn, args, batched):
    """``fn(*args)``, run once per device shard of the leading batch axis.

    XLA cannot partition a Pallas kernel: under an active mesh Mosaic
    refuses it ("Mosaic kernels cannot be automatically partitioned"), so
    the kernel dispatches go through ``jax.shard_map`` and each device runs
    the kernel on its own rows.  ``batched[i]`` says whether ``args[i]``
    leads with the batch axis (sharded) or is shared by every row
    (replicated, e.g. a broadcast mask); every output leads with the batch
    axis.  The batch shards over the ("pod", "data") mesh axes that divide
    it; when none does, every device runs the whole batch.  With no mesh
    active this is plain ``fn(*args)``.
    """
    mesh = get_abstract_mesh()
    if mesh is None:
        return fn(*args)
    batch = next(a.shape[0] for a, b in zip(args, batched) if b)
    spec = P(batch_axes(mesh, batch=batch) or None)
    in_specs = tuple(spec if b else P() for b in batched)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*args)
