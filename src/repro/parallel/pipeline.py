"""GPipe-style pipeline parallelism over shard_map + collective_permute.

An alternative distribution strategy for depth-dominated models: layers are
split into S contiguous stages laid out along a mesh axis; M microbatches
stream through, each device running its stage function and handing
activations to the next stage with ``jax.lax.ppermute``.

Schedule: the classic GPipe loop of T = M + S − 1 ticks.  At tick t, stage s
processes microbatch (t − s) when 0 ≤ t − s < M.  Bubble fraction
(S − 1)/T; utilisation is driven by M/S as usual.  All stages execute the
same program (SPMD), with ``jnp.where`` masking the warm-up/drain ticks.

Used by tests (tests/test_pipeline.py validates vs the unpipelined
reference) and available as strategy="pp" building block; the default
dry-run strategies are FSDP×TP (DESIGN.md §5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn,           # (stage_params, x [mb, ...]) -> [mb, ...]
    stacked_params,     # pytree, leaves [S, ...] — one slice per stage
    x,                  # [M, mb, ...] microbatched input
    *,
    mesh,
    axis: str = "stage",
):
    """Run x through S pipeline stages with a GPipe schedule.

    Returns [M, mb, ...] outputs (equal to folding stage_fn over stages for
    each microbatch).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def per_stage(params_slice, x_all):
        # params_slice: this stage's params (leaves [1, ...] -> squeeze);
        # x_all: [M, mb, ...] full input (only stage 0 actually consumes it).
        params_local = jax.tree.map(lambda a: a[0], params_slice)
        stage_id = jax.lax.axis_index(axis)

        mb_shape = x_all.shape[1:]
        buf = jnp.zeros(mb_shape, x_all.dtype)          # current activation
        outputs = jnp.zeros_like(x_all)                 # stage S-1 collects

        def tick(t, carry):
            buf, outputs = carry
            micro_idx = t - stage_id
            active = (micro_idx >= 0) & (micro_idx < n_micro)
            # Stage 0 ingests microbatch t; others use the permuted buffer.
            feed = jnp.where(
                stage_id == 0,
                x_all[jnp.clip(t, 0, n_micro - 1)],
                buf,
            )
            y = stage_fn(params_local, feed)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # Last stage writes its finished microbatch to the output slot.
            write_idx = jnp.clip(micro_idx, 0, n_micro - 1)
            is_last = stage_id == n_stages - 1
            outputs = jax.lax.cond(
                active & is_last,
                lambda o: o.at[write_idx].set(y),
                lambda o: o,
                outputs,
            )
            # Hand activations forward (ring; the wrap-around link is unused
            # because stage 0 always feeds from x_all).
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return buf, outputs

        _, outputs = jax.lax.fori_loop(0, n_micro + n_stages - 1, tick, (buf, outputs))
        # Only stage S-1 holds real outputs; broadcast them to all stages.
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    spec_params = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, x)


def make_stage_mesh(n_stages: int):
    devs = jax.devices()[:n_stages]
    import numpy as np

    return jax.sharding.Mesh(np.array(devs), ("stage",))
