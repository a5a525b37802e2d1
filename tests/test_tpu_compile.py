"""Compile the Pallas kernels of the main path for a described TPU v5e.

Interpret mode, which is all the CPU runs, never shows the kernels to
Mosaic: a block that is not aligned to the tiling, or one that needs more
VMEM than the kernel may use, compiles there and is refused on the chip.
These tests compile each kernel ahead of time for a v5e topology that is
described, not attached, at the widths the chip runs (NARMA10's N = 900,
the batches ``auto_block_s`` tiles, a Gram at F = 901 padded to 1024), and
assert that the kernel reached Mosaic as a ``tpu_custom_call``.  The CMT
cavity's sweep kernel, with per-lane parameter tiles, is compiled at the
benchmark's 4096 lanes.  The GCV solve's QDWH ``eigh`` is compiled under a
4-device mesh the same way.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a file that loaded it
while being collected would make test workers collect different tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.analysis import mosaic_kernels
from repro.core import SiliconMR
from repro.kernels.dfr_scan import auto_block_s, padded_lanes
from repro.kernels.dfr_scan import ops as dfr_ops
from repro.kernels.dfr_scan.dfr_scan import dfr_scan_tiled
from repro.kernels.ridge_gram.ridge_gram import gram_tiled_batched_into

LANES = 128
K_PERIODS = 8          # periods per compiled call (the grid's K axis)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Mosaic-compiled kernel names of ``jit(fn)`` at ``args`` (shapes)."""
    return mosaic_kernels(jax.jit(fn).lower(*args).compile().as_text())


def _dfr_scan_kernels(sharding, n_nodes, batch, out_dtype, per_lane):
    block_s = auto_block_s(batch, out_dtype)
    s_total = padded_lanes(batch, block_s, out_dtype) // LANES

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    mask = sds((n_nodes, s_total, LANES) if per_lane else (n_nodes, 1))
    return _compile(
        lambda j, m, s0: dfr_scan_tiled(SiliconMR(), j, m, s0, block_s=block_s,
                                        out_dtype=out_dtype),
        sds((K_PERIODS, s_total, LANES)), mask, sds((n_nodes, s_total, LANES)))


@pytest.mark.parametrize("per_lane", [False, True], ids=["broadcast", "per_lane"])
@pytest.mark.parametrize("out_dtype", [None, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("batch", [512, 1024, 4096])
def test_dfr_scan_compiles_at_narma_width(one_chip, batch, out_dtype, per_lane):
    """N = 900 at every tile ``auto_block_s`` picks for these batches —
    including the (8, 128) f32 and (16, 128) bf16-output tiles that overran
    the default 16 MiB scoped VMEM."""
    kernels = _dfr_scan_kernels(one_chip, 900, batch, out_dtype, per_lane)
    assert kernels["dfr_scan"] == 1, kernels


@pytest.mark.parametrize("n_nodes", [30, 40], ids=["chan_eq", "santa_fe"])
def test_dfr_scan_compiles_at_small_widths(one_chip, n_nodes):
    """The paper's channel-equalization and Santa Fe widths."""
    kernels = _dfr_scan_kernels(one_chip, n_nodes, 512, None, False)
    assert kernels["dfr_scan"] == 1, kernels


def test_dfr_scan_with_parameter_tiles_compiles_at_sweep_width(one_chip):
    """The CMT cavity's scan with per-lane (detune, loss, power) tiles at
    the sweep cell's G = 4096 lanes and N = 30."""
    from repro.devices import CMTSweepParams, MRCavityCMT

    batch, n_nodes = 4096, 30
    block_s = auto_block_s(batch)
    s_total = padded_lanes(batch, block_s) // LANES

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    tile = sds((s_total, LANES))
    kernels = _compile(
        lambda j, m, s0, p: dfr_scan_tiled(MRCavityCMT(), j, m, s0,
                                           block_s=block_s, params=p),
        sds((K_PERIODS, s_total, LANES)), sds((n_nodes, 1)),
        sds((n_nodes, s_total, LANES)), CMTSweepParams(tile, tile, tile))
    assert kernels["dfr_scan"] == 1, kernels


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gram_fold_compiles_at_narma_width(one_chip, x_dtype):
    """The streaming fit's accumulate-into Gram at F = 901 padded to 1024,
    one 256-period chunk of 512 instances."""
    b, t, f = 512, 256, 1024

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kernels = _compile(
        lambda g, c, x, y: gram_tiled_batched_into(g, c, x, y, block_t=t,
                                                   block_f=128),
        sds((b, f, f)), sds((b, f, 1)), sds((b, t, f), x_dtype), sds((b, t, 1)))
    assert kernels["ridge_gram_into"] == 1, kernels


def test_dfr_scan_sharded_over_four_chips(topo):
    """Under a 4-device ("data",) mesh the kernel runs per device shard
    (parallel/sharding.over_batch_shards): without the shard_map XLA
    refuses to partition a Mosaic kernel."""
    from repro.compat import use_mesh
    from repro.parallel.sharding import over_batch_shards

    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data"))
    model, n_nodes, batch = SiliconMR(), 900, 512

    def scan(j, m, s0):
        return dfr_ops.dfr_scan(model, j, m, s0, interpret=False,
                                return_final=True)

    with use_mesh(mesh):
        compiled = jax.jit(
            lambda j, m, s0: over_batch_shards(scan, (j, m, s0),
                                               (True, False, True))
        ).lower(
            jax.ShapeDtypeStruct((batch, 64), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((n_nodes,), jnp.float32,
                                 sharding=NamedSharding(mesh, P())),
            jax.ShapeDtypeStruct((batch, n_nodes), jnp.float32, sharding=rows),
        ).compile()
    assert mosaic_kernels(compiled.as_text())["dfr_scan"] == 1
    for out in compiled.output_shardings:
        assert out.spec == P("data"), out
    # each device holds its quarter of the [B, K, N] states
    states_bytes = batch * 64 * n_nodes * 4
    assert compiled.memory_analysis().output_size_in_bytes < states_bytes / 2


def test_gcv_solve_sharded_over_four_chips(topo):
    """Under a 4-device ("data",) mesh the GCV solve's QDWH ``eigh`` (F >
    256) maps each device's own shard of the batch: the outputs stay
    sharded and no collective moves a Gram between devices.  F = 300 takes
    the path F = 901 takes, at a fraction of its compile time."""
    from repro.compat import use_mesh
    from repro.pipeline import ridge, scopes

    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data"))
    f, batch = 300, 8
    solve = jax.vmap(lambda g, c, y2: ridge.solve_gcv(g, c, y2, 1000, (1e-10, 1e-6, 1e-2)))
    with use_mesh(mesh):
        compiled = jax.jit(solve).lower(
            jax.ShapeDtypeStruct((batch, f, f), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((batch, f, 1), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=rows),
        ).compile()
    for out in compiled.output_shardings:
        assert out.spec == P("data"), out
    text = compiled.as_text()
    assert scopes.EIGH_SPLIT in text
    for collective in ("all-gather", "all-to-all", "collective-permute", "all-reduce"):
        assert collective not in text, collective

