"""Pallas TPU kernels of the hot path: ``dfr_scan`` (the reservoir node
chain, state resident in VMEM) and ``ridge_gram`` (the readout's Gram
accumulation on the MXU).

Both kernels compile with one scoped-VMEM limit, ``VMEM_LIMIT_BYTES``.
Mosaic's default scoped limit on a v5e is 16 MiB, which the reservoir
kernel outgrows at the paper's NARMA10 width: at N = 900 its [N, S, 128]
state blocks take ~17.6 MiB for an f32 (8, 128) tile and ~42 MiB (by the
VmemBudget estimate) for the bf16-output (16, 128) tile.  64 MiB is half of
a v5e core's 128 MiB of VMEM, which leaves Mosaic its internal scratch.
``repro.analysis.rules.VMEM_BYTES`` checks every ``pallas_call`` against
this same number.
"""

VMEM_LIMIT_BYTES = 64 * 2 ** 20
