"""Pure-jnp oracle for the streaming Gram/moment accumulation kernel.

X [T, F], Y [T, C]  ->  G = XᵀX [F, F],  c = XᵀY [F, C], accumulated in f32.
``gram_ref_batched`` is the per-instance [B, ...] form.  Full-f32 matmuls:
a TPU's default precision would multiply the f32 operands in one bf16 pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jax.lax.Precision.HIGHEST


def gram_ref(x: jnp.ndarray, y: jnp.ndarray):
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    return (jnp.matmul(x32.T, x32, precision=_F32),
            jnp.matmul(x32.T, y32, precision=_F32))


def gram_ref_batched(x: jnp.ndarray, y: jnp.ndarray):
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    return (jnp.einsum("btf,btg->bfg", x32, x32, precision=_F32),
            jnp.einsum("btf,btc->bfc", x32, y32, precision=_F32))
