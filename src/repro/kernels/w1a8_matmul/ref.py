"""Pure-jnp oracle for the W1A8 packed matmul kernel.

Semantics (paper Eqs. 3-2/3-4 + §3.2 post-processing):
    y[m, n] = (Σ_k sign[k, n] · (mul_prev[k] · a[m, k])) · div_post[n] + bias[n]
optionally requantized to uint8 codes with step ``out_step``:
    q[m, n] = clip(round(y / out_step), 0, 255),
where a residual input (``skip`` codes, ``skip_ratio`` = their step over
``out_step``) is added first, after the ReLU:
    y ← max(y, 0) + skip[m, n] · skip_ratio[n].
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import packing
from repro.core.quant import ACT_QMAX, round_half_away


def w1a8_matmul_ref(a_u8: jnp.ndarray, w_packed: jnp.ndarray, k: int,
                    mul_prev: jnp.ndarray, div_post: jnp.ndarray,
                    bias: jnp.ndarray,
                    out_step: Optional[jnp.ndarray] = None, *,
                    skip: Optional[jnp.ndarray] = None,
                    skip_ratio: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    signs = packing.unpack_signs(w_packed, k, axis=0, dtype=jnp.float32)
    am = a_u8.astype(jnp.float32) * mul_prev.astype(jnp.float32)
    y = am @ signs
    y = y * div_post + bias
    if skip is not None:
        y = jnp.maximum(y, 0.0) + skip.astype(jnp.float32) * skip_ratio
    if out_step is None:
        return y
    q = jnp.clip(round_half_away(y / out_step), 0, ACT_QMAX)
    return q.astype(jnp.uint8)
