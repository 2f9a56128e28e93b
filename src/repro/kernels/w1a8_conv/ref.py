"""Pure-jnp oracle for the W1A8 3×3 conv kernels (NHWC, one pixel of zero
padding on each side, stride 1 or 2 — darknet's ``pad=1``; at stride 2 an
even side halves), with an optional residual input added in the epilogue.

Weight layout: w (3, 3, Cin, Cout) flattened to (9·Cin, Cout) in
(dy, dx, cin) order, matching the kernel's im2col concat order.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core import packing
from repro.core.quant import ACT_QMAX, round_half_away


def im2col_3x3(x: jnp.ndarray, stride: int = 1) -> jnp.ndarray:
    """(B, H, W, C) → (B, Ho, Wo, 9C) patches, one pixel of zero padding
    on each side, (dy,dx,c) order; Ho = (H - 1) // stride + 1."""
    b, h, w, c = x.shape
    ho, wo = (h - 1) // stride + 1, (w - 1) // stride + 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = [xp[:, dy:dy + stride * (ho - 1) + 1:stride,
               dx:dx + stride * (wo - 1) + 1:stride, :]
            for dy in range(3) for dx in range(3)]
    return jnp.concatenate(cols, axis=-1)


def w1a8_conv3x3_ref(a_u8: jnp.ndarray, w_packed: jnp.ndarray, cin: int,
                     mul_prev: jnp.ndarray, div_post: jnp.ndarray,
                     bias: jnp.ndarray,
                     out_step: Optional[jnp.ndarray] = None, *,
                     stride: int = 1, skip: Optional[jnp.ndarray] = None,
                     skip_ratio: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """a_u8 (B,H,W,Cin) uint8 codes; w_packed (ceil(9Cin/32), Cout) uint32;
    mul_prev (Cin,); div_post/bias (Cout,). ``skip`` (B,Ho,Wo,Cout) uint8
    codes and ``skip_ratio`` (Cout,) add a residual input after the ReLU:
    y ← max(y, 0) + skip · skip_ratio."""
    k = 9 * cin
    signs = packing.unpack_signs(w_packed, k, axis=0, dtype=jnp.float32)
    cols = im2col_3x3(a_u8.astype(jnp.float32), stride)   # (B,Ho,Wo,9Cin)
    m9 = jnp.tile(mul_prev.astype(jnp.float32), 9)
    y = (cols * m9) @ signs
    y = y * div_post + bias
    if skip is not None:
        y = jnp.maximum(y, 0.0) + skip.astype(jnp.float32) * skip_ratio
    if out_step is None:
        return y
    return jnp.clip(round_half_away(y / out_step), 0, ACT_QMAX).astype(jnp.uint8)
