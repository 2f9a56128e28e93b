"""Jit'd public wrappers for the streaming W1A8 3×3 conv kernels.

`w1a8_conv3x3` — conv + fused Mul_prev/Div/bias/round/clip epilogue.
`w1a8_conv3x3_pool` — the same conv with the 2×2 MaxPool fused into the
epilogue (the paper's §5.2 Post+MaxPool stage chain): the conv output never
round-trips through HBM, which is what lets the streaming serving path
(`serve.backends.DetectionBackend`) emit pooled uint8 rows directly.
Bit-exact vs conv-then-reduce_window (same per-row dot shapes, same
rounding, max commutes with the uint8 cast).

`w1a8_conv3x3_gemm` — a 3×3 conv at stride 1 or 2, with an optional
residual input fused into the epilogue, as an im2col view of the uint8
codes through the tiled W1A8 matmul kernel. The line-buffer kernels above
unpack a layer's whole sign matrix in every grid step and hold one image
row per step; the matmul kernel tiles K and N, so this route holds the
wide layers (9·Cin up to 9216, Cout up to 1024) and computes a stride-2
conv at its output pixels only.

Launch configuration (accum mode, row blocking, interpret, fused-vs-split
pool routing) comes from a `KernelConfig` (``config=``); the old per-call
kwargs survive one release behind a DeprecationWarning.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.packing import PACK, pack_signs
from repro.core.quant import fold_codes_to_uniform_step
from repro.kernels import config as _cfg
from repro.kernels.config import KernelConfig, _UNSET
from repro.kernels.w1a8_conv import kernel as _k
from repro.kernels.w1a8_conv import ref as _ref
from repro.kernels.w1a8_matmul import ops as _mm


def conv_pack_weights(w: jax.Array) -> jax.Array:
    """(3, 3, Cin, Cout) float → (ceil(9·Cin/32), Cout) uint32 sign words."""
    k9 = w.shape[0] * w.shape[1] * w.shape[2]
    return pack_signs(w.reshape(k9, w.shape[3]), axis=0)


def conv_mul9(mul_prev: jax.Array) -> jax.Array:
    """(Cin,) input-channel scales → (1, k9p) prologue vector (zeros pad K)."""
    m9 = jnp.tile(mul_prev.astype(jnp.float32), 9)
    k9 = m9.shape[0]
    k9p = (k9 + PACK - 1) // PACK * PACK
    return jnp.pad(m9, (0, k9p - k9)).reshape(1, k9p)


def w1a8_conv3x3(a_u8: jax.Array, w_packed: jax.Array, mul_prev: jax.Array,
                 div_post: jax.Array, bias: jax.Array, *, cin: int,
                 config: Optional[KernelConfig] = None,
                 out_step=_UNSET, accum=_UNSET, interpret=_UNSET,
                 use_kernel=_UNSET, name: Optional[str] = None) -> jax.Array:
    """Streaming 3×3 SAME conv on uint8 codes.

    a_u8 (B,H,W,Cin); w_packed (ceil(9Cin/32),Cout); mul_prev (Cin,);
    div_post/bias (Cout,). Returns (B,H,W,Cout) f32, or uint8 if
    config.out_step is set.

    config.accum="popcount" contracts in the binary domain (XNOR-popcount
    instead of unpack-then-dot). That path cannot apply a per-input-channel
    Mul_prev inside the bit-packed accumulation; a per-channel mul_prev is
    honoured by requantizing the codes onto the max step m̄ first
    (`core.quant.fold_codes_to_uniform_step`) and folding m̄ into
    Div_current: ``S·(div·m̄) + bias`` — the exact same f32 epilogue
    expression as the dot path with canonical ``(mul=1, div·m)`` operands.
    Under a uniform mul_prev the fold is a bit-exact identity, so the
    popcount-vs-dot bit-exactness contract holds; under per-channel steps
    it is an ≤½-LSB-per-channel approximation (the producer-side fold in
    ``models/yolo.py`` avoids even that by emitting uniform-step codes).

    ``name`` names the Pallas call (its custom call in a compiled program).
    """
    cfg = _cfg.normalize("conv3x3", config, out_step=out_step, accum=accum,
                         interpret=interpret, use_kernel=use_kernel)
    cfg = cfg.replace(interpret=cfg.resolved_interpret())
    return _w1a8_conv3x3(a_u8, w_packed, mul_prev, div_post, bias,
                         cin=cin, config=cfg, name=name)


@functools.partial(jax.jit, static_argnames=("cin", "config", "name"))
def _w1a8_conv3x3(a_u8, w_packed, mul_prev, div_post, bias, *, cin: int,
                  config: KernelConfig, name: Optional[str] = None
                  ) -> jax.Array:
    out_step = config.out_step
    if not config.use_kernel:
        return _ref.w1a8_conv3x3_ref(
            a_u8, w_packed, cin, mul_prev, div_post, bias,
            None if out_step is None else jnp.float32(out_step))
    mul9 = conv_mul9(mul_prev)
    k9p = mul9.shape[1]
    wp = w_packed
    if wp.shape[0] != k9p // PACK:
        wp = jnp.pad(wp, ((0, k9p // PACK - wp.shape[0]), (0, 0)))
    cout = wp.shape[1]
    dv = div_post.astype(jnp.float32).reshape(1, cout)
    if config.accum == "popcount":
        a_u8, mbar = fold_codes_to_uniform_step(a_u8, mul_prev)
        dv = dv * mbar
    a_pad = jnp.pad(a_u8, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return _k.w1a8_conv3x3_pallas(
        a_pad, wp, mul9, dv,
        bias.astype(jnp.float32).reshape(1, cout),
        out_step=out_step, accum=config.accum,
        rows=config.conv_rows(a_u8.shape[1]),
        interpret=config.interpret, name=name)


def w1a8_conv3x3_pool(a_u8: jax.Array, w_packed: jax.Array,
                      mul_prev: jax.Array, div_post: jax.Array,
                      bias: jax.Array, *, cin: int,
                      config: Optional[KernelConfig] = None,
                      out_step=_UNSET, interpret=_UNSET,
                      use_kernel=_UNSET,
                      name: Optional[str] = None) -> jax.Array:
    """Streaming 3×3 SAME conv + requant + 2×2 MaxPool.

    Same contract as `w1a8_conv3x3` with a quantizing epilogue, but H and W
    must be even and the output is the pooled (B, H/2, W/2, Cout) uint8
    code plane. config.fused=True (default) runs the single fused kernel
    (`fused_pool.w1a8_conv3x3_pool2`); config.fused=False runs the conv
    kernel then `reduce_window`. Both routes admit both accum modes and
    are bit-exact against each other (max commutes with the uint8 cast;
    the popcount contraction is integer-exact).
    """
    cfg = _cfg.normalize("conv3x3_pool", config, out_step=out_step,
                         interpret=interpret, use_kernel=use_kernel)
    cfg = cfg.replace(interpret=cfg.resolved_interpret())
    if cfg.out_step is None:
        cfg = cfg.replace(out_step=1.0)
    return _w1a8_conv3x3_pool(a_u8, w_packed, mul_prev, div_post, bias,
                              cin=cin, config=cfg, name=name)


@functools.partial(jax.jit, static_argnames=("cin", "config", "name"))
def _w1a8_conv3x3_pool(a_u8, w_packed, mul_prev, div_post, bias, *,
                       cin: int, config: KernelConfig,
                       name: Optional[str] = None) -> jax.Array:
    out_step = config.out_step
    if not config.use_kernel:
        out = _ref.w1a8_conv3x3_ref(a_u8, w_packed, cin, mul_prev, div_post,
                                    bias, jnp.float32(out_step))
        return jax.lax.reduce_window(out, jnp.uint8(0), jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    if not config.fused:
        out = _w1a8_conv3x3(a_u8, w_packed, mul_prev, div_post, bias,
                            cin=cin, config=config.replace(op="conv3x3"),
                            name=name)
        return jax.lax.reduce_window(out, jnp.uint8(0), jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    from repro.kernels.w1a8_conv.fused_pool import w1a8_conv3x3_pool2
    dv = div_post
    if config.accum == "popcount":
        a_u8, mbar = fold_codes_to_uniform_step(a_u8, mul_prev)
        dv = div_post.astype(jnp.float32) * mbar
    return w1a8_conv3x3_pool2(a_u8, w_packed, mul_prev, dv, bias,
                              cin=cin, out_step=out_step, accum=config.accum,
                              rows=config.conv_rows(a_u8.shape[1] // 2),
                              interpret=config.interpret, name=name)


def w1a8_conv3x3_gemm(a_u8: jax.Array, w_packed: jax.Array,
                      mul_prev: jax.Array, div_post: jax.Array,
                      bias: jax.Array, *, cin: int, stride: int = 1,
                      skip: Optional[jax.Array] = None,
                      skip_ratio: Optional[jax.Array] = None,
                      config: KernelConfig,
                      name: Optional[str] = None) -> jax.Array:
    """3×3 conv, one pixel of zero padding a side, at ``stride`` 1 or 2:
    a_u8 (B,H,W,Cin) uint8 → (B,Ho,Wo,Cout), Ho = (H-1)//stride + 1.

    The (B·Ho·Wo, 9·Cin) im2col view of the codes, in the kernel's
    (dy, dx, cin) order, goes through `w1a8_matmul` with ``config`` (op
    "matmul") against the same packed signs as `conv_pack_weights` makes;
    Mul_prev repeats per tap. ``skip``/``skip_ratio`` are the residual
    input of `w1a8_matmul`. Same contract as `_ref.w1a8_conv3x3_ref`.
    """
    if stride not in (1, 2):
        raise ValueError(f"stride must be 1 or 2, got {stride}")
    cols = _ref.im2col_3x3(a_u8, stride)
    return _mm.w1a8_matmul(cols, w_packed, jnp.tile(mul_prev, 9), div_post,
                           bias, k=9 * cin, config=config, skip=skip,
                           skip_ratio=skip_ratio, name=name)
