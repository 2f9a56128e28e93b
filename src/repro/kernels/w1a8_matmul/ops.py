"""Jit'd public wrapper for the W1A8 packed matmul kernel.

Handles batching (leading dims folded into M), padding to tile multiples
(zero activations × zero mul_prev ⇒ padded K contributes exactly 0), tile
resolution through `KernelConfig` (explicit bm/bk/bn or the heuristic
auto-shrink), and CPU fallback (interpret mode / jnp ref).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.packing import PACK, pack_signs
from repro.core.quant import fold_codes_to_uniform_step
from repro.kernels import config as _cfg
from repro.kernels.config import KernelConfig, _UNSET, _round_up
from repro.kernels.w1a8_matmul import kernel as _k
from repro.kernels.w1a8_matmul import ref as _ref


def w1a8_matmul(a_u8: jax.Array, w_packed: jax.Array, mul_prev: jax.Array,
                div_post: jax.Array, bias: jax.Array, *, k: int,
                config: Optional[KernelConfig] = None,
                skip: Optional[jax.Array] = None,
                skip_ratio: Optional[jax.Array] = None,
                out_step=_UNSET, accum=_UNSET, interpret=_UNSET,
                use_kernel=_UNSET, name: Optional[str] = None) -> jax.Array:
    """y = ((a ⊙ mul_prev) @ unpack(w_packed)) ⊙ div_post + bias  [+ requant].

    With ``skip`` ((..., N) uint8 codes of a residual block's input) and
    ``skip_ratio`` ((N,) f32, their step over the output step) the epilogue
    adds the shortcut after the ReLU, as darknet does:
    y = max(y, 0) + skip ⊙ skip_ratio, then the requant (config.out_step
    must be set).

    a_u8: (..., K) uint8 codes; w_packed: (ceil(K/32), N) uint32;
    mul_prev: (K,) f32; div_post, bias: (N,) f32.

    Launch configuration comes from ``config=`` (a `KernelConfig`, op
    "matmul"); the old per-call kwargs survive one release behind a
    DeprecationWarning. config.accum="popcount": XNOR-popcount contraction.
    A per-channel mul_prev is honoured by requantizing the codes onto the
    max step m̄ (`core.quant.fold_codes_to_uniform_step`), which then folds
    into div_post; under a uniform mul_prev the fold is a bit-exact
    identity, so the epilogue — and the rounding — matches the dot path
    bit for bit. ``name`` names the Pallas call.
    """
    cfg = _cfg.normalize("matmul", config, out_step=out_step, accum=accum,
                         interpret=interpret, use_kernel=use_kernel)
    cfg = cfg.replace(interpret=cfg.resolved_interpret())
    if skip is not None and cfg.out_step is None:
        raise ValueError("a residual input needs a quantizing epilogue "
                         "(config.out_step)")
    return _w1a8_matmul(a_u8, w_packed, mul_prev, div_post, bias, skip,
                        skip_ratio, k=k, config=cfg, name=name)


@functools.partial(jax.jit, static_argnames=("k", "config", "name"))
def _w1a8_matmul(a_u8, w_packed, mul_prev, div_post, bias, skip=None,
                 skip_ratio=None, *, k: int, config: KernelConfig,
                 name: Optional[str] = None) -> jax.Array:
    out_step = config.out_step
    if not config.use_kernel:
        y = _ref.w1a8_matmul_ref(a_u8, w_packed, k, mul_prev, div_post, bias,
                                 None if out_step is None else jnp.float32(out_step),
                                 skip=skip, skip_ratio=skip_ratio)
        return y

    lead = a_u8.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    n = w_packed.shape[1]
    a2 = a_u8.reshape(m, a_u8.shape[-1])

    bm, bk, bn = config.matmul_tiles(m, k, n)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)

    a2 = jnp.pad(a2[:, :k], ((0, mp - m), (0, kp - k)))
    mul = jnp.pad(mul_prev.astype(jnp.float32), (0, kp - k)).reshape(1, kp)
    wp = w_packed
    if kp // PACK != wp.shape[0] or np_ != n:
        wp = jnp.pad(wp, ((0, kp // PACK - wp.shape[0]), (0, np_ - n)))
    dv = jnp.pad(div_post.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    bs = jnp.pad(bias.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    res = {}
    if skip is not None:
        res = {"skip": jnp.pad(skip.reshape(m, n), ((0, mp - m), (0, np_ - n))),
               "skip_ratio": jnp.pad(skip_ratio.astype(jnp.float32),
                                     (0, np_ - n)).reshape(1, np_)}

    if config.accum == "popcount":
        # zero-padded K lanes carry zero codes (ratio 0 · zero pad) and
        # contribute 0 to popcount on their own — no mul operand needed,
        # the uniformized m̄ folds into Div_current.
        a2, mbar = fold_codes_to_uniform_step(a2, mul.reshape(-1))
        dv = dv * mbar
        y = _k.w1a8_matmul_popcount_pallas(a2, wp, dv, bs, out_step=out_step,
                                           bm=bm, bk=bk, bn=bn,
                                           interpret=config.interpret,
                                           name=name, **res)
    else:
        y = _k.w1a8_matmul_pallas(a2, wp, mul, dv, bs, out_step=out_step,
                                  bm=bm, bk=bk, bn=bn,
                                  interpret=config.interpret, name=name,
                                  **res)
    return y[:m, :n].reshape(lead + (n,))


def w1a8_pack_weights(w: jax.Array) -> jax.Array:
    """(K, N) float → (ceil(K/32), N) uint32 sign words (deploy-time)."""
    return pack_signs(w, axis=0)
