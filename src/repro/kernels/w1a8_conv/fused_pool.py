"""Fused W1A8 conv3x3 + requant + 2×2 MaxPool — the paper's Post+MaxPool
pipeline stage (§5.2, Table 1 layers conv1–4, conv7) as one Pallas kernel.

Grid over (batch, pooled output row blocks): each step stages
``2·rows + 2`` input row-stripes (the line buffers for ``2·rows`` conv
rows, halo included), computes all conv rows with one contraction over a
(2·rows·W, K9p) im2col block — an MXU dot for ``accum="dot"``, bit-plane
AND+popcount (`_xnor_accumulate`) for ``accum="popcount"`` — applies the
Mul_prev/Div/bias/round/clip epilogue, and max-reduces 2×2 windows —
``rows`` pooled uint8 rows go to HBM per step. Activation traffic for a
pool layer drops from (write HW + read HW + write HW/4) to (write HW/4):
the conv output never exists in HBM, exactly like the RTL stage chain.
The popcount route never leaves the bit domain between line buffer and
pooled codes — conv, quantization post-processing and max pooling run as
one dataflow, which is the paper's whole §5.2 stage chain in one kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PACK
from repro.core.quant import requant_epilogue
from repro.kernels.w1a8_matmul.kernel import _unpack_tile, _xnor_accumulate
from repro.kernels.w1a8_conv.kernel import _im2col_rows


def _pool_epilogue(y, out_step, rows: int, w_out: int, o_ref):
    """Requant + 2×2 max of (2·rows·W, Cout) f32 → ``rows`` pooled rows.

    Each window corner is gathered by a 0/1 selection matmul: the codes are
    integers ≤ 255, exact in bf16, and every output sums exactly one of
    them, so the gather is exact and needs no strided slice or reshape."""
    codes = requant_epilogue(y, out_step, jnp.float32).astype(jnp.bfloat16)
    shape = (w_out // 2, 2 * w_out)
    q = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    sels = [(j == dy * w_out + 2 * q + dx).astype(jnp.bfloat16)
            for dy in range(2) for dx in range(2)]
    for p in range(rows):
        pair = codes[2 * p * w_out:(2 * p + 2) * w_out]      # two conv rows
        pooled = functools.reduce(jnp.maximum, [
            jnp.dot(sel, pair, preferred_element_type=jnp.float32)
            for sel in sels])
        o_ref[0, p] = pooled.astype(jnp.int32).astype(o_ref.dtype)


def _kernel(*refs, rows: int, w_out: int, k9p: int, cout: int,
            out_step: float, compute_dtype):
    nconv = 2 * rows
    line_rows = [r[0, 0] for r in refs[:nconv + 2]]
    wp_ref, m_ref, d_ref, b_ref, o_ref = refs[nconv + 2:]
    signs = _unpack_tile(wp_ref[...], k9p, cout, compute_dtype)
    cols = _im2col_rows(line_rows, nconv, w_out, k9p).astype(jnp.float32)
    am = (cols * m_ref[...].astype(jnp.float32)).astype(compute_dtype)
    y = jnp.dot(am, signs, preferred_element_type=jnp.float32)
    y = (y * d_ref[...].astype(jnp.float32)
         + b_ref[...].astype(jnp.float32))
    _pool_epilogue(y, out_step, rows, w_out, o_ref)


def _popcount_kernel(*refs, rows: int, w_out: int, k9p: int,
                     out_step: float):
    """Binary-domain fused conv+pool: the im2col codes stay 32-bit bit
    planes, contracted against the stored weight words with AND+popcount
    (the FPGA PE's XNOR tree); requant + 2×2 max fold into the same step.
    Uniform-Mul_prev contract: ops.py folds the scalar step into Div.
    """
    nconv = 2 * rows
    line_rows = [r[0, 0] for r in refs[:nconv + 2]]
    wp_ref, d_ref, b_ref, o_ref = refs[nconv + 2:]
    cols = _im2col_rows(line_rows, nconv, w_out, k9p)
    s = _xnor_accumulate(cols, wp_ref[...], k9p).astype(jnp.float32)
    y = s * d_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    _pool_epilogue(y, out_step, rows, w_out, o_ref)


def w1a8_conv3x3_pool2(a_u8: jax.Array, w_packed: jax.Array,
                       mul_prev: jax.Array, div_post: jax.Array,
                       bias: jax.Array, *, cin: int, out_step: float,
                       accum: str = "dot", rows: int = 1,
                       compute_dtype=jnp.bfloat16,
                       interpret: bool = False,
                       name: Optional[str] = None) -> jax.Array:
    """a_u8 (B,H,W,Cin) uint8 (H,W even) → (B,H/2,W/2,Cout) uint8 codes.

    ``rows`` pooled rows per grid step ((H/2) % rows == 0); bit-exact
    across rows choices — per-conv-row contraction operands are unchanged.

    accum="popcount" contracts in the binary domain (uniform-Mul_prev
    contract — caller folds the scalar step into div_post; mul_prev is
    used only for its K9p layout). The integer accumulation is exact and
    shares the dot path's f32 epilogue expression, so under canonical
    ``(mul=1, div·m)`` operands the two accum modes are bit-exact.
    """
    from repro.kernels.w1a8_conv.ops import conv_mul9
    b, h, w, _ = a_u8.shape
    a_pad = jnp.pad(a_u8, ((0, 0), (1, 1), (1, 1), (0, 0)))
    mul9 = conv_mul9(mul_prev)
    k9p = mul9.shape[1]
    wp = w_packed
    if wp.shape[0] != k9p // PACK:
        wp = jnp.pad(wp, ((0, k9p // PACK - wp.shape[0]), (0, 0)))
    cout = wp.shape[1]
    wp_ = w + 2
    assert accum in ("dot", "popcount"), accum
    assert (h // 2) % rows == 0, (h, rows)
    def row(dy):
        return pl.BlockSpec(
            (1, 1, wp_, cin),
            lambda bb, i, dy=dy: (bb, 2 * rows * i + dy, 0, 0))
    nconv = 2 * rows
    row_specs = [row(dy) for dy in range(nconv + 2)]
    row_ops = (a_pad,) * (nconv + 2)
    wspec = pl.BlockSpec((k9p // PACK, cout), lambda bb, i: (0, 0))
    cspec = pl.BlockSpec((1, cout), lambda bb, i: (0, 0))
    dv = div_post.astype(jnp.float32).reshape(1, cout)
    bs = bias.astype(jnp.float32).reshape(1, cout)
    if accum == "popcount":
        kernel = functools.partial(_popcount_kernel, rows=rows, w_out=w,
                                   k9p=k9p, out_step=out_step)
        in_specs = row_specs + [wspec, cspec, cspec]
        operands = row_ops + (wp, dv, bs)
    else:
        kernel = functools.partial(_kernel, rows=rows, w_out=w, k9p=k9p,
                                   cout=cout, out_step=out_step,
                                   compute_dtype=compute_dtype)
        in_specs = row_specs + [wspec,
                                pl.BlockSpec((1, k9p), lambda bb, i: (0, 0)),
                                cspec, cspec]
        operands = row_ops + (wp, mul9, dv, bs)
    return pl.pallas_call(
        kernel,
        grid=(b, (h // 2) // rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, w // 2, cout),
                               lambda bb, i: (bb, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h // 2, w // 2, cout), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*operands)
