"""Pallas TPU kernel: streaming W1A8 3×3 conv — the LineBuffer_3x3 analogue.

The paper's RTL streams rows through a padding adapter + 3-row line buffer so
each input row is fetched from external memory once (§5.2). The TPU-native
equivalent: grid over (batch, output row blocks); per step the BlockSpec
machinery stages ``rows + 2`` input row-stripes of the padded input — the
same array passed once per stripe with shifted index maps — into VMEM, forms
the 3×3 windows by in-register shifts, and contracts on the MXU against ±1
weights unpacked from 1-bit storage. ``rows`` (from `KernelConfig`, default
1) is the row-blocking factor: all ``rows`` output rows of a step share one
(rows·W, K9p) im2col block and one MXU dot, so larger rows amortise grid
overhead at the cost of a taller VMEM working set. Mul_prev prologue +
Div/bias/round/clip epilogue are fused exactly as in ``w1a8_matmul``.

HBM traffic per layer ≈ one read of the uint8 input + 1-bit weights + one
write of the uint8 output — the streaming-dataflow property, ported.
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


def _im2col_rows(line_rows, nrows: int, w_out: int, k9p: int):
    """Staged line buffers → (nrows·W, K9p) int32 im2col block in (dy, dx,
    cin) order — the "3x3 window former", one block row per output row.
    Each staged uint8 row is widened to int32 once, before any slicing:
    the TPU casts uint8 only to 32-bit integers, and slices those freely."""
    wide = [r.astype(jnp.int32) for r in line_rows]
    blocks = []
    for r in range(nrows):
        blocks.append(jnp.concatenate(
            [wide[r + dy][dx:dx + w_out, :]
             for dy in range(3) for dx in range(3)], axis=-1))  # (W, 9Cin)
    cols = blocks[0] if nrows == 1 else jnp.concatenate(blocks, axis=0)
    if cols.shape[1] < k9p:                                # K padding lanes
        cols = jnp.pad(cols, ((0, 0), (0, k9p - cols.shape[1])))
    return cols


def _store_rows(o_ref, y, out_step: Optional[float], rows: int, w_out: int):
    """Epilogue tail: (rows·W, Cout) f32 → ``rows`` output rows, requantized
    to uint8 codes when ``out_step`` is given."""
    if out_step is not None:
        y = requant_epilogue(y, out_step, o_ref.dtype)
    y = y.astype(o_ref.dtype)
    for r in range(rows):
        o_ref[0, r] = y[r * w_out:(r + 1) * w_out]


def _conv_kernel(*refs, rows: int, w_out: int, k9p: int, cout: int,
                 out_step: Optional[float], compute_dtype):
    line_rows = [r[0, 0] for r in refs[:rows + 2]]        # each (Wp, Cin)
    wp_ref, m_ref, d_ref, b_ref, o_ref = refs[rows + 2:]
    cols = _im2col_rows(line_rows, rows, w_out, k9p).astype(jnp.float32)
    am = (cols * m_ref[...].astype(jnp.float32)).astype(compute_dtype)
    signs = _unpack_tile(wp_ref[...], k9p, cout, compute_dtype)
    y = jnp.dot(am, signs, preferred_element_type=jnp.float32)
    y = y * d_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    _store_rows(o_ref, y, out_step, rows, w_out)


def _conv_popcount_kernel(*refs, rows: int, w_out: int, k9p: int,
                          out_step: Optional[float]):
    """Binary-domain conv rows: the im2col codes never leave the 1-bit/8-bit
    domain — bit-planes are packed to 32-bit words and contracted against
    the stored weight words with AND+popcount (the FPGA PE's XNOR tree).
    Uniform-Mul_prev contract: ops.py folds the scalar step into Div.
    """
    line_rows = [r[0, 0] for r in refs[:rows + 2]]
    wp_ref, d_ref, b_ref, o_ref = refs[rows + 2:]
    cols = _im2col_rows(line_rows, rows, w_out, k9p)
    s = _xnor_accumulate(cols, wp_ref[...], k9p).astype(jnp.float32)
    y = s * d_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    _store_rows(o_ref, y, out_step, rows, w_out)


def w1a8_conv3x3_pallas(a_pad: jax.Array, w_packed: jax.Array,
                        mul9: jax.Array, div_post: jax.Array,
                        bias: jax.Array, *, out_step: Optional[float] = None,
                        accum: str = "dot", rows: int = 1,
                        compute_dtype=jnp.bfloat16,
                        interpret: bool = False,
                        name: Optional[str] = None) -> jax.Array:
    """a_pad: (B, H+2, W+2, Cin) uint8 (SAME-padded, K-padding included in
    w/mul layout); w_packed: (K9p/32, Cout); mul9: (1, K9p) with zeros in
    padded lanes; div_post/bias: (1, Cout). Returns (B, H, W, Cout).

    ``rows`` output rows are produced per grid step (H % rows == 0); the
    result is bit-exact across rows choices — each output row's dot sees
    identical operands, only the launch grid changes.

    accum="popcount" contracts in the binary domain (uniform-Mul_prev
    contract — caller folds the scalar step into div_post and passes
    mul9 only for its K9p layout).
    """
    b, hp, wp_, cin = a_pad.shape
    h, w_out = hp - 2, wp_ - 2
    k9p = mul9.shape[1]
    cout = w_packed.shape[1]
    assert w_packed.shape[0] * PACK == k9p
    assert accum in ("dot", "popcount"), accum
    assert h % rows == 0, (h, rows)
    def row(dy):
        return pl.BlockSpec((1, 1, wp_, cin),
                            lambda bb, i, dy=dy: (bb, i * rows + dy, 0, 0))
    row_specs = [row(dy) for dy in range(rows + 2)]
    row_ops = (a_pad,) * (rows + 2)
    wspec = pl.BlockSpec((k9p // PACK, cout), lambda bb, i: (0, 0))
    cspec = pl.BlockSpec((1, cout), lambda bb, i: (0, 0))
    if accum == "popcount":
        kernel = functools.partial(_conv_popcount_kernel, rows=rows,
                                   w_out=w_out, k9p=k9p, out_step=out_step)
        in_specs = row_specs + [wspec, cspec, cspec]
        operands = row_ops + (w_packed, div_post, bias)
    else:
        kernel = functools.partial(_conv_kernel, rows=rows, w_out=w_out,
                                   k9p=k9p, cout=cout, out_step=out_step,
                                   compute_dtype=compute_dtype)
        in_specs = row_specs + [wspec,
                                pl.BlockSpec((1, k9p), lambda bb, i: (0, 0)),
                                cspec, cspec]
        operands = row_ops + (w_packed, mul9, div_post, bias)
    out_dtype = jnp.float32 if out_step is None else jnp.uint8
    return pl.pallas_call(
        kernel,
        grid=(b, h // rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, w_out, cout),
                               lambda bb, i: (bb, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w_out, cout), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*operands)
