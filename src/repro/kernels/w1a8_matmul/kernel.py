"""Pallas TPU kernel: bit-packed W1A8 matmul with fused scale split.

TPU adaptation of the paper's binary PE (§5.2):
  * weights live in HBM as 1 bit each (uint32 words, reduction-major) and are
    unpacked to ±1 *inside* the kernel's VMEM tiles — HBM weight traffic is
    1/16 of bf16 (the COE/BRAM-ROM streaming analogue),
  * ``Mul_prev`` (per-input-channel) is applied in the **prologue**, before
    the MXU contraction — Eq. 3-4's "compensation during accumulation",
  * ``Div_current``/bias/round/clip run in the **epilogue** on the final
    K-step, optionally emitting uint8 codes for the next layer (the paper's
    Post-process module, fused). A residual input (uint8 codes of the
    block's input, with their step over the output step) can be added
    there too, after the ReLU, as darknet's shortcut adds it.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"); f32 accumulation in a
VMEM scratch tile. MXU operands are bf16 (entries |m·a| ≤ 255·m exactly
representable errs <0.4%, validated vs. ref to corr>0.99999) or, in the
``exact`` path (uniform scale), int8 with the zero-point trick:
  Σ_k s·a = Σ_k s·(a−128) + 128·Σ_k s   (a−128 ∈ int8, exact int32 MXU).
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

DEF_BM, DEF_BK, DEF_BN = 256, 512, 256


def _unpack_tile(wp_tile: jax.Array, bk: int, bn: int, dtype) -> jax.Array:
    """(bk/32, bn) uint32 → (bk, bn) ±1 in `dtype`, in VMEM.

    The bits are tested as int32 and the ±1 values formed in f32: the TPU
    vector unit multiplies no 8-bit integers and selects no 16-bit values.
    """
    w = jax.lax.bitcast_convert_type(wp_tile, jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (bk // PACK, PACK, bn), 1)
    bits = (w[:, None, :] >> shifts) & 1
    signs = jnp.where(bits != 0, 1.0, -1.0).astype(jnp.float32)
    return signs.reshape(bk, bn).astype(dtype)


def _word_packers(kp: int):
    """(Kp, Kp/32) bf16 matrices that pack 0/1 lanes into 32-bit words.

    ``bits @ lo`` gives the low 16 bits of each word, ``bits @ hi`` the
    high 16: every entry is a power of two below 2^16 and every sum is
    below 2^16, so both products are exact in bf16 × bf16 → f32. The MXU
    does the packing because the vector unit cannot split a lane axis into
    (words, 32) in place."""
    k = jax.lax.broadcasted_iota(jnp.int32, (kp, kp // PACK), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (kp, kp // PACK), 1)
    t = k & (PACK - 1)
    own = (k >> 5) == j
    half = PACK // 2
    lo = jnp.where(own & (t < half), jnp.left_shift(1, t), 0)
    hi = jnp.where(own & (t >= half), jnp.left_shift(1, t - half), 0)
    return (lo.astype(jnp.float32).astype(jnp.bfloat16),
            hi.astype(jnp.float32).astype(jnp.bfloat16))


def _pack_act_bitplane(a_i32: jax.Array, bit: int, packers) -> jax.Array:
    """Bit-plane ``bit`` of uint8 codes held as int32 (M, Kp) → (M, Kp/32)
    int32 words, LSB first — the ``core.packing.pack_signs`` convention, so
    the words AND directly against the stored weight sign words."""
    lo_p, hi_p = packers
    bits = ((a_i32 >> bit) & 1).astype(jnp.float32).astype(jnp.bfloat16)
    lo = jnp.dot(bits, lo_p, preferred_element_type=jnp.float32)
    hi = jnp.dot(bits, hi_p, preferred_element_type=jnp.float32)
    return lo.astype(jnp.int32) | (hi.astype(jnp.int32) << (PACK // 2))


def _xnor_accumulate(a_i32: jax.Array, wp_tile: jax.Array,
                     kp: int) -> jax.Array:
    """Σ_k sign_k·a_k via XNOR-popcount on packed words — exact int32.

    a_i32: (M, Kp) uint8 codes held as int32; wp_tile: (Kp/32, N) uint32
    sign words (bit=1 ⇔ +1). FracBNN-style bit decomposition: a = Σ_b 2^b·a_b
    with a_b ∈ {0,1}, and for each binary plane
        Σ_k s_k·a_{b,k} = 2·popcount(w ∧ a_b) − popcount(a_b)
    so the whole inner product is bitwise AND + population_count — no
    unpack, no multiply. Zero codes contribute 0 to both terms, so K
    padding lanes (zero activations, +1 weight pad bits) are free. All
    words are int32: the TPU vector unit reduces signed integers only.
    """
    w = jax.lax.bitcast_convert_type(wp_tile, jnp.int32)
    packers = _word_packers(kp)
    acc = jnp.zeros((a_i32.shape[0], w.shape[1]), jnp.int32)
    for bit in range(8):
        words = _pack_act_bitplane(a_i32, bit, packers)     # (M, Kp/32)
        pc = jnp.zeros_like(acc)
        for j in range(kp // PACK):                         # (M,1) & (1,N)
            pc = pc + jax.lax.population_count(
                words[:, j:j + 1] & w[j:j + 1, :])
        cnt = jnp.sum(jax.lax.population_count(words), axis=1,
                      keepdims=True)                         # (M, 1)
        acc = acc + ((2 * pc - cnt) << bit)
    return acc


def _epilogue(acc, d_ref, b_ref, skip, o_ref, out_step: Optional[float]):
    """Div_current and bias, then, for a residual block's last conv, the
    shortcut: ReLU on the conv, plus the block input's codes times their
    step over the output step (``skip`` = (codes ref, ratio ref)); then
    round/clip to codes when ``out_step`` is given."""
    y = acc * d_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if skip is not None:
        s_ref, r_ref = skip
        y = (jnp.maximum(y, 0.0)
             + s_ref[...].astype(jnp.int32).astype(jnp.float32)
             * r_ref[...].astype(jnp.float32))
    if out_step is None:
        o_ref[...] = y.astype(o_ref.dtype)
    else:
        o_ref[...] = requant_epilogue(y, out_step, o_ref.dtype)


def _split_skip(refs, has_skip: bool):
    """(skip codes ref, ratio ref) or None, the output ref, the scratch."""
    if has_skip:
        return (refs[0], refs[1]), refs[2], refs[3]
    return None, refs[0], refs[1]


def _matmul_kernel(a_ref, wp_ref, m_ref, d_ref, b_ref, *refs, nk: int,
                   bk: int, bn: int, out_step: Optional[float],
                   compute_dtype, has_skip: bool):
    skip, o_ref, acc_ref = _split_skip(refs, has_skip)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Prologue: per-input-channel Mul_prev fused before the contraction.
    a = a_ref[...].astype(jnp.int32).astype(jnp.float32)   # uint8 → f32
    am = (a * m_ref[...].astype(jnp.float32)).astype(compute_dtype)
    signs = _unpack_tile(wp_ref[...], bk, bn, compute_dtype)
    acc_ref[...] += jnp.dot(am, signs, preferred_element_type=jnp.float32)

    # Epilogue on the last K step: Div_current, bias, (shortcut), (round,
    # clip).
    @pl.when(kk == nk - 1)
    def _fin():
        _epilogue(acc_ref[...], d_ref, b_ref, skip, o_ref, out_step)


def _popcount_matmul_kernel(a_ref, wp_ref, d_ref, b_ref, *refs, nk: int,
                            bk: int, out_step: Optional[float],
                            has_skip: bool):
    """XNOR-popcount accumulation (uniform-Mul_prev contract).

    No per-input-channel prologue is possible once the activations are bit
    packed, so this path requires a uniform input step; ops.py folds that
    scalar into Div_current so the epilogue expression — and hence the
    rounding — is identical to the dot path's.
    """
    skip, o_ref, acc_ref = _split_skip(refs, has_skip)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _xnor_accumulate(a_ref[...].astype(jnp.int32),
                                     wp_ref[...], bk)

    @pl.when(kk == nk - 1)
    def _fin():
        _epilogue(acc_ref[...].astype(jnp.float32), d_ref, b_ref, skip,
                  o_ref, out_step)


def _skip_operands(skip, skip_ratio, bm: int, bn: int) -> tuple:
    """In-specs and operands of the residual input: (M, N) uint8 codes
    tiled like the output, and its (1, N) step ratio."""
    if skip is None:
        return [], ()
    return ([pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
             pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))],
            (skip, skip_ratio))


def w1a8_matmul_popcount_pallas(a_u8: jax.Array, w_packed: jax.Array,
                                div_post: jax.Array, bias: jax.Array, *,
                                out_step: Optional[float] = None,
                                skip: Optional[jax.Array] = None,
                                skip_ratio: Optional[jax.Array] = None,
                                bm: int = DEF_BM, bk: int = DEF_BK,
                                bn: int = DEF_BN,
                                interpret: bool = False,
                                name: Optional[str] = None) -> jax.Array:
    """Binary-domain matmul: same shapes/epilogue as ``w1a8_matmul_pallas``
    minus the Mul_prev operand (already folded into ``div_post``)."""
    m, k = a_u8.shape
    n = w_packed.shape[1]
    assert k % bk == 0 and m % bm == 0 and n % bn == 0 and bk % PACK == 0
    nk = k // bk
    skip_specs, skip_ops = _skip_operands(skip, skip_ratio, bm, bn)
    kernel = functools.partial(_popcount_matmul_kernel, nk=nk, bk=bk,
                               out_step=out_step, has_skip=skip is not None)
    out_dtype = jnp.float32 if out_step is None else jnp.uint8
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // PACK, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ] + skip_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(a_u8, w_packed, div_post, bias, *skip_ops)


def w1a8_matmul_pallas(a_u8: jax.Array, w_packed: jax.Array,
                       mul_prev: jax.Array, div_post: jax.Array,
                       bias: jax.Array, *,
                       out_step: Optional[float] = None,
                       skip: Optional[jax.Array] = None,
                       skip_ratio: Optional[jax.Array] = None,
                       bm: int = DEF_BM, bk: int = DEF_BK, bn: int = DEF_BN,
                       compute_dtype=jnp.bfloat16,
                       interpret: bool = False,
                       name: Optional[str] = None) -> jax.Array:
    """Shapes (pre-padded to tile multiples by ops.py):
    a_u8 (M, K) uint8 · w_packed (K/32, N) uint32 · mul_prev (1, K) f32 ·
    div_post/bias (1, N) f32 → (M, N) f32, or uint8 codes when out_step given.
    ``skip`` (M, N) uint8 codes and ``skip_ratio`` (1, N) f32 add a residual
    input in the epilogue (see `_epilogue`).
    """
    m, k = a_u8.shape
    n = w_packed.shape[1]
    assert k % bk == 0 and m % bm == 0 and n % bn == 0 and bk % PACK == 0
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    skip_specs, skip_ops = _skip_operands(skip, skip_ratio, bm, bn)
    kernel = functools.partial(_matmul_kernel, nk=nk, bk=bk, bn=bn,
                               out_step=out_step, compute_dtype=compute_dtype,
                               has_skip=skip is not None)
    out_dtype = jnp.float32 if out_step is None else jnp.uint8
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // PACK, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bk), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ] + skip_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(a_u8, w_packed, mul_prev, div_post, bias, *skip_ops)


# ---------------------------------------------------------------------------
# Exact integer path (uniform input scale): int8 MXU + zero-point correction.
# ---------------------------------------------------------------------------

def _int_kernel(a_ref, wp_ref, cs_ref, o_ref, acc_ref, *, nk, bk, bn):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_c = (a_ref[...].astype(jnp.int32) - 128).astype(jnp.int8)
    signs = _unpack_tile(wp_ref[...], bk, bn, jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        a_c, signs, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(kk == nk - 1)
    def _fin():
        # zero-point correction: + 128 · Σ_k sign[k, n]  (colsum, precomputed)
        o_ref[...] = acc_ref[...] + 128 * cs_ref[...]


def w1a8_matmul_int_pallas(a_u8: jax.Array, w_packed: jax.Array,
                           colsum: jax.Array, *, bm: int = DEF_BM,
                           bk: int = DEF_BK, bn: int = DEF_BN,
                           interpret: bool = False) -> jax.Array:
    """Exact Σ_k s·a in int32. colsum: (1, N) int32 = Σ_k sign[k, n]."""
    m, k = a_u8.shape
    n = w_packed.shape[1]
    assert k % bk == 0 and m % bm == 0 and n % bn == 0
    nk = k // bk
    kernel = functools.partial(_int_kernel, nk=nk, bk=bk, bn=bn)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // PACK, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_u8, w_packed, colsum)
