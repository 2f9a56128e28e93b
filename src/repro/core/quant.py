"""W1A8 quantization primitives (paper §3.2, Eqs. 3-1..3-4).

Weights:      w_b = sign(w) ∈ {-1,+1}, straight-through estimator in training.
Activations:  q_a = clip(round(x / s_a), 0, 255)  (LSQ — learned step size).

The inference graph carries two channel-indexed scales:
  Mul_prev    — indexed by *input* channel  (previous layer's dequant step)
  Div_current — indexed by *output* channel (current layer's quant step)
Fusing them into one constant would collapse per-input-channel information;
the paper fuses Mul_prev into the accumulation (Eq. 3-4) and applies
Div_current in the post-processing epilogue. `core/w1a8.py` and the Pallas
kernels preserve exactly that split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ACT_QMAX = 255  # uint8 activations, ReLU-style non-negative range [0, 255]


# ---------------------------------------------------------------------------
# Eq. 3-1: weight binarization with STE
# ---------------------------------------------------------------------------

def binarize_weight(w: jax.Array) -> jax.Array:
    """sign(w) ∈ {-1,+1} (0 maps to +1, matching RTL sign-bit convention)."""
    return jnp.where(w >= 0, 1.0, -1.0).astype(w.dtype)


@jax.custom_vjp
def binarize_ste(w: jax.Array) -> jax.Array:
    """Binarize with straight-through estimator, clipped to |w|<=1 region.

    Forward: sign(w).  Backward: dL/dw = dL/dw_b * 1[|w| <= 1]
    (the standard BNN/XNOR-Net STE with saturation clipping).
    """
    return binarize_weight(w)


def _binarize_fwd(w):
    return binarize_weight(w), w


def _binarize_bwd(w, g):
    return (g * (jnp.abs(w) <= 1.0).astype(g.dtype),)


binarize_ste.defvjp(_binarize_fwd, _binarize_bwd)


# ---------------------------------------------------------------------------
# Eq. 3-3: LSQ activation quantization (uint8, non-negative)
# ---------------------------------------------------------------------------

def round_half_away(x: jax.Array) -> jax.Array:
    """Round half away from zero — matches the paper's RTL rounding."""
    return jnp.trunc(x + jnp.where(x >= 0, 0.5, -0.5))


def quantize_act(x: jax.Array, step: jax.Array) -> jax.Array:
    """q = clip(round(x / s), 0, 255) → uint8-valued float (dtype preserved)."""
    return jnp.clip(round_half_away(x / step), 0, ACT_QMAX)


def dequantize_act(q: jax.Array, step: jax.Array) -> jax.Array:
    return q * step


@jax.custom_vjp
def lsq_fake_quant(x: jax.Array, step: jax.Array, grad_scale: jax.Array):
    """LSQ fake-quantization: forward quant-dequant; backward trains `step`.

    Gradients follow Esser et al. (ICLR 2020):
      d q̂/d s = (q - x/s) inside the range, {0, QMAX} at the clip rails,
      scaled by grad_scale = 1/sqrt(numel * QMAX).
    d q̂/d x = 1 inside the range, 0 outside (STE with clipping).
    """
    return dequantize_act(quantize_act(x, step), step)


def _lsq_fwd(x, step, grad_scale):
    return lsq_fake_quant(x, step, grad_scale), (x, step, grad_scale)


def _reduce_to_shape(g: jax.Array, shape) -> jax.Array:
    """Sum-reduce ``g`` down to broadcast shape ``shape`` (per-channel steps)."""
    if g.shape == tuple(shape):
        return g
    ndiff = g.ndim - len(shape)
    axes = tuple(range(ndiff)) + tuple(
        i + ndiff for i, s in enumerate(shape) if s == 1 and g.shape[i + ndiff] != 1)
    return jnp.sum(g, axis=axes).reshape(shape)


def _lsq_bwd(res, g):
    x, step, grad_scale = res
    xs = x / step
    q = jnp.clip(round_half_away(xs), 0, ACT_QMAX)
    in_range = (xs >= 0) & (xs <= ACT_QMAX)
    dx = g * in_range.astype(g.dtype)
    # In-range: d(q̂)/ds = q - x/s.  At the rails: q̂ = rail*s so d/ds = rail (= q).
    dstep_elem = jnp.where(in_range, q - xs, q)
    dstep = _reduce_to_shape(g * dstep_elem, step.shape) * grad_scale
    return dx, dstep.astype(step.dtype), None


lsq_fake_quant.defvjp(_lsq_fwd, _lsq_bwd)


def lsq_grad_scale(numel: int) -> float:
    """LSQ gradient scale g = 1/sqrt(N * Q_max).

    Pure-Python math: this runs inside traced scan bodies where any jnp op
    would be staged (omnistaging) and poison the static value.
    """
    return float(numel * ACT_QMAX) ** -0.5


def init_step_from_batch(x: jax.Array) -> jax.Array:
    """LSQ init: s0 = 2*mean(|x|)/sqrt(QMAX)."""
    return 2.0 * jnp.mean(jnp.abs(x)) / jnp.sqrt(jnp.asarray(ACT_QMAX, x.dtype))


def requant_epilogue(y: jax.Array, out_step: float,
                     out_dtype=jnp.uint8) -> jax.Array:
    """Requantize an f32 post-scale accumulator to next-layer uint8 codes.

    q = clip(round_half_away(y / s_out), 0, 255) — the Eq. 3-3 epilogue the
    conv, fused conv+pool, and matmul kernels all apply after Div_current
    and bias. One definition so the three paths cannot drift in rounding.
    """
    q = jnp.clip(round_half_away(y / out_step), 0, ACT_QMAX)
    if jnp.issubdtype(out_dtype, jnp.integer):
        q = q.astype(jnp.int32)        # the TPU has no direct f32 → u8 cast
    return q.astype(out_dtype)


def fold_codes_to_uniform_step(a_u8: jax.Array,
                               mul_prev: jax.Array) -> tuple:
    """(codes, per-input-channel steps) → (codes', uniform scalar step m̄).

    The XNOR-popcount accumulation contracts bit planes against packed
    sign words — a per-input-channel Mul_prev cannot ride inside the
    bit-packed tree (Σ_k s_k·m_k·a_k does not factor out of the popcount).
    Instead the codes are requantized onto the coarsest channel's grid,
    m̄ = max_k m_k:

        a'_k = clip(round(a_k · m_k / m̄), 0, 255),   value ≈ a'_k · m̄

    and the single m̄ folds into Div_current exactly like the RTL's
    scale-into-the-accumulator discipline. No clipping ever engages
    (m_k/m̄ ≤ 1), and when the steps are already uniform the ratio is
    exactly 1.0 in IEEE arithmetic, so the fold is a bit-exact identity —
    preserving the popcount-vs-dot bit-exactness contract. ``mul_prev``
    broadcasts against the trailing axis of ``a_u8``.
    """
    m = mul_prev.astype(jnp.float32)
    mbar = jnp.maximum(jnp.max(m), 1e-20)
    codes = jnp.clip(round_half_away(a_u8.astype(jnp.float32) * (m / mbar)),
                     0, ACT_QMAX).astype(jnp.uint8)
    return codes, mbar


# ---------------------------------------------------------------------------
# Eq. 3-2 / 3-4: sign-controlled accumulation (reference semantics)
# ---------------------------------------------------------------------------

def sign_accumulate(acts: jax.Array, signs: jax.Array) -> jax.Array:
    """y_o = Σ_i s_{o,i} a_i  — reference for the binary PE.

    acts:  (..., K) uint8-valued; signs: (K, N) ∈ {-1,+1}.
    Integer-exact when inputs are integers carried in int32.
    """
    return acts @ signs


def sign_accumulate_fused(acts: jax.Array, mul_prev: jax.Array,
                          signs: jax.Array) -> jax.Array:
    """Eq. 3-4: y_o = Σ_i s_{o,i} (m_i a_i) — Mul_prev fused into the PE."""
    return (acts * mul_prev) @ signs
