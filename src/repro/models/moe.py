"""Top-k dropping MoE with expert-parallel all-to-all dispatch.

Production path (DeepSpeed-MoE/Switch style, TPU-native):
  experts sharded over the `ep` mesh axis, expert-FFN hidden over `tp`;
  tokens are sorted by destination expert, packed into a static
  (ep, E_local, C, D) buffer, exchanged with `lax.all_to_all`, processed as
  grouped GEMMs, exchanged back, and combined with router gates. Capacity
  C = ceil(T_local · k / E · cf) bounds the buffers (dropped tokens pass
  through with gate 0 — standard dropping semantics).

Single-device path: identical math with the a2a as identity (ep=1), used by
smoke tests; the shard_map wiring lives in repro/dist/sharding.py.

W1A8: expert weights are (E, K, N) stacks; in QAT mode they binarize with
sign-STE exactly like dense layers (per-expert α) — for kimi-k2 this is the
headline 1-bit-expert capacity win (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import binarize_ste, lsq_fake_quant, lsq_grad_scale
from repro.models.layers import ModelConfig, _act


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = 1.0 / math.sqrt(d)
    p = {
        "router": jax.random.normal(ks[0], (d, e), dtype) * s,
        "up": jax.random.normal(ks[1], (e, d, f), dtype) * s,
        "gate": jax.random.normal(ks[2], (e, d, f), dtype) * s,
        "down": jax.random.normal(ks[3], (e, f, d), dtype) / math.sqrt(f),
    }
    if cfg.w1a8_body:
        p["act_step"] = jnp.full((), 0.05, dtype)
    if cfg.shared_experts:
        fs = f * cfg.shared_experts
        p["shared_up"] = jax.random.normal(ks[4], (d, fs), dtype) * s
        p["shared_gate"] = jax.random.normal(
            jax.random.fold_in(ks[4], 1), (d, fs), dtype) * s
        p["shared_down"] = jax.random.normal(
            jax.random.fold_in(ks[4], 2), (fs, d), dtype) / math.sqrt(fs)
    return p


def _expert_mm(p: dict, name: str, x: jax.Array, mode: str,
               mean_axis: Optional[str] = None) -> jax.Array:
    """Grouped GEMM (E, T, K) @ (E, K, N), W1A8 QAT / packed-deploy aware.

    mean_axis: mesh axis the contraction (K) dim is TP-sliced over — the
    XNOR α = mean_K|w| must then be pmean'd to equal the global mean
    (down-proj under TP-in-expert).
    """
    act_step = p.get("act_step")
    if name + "_packed" in p:                     # deployed 1-bit experts
        from repro.core.packing import unpack_signs
        from repro.core.quant import quantize_act
        signs = unpack_signs(p[name + "_packed"], x.shape[-1], axis=-2,
                             dtype=x.dtype)
        step = act_step.astype(x.dtype)
        xq = quantize_act(x, step) * step
        return jnp.einsum("etk,ekn->etn", xq, signs) \
            * p[name + "_alpha"].astype(x.dtype)
    w = p[name]
    if act_step is not None and mode != "float":
        gs = lsq_grad_scale(max(x.size // max(x.shape[-1], 1), 1))
        x = lsq_fake_quant(x, act_step, jnp.asarray(gs, x.dtype))
        wb = binarize_ste(w)
        alpha = jnp.mean(jnp.abs(w), axis=1, keepdims=True)
        if mean_axis:
            alpha = jax.lax.pmean(alpha, mean_axis)
        alpha = jax.lax.stop_gradient(alpha)
        return jnp.einsum("etk,ekn->etn", x, wb.astype(x.dtype)) \
            * alpha.astype(x.dtype)
    return jnp.einsum("etk,ekn->etn", x, w.astype(x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _a2a_u8(x_and_step, axis: str):
    """uint8-wire all_to_all of activation codes (W1A8 theme → collectives).

    Forward: quantize to uint8 codes against `step`, exchange 1-byte payload
    (4× less ICI traffic than f32, 2× less than bf16), dequantize.
    Backward: plain a2a of the cotangent (a2a is a permutation) with STE
    through the quantizer.
    """
    x, step = x_and_step
    from repro.core.quant import quantize_act
    codes = quantize_act(x, step).astype(jnp.uint8)
    codes = jax.lax.all_to_all(codes, axis, split_axis=0, concat_axis=0)
    return codes.astype(x.dtype) * step


def _a2a_u8_fwd(x_and_step, axis):
    return _a2a_u8(x_and_step, axis), None


def _a2a_u8_bwd(axis, _, ct):
    return ((jax.lax.all_to_all(ct, axis, split_axis=0, concat_axis=0),
             jnp.zeros((), ct.dtype)),)


_a2a_u8.defvjp(_a2a_u8_fwd, _a2a_u8_bwd)


@dataclasses.dataclass(frozen=True)
class MoEDispatch:
    """Static dispatch plan for one MoE call."""
    num_experts: int
    top_k: int
    capacity: int       # per-expert, per source shard
    ep: int             # expert-parallel degree (1 = single shard)


def plan_dispatch(cfg: ModelConfig, tokens_local: int, ep: int) -> MoEDispatch:
    """NOTE: capacity dropping means outputs depend on batch composition —
    a 12-token prefill and the same 12 tokens inside a longer batch may
    drop differently (standard Switch/dropping semantics). For strict
    decode≡forward determinism set capacity_factor ≥ num_experts
    (mathematical no-drop bound: cap ≥ T·k), as the reduced test configs do.
    """
    cap = max(1, math.ceil(tokens_local * cfg.top_k * cfg.capacity_factor
                           / cfg.num_experts))
    cap = min(cap, tokens_local * cfg.top_k)      # no point beyond T·k
    # pad capacity to an MXU-friendly multiple where it matters
    cap = max(8, -(-cap // 8) * 8)
    return MoEDispatch(cfg.num_experts, cfg.top_k, cap, ep)


def moe_ffn(p: dict, cfg: ModelConfig, x: jax.Array, *, mode: str,
            ep_axis: Optional[str] = None,
            tp_axis: Optional[str] = None,
            shared_tp: Optional[str] = None,
            a2a_quant: bool = False) -> jax.Array:
    """x: (T_local, D) tokens on this shard → (T_local, D).

    When `ep_axis` is set (inside shard_map), experts are sharded over that
    axis and tokens are exchanged with all_to_all; otherwise all experts are
    local (ep=1) and the same code runs without collectives. When `tp_axis`
    is set, expert FFN hidden dims are sharded over it and the down-proj is
    psum-reduced (TP within expert).
    """
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    disp = plan_dispatch(cfg, t, ep)
    cap, e_local = disp.capacity, e // ep

    # --- routing -----------------------------------------------------------
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    gates, idx = jax.lax.top_k(logits, k)                    # (T, k)
    gates = jax.nn.softmax(gates, axis=-1).astype(x.dtype)

    # --- pack: order assignments by expert, keep first `cap` per expert ----
    flat_e = idx.reshape(-1)                                  # (T·k,)
    order = jnp.argsort(flat_e)                               # stable
    sorted_e = flat_e[order]
    # rank of each assignment within its expert
    pos_in_e = jnp.arange(t * k) - jnp.searchsorted(sorted_e, sorted_e,
                                                    side="left")
    keep = pos_in_e < cap
    src_tok = order // k                                      # token index
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[sorted_e, pos_in_e].add(
        jnp.where(keep[:, None], x[src_tok], 0))

    # --- all_to_all to expert shards ---------------------------------------
    if ep_axis:
        buf = buf.reshape(ep, e_local, cap, d)
        if a2a_quant and "act_step" in p:
            # W1A8 dispatch: ship uint8 codes (the experts re-quantize with
            # the same step anyway, so this is ~lossless — §Perf cell B)
            buf = _a2a_u8((buf, p["act_step"]), ep_axis)
        else:
            buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
        buf = buf.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)
    else:
        buf = buf.reshape(e_local, cap, d)

    # --- expert computation (grouped GEMM, W1A8-aware, TP over tp_axis) ----
    up = _expert_mm(p, "up", buf, mode)
    gate = _expert_mm(p, "gate", buf, mode)
    h = up * _act(cfg.act_fn)(gate)
    out = _expert_mm(p, "down", h, mode, mean_axis=tp_axis)  # (e_l, ep·cap, d)
    if tp_axis:
        out = jax.lax.psum(out, tp_axis)                      # TP reduce

    # --- return to source shards & unpack ----------------------------------
    if ep_axis:
        out = out.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
        if a2a_quant and out.dtype == jnp.float32:
            out = out.astype(jnp.bfloat16)        # halve the return wire
        out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        out = out.reshape(e, cap, d).astype(x.dtype)
    else:
        out = out.reshape(e, cap, d)

    fetched = jnp.where(keep[:, None], out[sorted_e, pos_in_e], 0)
    contrib = jnp.zeros((t, k, d), x.dtype).at[src_tok, order % k].add(fetched)
    y = jnp.sum(contrib * gates[..., None], axis=1)

    # --- shared experts (kimi-k2): always-on dense path --------------------
    if "shared_up" in p:
        h = (x @ p["shared_up"].astype(x.dtype)) \
            * _act(cfg.act_fn)(x @ p["shared_gate"].astype(x.dtype))
        sh = h @ p["shared_down"].astype(x.dtype)
        y = y + (jax.lax.psum(sh, shared_tp) if shared_tp else sh)

    # auxiliary load-balance loss (Switch): stored via jax.debug? — returned
    # by caller-side hook; kept here as an attribute-free pure function.
    return y


def load_balance_loss(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style auxiliary loss: E · Σ_e f_e · p_e  (train-time hook)."""
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(logits, cfg.top_k)
    f = jnp.mean(jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32),
                 axis=(0, 1))
    return cfg.num_experts * jnp.sum(f * jnp.mean(probs, 0)) * 1e-2
