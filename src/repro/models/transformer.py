"""Generic LM assembly: dense / MoE / SSM / hybrid / enc-dec, scan-over-stages.

The layer pattern repeats with period ``cfg.period`` (1 for uniform stacks,
2 for gemma2 local/global + MoE-every-other, 8 for jamba's 1-attn:7-mamba).
Parameters are stacked over stages (leading dim L/period) and the stack is
consumed by ``lax.scan`` — HLO holds one period's body regardless of depth,
keeping multi-hundred-layer configs compilable in the dry-run.

W1A8 (the paper's technique): every body projection runs through
``layers.linear`` in the requested mode; embedding and LM head stay
full-precision (the Conv1/Conv11 rule — cf. BitNet-style W1A8 transformers).

MoE layers execute inside ``shard_map`` (EP all-to-all over the data axis,
TP psum over the model axis) when a ShardCtx is provided; without one the
identical math runs single-device (smoke tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models.layers import (ModelConfig, attention, embed,
                                 init_attention, init_embed, init_mlp,
                                 init_norm, mlp, norm, unembed)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Distribution context threaded through the model (None ⇒ local)."""
    mesh: Any
    dp_axes: tuple            # axes the batch/tokens are sharded over
    tp_axis: Optional[str]    # tensor-parallel axis (FFN hidden / heads)
    ep_axis: Optional[str]    # expert-parallel axis (None ⇒ replicated experts)
    a2a_quant: bool = False   # uint8-wire MoE dispatch (§Perf)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_slot(key, cfg: ModelConfig, mixer_kind: str, ffn_kind: str,
               dtype) -> dict:
    ks = jax.random.split(key, 4)
    slot = {"norm1": init_norm(cfg.d_model, cfg.norm_kind, dtype)}
    if mixer_kind.startswith("attn"):
        slot["attn"] = init_attention(ks[0], cfg, dtype)
    else:
        slot["mamba"] = mb.init_mamba(ks[0], cfg, dtype)
    if cfg.post_norms:
        slot["post_norm1"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
    if ffn_kind != "none":
        slot["norm2"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
        if ffn_kind == "moe":
            slot["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            slot["mlp"] = init_mlp(ks[1], cfg, dtype=dtype)
        if cfg.post_norms:
            slot["post_norm2"] = init_norm(cfg.d_model, cfg.norm_kind, dtype)
    return slot


def _stack_stages(per_stage: list) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)


def init_lm_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    period = cfg.period
    assert cfg.num_layers % period == 0, (cfg.name, cfg.num_layers, period)
    n_stages = cfg.num_layers // period
    kinds = [(cfg.mixer_kind(i), cfg.ffn_kind(i)) for i in range(period)]
    key, ke, kf = jax.random.split(key, 3)
    params = {"embed": init_embed(ke, cfg, dtype),
              "final_norm": init_norm(cfg.d_model, cfg.norm_kind, dtype)}
    slots = []
    for s_idx, (mk, fk) in enumerate(kinds):
        stages = [_init_slot(jax.random.fold_in(key, st * period + s_idx),
                             cfg, mk, fk, dtype) for st in range(n_stages)]
        slots.append(_stack_stages(stages))
    params["slots"] = tuple(slots)
    if cfg.encoder_layers:
        enc_cfg = dataclasses.replace(cfg, num_layers=cfg.encoder_layers,
                                      attn_every=0, local_global=False,
                                      num_experts=0)
        kenc = jax.random.fold_in(kf, 7)
        enc_slots = [_stack_stages(
            [_init_slot(jax.random.fold_in(kenc, st), enc_cfg, "attn",
                        "dense", dtype) for st in range(cfg.encoder_layers)])]
        cross = [_stack_stages(
            [{"norm": init_norm(cfg.d_model, cfg.norm_kind, dtype),
              "attn": init_attention(jax.random.fold_in(kenc, 1000 + st),
                                     cfg, dtype)}
             for st in range(cfg.num_layers)])]
        params["encoder"] = {"slots": tuple(enc_slots),
                             "final_norm": init_norm(cfg.d_model,
                                                     cfg.norm_kind, dtype)}
        params["cross"] = cross[0]
    return params


def count_lm_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

def _apply_moe(slot_moe, cfg: ModelConfig, x: jax.Array, mode: str,
               ctx: Optional[ShardCtx]):
    b, s, d = x.shape
    toks = x.reshape(b * s, d)
    if ctx is None:
        y = moe_mod.moe_ffn(slot_moe, cfg, toks, mode=mode, ep_axis=None)
        return y.reshape(b, s, d)

    shard_map = jax.shard_map
    ep = ctx.ep_axis if (ctx.ep_axis and
                         cfg.num_experts %
                         ctx.mesh.shape[ctx.ep_axis] == 0) else None
    tp = ctx.tp_axis
    tp_n = ctx.mesh.shape[tp] if tp else 1
    packed = "up_packed" in slot_moe
    # the expert hidden dim F is TP-sliced only if every F-indexed tensor
    # (up/gate cols, down rows — /32 when bit-packed — and α vectors) splits
    ok = tp and cfg.d_ff % tp_n == 0 and \
        (not packed or (cfg.d_ff // 32) % tp_n == 0)
    tp_eff = tp if ok else None
    sh_ok = tp and cfg.shared_experts and \
        (cfg.d_ff * cfg.shared_experts) % tp_n == 0
    tp_sh = tp if sh_ok else None

    specs = {}
    for name in slot_moe:
        if name in ("up", "gate", "up_packed", "gate_packed", "up_alpha",
                    "gate_alpha"):
            specs[name] = P(ep, None, tp_eff)   # (E, K[/32]|1, F[/32])
        elif name in ("down", "down_packed"):
            specs[name] = P(ep, tp_eff, None)   # (E, F[/32], D)
        elif name == "down_alpha":
            specs[name] = P(ep, None, None)
        elif name in ("shared_up", "shared_gate"):
            specs[name] = P(None, tp_sh)
        elif name == "shared_down":
            specs[name] = P(tp_sh, None)
        elif name == "router":
            specs[name] = P(None, None)
        else:
            specs[name] = P()

    def inner(p_local, t_local):
        y = moe_mod.moe_ffn(p_local, cfg, t_local, mode=mode, ep_axis=ep,
                            tp_axis=tp_eff, shared_tp=tp_sh,
                            a2a_quant=ctx.a2a_quant)
        return y

    y = shard_map(inner, mesh=ctx.mesh,
                  in_specs=(specs, P(ctx.dp_axes, None)),
                  out_specs=P(ctx.dp_axes, None),
                  check_vma=False)(slot_moe, toks)
    return y.reshape(b, s, d)


def _apply_slot(slot: dict, cfg: ModelConfig, x: jax.Array, *,
                mixer_kind: str, ffn_kind: str, mode: str,
                positions: jax.Array, ctx: Optional[ShardCtx]) -> jax.Array:
    h = norm(slot["norm1"], x, cfg.norm_kind)
    if mixer_kind.startswith("attn"):
        window = 0
        if mixer_kind == "attn_local" or (cfg.sliding_window and
                                          not cfg.local_global):
            window = cfg.sliding_window
        out = attention(slot["attn"], cfg, h, mode=mode, causal=True,
                        window=window, positions=positions)
    else:
        mixer = (mb.mamba2_mixer if cfg.ssm_kind == "mamba2"
                 else mb.mamba1_mixer)
        out = mixer(slot["mamba"], cfg, h, mode=mode)
    if cfg.post_norms:
        out = norm(slot["post_norm1"], out, cfg.norm_kind)
    x = x + out.astype(x.dtype)          # keep the scan carry dtype stable
    if ffn_kind != "none":
        h = norm(slot["norm2"], x, cfg.norm_kind)
        if ffn_kind == "moe":
            out = _apply_moe(slot["moe"], cfg, h, mode, ctx)
        else:
            out = mlp(slot["mlp"], cfg, h, mode)
        if cfg.post_norms:
            out = norm(slot["post_norm2"], out, cfg.norm_kind)
        x = x + out.astype(x.dtype)
    return x


# ---------------------------------------------------------------------------
# Forward (train/eval)
# ---------------------------------------------------------------------------

def lm_forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
               mode: str = "float", prefix_embeds: Optional[jax.Array] = None,
               encoder_embeds: Optional[jax.Array] = None,
               ctx: Optional[ShardCtx] = None,
               remat: bool = False) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S_total, vocab).

    prefix_embeds: (B, S_p, D) modality stub (vision patches / audio frames)
    prepended to the token embeddings (internvl2 path).
    encoder_embeds: (B, S_enc, D) encoder *input* features for enc-dec
    (seamless path) — runs the encoder stack, then decoder cross-attends.
    """
    kinds = [(cfg.mixer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.period)]
    x = embed(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    enc_out = None
    if encoder_embeds is not None:
        enc_out = encode(cfg, params, encoder_embeds, mode=mode)

    cross = params.get("cross")

    def stage(x, slot_stack):
        for i, (mk, fk) in enumerate(kinds):
            x = _apply_slot(slot_stack[i], cfg, x, mixer_kind=mk, ffn_kind=fk,
                            mode=mode, positions=positions, ctx=ctx)
        return x, None

    if enc_out is None and cross is None:
        body = jax.checkpoint(stage) if remat else stage
        x, _ = jax.lax.scan(body, x, params["slots"])
    else:
        # enc-dec: interleave cross-attention after each decoder self-attn
        def stage_cross(x, slots_and_cross):
            slot_stack, cr = slots_and_cross
            for i, (mk, fk) in enumerate(kinds):
                x = _apply_slot(slot_stack[i], cfg, x, mixer_kind=mk,
                                ffn_kind=fk, mode=mode, positions=positions,
                                ctx=ctx)
            h = norm(cr["norm"], x, cfg.norm_kind)
            x = x + attention(cr["attn"], cfg, h, mode=mode, causal=False,
                              positions=positions,
                              kv_x=enc_out).astype(x.dtype)
            return x, None
        body = jax.checkpoint(stage_cross) if remat else stage_cross
        x, _ = jax.lax.scan(body, x, (params["slots"], cross))

    x = norm(params["final_norm"], x, cfg.norm_kind)
    return unembed(params["embed"], cfg, x)


def encode(cfg: ModelConfig, params: dict, feats: jax.Array, *,
           mode: str = "float") -> jax.Array:
    """Bidirectional encoder over stub features (B, S_enc, D)."""
    enc = params["encoder"]
    b, s, _ = feats.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    dtype = params["embed"]["emb"].dtype

    def stage(x, slot_stack):
        h = norm(slot_stack[0]["norm1"], x, cfg.norm_kind)
        out = attention(slot_stack[0]["attn"], cfg, h, mode=mode,
                        causal=False, positions=positions)
        x = x + out.astype(x.dtype)
        h = norm(slot_stack[0]["norm2"], x, cfg.norm_kind)
        x = x + mlp(slot_stack[0]["mlp"], cfg, h, mode).astype(x.dtype)
        return x, None

    x, _ = jax.lax.scan(stage, feats.astype(dtype), enc["slots"])
    return norm(enc["final_norm"], x, cfg.norm_kind)
