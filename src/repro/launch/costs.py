"""Decomposed roofline measurement (§Roofline).

XLA's cost analysis reports **per-device** FLOPs/bytes and counts while-loop
(scan) bodies **once** (calibrated in EXPERIMENTS.md §Dry-run). A full
train_step therefore under-reports by the trip counts. Instead we compile
the program's repeating units separately and assemble:

  train:  microbatches × [ stages × C(stage fwd+bwd) + C(embed+head fwd+bwd) ]
          + C(optimizer update)
  prefill: stages × C(stage fwd) + C(embed+head fwd)
  decode:  stages × C(decode stage) + C(embed+head fwd)

Each unit is compiled under the production mesh with the real shardings, so
its HLO contains the real collectives; collective bytes scale by the same
trip counts. Remat is *not* applied to the measured stage (the assembled
backward already recomputes nothing) — the full module uses remat, so the
assembled compute term is a lower bound the full program approaches within
the remat factor (reported as `remat_overhead`).
"""
import os  # noqa: E402
import sys  # noqa: E402
if "jax" not in sys.modules and \
        "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
    # only force the 512-device pool on fresh module execution — library
    # imports from a live jax process (tests) must not repoison the count
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse            # noqa: E402
import json                # noqa: E402
import time                # noqa: E402
import traceback           # noqa: E402

import jax                 # noqa: E402
import jax.numpy as jnp    # noqa: E402
import numpy as np         # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                      # noqa: E402
from repro.configs.shapes import SHAPES, skip_reason       # noqa: E402
from repro.dist import sharding as shard_rules  # noqa: E402
from repro.launch import dryrun as dr          # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.peaks import V5E, chip_peaks  # noqa: E402
from repro.models.layers import embed, norm, unembed       # noqa: E402
from repro.models.transformer import (ShardCtx, _apply_slot,  # noqa: E402
                                      init_lm_params)
from repro.optim import adafactor, adamw       # noqa: E402
from repro.serve import engine as serve_engine  # noqa: E402
from repro.serve.packed import deploy_lm       # noqa: E402

HW = chip_peaks(V5E)


def _cost_of(jitted, *args):
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = dr.parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll}


def _scale(unit: dict, trips: int) -> dict:
    coll = {k: (v * trips if isinstance(v, (int, float)) else v)
            for k, v in unit["coll"].items()}
    return {"flops": unit["flops"] * trips, "bytes": unit["bytes"] * trips,
            "coll": coll}


def _merge(parts) -> dict:
    tot = {"flops": 0.0, "bytes": 0.0,
           "coll": {k: 0 for k in ("all-reduce", "all-gather",
                                   "reduce-scatter", "all-to-all",
                                   "collective-permute")}}
    for p in parts:
        tot["flops"] += p["flops"]
        tot["bytes"] += p["bytes"]
        for k in tot["coll"]:
            tot["coll"][k] += p["coll"].get(k, 0)
    return tot


def _slot_slice_sds(slots_sds):
    """Drop the leading stage dim from the stacked slot ShapeDtypeStructs."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), slots_sds)


def _slot_shardings(slot_sds, cfg, mesh):
    return shard_rules.tree_shardings(slot_sds, cfg, mesh)


def _stage_fn(cfg, ctx, mode):
    kinds = [(cfg.mixer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.period)]

    def stage(slots, x):
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        for i, (mk, fk) in enumerate(kinds):
            x = _apply_slot(slots[i], cfg, x, mixer_kind=mk, ffn_kind=fk,
                            mode=mode, positions=positions, ctx=ctx)
        return x
    return stage


def _top_fn(cfg, mode):
    """Embedding + final-norm + LM head (+loss in train) on a (B,S) batch."""
    def top(embed_p, norm_p, tokens, labels):
        x = embed(embed_p, tokens)
        logits = unembed(embed_p, cfg, norm(norm_p, x, cfg.norm_kind))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                             -1))
    return top


def measure_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                 microbatches: int = 8, variant: dict = None) -> dict:
    """variant (§Perf hillclimb knobs): {"flash_block": int,
    "cache_seq_shard": bool, "packed": bool, "microbatches": int}."""
    variant = variant or {}
    microbatches = variant.get("microbatches", microbatches)
    cfg = configs.get_config(arch)
    import dataclasses as _dc
    cfg_updates = {}
    for key in ("flash_block", "pad_heads_to", "capacity_factor"):
        if key in variant:
            cfg_updates[key] = variant[key]
    if variant.get("flat_head"):
        cfg_updates["flat_head_attn"] = True
    if cfg_updates:
        cfg = _dc.replace(cfg, **cfg_updates)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    dp = shard_rules.dp_axes(mesh)
    stages = cfg.num_layers // cfg.period
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names)}
    skip = skip_reason(arch, shape_name)
    if skip:
        rec.update(status="skipped", reason=skip)
        return rec
    if spec.kind == "train":
        rec["pipeline_bubble"] = dr.pipeline_bubble_record(
            cfg, microbatches=microbatches)

    dtype = jnp.bfloat16 if (arch in dr.BIG or spec.kind != "train") \
        else jnp.float32
    params_sds = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), cfg, dtype))
    long_ctx = spec.global_batch < dr._axsize(mesh, dp)
    ctx = ShardCtx(mesh=mesh, dp_axes=dp if not long_ctx else (),
                   tp_axis="model",
                   ep_axis="data" if cfg.num_experts else None,
                   a2a_quant=bool(variant.get("a2a_quant", False)))
    mode = "w1a8_train" if spec.kind == "train" else "w1a8_eval"
    with mesh:
        if spec.kind == "train":
            b_mb = spec.global_batch // microbatches
            s = spec.seq_len
            x_sds = jax.ShapeDtypeStruct((b_mb, s, cfg.d_model), dtype)
            x_sh = NamedSharding(mesh, P(dp, None, None))
            slots_sds = _slot_slice_sds(params_sds["slots"])
            slots_sh = _slot_shardings(slots_sds, cfg, mesh)
            stage = _stage_fn(cfg, ctx, mode)

            def stage_vjp(slots, x, ct):
                _, f = jax.vjp(stage, slots, x)
                return f(ct)

            c_stage = _cost_of(
                jax.jit(stage_vjp, in_shardings=(slots_sh, x_sh, x_sh)),
                slots_sds, x_sds, x_sds)

            top = _top_fn(cfg, mode)

            def top_vjp(ep_, np_, tokens, labels):
                (loss, f) = jax.vjp(
                    lambda e, n: top(e, n, tokens, labels), ep_, np_)
                return f(jnp.ones_like(loss))

            tok_sds = jax.ShapeDtypeStruct((b_mb, s), jnp.int32)
            tok_sh = NamedSharding(mesh, P(dp, None))
            ep_sds = _sds_of(params_sds["embed"])
            np_sds = _sds_of(params_sds["final_norm"])
            ep_sh = shard_rules.tree_shardings(ep_sds, cfg, mesh)
            np_sh = shard_rules.tree_shardings(np_sds, cfg, mesh)
            c_top = _cost_of(
                jax.jit(top_vjp,
                        in_shardings=(ep_sh, np_sh, tok_sh, tok_sh)),
                ep_sds, np_sds, tok_sds, tok_sds)

            opt = adafactor(1e-3) if arch in dr.BIG else adamw(1e-3)
            opt_sds = jax.eval_shape(opt[0], params_sds)
            p_sh = shard_rules.tree_shardings(params_sds, cfg, mesh)
            o_sh = shard_rules.tree_shardings(opt_sds, cfg, mesh)
            c_opt = _cost_of(
                jax.jit(lambda g, s_, p: opt[1](g, s_, p),
                        in_shardings=(p_sh, o_sh, p_sh)),
                params_sds, opt_sds, params_sds)

            total = _merge([_scale(c_stage, stages * microbatches),
                            _scale(c_top, microbatches), c_opt])
            rec["parts"] = {"stage_fwdbwd": c_stage, "top_fwdbwd": c_top,
                            "optimizer": c_opt,
                            "trips": {"stage": stages * microbatches,
                                      "top": microbatches}}
        elif spec.kind == "prefill":
            b, s = spec.global_batch, spec.seq_len
            if cfg.w1a8_body and variant.get("packed", True):
                params_sds = jax.eval_shape(deploy_lm, params_sds)
            x_sds = jax.ShapeDtypeStruct((b, s, cfg.d_model), dtype)
            x_sh = NamedSharding(mesh, P(dp, None, None))
            slots_sds = _slot_slice_sds(params_sds["slots"])
            slots_sh = _slot_shardings(slots_sds, cfg, mesh)
            stage = _stage_fn(cfg, ctx, mode)
            c_stage = _cost_of(
                jax.jit(stage, in_shardings=(slots_sh, x_sh)),
                slots_sds, x_sds)
            c_top = _top_cost_fwd(cfg, params_sds, mesh, dp, b, s, mode)
            total = _merge([_scale(c_stage, stages), c_top])
            rec["parts"] = {"stage_fwd": c_stage, "top_fwd": c_top,
                            "trips": {"stage": stages}}
        else:  # decode
            b = spec.global_batch
            if cfg.w1a8_body and variant.get("packed", True):
                params_sds = jax.eval_shape(deploy_lm, params_sds)
            cache_sds = jax.eval_shape(
                lambda: serve_engine.init_cache(cfg, b, spec.seq_len,
                                                jnp.bfloat16))
            cache_sh = dr._cache_shardings(
                cache_sds, mesh, cfg, dp=dp, long_ctx=long_ctx,
                seq_shard_fallback=variant.get("cache_seq_shard", False))
            slots_sds = _slot_slice_sds(params_sds["slots"])
            slots_sh = _slot_shardings(slots_sds, cfg, mesh)
            cslots_sds = _slot_slice_sds(cache_sds["slots"])
            cslots_sh = _slot_slice_shardings(cache_sh["slots"])
            x_sds = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
            bspec = dp if not long_ctx else None
            x_sh = NamedSharding(mesh, P(bspec, None, None))
            pos_sds = jax.ShapeDtypeStruct((b,), jnp.int32)
            pos_sh = NamedSharding(mesh, P(bspec))
            dstage = _decode_stage_fn(cfg, ctx, "w1a8_eval")
            c_stage = _cost_of(
                jax.jit(dstage, in_shardings=(slots_sh, cslots_sh, x_sh,
                                              pos_sh)),
                slots_sds, cslots_sds, x_sds, pos_sds)
            c_top = _top_cost_fwd(cfg, params_sds, mesh, dp, b, 1,
                                  "w1a8_eval", bspec=bspec)
            total = _merge([_scale(c_stage, stages), c_top])
            rec["parts"] = {"stage_decode": c_stage, "top_fwd": c_top,
                            "trips": {"stage": stages}}

    cw = dr.wire_bytes(total["coll"], n_chips)
    ana_bytes = analytic_bytes(cfg, spec, params_sds, n_chips,
                               microbatches=microbatches,
                               cache_seq_shard=variant.get("cache_seq_shard",
                                                           False))
    rec["totals"] = {"flops_per_device": total["flops"],
                     "bytes_per_device_measured_unfused": total["bytes"],
                     "bytes_per_device_analytic": ana_bytes,
                     "collective_wire_bytes": cw}
    # per-device terms (cost analysis is per-device — calibrated).
    # memory: the measured "bytes accessed" comes from UNFUSED CPU HLO and
    # over-counts intermediates ~5-20×; the analytic model (weights+state
    # traffic + stage-boundary activations) is the roofline term, with the
    # measured value kept as an upper bound.
    t_comp = total["flops"] / HW["peak_flops_bf16"]
    t_mem = ana_bytes / HW["hbm_bw"]
    t_mem_upper = total["bytes"] / HW["hbm_bw"]
    t_coll = cw / HW["ici_bw"]
    dom = max(("compute", t_comp), ("memory", t_mem),
              ("collective", t_coll), key=lambda kv: kv[1])[0]
    mf = dr.model_flops(arch, shape_name) / n_chips
    rec["roofline"] = {
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_memory_upper_s": t_mem_upper, "t_collective_s": t_coll,
        "bottleneck": dom,
        "model_flops_per_device": mf,
        "useful_flops_ratio": mf / total["flops"] if total["flops"] else None,
        "step_time_bound_s": max(t_comp, t_mem, t_coll),
        "roofline_fraction": (mf / HW["peak_flops_bf16"]) /
                             max(t_comp, t_mem, t_coll)
                             if max(t_comp, t_mem, t_coll) > 0 else None,
    }
    rec["status"] = "ok"
    return rec


def analytic_bytes(cfg, spec, params_sds, n_chips, *,
                   microbatches: int = 8,
                   cache_seq_shard: bool = False) -> float:
    """Per-device HBM traffic model (fused-execution napkin roofline).

    train:   3 weight passes/microbatch (fwd, remat-fwd, bwd) + grad
             accumulation r/w (f32) + optimizer state r/w + residual-stream
             activations at stage boundaries (×4 traversals).
    prefill: 1 weight pass + activations.
    decode:  1 weight pass + KV/SSM cache read+write (the dominant term; with
             packed W1A8 the weight pass is 1 bit/weight — the §Perf lever).
    """
    leaves = jax.tree_util.tree_leaves(params_sds)
    p_bytes = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                  for l in leaves) / n_chips
    p_count = sum(int(np.prod(l.shape)) for l in leaves) / n_chips
    d = cfg.d_model
    act_bytes = 2  # bf16 residual stream
    stages = cfg.num_layers // cfg.period
    if spec.kind == "train":
        # tokens shard over dp axes only (model axis = 16 in both meshes)
        tok_pd = spec.global_batch * spec.seq_len / (n_chips / 16)
        weights = 3 * microbatches * p_bytes
        grads = 2 * microbatches * p_count * 4
        opt = 5 * p_count * 4
        acts = 4 * stages * tok_pd * d * act_bytes
        return weights + grads + opt + acts
    if spec.kind == "prefill":
        tok_pd = spec.global_batch * spec.seq_len / (n_chips / 16)
        return p_bytes + 4 * stages * tok_pd * d * act_bytes
    # decode
    cache = jax.eval_shape(
        lambda: serve_engine.init_cache(cfg, spec.global_batch,
                                        spec.seq_len, jnp.bfloat16))
    c_leaves = jax.tree_util.tree_leaves(cache)
    c_total = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                  for l in c_leaves)
    # cache shards over dp (batch) when divisible, else over data (seq);
    # kv-head dim additionally over model when divisible.
    dp_size = n_chips / 16                      # data(+pod) axes
    kv_shard = 16 if (cfg.num_kv_heads % 16 == 0 or cache_seq_shard) else 1
    c_pd = c_total / min(dp_size * kv_shard, n_chips)
    return p_bytes + 2 * c_pd


def _sds_of(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _slot_slice_shardings(cache_sh_slots):
    """Drop the stage dim from cache shardings (first axis of each spec)."""
    def conv(ns):
        spec = list(ns.spec) + [None] * 8
        return NamedSharding(ns.mesh, P(*spec[1:len(ns.spec)]))
    return jax.tree_util.tree_map(
        conv, cache_sh_slots,
        is_leaf=lambda x: isinstance(x, NamedSharding))


def _top_cost_fwd(cfg, params_sds, mesh, dp, b, s, mode, bspec="unset"):
    if bspec == "unset":
        bspec = dp
    top = _top_fn(cfg, mode)
    tok_sds = jax.ShapeDtypeStruct((b, s), jnp.int32)
    tok_sh = NamedSharding(mesh, P(bspec, None))
    ep_sds = _sds_of(params_sds["embed"])
    np_sds = _sds_of(params_sds["final_norm"])
    ep_sh = shard_rules.tree_shardings(ep_sds, cfg, mesh)
    np_sh = shard_rules.tree_shardings(np_sds, cfg, mesh)
    return _cost_of(
        jax.jit(lambda e, n, t: top(e, n, t, t),
                in_shardings=(ep_sh, np_sh, tok_sh)),
        ep_sds, np_sds, tok_sds)


def _decode_stage_fn(cfg, ctx, mode):
    kinds = [(cfg.mixer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.period)]

    def dstage(slots, caches, x, pos):
        from repro.models.layers import mlp
        from repro.models.transformer import _apply_moe
        from repro.serve.engine import _attn_decode
        from repro.models import mamba as mb
        for i, (mk, fk) in enumerate(kinds):
            slot, c = slots[i], caches[i]
            h = norm(slot["norm1"], x, cfg.norm_kind)
            if mk.startswith("attn"):
                window = 0
                if mk == "attn_local" or (cfg.sliding_window and
                                          not cfg.local_global):
                    window = cfg.sliding_window
                out, *_ = _attn_decode(slot["attn"], cfg, h, c["k"], c["v"],
                                       c["pos"], pos, mode=mode,
                                       window=window)
            else:
                step_fn = (mb.mamba2_decode_step if cfg.ssm_kind == "mamba2"
                           else mb.mamba1_decode_step)
                out, _ = step_fn(slot["mamba"], cfg, h, c, mode)
            if cfg.post_norms:
                out = norm(slot["post_norm1"], out, cfg.norm_kind)
            x = x + out
            if fk != "none":
                h = norm(slot["norm2"], x, cfg.norm_kind)
                if fk == "moe":
                    out = _apply_moe(slot["moe"], cfg, h, mode, ctx)
                else:
                    out = mlp(slot["mlp"], cfg, h, mode)
                if cfg.post_norms:
                    out = norm(slot["post_norm2"], out, cfg.norm_kind)
                x = x + out
        return x
    return dstage


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=os.path.join(dr.RESULTS_DIR,
                                                  "costs.json"))
    ap.add_argument("--variant", default=None,
                    help="k=v[,k=v] hillclimb knobs, e.g. flash_block=1024")
    args = ap.parse_args()
    variant = {}
    if args.variant:
        for kv in args.variant.split(","):
            k, v = kv.split("=")
            if v.lower() in ("true", "false"):
                variant[k] = v.lower() == "true"
            else:
                variant[k] = int(v)
    archs = list(configs.ARCH_NAMES) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    os.makedirs(dr.RESULTS_DIR, exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = [r for r in json.load(f)
                       if r.get("status") in ("ok", "skipped")]
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    for arch in archs:
        for shape in shapes:
            if (arch, shape, mesh_name) in done:
                continue
            print(f"=== cost {arch} × {shape} × {mesh_name}", flush=True)
            t0 = time.time()
            try:
                rec = measure_cell(arch, shape, multi_pod=args.multi_pod,
                                   variant=variant)
            except Exception as e:                         # noqa: BLE001
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-1500:]}
            rec["measure_s"] = round(time.time() - t0, 1)
            if variant:
                rec["variant"] = variant
            results.append(rec)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(f"    comp={r['t_compute_s']:.4g}s "
                      f"mem={r['t_memory_s']:.4g}s "
                      f"coll={r['t_collective_s']:.4g}s → {r['bottleneck']} "
                      f"(roofline {r['roofline_fraction'] and round(r['roofline_fraction'],3)})",
                      flush=True)
            else:
                print("    " + rec.get("error", rec["status"])[:200],
                      flush=True)


if __name__ == "__main__":
    main()
