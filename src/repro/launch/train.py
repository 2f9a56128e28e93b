"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On a real pod this process runs per host (jax.distributed.initialize is
called when JAX_COORDINATOR is set); in this container it runs the same
code single-host. Supports --reduced for CPU-scale runs, checkpoint/resume,
preemption handling, and the W1A8 QAT mode (the paper's training recipe).
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="w1a8_train",
                    choices=["w1a8_train", "float"])
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "sgdm"])
    ap.add_argument("--reduced", action="store_true",
                    help="tiny config (CPU-scale)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the (16,16) pod mesh (needs 256 devices)")
    ap.add_argument("--pipeline", default="none",
                    choices=["none", "gpipe", "1f1b"],
                    help="pipelined training schedule (dist/pipeline)")
    ap.add_argument("--pipeline-stages", type=int, default=4,
                    help="pipeline depth n; mesh = (devices/n, n) over "
                         "('data', 'stage')")
    ap.add_argument("--grad-wire", default="fp32",
                    choices=["fp32", "int8"],
                    help="DP gradient all-reduce wire format "
                         "(int8 → dist/collectives.tree_quantized_allreduce)")
    args = ap.parse_args()

    if args.pipeline != "none" and args.production_mesh:
        raise SystemExit("--pipeline and --production-mesh are separate "
                         "mesh layouts; pick one")
    if args.production_mesh and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   "256 " + os.environ.get("XLA_FLAGS", ""))
    if args.pipeline != "none" and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # (data, stage) mesh on the 16-device host pool (CPU smoke runs)
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   "16 " + os.environ.get("XLA_FLAGS", ""))
    if os.environ.get("JAX_COORDINATOR"):
        import jax
        jax.distributed.initialize()       # multi-host pod entry

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.data import pipeline as data
    from repro.dist import sharding as shard_rules
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import ShardCtx, init_lm_params
    from repro.optim import adafactor, adamw, sgdm
    from repro.optim.schedules import cosine_schedule
    from repro.train.loop import resume_or_init, run_train
    from repro.train.step import make_pipeline_train_step, make_train_step

    enable_compile_cache()
    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    sched = cosine_schedule(args.lr, max(args.steps // 20, 1), args.steps)
    opt = {"adamw": adamw, "adafactor": adafactor, "sgdm": sgdm}[
        args.optimizer](sched)

    ctx = None
    mesh = None
    b_sh = None
    if args.production_mesh:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                       ep_axis="data" if cfg.num_experts else None)

    if args.pipeline != "none":
        # (data, stage) mesh: stage partitioning of the body, DP over data,
        # grads over the fp32/int8 wire (DESIGN.md §9)
        n_dev = len(jax.devices())
        n_st = args.pipeline_stages
        if n_dev % n_st:
            raise SystemExit(f"{n_dev} devices do not split into "
                             f"{n_st} pipeline stages")
        mesh = make_mesh((n_dev // n_st, n_st), ("data", "stage"))
        num_micro = max(args.microbatches, 1)
        raw_step = make_pipeline_train_step(
            cfg, opt, mesh=mesh, num_micro=num_micro, mode=args.mode,
            schedule=args.pipeline, grad_wire=args.grad_wire)
        p_sds = jax.eval_shape(
            lambda: init_lm_params(jax.random.PRNGKey(args.seed), cfg))
        p_sh = shard_rules.pipeline_tree_shardings(p_sds, mesh,
                                                   cfg.num_layers)
        o_sh = shard_rules.pipeline_tree_shardings(
            jax.eval_shape(opt[0], p_sds), mesh, cfg.num_layers)
        b_sh = {"tokens": NamedSharding(mesh, P("data", None)),
                "labels": NamedSharding(mesh, P("data", None))}
        from repro.dist.pipeline import (bubble_fraction,
                                         bubble_fraction_1f1b)
        bf = (bubble_fraction_1f1b if args.pipeline == "1f1b"
              else bubble_fraction)(n_st, num_micro)
        print(f"[pipeline] {args.pipeline} n={n_st} M={num_micro} "
              f"bubble={bf:.3f} grad-wire={args.grad_wire}")
        step_fn = jax.jit(raw_step, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
    elif mesh is not None:
        # dist-layer wiring: place params/opt state with the sharding rules
        # so jit never has to guess (and resharding collectives never appear)
        raw_step = make_train_step(cfg, opt, mode=args.mode,
                                   microbatches=args.microbatches, ctx=ctx,
                                   remat=not args.reduced)
        p_sds = jax.eval_shape(
            lambda: init_lm_params(jax.random.PRNGKey(args.seed), cfg))
        p_sh = shard_rules.tree_shardings(p_sds, cfg, mesh)
        o_sh = shard_rules.tree_shardings(jax.eval_shape(opt[0], p_sds),
                                          cfg, mesh)
        step_fn = jax.jit(raw_step, in_shardings=(p_sh, o_sh, None),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
    else:
        raw_step = make_train_step(cfg, opt, mode=args.mode,
                                   microbatches=args.microbatches, ctx=ctx,
                                   remat=not args.reduced)
        step_fn = jax.jit(raw_step)

    def init_fn():
        params = init_lm_params(jax.random.PRNGKey(args.seed), cfg)
        return {"params": params, "opt_state": opt[0](params)}

    shardings = {"params": p_sh, "opt_state": o_sh} if mesh is not None \
        else None
    state, start = resume_or_init(args.ckpt_dir, init_fn,
                                  shardings=shardings)
    ds = data.make_lm_dataset(cfg.vocab_size, args.seq_len,
                              args.global_batch, seed=args.seed)

    def batch_fn(step):
        toks, labels = data.lm_batch(ds, step)
        batch = {"tokens": toks, "labels": labels}
        if cfg.family == "encdec":
            batch["encoder_embeds"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.global_batch, args.seq_len,
                                           cfg.d_model)) * 0.1
        if cfg.frontend == "vision":
            batch["prefix_embeds"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.global_batch, cfg.prefix_len,
                                           cfg.d_model)) * 0.1
        return batch

    run_train(train_step=step_fn, params=state["params"],
              opt_state=state["opt_state"], batch_fn=batch_fn,
              steps=args.steps, start_step=start, ckpt_dir=args.ckpt_dir)


if __name__ == "__main__":
    main()
