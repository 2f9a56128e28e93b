"""Multi-device correctness checks, run in a fresh process with 16 virtual
devices (tests/test_dist.py shells out to this). Asserts:

  1. MoE EP all-to-all path ≡ single-device reference
  2. TP-in-expert (psum) ≡ reference, incl. QAT α pmean
  3. GPipe pipeline ≡ sequential stage application
  4. int8-quantized all-reduce ≈ exact mean (< 1% rel err)
  5. sharded W1A8 train step ≡ single-device step (same loss)
  6. SP (context-parallel) decode attention ≡ dense attention
  7. 1F1B/GPipe pipelined *training* ≡ sequential jax.grad oracle
     (loss + grads ≤ 1e-5 rel err), int8-wire DP grads in envelope
  8. pipelined LM train step (train/step.make_pipeline_train_step)
     ≡ single-device make_train_step (same loss)
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=16 "
                           + os.environ.get("XLA_FLAGS", ""))

import dataclasses  # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.dist.collectives import tree_quantized_allreduce  # noqa: E402
from repro.dist.pipeline import (gpipe, pipeline_train_reference,  # noqa: E402
                                 pipeline_train_step)
from repro.dist import sharding as shard_rules  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.layers import ModelConfig  # noqa: E402
from repro.models.transformer import ShardCtx, init_lm_params  # noqa: E402
from repro.optim import sgdm  # noqa: E402
from repro.train.step import (make_pipeline_train_step,  # noqa: E402
                              make_train_step)


def check_moe_ep():
    cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      num_experts=4, top_k=2, capacity_factor=4.0,
                      w1a8_body=True)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 32))
    for mode in ("float", "w1a8_train"):
        y_ref = moe_mod.moe_ffn(p, cfg, x, mode=mode, ep_axis=None)
        mesh = make_mesh((4, 4), ("data", "model"))

        def inner(pl, xl):
            return moe_mod.moe_ffn(pl, cfg, xl, mode=mode, ep_axis="data",
                                   tp_axis="model")
        specs = {"router": P(None, None), "up": P("data", None, "model"),
                 "gate": P("data", None, "model"),
                 "down": P("data", "model", None), "act_step": P()}
        with mesh:
            y = jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=(specs, P("data", None)),
                out_specs=P("data", None), check_vma=False))(p, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 2e-5, f"moe ep ({mode}): {err}"
    print("1/2. MoE EP+TP (float & QAT) OK")


def check_gpipe():
    mesh = make_mesh((4, 4), ("pod", "model"))
    n_stages, num_micro, mb, d = 4, 8, 2, 16
    ws = jax.random.normal(jax.random.PRNGKey(2), (n_stages, d, d)) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(3), (num_micro, mb, d))
    want = x
    for i in range(n_stages):
        want = jax.vmap(lambda xm: stage_fn(ws[i], xm))(want)
    f = gpipe(stage_fn, mesh=mesh, axis="pod", num_micro=num_micro)
    with mesh:
        got = f(ws, x)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, f"gpipe: {err}"
    print("3. GPipe pipeline OK")


def check_quantized_allreduce():
    mesh = make_mesh((16,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(4), (16, 64, 64))

    def inner(gl):
        return tree_quantized_allreduce({"g": gl[0]}, "data")["g"]

    with mesh:
        out = jax.jit(jax.shard_map(inner, mesh=mesh,
                                    in_specs=(P("data", None, None),),
                                    out_specs=P(), check_vma=False))(g)
    want = jnp.mean(g, axis=0)
    rel = float(jnp.linalg.norm(out - want) / jnp.linalg.norm(want))
    # int8 wire format carries ~1% relative noise on unit-normal grads —
    # the bandwidth/precision trade documented in dist/collectives.py
    assert rel < 0.03, f"quantized allreduce rel err {rel}"
    print(f"4. int8 all-reduce OK (rel err {rel:.4f})")


def check_sharded_train_step():
    cfg = dataclasses.replace(configs.get_reduced("mixtral-8x7b"),
                              num_experts=4, d_ff=64)
    params = init_lm_params(jax.random.PRNGKey(5), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(6), (8, 16), 0,
                              cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    opt = sgdm(1e-2)
    s_ref = make_train_step(cfg, opt, remat=False)
    _, _, m_ref = s_ref(params, opt[0](params), batch)

    mesh = make_mesh((4, 4), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                   ep_axis="data")
    p_sh = shard_rules.tree_shardings(params, cfg, mesh)
    o_sh = shard_rules.tree_shardings(opt[0](params), cfg, mesh)
    b_sh = {"tokens": NamedSharding(mesh, P("data", None)),
            "labels": NamedSharding(mesh, P("data", None))}
    s_dist = jax.jit(make_train_step(cfg, opt, remat=True, ctx=ctx,
                                     microbatches=2),
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None))
    with mesh:
        _, _, m = s_dist(jax.device_put(params, p_sh),
                         jax.device_put(opt[0](params), o_sh),
                         jax.device_put(batch, b_sh))
    diff = abs(float(m["loss"]) - float(m_ref["loss"]))
    assert diff < 5e-3, f"sharded train loss diff {diff}"
    print(f"5. sharded train step OK (loss diff {diff:.2e})")


def _tree_rel_err(got, want) -> float:
    d = jnp.sqrt(sum(jnp.sum((a - b) ** 2) for a, b in
                     zip(jax.tree_util.tree_leaves(got),
                         jax.tree_util.tree_leaves(want))))
    n = jnp.sqrt(sum(jnp.sum(b ** 2)
                     for b in jax.tree_util.tree_leaves(want)))
    return float(d / n)


def check_pipeline_train():
    mesh = make_mesh((4, 4), ("stage", "data"))
    n, num_micro, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(8)
    ws = {"w": jax.random.normal(key, (n, d, d)) * 0.3,
          "b": jax.random.normal(jax.random.fold_in(key, 1), (n, d)) * 0.1}
    top = {"head": jax.random.normal(jax.random.fold_in(key, 2),
                                     (d, d)) * 0.2}

    def stage_fn(w, x):
        return jnp.tanh(x @ w["w"] + w["b"])

    def loss_fn(tp, y, aux):
        return jnp.mean((y @ tp["head"] - aux["tgt"]) ** 2)

    x = jax.random.normal(jax.random.fold_in(key, 3), (num_micro, mb, d))
    aux = {"tgt": jax.random.normal(jax.random.fold_in(key, 4),
                                    (num_micro, mb, d))}
    l_ref, g_ref, gt_ref, dx_ref = pipeline_train_reference(
        stage_fn, loss_fn, ws, x, aux=aux, top=top)
    for sched in ("1f1b", "gpipe"):
        f = pipeline_train_step(stage_fn, loss_fn, mesh=mesh, axis="stage",
                                num_micro=num_micro, schedule=sched)
        with mesh:
            loss, gws, gtop, dx = f(ws, x, aux=aux, top=top)
        rel = max(_tree_rel_err(gws, g_ref), _tree_rel_err(gtop, gt_ref),
                  _tree_rel_err(dx, dx_ref),
                  abs(float(loss) - float(l_ref)) / abs(float(l_ref)))
        assert rel < 1e-5, f"pipeline train ({sched}): rel err {rel}"

    # DP composition: mb shards over 'data', grads ride the int8 wire
    x = jax.random.normal(jax.random.fold_in(key, 5), (num_micro, 8, d))
    aux = {"tgt": jax.random.normal(jax.random.fold_in(key, 6),
                                    (num_micro, 8, d))}
    ref = pipeline_train_reference(stage_fn, loss_fn, ws, x, aux=aux,
                                   top=top)
    for wire, tol in (("fp32", 1e-5), ("int8", 0.03)):
        f = pipeline_train_step(stage_fn, loss_fn, mesh=mesh, axis="stage",
                                num_micro=num_micro, dp_axis="data",
                                grad_wire=wire)
        with mesh:
            loss, gws, gtop, _ = f(ws, x, aux=aux, top=top)
        rel = max(_tree_rel_err(gws, ref[1]), _tree_rel_err(gtop, ref[2]))
        assert abs(float(loss) - float(ref[0])) < 1e-5, (wire, loss)
        assert rel < tol, f"pipeline train dp ({wire}): rel err {rel}"

    # int8 activation/cotangent wire on the stage-boundary permutes
    for sched in ("1f1b", "gpipe"):
        f = pipeline_train_step(stage_fn, loss_fn, mesh=mesh, axis="stage",
                                num_micro=num_micro, dp_axis="data",
                                schedule=sched, act_wire="int8")
        with mesh:
            loss, gws, gtop, _ = f(ws, x, aux=aux, top=top)
        rel = max(_tree_rel_err(gws, ref[1]), _tree_rel_err(gtop, ref[2]))
        assert abs(float(loss) - float(ref[0])) / abs(float(ref[0])) < 0.02, \
            (sched, loss)
        assert rel < 0.05, f"pipeline train act_wire ({sched}): rel err {rel}"
    print("7. 1F1B/GPipe pipelined training ≡ jax.grad oracle OK "
          "(int8-wire DP grads + int8 stage-permute acts in envelope)")


def check_pipeline_lm_train_step():
    import dataclasses
    cfg = dataclasses.replace(configs.get_reduced("qwen2.5-14b"))
    params = init_lm_params(jax.random.PRNGKey(9), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(10), (16, 16), 0,
                              cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    opt = sgdm(1e-2)
    s_ref = make_train_step(cfg, opt, remat=False)
    _, _, m_ref = s_ref(params, opt[0](params), batch)

    mesh = make_mesh((8, 2), ("data", "stage"))
    p_sh = shard_rules.pipeline_tree_shardings(params, mesh,
                                               cfg.num_layers)
    s_pipe = jax.jit(make_pipeline_train_step(cfg, opt, mesh=mesh,
                                              num_micro=2,
                                              grad_wire="int8"))
    with mesh:
        _, _, m = s_pipe(jax.device_put(params, p_sh),
                         jax.device_put(opt[0](params),
                                        shard_rules.pipeline_tree_shardings(
                                            opt[0](params), mesh,
                                            cfg.num_layers)),
                         batch)
    diff = abs(float(m["loss"]) - float(m_ref["loss"]))
    assert diff < 5e-3, f"pipelined LM train loss diff {diff}"
    print(f"8. pipelined LM train step OK (loss diff {diff:.2e})")


def check_sp_attention():
    from repro.serve.sp import sp_decode_attention
    mesh = make_mesh((16,), ("data",))
    b, h, kv, hd, t = 2, 8, 4, 16, 64
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (b, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, hd))
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    cur = jnp.full((b,), 40)
    from repro.serve.sp import sp_attention_local
    o_ref, m_ref, l_ref = sp_attention_local(q, k, v, pos, cur)
    o_ref = o_ref / l_ref[..., None]
    with mesh:
        got = sp_decode_attention(mesh, "data", q, k, v, pos, cur)
    err = float(jnp.max(jnp.abs(got - o_ref)))
    assert err < 1e-5, f"sp attention: {err}"
    print("6. SP decode attention OK")


if __name__ == "__main__":
    check_moe_ep()
    check_gpipe()
    check_quantized_allreduce()
    check_sharded_train_step()
    check_sp_attention()
    check_pipeline_train()
    check_pipeline_lm_train_step()
    print("ALL DIST CHECKS PASSED")
