"""Training-stack tests: optimizers, grad-accum equivalence, checkpoint
restart (incl. elastic), loop preemption, data determinism, YOLO QAT step."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ckpt as ckpt_lib
from repro import configs
from repro.data import pipeline as data
from repro.launch.mesh import make_mesh
from repro.models import yolo
from repro.models.transformer import init_lm_params
from repro.optim import adafactor, adamw, apply_updates, sgdm
from repro.optim.schedules import cosine_schedule
from repro.train.loop import run_train
from repro.train.step import make_train_step
from repro.train.yolo_qat import make_yolo_train_step

tmap = jax.tree_util.tree_map


def _quad_params():
    return {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(1.5)}


@pytest.mark.parametrize("opt_name", ["adamw", "adafactor", "sgdm"])
def test_optimizers_minimize_quadratic(opt_name):
    opt = {"adamw": adamw(0.1),
           "adafactor": adafactor(lambda s: 0.5 / jnp.sqrt(s.astype(jnp.float32))),
           "sgdm": sgdm(0.05)}[opt_name]
    init, update = opt
    params = _quad_params()
    state = init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    for _ in range(60):
        g = jax.grad(loss)(params)
        upd, state = update(g, state, params)
        params = apply_updates(params, upd)
    assert float(loss(params)) < 0.05, f"{opt_name}: {float(loss(params))}"


def test_cosine_schedule_shape():
    sched = cosine_schedule(1e-3, 10, 100)
    assert float(sched(0)) == 0.0
    assert abs(float(sched(10)) - 1e-3) < 1e-9
    assert float(sched(100)) < 2e-4


def test_grad_accum_matches_full_batch():
    cfg = configs.get_reduced("qwen2.5-14b")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    ds = data.make_lm_dataset(cfg.vocab_size, 8, 8)
    toks, labels = data.lm_batch(ds, 0)
    batch = {"tokens": toks, "labels": labels}
    # sgdm: update ∝ grads, so accumulation equivalence is exact-ish
    # (adam would amplify 1e-8 summation-order noise to ±lr at sqrt(v)≈0)
    opt = sgdm(1e-2)
    s1 = make_train_step(cfg, opt, microbatches=1, remat=False)
    s4 = make_train_step(cfg, opt, microbatches=4, remat=False)
    p1, _, m1 = s1(params, opt[0](params), batch)
    p4, _, m4 = s4(params, opt[0](params), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-4
    diff = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree_util.tree_leaves(p1),
                               jax.tree_util.tree_leaves(p4)))
    assert diff < 5e-5, f"accum mismatch {diff}"


def test_remat_matches_no_remat():
    cfg = configs.get_reduced("chatglm3-6b")
    params = init_lm_params(jax.random.PRNGKey(1), cfg)
    ds = data.make_lm_dataset(cfg.vocab_size, 8, 4)
    toks, labels = data.lm_batch(ds, 3)
    batch = {"tokens": toks, "labels": labels}
    opt = adamw(1e-3)
    pa, _, ma = make_train_step(cfg, opt, remat=False)(params, opt[0](params),
                                                       batch)
    pb, _, mb = make_train_step(cfg, opt, remat=True)(params, opt[0](params),
                                                      batch)
    assert abs(float(ma["loss"]) - float(mb["loss"])) < 1e-5


def test_loss_decreases_lm():
    cfg = configs.get_reduced("chatglm3-6b")
    params = init_lm_params(jax.random.PRNGKey(2), cfg)
    opt = adamw(3e-3)
    step = jax.jit(make_train_step(cfg, opt, remat=False))
    state = opt[0](params)
    ds = data.make_lm_dataset(cfg.vocab_size, 16, 8)
    losses = []
    for i in range(40):
        toks, labels = data.lm_batch(ds, i)
        params, state, m = step(params, state,
                                {"tokens": toks, "labels": labels})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.25, losses[::8]


def test_data_pipeline_deterministic_and_sharded():
    ds = data.make_lm_dataset(1000, 32, 16)
    a1, _ = data.lm_batch(ds, 5)
    a2, _ = data.lm_batch(ds, 5)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    b, _ = data.lm_batch(ds, 6)
    assert not np.array_equal(np.asarray(a1), np.asarray(b))
    s0, _ = data.lm_batch(ds, 5, shard=0, num_shards=2)
    s1, _ = data.lm_batch(ds, 5, shard=1, num_shards=2)
    assert s0.shape == (8, 32)
    assert not np.array_equal(np.asarray(s0), np.asarray(s1))


def test_checkpoint_roundtrip_and_latest(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    d = str(tmp_path)
    ckpt_lib.save_checkpoint(d, 3, tree, metadata={"x": 1})
    ckpt_lib.save_checkpoint(d, 7, tmap(lambda x: x * 2, tree))
    assert ckpt_lib.latest_step(d) == 7
    restored, meta = ckpt_lib.restore_checkpoint(d, 3, tree)
    assert meta == {"x": 1}
    for x, y in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(tree)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_async_commit(tmp_path):
    tree = {"w": jnp.zeros((128, 128))}
    d = str(tmp_path)
    ckpt_lib.save_checkpoint(d, 1, tree, async_=True)
    ckpt_lib.wait_for_async()
    assert ckpt_lib.latest_step(d) == 1


def test_elastic_restore_new_sharding(tmp_path):
    """Save unsharded, restore onto a 4-device mesh — elastic rescale."""
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    d = str(tmp_path)
    ckpt_lib.save_checkpoint(d, 1, tree)
    devs = jax.devices()
    if len(devs) < 2:
        restored, _ = ckpt_lib.restore_checkpoint(d, 1, tree)
        assert np.array_equal(np.asarray(restored["w"]),
                              np.asarray(tree["w"]))
        return
    # largest power-of-two mesh that still divides the (8, 8) leaf
    n = next(d for d in (8, 4, 2) if len(devs) >= d)
    mesh = make_mesh((n,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = ckpt_lib.restore_checkpoint(d, 1, tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    assert np.array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


def test_run_train_with_restart(tmp_path):
    cfg = configs.get_reduced("granite-20b")
    opt = adamw(1e-3)
    step_fn = jax.jit(make_train_step(cfg, opt, remat=False))
    ds = data.make_lm_dataset(cfg.vocab_size, 8, 4)

    def batch_fn(i):
        t, l = data.lm_batch(ds, i)
        return {"tokens": t, "labels": l}

    params = init_lm_params(jax.random.PRNGKey(3), cfg)
    state = opt[0](params)
    d = str(tmp_path)
    p1, s1, n1 = run_train(train_step=step_fn, params=params,
                           opt_state=state, batch_fn=batch_fn, steps=4,
                           ckpt_dir=d, ckpt_every=2, async_ckpt=False,
                           print_fn=lambda *_: None)
    assert ckpt_lib.latest_step(d) == 4
    # restart from checkpoint and continue
    template = {"params": params, "opt_state": state}
    restored, _ = ckpt_lib.restore_checkpoint(d, 4, template)
    p2, s2, n2 = run_train(train_step=step_fn, params=restored["params"],
                           opt_state=restored["opt_state"],
                           batch_fn=batch_fn, steps=6, start_step=4,
                           ckpt_dir=d, ckpt_every=2, async_ckpt=False,
                           print_fn=lambda *_: None)
    assert n2 == 6 and ckpt_lib.latest_step(d) == 6


def test_run_train_preemption(tmp_path):
    cfg = configs.get_reduced("granite-20b")
    opt = adamw(1e-3)
    step_fn = jax.jit(make_train_step(cfg, opt, remat=False))
    ds = data.make_lm_dataset(cfg.vocab_size, 8, 4)
    params = init_lm_params(jax.random.PRNGKey(3), cfg)
    d = str(tmp_path)
    os.makedirs(d, exist_ok=True)
    open(os.path.join(d, "PREEMPT"), "w").close()

    def batch_fn(i):
        t, l = data.lm_batch(ds, i)
        return {"tokens": t, "labels": l}

    _, _, n = run_train(train_step=step_fn, params=params,
                        opt_state=opt[0](params), batch_fn=batch_fn,
                        steps=100, ckpt_dir=d, ckpt_every=50,
                        async_ckpt=False, print_fn=lambda *_: None)
    assert n == 1                      # preempted at the first boundary
    assert ckpt_lib.latest_step(d) == 1


def test_yolo_qat_loss_decreases():
    params = yolo.init_yolo_params(jax.random.PRNGKey(0))
    ds = data.make_detection_dataset(2)
    img, boxes, classes = data.detection_batch(ds, 0)
    params = yolo.calibrate_yolo(params, img)
    opt = adamw(2e-3)
    step = make_yolo_train_step(opt)
    state = opt[0](params)
    losses = []
    for i in range(6):
        img, boxes, classes = data.detection_batch(ds, i % 2)
        params, state, m = step(params, state, img, boxes, classes)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    # batches alternate (i % 2): compare same-batch losses across epochs
    assert losses[4] < losses[0], losses
    assert losses[5] < losses[1], losses
