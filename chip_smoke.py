#!/usr/bin/env python3
"""Chip smoke test: the paper's W1A8 detector server, once, on one TPU.

    python chip_smoke.py             # one chip: build, serve, check
    python chip_smoke.py --chips 4   # four chips: sharded + pipelined steps

One chip (the default): `build_detector` at 320x320x3 with seeded random
weights, a `DetectionBackend` with compiled Pallas kernels (32-wide
batches, 2-deep dispatch window, device NMS) plus a raw-head twin, and
`Scheduler.run` serving 80 seeded uint8 images (two full batches and a
partial one). It checks that every request completes in dispatch order,
that the compiled bundle holds a Pallas kernel (``tpu_custom_call``) for
each of the 9 W1A8 layers, and that the served raw head matches
`yolo_forward_float` within one 0.02 LSB everywhere.

Four chips (``--chips 4``): the EP+TP sharded train step on a (2 data x 2
model) mesh and the 1F1B pipelined steps on (2 stage x 2 data), each
against its one-device reference, and that their outputs span 4 devices.

Every check that fails raises, so the script exits non-zero; it exits
non-zero before running anything when JAX finds no TPU. Timings printed
here are smoke observations, not benchmark numbers. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_W1A8_LAYERS = 9           # conv2 .. conv10 (Table 1)
RAW_LSB = 0.02              # raw-head bound of tests/test_serve_detect.py
LOSS_TOL = 5e-3             # sharded / pipelined LM step vs one device
GRAD_REL_TOL = 1e-5         # 1F1B pipeline vs the sequential jax.grad oracle


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def count_custom_calls(hlo_text: str) -> int:
    """Pallas kernels in a compiled module's HLO text."""
    return sum('custom_call_target="tpu_custom_call"' in line
               for line in hlo_text.splitlines())


def _launch(cfg, spec, h: int, batch: int) -> str:
    """The grid a layer's kernel launches with: matmul tiles or conv rows
    per step, as the kernel entry points resolve them."""
    if cfg.op == "matmul":
        tiles = cfg.matmul_tiles(batch * h * h, spec.cin, spec.cout)
        return "tiles(bm,bk,bn)=" + ",".join(map(str, tiles))
    pooled = cfg.op == "conv3x3_pool" and cfg.fused
    return f"rows={cfg.conv_rows(h // 2 if pooled else h)}"


def serve_phase(*, size: int = 320, slots: int = 32, n_requests: int = 80,
                seed: int = 0) -> dict:
    """Build the detector, serve ``n_requests`` images twice (device-NMS
    wire and raw-head twin) and check the results; returns what it saw."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import verify
    from repro.models import yolo
    from repro.serve import DetectionBackend, Scheduler, ServeRequest

    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, (n_requests, size, size, 3), np.uint8)
    t0 = time.perf_counter()
    params, art = yolo.build_detector(
        jax.random.PRNGKey(seed), jnp.asarray(images[:1], jnp.float32) / 256)
    backend = DetectionBackend(art, slots=slots, depth=2, profile="tuned",
                               device_nms=True)
    twin = DetectionBackend(art, slots=slots, depth=2, profile="tuned")
    build_s = time.perf_counter() - t0
    sizes = yolo.spatial_sizes(size)
    for entry, (name, cfg) in zip(
            art["layers"][1:-1],
            yolo.layer_configs(art, size, slots, profile="tuned")):
        log(f"layer {name}: op={cfg.op} accum={cfg.accum} fused={cfg.fused} "
            f"{_launch(cfg, entry['spec'], sizes[name], slots)} "
            f"interpret={cfg.resolved_interpret()} source={cfg.source}")

    t0 = time.perf_counter()
    backend.warmup()
    twin.warmup()
    compile_s = time.perf_counter() - t0
    custom_calls = count_custom_calls(
        backend.lower(size).compile().as_text())
    log(f"set-up: build {build_s:.3f} s, compile+warmup {compile_s:.3f} s; "
        f"tpu_custom_call in the served bundle: {custom_calls}")

    def requests():
        return [ServeRequest(rid=i, image=images[i])
                for i in range(n_requests)]

    sched = Scheduler(backend)
    t0 = time.perf_counter()
    results = sched.run(requests())
    wall_s = time.perf_counter() - t0
    m = sched.metrics
    dropped = m.rejected + m.expired + m.expired_inflight
    served = sum(r.finish_reason == "ok" for r in results)
    log(f"served {served}/{n_requests} in {m.ticks} ticks, dropped "
        f"{dropped}, wall {wall_s:.3f} s (smoke observation)")
    check(served == n_requests and len(results) == n_requests,
          f"served {served}/{n_requests}")
    check(dropped == 0, f"{dropped} requests dropped")
    check([r.rid for r in results] == list(range(n_requests)),
          "completions are not in dispatch order")
    check(all(0 <= r.detections["valid"] <= backend.post["max_out"]
              for r in results), "device-NMS valid counts out of range")

    raw_results = Scheduler(twin).run(requests())
    check(sorted(r.rid for r in raw_results) == list(range(n_requests)),
          "raw-head twin lost requests")
    by_rid = {r.rid: r.detections["raw"] for r in raw_results}
    got = np.stack([by_rid[i] for i in range(n_requests)])
    ref_dev = jax.devices("cpu")[0]     # the float oracle off the chip
    ref_params = jax.device_put(params, ref_dev)
    forward = jax.jit(yolo.yolo_forward_float)
    ref = np.concatenate([
        np.asarray(forward(ref_params, jax.device_put(
            jnp.asarray(images[i:i + slots], jnp.float32) / 256, ref_dev)))
        for i in range(0, n_requests, slots)])
    rep = verify.compare("served_raw_vs_float", got.astype(np.float64),
                         ref.astype(np.float64), lsb=RAW_LSB)
    log(f"alignment (reference on {ref_dev.platform}): {rep.row()}")
    check(rep.max_abs < RAW_LSB and rep.within_1lsb == 1.0,
          f"served raw head off the float reference: {rep.row()}")
    return {"custom_calls": custom_calls, "served": served,
            "dropped": dropped, "compile_s": compile_s, "wall_s": wall_s,
            "alignment": rep}


def _placement(tree) -> tuple:
    """(devices the leaves live on, leaves split across devices)."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    devices = {d for leaf in leaves for d in leaf.sharding.device_set}
    return len(devices), sum(not leaf.sharding.is_fully_replicated
                             for leaf in leaves)


def four_chip_phase(devices) -> dict:
    """Sharded and pipelined training steps over 4 devices, each against
    its one-device reference (the bounds of tests/dist_main.py checks 5, 7
    and 8); returns the differences."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.dist import sharding as shard_rules
    from repro.dist.pipeline import (pipeline_train_reference,
                                     pipeline_train_step)
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import ShardCtx, init_lm_params
    from repro.optim import sgdm
    from repro.train.step import make_pipeline_train_step, make_train_step

    check(len(devices) == 4, f"need 4 devices, got {len(devices)}")
    one = devices[0]
    opt = sgdm(1e-2)
    out = {}

    def lm_batch(cfg, key, shape):
        toks = jax.random.randint(key, shape, 0, cfg.vocab_size, jnp.int32)
        return {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

    def one_device_loss(cfg, params, batch):
        step = jax.jit(make_train_step(cfg, opt, remat=False))
        _, _, m = step(*jax.device_put((params, opt[0](params), batch), one))
        return float(m["loss"])

    with jax.default_matmul_precision("highest"):
        # EP+TP sharded train step; mixtral reduced, 4 experts, d_ff 64
        cfg = dataclasses.replace(configs.get_reduced("mixtral-8x7b"),
                                  num_experts=4, d_ff=64)
        params = init_lm_params(jax.random.PRNGKey(5), cfg)
        batch = lm_batch(cfg, jax.random.PRNGKey(6), (8, 16))
        ref = one_device_loss(cfg, params, batch)
        mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
        ctx = ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                       ep_axis="data")
        p_sh = shard_rules.tree_shardings(params, cfg, mesh)
        o_sh = shard_rules.tree_shardings(opt[0](params), cfg, mesh)
        b_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        step = jax.jit(make_train_step(cfg, opt, remat=True, ctx=ctx,
                                       microbatches=2),
                       in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, None))
        with mesh:
            new_params, _, m = step(jax.device_put(params, p_sh),
                                    jax.device_put(opt[0](params), o_sh),
                                    jax.device_put(batch, b_sh))
        diff = abs(float(m["loss"]) - ref)
        span, split = _placement(new_params)
        log(f"EP+TP sharded train step (2 data x 2 model): loss diff "
            f"{diff:.3e} vs one device; params span {span} devices, "
            f"{split} leaves split")
        check(diff < LOSS_TOL, f"sharded train loss diff {diff}")
        check(span == 4 and split > 0,
              f"sharded params span {span} devices, {split} split")
        out["sharded_loss_diff"] = diff

        # 1F1B pipeline schedule vs the sequential jax.grad oracle
        mesh = make_mesh((2, 2), ("stage", "data"), devices=devices)
        n, num_micro, d = 2, 4, 16
        key = jax.random.PRNGKey(8)
        ws = {"w": jax.random.normal(key, (n, d, d)) * 0.3,
              "b": jax.random.normal(jax.random.fold_in(key, 1), (n, d)) * .1}
        top = {"head": jax.random.normal(jax.random.fold_in(key, 2),
                                         (d, d)) * 0.2}
        x = jax.random.normal(jax.random.fold_in(key, 3), (num_micro, 8, d))
        aux = {"tgt": jax.random.normal(jax.random.fold_in(key, 4),
                                        (num_micro, 8, d))}

        def stage_fn(w, h):
            return jnp.tanh(h @ w["w"] + w["b"])

        def loss_fn(tp, y, a):
            return jnp.mean((y @ tp["head"] - a["tgt"]) ** 2)

        l_ref, g_ref, gt_ref, _ = pipeline_train_reference(
            stage_fn, loss_fn, *jax.device_put((ws, x), one),
            aux=jax.device_put(aux, one), top=jax.device_put(top, one))
        f = pipeline_train_step(stage_fn, loss_fn, mesh=mesh, axis="stage",
                                num_micro=num_micro, dp_axis="data",
                                schedule="1f1b")
        with mesh:
            loss, gws, gtop, _ = f(ws, x, aux=aux, top=top)
        rel = max(_tree_rel_err(gws, g_ref), _tree_rel_err(gtop, gt_ref),
                  abs(float(loss) - float(l_ref)) / abs(float(l_ref)))
        span, split = _placement(gws)
        log(f"1F1B pipeline (2 stage x 2 data) vs jax.grad oracle: max rel "
            f"err {rel:.3e} (loss and grads); stage grads span {span} "
            f"devices, {split} leaves split")
        check(rel < GRAD_REL_TOL, f"1F1B pipeline rel err {rel}")
        check(span == 4 and split > 0,
              f"pipeline grads span {span} devices, {split} split")
        out["pipeline_grad_rel_err"] = rel

        # pipelined LM train step (1F1B, int8 DP grads) vs one device
        cfg = configs.get_reduced("qwen2.5-14b")
        params = init_lm_params(jax.random.PRNGKey(9), cfg)
        batch = lm_batch(cfg, jax.random.PRNGKey(10), (16, 16))
        ref = one_device_loss(cfg, params, batch)
        mesh = make_mesh((2, 2), ("data", "stage"), devices=devices)

        def pipe_sh(tree):
            return shard_rules.pipeline_tree_shardings(tree, mesh,
                                                       cfg.num_layers)
        step = jax.jit(make_pipeline_train_step(cfg, opt, mesh=mesh,
                                                num_micro=2,
                                                grad_wire="int8"))
        with mesh:
            new_params, _, m = step(
                jax.device_put(params, pipe_sh(params)),
                jax.device_put(opt[0](params), pipe_sh(opt[0](params))),
                batch)
        diff = abs(float(m["loss"]) - ref)
        span, split = _placement(new_params)
        log(f"pipelined LM train step (2 data x 2 stage, 1F1B, int8 grads): "
            f"loss diff {diff:.3e} vs one device; params span {span} "
            f"devices, {split} leaves split")
        check(diff < LOSS_TOL, f"pipelined LM train loss diff {diff}")
        check(span == 4 and split > 0,
              f"pipelined params span {span} devices, {split} split")
        out["pipeline_lm_loss_diff"] = diff
    return out


def _tree_rel_err(got, want) -> float:
    import jax
    import numpy as np
    pairs = [(np.asarray(a, np.float64), np.asarray(b, np.float64))
             for a, b in zip(jax.tree_util.tree_leaves(got),
                             jax.tree_util.tree_leaves(want))]
    num = sum(float(np.sum((a - b) ** 2)) for a, b in pairs)
    den = sum(float(np.sum(b ** 2)) for _, b in pairs)
    return (num / den) ** 0.5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded and pipelined training "
                         "steps across four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device_kind: {devices[0].device_kind}; devices: {len(devices)}; "
        f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chip_phase(devices[:4])
    else:
        obs = serve_phase()
        check(obs["custom_calls"] >= N_W1A8_LAYERS,
              f"{obs['custom_calls']} tpu_custom_call in the served bundle, "
              f"want one per W1A8 layer ({N_W1A8_LAYERS})")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
