"""Serving launcher: ``python -m repro.launch.serve --workload {lm,detect}``.

Drives the serve-v3 Scheduler over a synthetic request stream against one of
the two backends:

  lm      — continuous-batched decode of an LM arch (--packed deploys 1-bit
            W1A8 weights, the paper's deployed form, and decodes with them).
            Runs the host-checked termination path AND the device-side
            done-mask path over the same request stream and records both —
            the done-mask run is the headline record, the host-checked run
            lands under ``baseline_host_check`` (token sequences asserted
            identical).
  detect  — the paper's deployed artifact: batched image requests through
            the packed-W1A8 YOLO Pallas path + NMS, with a core.verify
            alignment check against the float reference. Sweeps the K-deep
            dispatch window over K ∈ {1, 2, 4, 8} on the device-NMS wire
            (one shared executable via spawn(depth=K)), asserting every
            K ≥ 2 run bit-exact vs the K=1 single-shot payloads and
            completion in dispatch order; the HEADLINE record is the
            ``--depth`` run, with the full per-K saturation curve under
            ``depth_sweep``. Also runs single-shot and depth-2 raw-wire
            baselines — asserting the device-NMS detection set matches the
            raw-wire path and shrinks per-sync bytes ≥ 10×. ``--burst 4x``
            submits the whole stream as one burst (4× the slot width)
            through the bounded wait queue and asserts zero drops and ≤ 1
            host sync per tick. ``--replicas N`` (and ``--autoscale``)
            additionally routes the same stream through a fleet Router of
            N spawned replicas (serve.fleet) and asserts the payloads stay
            bit-exact vs the single-scheduler run.
  multires — bucketed multi-resolution admission: one detector artifact
            serving ``--buckets`` (default 256,320) image sizes through
            ONE scheduler, per-bucket batches packed off
            `ServeRequest.image_shape`, one fixed-width executable per
            bucket sharing packed weights. Asserts each bucket's raw head
            bit-exact vs its single-resolution reference run, then records
            the per-bucket × per-K saturation table on the device-NMS
            wire.
  compose — the detect→LM pipeline (`serve.compose`): detection emissions
            template into an LM prompt ("describe what was detected") and
            re-admit to the LMBackend on the same tick loop. Asserts zero
            lost / duplicated requests and hand-off determinism.

Writes/merges throughput + latency + occupancy + host-sync numbers into
``benchmarks/results/BENCH_serve.json`` (methodology: EXPERIMENTS.md §Serve).
``--gate-bench`` reads the committed record for the workload BEFORE
overwriting it and fails when the new ``host_sync_bytes_per_tick`` regresses
above committed × 1.05 (lm, detect) or ``img_per_s`` at the chosen K drops
below committed × 0.95 (detect, multires) — the CI guards on the serving
wire and the dispatch pipeline.
"""
from __future__ import annotations

import argparse
import json
import pathlib

DEFAULT_OUT = "benchmarks/results/BENCH_serve.json"


def _write_bench(path: str, workload: str, record: dict) -> None:
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    data = {}
    if p.exists():
        try:
            data = json.loads(p.read_text())
        except json.JSONDecodeError:
            data = {}
    data[workload] = record
    p.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    print(f"[bench] wrote {path} [{workload}]")


def _parse_burst(burst: str, slots: int) -> int:
    """'4x' → 4·slots requests submitted as one burst; '0' → streaming."""
    if not burst:
        return 0
    mult = burst[:-1] if burst.endswith(("x", "X")) else burst
    return int(mult) * slots


def run_lm(args) -> dict:
    import jax
    from repro import configs
    from repro.models.transformer import init_lm_params
    from repro.serve import (LMBackend, SamplingParams, Scheduler,
                             ServeRequest, deploy_lm, packed_param_bytes)

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    params = init_lm_params(jax.random.PRNGKey(args.seed), cfg)
    mode = "float"
    if args.packed:
        params = deploy_lm(params)
        acct = packed_param_bytes(params)
        print(f"[packed] {acct['packed_bytes']/1e6:.1f} MB "
              f"(bf16-equivalent {acct['bf16_equivalent_bytes']/1e6:.1f} MB, "
              f"{acct['ratio']:.1f}x smaller)")
        mode = "w1a8_eval"

    sp = SamplingParams(max_new=args.max_new, temperature=args.temperature,
                        stop_tokens=tuple(args.stop_token))

    def serve(done_mask: bool):
        backend = LMBackend(cfg, params, slots=args.slots,
                            max_len=args.max_len, mode=mode, seed=args.seed,
                            done_mask=done_mask)

        def stream():
            return [ServeRequest(rid=i, prompt=[2 + i, 11, 7 + i % 3],
                                 sampling=sp) for i in range(args.requests)]

        # warm pass on a throwaway scheduler compiles this backend's jitted
        # step (and warms the eager prefill ops) so both modes' measured
        # numbers are steady-state — same discipline as detect's warmup().
        # Both modes consume the PRNG stream identically in the warm pass,
        # so the measured token sequences stay comparable across modes.
        Scheduler(backend).run(stream())
        sched = Scheduler(backend)
        results = sched.run(stream())
        return results, sched.metrics.summary()

    host_results, host_summary = serve(done_mask=False)
    dm_results, summary = serve(done_mask=True)
    host_toks = {r.rid: r.tokens for r in host_results}
    dm_toks = {r.rid: r.tokens for r in dm_results}
    assert dm_toks == host_toks, "done-mask decode diverged from host check"
    print(f"served {len(dm_results)} requests, {summary['tokens']} tokens in "
          f"{summary['wall_s']:.2f}s ({summary['tok_per_s']:.1f} tok/s, "
          f"p50 tick {summary['tick_p50_ms']:.1f} ms, "
          f"occupancy {summary['batch_occupancy']:.2f}); "
          f"per-tick sync {summary['host_sync_bytes_per_tick']:.0f} B "
          f"done-mask vs {host_summary['host_sync_bytes_per_tick']:.0f} B "
          f"token-row host-checked")
    for r in dm_results[:3]:
        print(f"  req {r.rid} [{r.finish_reason}]: {r.tokens[:10]}...")
    return {"arch": args.arch, "reduced": args.reduced, "packed": args.packed,
            "slots": args.slots, "max_new": args.max_new,
            "termination": "device_done_mask",
            "sync_wire": "per-slot bool bitmask/tick + bulk tokens at finish",
            **summary,
            "baseline_host_check": {
                "termination": "host_token_check",
                "sync_wire": "token row/tick",
                **host_summary}}


def run_detect(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import verify
    from repro.models import detection, yolo
    from repro.serve import DetectionBackend, Scheduler, ServeRequest

    n_req = 2 if args.reduced else args.requests
    burst = _parse_burst(args.burst, args.slots)
    if burst:
        n_req = max(n_req, burst)
    rng = np.random.default_rng(args.seed)
    imgs_u8 = rng.integers(0, 256, (n_req, yolo.INPUT_SIZE, yolo.INPUT_SIZE,
                                    3), np.uint8)
    params, art = yolo.build_detector(
        jax.random.PRNGKey(args.seed),
        jnp.asarray(imgs_u8[:1], jnp.float32) / 256.0,
        profile=args.profile)

    def stream():
        return [ServeRequest(rid=i, image=imgs_u8[i]) for i in range(n_req)]

    def serve(backend):
        sched = Scheduler(backend, max_queue=max(n_req, 1))
        results = sched.run(stream())
        return results, sched.metrics.summary()

    # one compiled executable per wire, shared across every depth via
    # spawn(depth=K) — the sweep measures the window, not recompiles
    raw_t = DetectionBackend(art, slots=args.slots, depth=1,
                             profile=args.profile)
    raw_t.warmup()                        # compile outside the timed ticks
    dn_t = DetectionBackend(art, slots=args.slots, depth=1,
                            profile=args.profile, device_nms=True)
    dn_t.warmup()

    ss_results, ss_summary = serve(raw_t.spawn(depth=1))
    ov_results, ov_summary = serve(raw_t.spawn(depth=2))

    # K-deep saturation sweep on the headline device-NMS wire: results must
    # stay bit-exact vs single-shot and surface in dispatch order at any K
    depths = sorted({1, 2, 4, 8, args.depth})
    sweep_results, sweep_summaries, depth_sweep = {}, {}, {}
    for k in depths:
        res, summ = serve(dn_t.spawn(depth=k))
        assert [r.rid for r in res] == list(range(n_req)), \
            f"depth={k}: completions left dispatch order"
        sweep_results[k], sweep_summaries[k] = res, summ
        depth_sweep[str(k)] = {
            key: summ[key] for key in
            ("img_per_s", "tick_p50_ms", "tick_p95_ms", "ticks", "wall_s",
             "host_syncs_per_tick", "batch_occupancy")}
    base = {r.rid: r.detections for r in sweep_results[1]}
    for k in depths[1:]:
        for r in sweep_results[k]:
            for leaf, ref_v in base[r.rid].items():
                assert np.array_equal(np.asarray(r.detections[leaf]),
                                      np.asarray(ref_v)), \
                    f"depth={k} diverged from single-shot: rid {r.rid} " \
                    f"field {leaf!r}"
    # headline = the chosen-K sweep run (gated vs committed img_per_s)
    dn_results, summary = sweep_results[args.depth], sweep_summaries[args.depth]

    # K-deep correctness on the raw wire too: depth-2 serving is bit-exact
    # vs single-shot (same fixed-width executable, same batch composition)
    ss_raw = {r.rid: r.detections["raw"] for r in ss_results}
    for r in ov_results:
        assert np.array_equal(r.detections["raw"], ss_raw[r.rid]), \
            f"depth-2 raw head diverged for rid {r.rid}"

    # device-NMS wire correctness: same NMS ran on device in both modes —
    # the compact fp16/int8 emissions must carry the identical detection set
    host_sets = {r.rid: detection.detections_to_list(
        r.detections["boxes"], r.detections["scores"],
        r.detections["classes"]) for r in ov_results}
    for r in dn_results:
        got = detection.detections_to_list(
            r.detections["boxes"], r.detections["scores"],
            r.detections["classes"])
        ref = list(host_sets[r.rid])
        assert len(got) == len(ref) == r.detections["valid"], r.rid
        for d in got:
            for j, e in enumerate(ref):
                iou = float(detection.iou_cxcywh(
                    jnp.asarray(d["box_cxcywh"]),
                    jnp.asarray(e["box_cxcywh"])))
                if (d["class_id"] == e["class_id"] and iou > 0.9
                        and abs(d["score"] - e["score"]) < 0.01):
                    ref.pop(j)
                    break
            else:
                raise AssertionError(
                    f"device-NMS detection unmatched for rid {r.rid}: {d}")
    reduction = (ov_summary["host_sync_bytes_per_sync"]
                 / max(summary["host_sync_bytes_per_sync"], 1e-9))
    assert reduction >= 10.0, \
        f"device-NMS wire only {reduction:.1f}x smaller (need >= 10x)"

    if burst:
        assert summary["requests_dropped"] == 0, summary
        assert summary["requests_completed"] == n_req, summary
        assert summary["host_syncs_per_tick"] <= 1.0 + 1e-9, \
            f"host syncs/tick {summary['host_syncs_per_tick']} > 1"
        print(f"[burst] {n_req} requests ({args.burst}) drained: 0 dropped, "
              f"{summary['host_syncs_per_tick']:.2f} host syncs/tick, "
              f"queue depth max {summary['queue_depth_max']}")

    # fleet tier (--replicas N / --autoscale): the same stream through a
    # Router of spawned replicas must complete the same request-id set with
    # bit-exact payloads as the single-scheduler headline run above
    fleet_record = None
    if args.replicas > 1 or args.autoscale:
        from repro.serve.fleet import (Autoscaler, AutoscalerConfig,
                                       FleetMetrics, Router)
        template = dn_t.spawn(depth=args.depth)   # shares the warm executable
        scaler = None
        if args.autoscale:
            scaler = Autoscaler(AutoscalerConfig(
                min_replicas=args.replicas, max_replicas=2 * args.replicas))
        router = Router(template.spawn, replicas=args.replicas,
                        autoscaler=scaler, metrics=FleetMetrics(),
                        keep_results=True)
        fleet_results = router.run([ServeRequest(rid=i, image=imgs_u8[i])
                                    for i in range(n_req)])
        assert router.metrics.lost == 0 and router.metrics.dropped == 0
        dn_payloads = {r.rid: r.detections for r in dn_results}
        assert sorted(r.rid for r in fleet_results) == sorted(dn_payloads)
        for r in fleet_results:
            ref_p = dn_payloads[r.rid]
            for leaf in ref_p:
                assert np.array_equal(np.asarray(r.detections[leaf]),
                                      np.asarray(ref_p[leaf])), \
                    f"fleet payload diverged: rid {r.rid} field {leaf!r}"
        fleet_record = {"replicas": args.replicas,
                        "autoscale": bool(args.autoscale),
                        "equivalence": "completed-id sets equal, payloads "
                                       "bit-exact vs single-scheduler run",
                        **router.metrics.summary()}
        print(f"[fleet] {n_req} requests through {args.replicas} replicas"
              f"{' (+autoscale)' if args.autoscale else ''}: payloads "
              f"bit-exact vs single-scheduler run")

    # §6.3 alignment of the served (packed/Pallas) path vs float reference
    ref = np.asarray(yolo.yolo_forward_float(
        params, jnp.asarray(imgs_u8, jnp.float32) / 256.0), np.float64)
    served_raw = np.stack([r.detections["raw"] for r in
                           sorted(ov_results, key=lambda r: r.rid)])
    rep = verify.compare("serve_detect_raw", served_raw, ref, lsb=0.02)
    print(rep.row())
    n_boxes = [len(detection.detections_to_list(
        r.detections["boxes"], r.detections["scores"],
        r.detections["classes"])) for r in dn_results]
    curve = ", ".join(f"K={k}: {depth_sweep[str(k)]['img_per_s']:.2f}"
                      for k in depths)
    print(f"served {len(dn_results)} images in {summary['wall_s']:.2f}s "
          f"({summary['img_per_s']:.2f} img/s device-NMS depth={args.depth} "
          f"vs {ov_summary['img_per_s']:.2f} raw-wire depth-2 vs "
          f"{ss_summary['img_per_s']:.2f} single-shot, p50 tick "
          f"{summary['tick_p50_ms']:.1f} ms); saturation img/s [{curve}]; "
          f"detections/img {n_boxes}; "
          f"sync wire {summary['host_sync_bytes_per_sync']:.0f} B/dispatch "
          f"vs {ov_summary['host_sync_bytes_per_sync']:.0f} raw "
          f"({reduction:.1f}x smaller)")
    return {"reduced": args.reduced, "slots": args.slots,
            "burst": args.burst or None, "profile": args.profile,
            "pipelining": f"k_deep_window(depth={args.depth})",
            "depth": args.depth,
            "depth_sweep": depth_sweep,
            "nms": "device",
            "emission_wire": "fp16 boxes+scores, int8 classes, int32 valid",
            "sync_bytes_reduction_vs_raw_wire": reduction,
            "alignment": {"max_abs": rep.max_abs, "mean_abs": rep.mean_abs,
                          "within_1lsb": rep.within_1lsb},
            **({"fleet": fleet_record} if fleet_record else {}),
            **summary,
            "baseline_raw_wire": {"pipelining": "k_deep_window(depth=2)",
                                  "nms": "device_plus_raw_head_wire",
                                  **ov_summary},
            "baseline_single_shot": {"pipelining": "single_shot",
                                     "nms": "device_plus_raw_head_wire",
                                     **ss_summary}}


def run_multires(args) -> dict:
    """≥ 2 resolution buckets through ONE scheduler: per-bucket batches,
    per-bucket executables sharing packed weights, per-bucket references."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import yolo
    from repro.serve import DetectionBackend, Scheduler, ServeRequest

    buckets = tuple(int(b) for b in args.buckets.split(","))
    assert len(buckets) >= 2, "--workload multires needs >= 2 --buckets"
    n_req = max(2 * len(buckets), 4) if args.reduced else args.requests
    n_req = max(n_req, len(buckets))
    rng = np.random.default_rng(args.seed)
    # round-robin bucket assignment: mixed-size traffic through one queue
    sizes = [buckets[i % len(buckets)] for i in range(n_req)]
    imgs = [rng.integers(0, 256, (s, s, 3), np.uint8) for s in sizes]
    _, art = yolo.build_detector(
        jax.random.PRNGKey(args.seed),
        jnp.asarray(imgs[0][None], jnp.float32) / 256.0,
        profile=args.profile, buckets=buckets)

    def stream(rids):
        return [ServeRequest(rid=i, image=imgs[i]) for i in rids]

    def serve(backend, rids):
        sched = Scheduler(backend, max_queue=n_req)
        results = sched.run(stream(rids))
        return results, sched.metrics.summary()

    raw_t = DetectionBackend(art, slots=args.slots, depth=args.depth,
                             profile=args.profile)
    raw_t.warmup()                       # compiles every bucket's executable
    mixed_results, mixed_raw_summary = serve(raw_t.spawn(), range(n_req))
    assert len(mixed_results) == n_req
    mixed_raw = {r.rid: r.detections["raw"] for r in mixed_results}
    for r in mixed_results:              # grid follows the request's bucket
        g = sizes[r.rid] // 32
        assert r.detections["raw"].shape == (g, g, 75), \
            (r.rid, r.detections["raw"].shape)

    # per-bucket reference: the same bucket sub-stream served alone (same
    # executable, same batch composition) must reproduce the mixed run's
    # raw heads bit-exactly
    for b in buckets:
        rids = [i for i in range(n_req) if sizes[i] == b]
        ref_results, _ = serve(raw_t.spawn(depth=1), rids)
        for r in ref_results:
            assert np.array_equal(r.detections["raw"], mixed_raw[r.rid]), \
                f"bucket {b}: mixed raw head diverged for rid {r.rid}"
    print(f"[multires] {n_req} mixed requests across buckets {buckets} "
          f"served through one scheduler; per-bucket raw heads bit-exact "
          f"vs single-resolution reference runs")

    # headline + saturation: device-NMS wire, per-bucket × per-K img/s
    dn_t = DetectionBackend(art, slots=args.slots, depth=args.depth,
                            profile=args.profile, device_nms=True)
    dn_t.warmup()
    dn_results, summary = serve(dn_t.spawn(), range(n_req))
    assert summary["requests_dropped"] == 0, summary
    assert sorted(r.rid for r in dn_results) == list(range(n_req))
    depths = (1, 2) if args.reduced else (1, 2, 4, 8)
    saturation = {}
    for b in buckets:
        rids = [i for i in range(n_req) if sizes[i] == b]
        saturation[str(b)] = {}
        for k in depths:
            _, summ = serve(dn_t.spawn(depth=k), rids)
            saturation[str(b)][str(k)] = {
                "img_per_s": summ["img_per_s"],
                "tick_p50_ms": summ["tick_p50_ms"],
                "tick_p95_ms": summ["tick_p95_ms"],
                "ticks": summ["ticks"]}
        curve = ", ".join(
            f"K={k}: {saturation[str(b)][str(k)]['img_per_s']:.2f}"
            for k in depths)
        print(f"[multires] bucket {b} saturation img/s [{curve}]")
    per_bucket = {str(b): sizes.count(b) for b in buckets}
    print(f"[multires] mixed headline {summary['img_per_s']:.2f} img/s at "
          f"depth={args.depth} ({per_bucket} images/bucket)")
    return {"reduced": args.reduced, "slots": args.slots,
            "profile": args.profile, "depth": args.depth,
            "buckets": list(buckets), "requests_per_bucket": per_bucket,
            "pipelining": f"k_deep_window(depth={args.depth})",
            "nms": "device",
            "reference": "per-bucket raw heads bit-exact vs "
                         "single-resolution runs",
            "saturation": saturation,
            **summary,
            "baseline_raw_wire": mixed_raw_summary}


def run_compose(args) -> dict:
    """Detect→LM composition on one tick loop, zero lost/duplicated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.models.transformer import init_lm_params
    from repro.serve import (ComposePipeline, ComposeRequest,
                             DetectionBackend, LMBackend, SamplingParams,
                             detections_to_prompt)
    from repro.models import yolo

    n_req = 3 if args.reduced else args.requests
    rng = np.random.default_rng(args.seed)
    bucket = int(args.buckets.split(",")[0])
    imgs = rng.integers(0, 256, (n_req, bucket, bucket, 3), np.uint8)
    _, art = yolo.build_detector(
        jax.random.PRNGKey(args.seed),
        jnp.asarray(imgs[:1], jnp.float32) / 256.0,
        profile=args.profile, buckets=(bucket,))
    detect = DetectionBackend(art, slots=args.slots, depth=args.depth,
                              profile=args.profile, device_nms=True)
    detect.warmup()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    lm_params = init_lm_params(jax.random.PRNGKey(args.seed + 1), cfg)
    lm = LMBackend(cfg, lm_params, slots=args.slots, max_len=args.max_len,
                   seed=args.seed)

    sp = SamplingParams(max_new=args.max_new, temperature=args.temperature,
                       stop_tokens=tuple(args.stop_token))
    pipe = ComposePipeline(detect, lm, vocab=cfg.vocab_size)
    results = pipe.run([ComposeRequest(rid=i, image=imgs[i], sampling=sp)
                        for i in range(n_req)])
    summary = pipe.summary()
    # conservation: every request surfaces exactly once, fully described
    assert summary["lost"] == 0 and summary["duplicated"] == 0, summary
    assert len(results) == n_req
    for r in results:
        assert r.finish_reason in ("length", "stop"), (r.rid, r.finish_reason)
        assert r.detections is not None and len(r.tokens) >= 1
        # hand-off determinism: the prompt IS the detections template
        assert r.prompt == detections_to_prompt(r.detections,
                                                vocab=cfg.vocab_size), r.rid
    assert len(pipe.handoffs) == n_req
    assert all(h.kind == "compose" for h in pipe.handoffs)
    print(f"[compose] {n_req} detect→LM requests completed on one tick "
          f"loop in {summary['ticks']} ticks: 0 lost, 0 duplicated; "
          f"prompts {[list(r.prompt) for r in results[:3]]}...")
    return {"reduced": args.reduced, "slots": args.slots,
            "arch": args.arch, "bucket": bucket, "depth": args.depth,
            "max_new": args.max_new,
            "prompt_template": "describe-token, count-token, class tokens",
            **{k: summary[k] for k in ("submitted", "completed", "lost",
                                       "duplicated", "handoffs", "ticks")},
            "detect": summary["detect"], "lm": summary["lm"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    choices=("lm", "detect", "multires", "compose"),
                    default="lm")
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--packed", action="store_true",
                    help="deploy 1-bit packed W1A8 weights (lm)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--stop-token", type=int, action="append", default=[],
                    help="token id ending a request early (repeatable)")
    ap.add_argument("--burst", default="",
                    help="submit the whole stream as one burst, e.g. 4x = "
                         "4×slots requests (detect)")
    ap.add_argument("--depth", type=int, default=2,
                    help="K-deep dispatch window for the headline detect/"
                         "multires/compose runs (the full K sweep is always "
                         "recorded for detect)")
    ap.add_argument("--buckets", default="",
                    help="comma-separated resolution buckets; defaults to "
                         "256,320 for multires and 320 for compose")
    ap.add_argument("--replicas", type=int, default=1,
                    help="detect: also run the stream through a fleet "
                         "Router of N spawned replicas and assert payload "
                         "bit-exactness vs the single-scheduler run")
    ap.add_argument("--autoscale", action="store_true",
                    help="detect: attach an Autoscaler "
                         "(--replicas..2x--replicas) to the fleet run")
    ap.add_argument("--profile", choices=("tuned", "default", "interpret"),
                    default="tuned",
                    help="kernel tuning profile for the detect backend "
                         "(tuned = committed autotune table winners, incl. "
                         "the fused conv+maxpool routing)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--gate-bench", action="store_true",
                    help="fail when host_sync_bytes_per_tick regresses >5%% "
                         "above the committed record (lm/detect) or "
                         "img_per_s at the chosen K drops >5%% below it "
                         "(detect/multires)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if not args.buckets:
        args.buckets = "256,320" if args.workload == "multires" else "320"

    committed = {}
    if args.gate_bench:
        p = pathlib.Path(args.out)
        if p.exists():
            try:
                committed = json.loads(p.read_text()).get(
                    args.workload) or {}
            except json.JSONDecodeError:
                committed = {}

    runner = {"lm": run_lm, "detect": run_detect,
              "multires": run_multires, "compose": run_compose}
    record = runner[args.workload](args)

    if args.gate_bench:
        if not committed:
            print(f"[gate] no committed {args.workload} record in "
                  f"{args.out} — gate records, next run enforces")
        else:
            if args.workload in ("lm", "detect") \
                    and committed.get("host_sync_bytes_per_tick") is not None:
                ref = committed["host_sync_bytes_per_tick"]
                got = record["host_sync_bytes_per_tick"]
                assert got <= ref * 1.05, \
                    (f"host_sync_bytes_per_tick regressed: {got:.1f} > "
                     f"committed {ref:.1f} x 1.05")
                print(f"[gate] host_sync_bytes_per_tick {got:.1f} <= "
                      f"committed {ref:.1f} x 1.05 OK")
            if args.workload in ("detect", "multires") \
                    and committed.get("img_per_s") is not None:
                ref = committed["img_per_s"]
                got = record["img_per_s"]
                assert got >= ref * 0.95, \
                    (f"img_per_s at depth={args.depth} regressed: "
                     f"{got:.2f} < committed {ref:.2f} x 0.95")
                print(f"[gate] img_per_s {got:.2f} >= committed "
                      f"{ref:.2f} x 0.95 OK")
            if args.workload == "compose":
                assert record["lost"] == 0 and record["duplicated"] == 0
                print("[gate] compose conservation OK (0 lost, "
                      "0 duplicated)")
    _write_bench(args.out, args.workload, record)


if __name__ == "__main__":
    main()
