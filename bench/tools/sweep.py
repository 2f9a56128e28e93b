"""Find the knee of open-loop camera traffic, in one process.

    python bench/tools/sweep.py --config yolo-w1a8-320 --streams 8,12,16 \
        --fps 30 --seconds 8

Builds the configuration's system once (as ``bench/run.py`` does), warms
every batch width, then serves ``streams`` cameras at ``fps`` each for
``--seconds`` per point, open loop, and prints one JSON line per point:
frames due, failed and answered late, latency p50/p95, how much later the
last quarter of the window answered than the first (a growing queue), the
backlog left at the close, and the batch fill. The knee is the largest
count whose queue does not grow and where nothing fails; the cell then
runs at four fifths of it. Writes ``<out>/sweep/<config>.json``
(``--out``, default ``bench_out``).
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def point(system, pool, streams, fps, seconds, seed) -> dict:
    import numpy as np
    from bench.core import loadgen, seeds
    traffic = {"kind": "streams", "streams": streams, "fps": fps}
    d = loadgen.Feeder(None, system.request, len(pool), traffic,
                       system.width, seeds.rng(seed, "traffic"))
    d.sched = system.scheduler(d.on_result, d.span)
    d0 = system.dispatches()
    log = d.window(seconds)
    backlog = len(log.due) - d.answered
    dispatches = system.dispatches() - d0
    d.drain()
    due, done = np.asarray(log.due), np.asarray(log.done)
    ok = np.asarray(log.ok, bool)
    lat = (done - due) * 1e3
    q = len(due) // 4
    grow = (float(np.median(lat[-q:]) - np.median(lat[:q]))
            if q else float("nan"))
    return {"streams": streams, "rate": streams * fps, "due": len(due),
            "failed": int((~ok).sum()),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "late_p95_ms": float(np.percentile(
                (np.asarray(log.submit) - due) * 1e3, 95)),
            "last_minus_first_quarter_ms": grow,
            "backlog_at_close": backlog,
            "fill": float(ok.sum()) / max(1, dispatches * system.width)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="yolo-w1a8-320")
    ap.add_argument("--streams", default="8,12,16,20")
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default="bench_out")
    args = ap.parse_args()
    from bench import run
    from bench.core import loadgen, spec
    run.devices_for(1, require_tpu=True)
    run.enable_cache(run.CACHE_DIR)
    cfg = spec.config(spec.benchmark(), args.config)
    sysmod = spec.module("systems", cfg["system"])
    pool = sysmod.frames(cfg, args.seed)
    system = sysmod.System(cfg, args.seed, pool)
    system.warm(loadgen.batch_sizes({"kind": "streams"}, system.width))
    rows = []
    for n in [int(s) for s in args.streams.split(",")]:
        row = point(system, pool, n, args.fps, args.seconds, args.seed)
        rows.append(row)
        print(json.dumps(row), flush=True)
    dest = ROOT / args.out / "sweep"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{args.config}.json").write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
