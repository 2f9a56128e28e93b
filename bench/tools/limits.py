"""Readings that a cell's check limits are set from, in one process.

    python bench/tools/limits.py --workload det320-backlog --seeds 1-12 \
        --seconds 3 --control-seeds 1-4 --fault-seed 13

For each seed of ``--seeds`` it makes one run of the cell as
``bench/run.py`` does (at the cell's own load, for ``--seconds``) and
records every compared number. For each seed of ``--control-seeds`` it
puts the control in the program's place: the plain reference computed
with int4 activations (the next precision below the configuration's
int8), whose detections, after the reference NMS and the float16 wire,
are compared with the int8 reference on the same frames the run checks.
With ``--fault-seed`` it also makes one run with each fault of
``faults.py`` planted in the timed path. Writes
``<out>/limits/<workload>.json`` (``--out``, default ``bench_out``) and
prints a summary.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def control_readings(cfg: dict, seed: int, n_frames: int,
                     act_bits: int = 4) -> dict:
    """The control's compared numbers over ``n_frames`` frames of the
    seed's pool, drawn as a run's check draws them."""
    import jax.numpy as jnp
    import numpy as np
    from bench.core import seeds, spec
    sysmod = spec.module("systems", cfg["system"])
    refmod = spec.module("reference", cfg["reference"])
    pool = sysmod.frames(cfg, seed)
    rng = seeds.rng(seed, "check")
    frames = np.sort(rng.choice(len(pool), size=min(n_frames, len(pool)),
                                replace=False))
    calib = jnp.asarray(pool[:1], jnp.float32) / 256.0
    key = seeds.jax_key(seed, "weights")
    ref = refmod.Reference(cfg, key, calib)
    low = refmod.Reference(cfg, key, calib, act_bits=act_bits)
    boxes, scores = ref.candidates(pool[frames])
    lboxes, lscores = low.candidates(pool[frames])
    readings = []
    for i in range(len(frames)):
        served = refmod.nms(cfg, lboxes[i], lscores[i])
        kept = refmod.nms(cfg, boxes[i], scores[i])
        readings.append(refmod.compare(cfg, served, boxes[i], scores[i],
                                       kept))
    return refmod.summarize(readings)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-4")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault-seed", type=int, default=None)
    ap.add_argument("--out", default="bench_out")
    args = ap.parse_args()
    from bench import run
    from bench.core import spec
    bench = spec.benchmark()
    cfg = spec.config(bench, spec.cell(bench, args.workload)["config"])
    out = {"workload": args.workload, "program": {}, "control": {},
           "faults": {}}
    for seed in seed_list(args.seeds):
        t = time.perf_counter()
        res, _ = run.run_cell(bench, args.workload, seed, args.seconds,
                              False, t_start=t)
        nums = {k: v["value"] for k, v in res["check"].items()}
        out["program"][seed] = nums
        print("program", seed, nums, f"{time.perf_counter() - t:.1f} s",
              flush=True)
    for seed in seed_list(args.control_seeds):
        nums = control_readings(cfg, seed, int(cfg["check"]["sample"]))
        out["control"][seed] = nums
        print("control", seed, nums, flush=True)
    if args.fault_seed is not None:
        from bench.tools.faults import FAULTS
        for name, hook in FAULTS.items():
            res, _ = run.run_cell(bench, args.workload, args.fault_seed,
                                  args.seconds, False,
                                  t_start=time.perf_counter(),
                                  system_hook=hook)
            nums = {k: v["value"] for k, v in res["check"].items()}
            out["faults"][name] = nums
            print("fault", name, nums, "correct", res["correct"], flush=True)
    for side in ("program", "control"):
        rows = list(out[side].values())
        if rows:
            agg = {k: (min(r[k] for r in rows), max(r[k] for r in rows))
                   for k in rows[0]}
            print(side, "min/max", agg)
    dest = ROOT / args.out / "limits"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{args.workload}.json").write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
