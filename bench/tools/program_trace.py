"""One traced run of a cell that also reads the program's own spans and the
served bundle's layer scopes.

    python bench/tools/program_trace.py --workload det320-backlog --seed 7

Runs the cell as ``bench/run.py --trace 1`` does, handing a span recorder
(``repro.serve.tracing.Recorder``) to the backend and the scheduler of the
traced window only: the untraced pre-roll keeps the null recorder, so its
tick percentiles against the window's are what the recorder and the
profiler cost together. The layer scopes come from the served executable's
compiled HLO. Prints ``run.py``'s lines and result, then:

- the readings of the readers that read program spans and scopes;
- idle gaps by the innermost span open, program or harness;
- device seconds per layer scope of the served program, with the share
  under no scope;
- how much of the harness's ``backend.step`` time ``detect.*`` spans cover;
- the program's spans inside the longest tick.

``--slice`` also writes ``<out>/program_trace/slice.json`` for the
reduction's tests: the first two executions of the served program with
their ops and the op scopes, and the spans over them.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

READ = ("stage_ms_per_batch.backlog", "wait_ms_per_batch.backlog",
        "idle_in_stage_frac.backlog", "conv1_ms_per_batch.backlog",
        "queue_ms_p50.cams")
DETECT = ("detect.stage", "detect.dispatch", "detect.wait", "detect.unpack")


def covered(outer: list, inner: list) -> float:
    """Seconds of the ``outer`` intervals that ``inner`` intervals cover."""
    from bench.core import trace
    total = 0.0
    for s, e in trace.merge(outer):
        total += sum(b - a for a, b in trace.merge(trace.clip(inner, s, e)))
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="det320-backlog")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--slice", action="store_true")
    ap.add_argument("--out", default="bench_out")
    args = ap.parse_args()
    from bench import run as run_mod
    from bench.core import program, spec, trace
    from repro.serve import tracing

    bench = spec.benchmark()
    cfg = spec.config(bench, spec.cell(bench, args.workload)["config"])
    rec = tracing.Recorder()
    seen = {"runs": []}

    def hook(system):
        size = int(cfg["input_size"])
        hlo = system.backend.lower(size).compile().as_text()
        seen["scopes"] = program.op_scopes(hlo, program.layer_scopes(cfg))
        plain, made = system.scheduler, []

        def scheduler(sink, span):
            sched = plain(sink, span)
            made.append(sched)
            if len(made) == 2:   # the window's; the first is the pre-roll's
                sched.tracer = system.backend.tracer = rec
            return sched
        system.scheduler = scheduler

    class Keep(run_mod.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen["runs"].append(self)

    offset, idle = trace.offset, trace.idle_by_host_state

    def keep_offset(tr, host_start):
        seen["off"] = offset(tr, host_start)
        return seen["off"]

    def keep_idle(tr, spans, lo, hi):
        seen["harness"] = spans
        return idle(tr, spans, lo, hi)

    run_mod.Run = Keep
    trace.offset, trace.idle_by_host_state = keep_offset, keep_idle
    res, lines = run_mod.run_cell(bench, args.workload, args.seed,
                                  args.seconds, True, system_hook=hook)
    print("\n".join(lines))
    print(json.dumps(res))

    run = seen["runs"][-1]
    off = seen["off"]
    run.program_spans = [(n, s + off, d, k, a) for n, s, d, k, a in rec.items]
    run.op_scopes = seen["scopes"]
    lo, hi = run.trace_window
    readings = {m: spec.reader(m)(run) for m in READ}
    print("program readings:", json.dumps(readings))

    harness = seen["harness"]
    gaps = trace.idle_by_host_state(
        run.trace, harness + program.host_spans(run.program_spans), lo, hi)
    print("idle gaps by innermost span:",
          json.dumps([[k, round(v, 6)] for k, v in gaps[:10]]))

    execs = trace.modules_named(run.trace, run.bundle, lo, hi)
    per = program.scope_seconds(run.trace, execs, run.op_scopes)
    total = sum(per.values())
    if total:
        print(f"device seconds per scope over {len(execs)} executions "
              f"({total:.6f} s):")
        for k, v in per.most_common():
            print(f"  {k:8s} {v:.6f} s  {100 * v / total:.3f}%  "
                  f"{1e3 * v / len(execs):.4f} ms/execution")
        print(f"unscoped share {100 * per[program.OTHER] / total:.3f}%")

    steps = [(s, s + d) for n, s, d in harness
             if n == "backend.step" and lo <= s < hi]
    detect = [(s, s + d) for n, s, d, *_ in run.program_spans if n in DETECT]
    step_s = sum(e - s for s, e in trace.merge(steps))
    if step_s:
        cover = covered(steps, detect)
        print(f"backend.step {step_s:.6f} s, covered by detect.* "
              f"{cover:.6f} s ({100 * cover / step_s:.3f}%)")

    ticks = program.spans(run, "sched.tick")
    if ticks:
        _, t0, dur, key, _ = max(ticks, key=lambda sp: sp[2])
        print(f"longest tick {key}: {1e3 * dur:.3f} ms at "
              f"{t0 - lo:.3f} s into the traced window; its spans:")
        for n, s, d, k, a in sorted(run.program_spans, key=lambda sp: sp[1]):
            if t0 <= s <= t0 + dur and n != program.QUEUE:
                print(f"  +{1e3 * (s - t0):9.3f} ms {n:16s} "
                      f"{1e3 * d:9.3f} ms key {k} {a or ''}")

    if args.slice and len(execs) >= 2:
        from bench.tools.trace_layout import short
        a, b = execs[0][1], execs[1][2]
        tr = run.trace
        ops = [[d, short(t), s, du] for d, t, s, du in tr["ops"]
               if a <= s and s + du <= b]
        names = {trace.split_hlo(t)[0] for _, t, _, _ in ops}
        small = {
            "about": "two executions of the served bundle, their ops, the "
                     "layer scope of each op, and the harness's and the "
                     "program's spans over them, on the trace's clock (s)",
            "window": [a, b],
            "modules": [m for m in tr["modules"]
                        if a <= m[2] and m[2] + m[3] <= b],
            "ops": ops,
            "op_scopes": {n: run.op_scopes[n] for n in sorted(names)
                          if n in run.op_scopes},
            "spans": [list(sp) for sp in harness
                      if sp[1] <= b and sp[1] + sp[2] >= a],
            "program_spans": [[n, s, d, k, a_] for n, s, d, k, a_
                              in run.program_spans
                              if s <= b and s + d >= a]}
        out = ROOT / args.out / "program_trace"
        out.mkdir(parents=True, exist_ok=True)
        (out / "slice.json").write_text(json.dumps(small))


if __name__ == "__main__":
    main()
