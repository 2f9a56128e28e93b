"""Record a small slice of a traced run, for the reduction's tests.

    python bench/tools/trace_layout.py --workload det320-backlog --seconds 3

Makes one traced run of the cell as ``bench/run.py --trace 1`` does and
writes ``<out>/trace_layout/slice.json`` (``--out``, default ``bench_out``):
the marker execution, the
first two executions of the served program with every device op inside
them, the ops between them, and the benchmark's host spans, all on the
trace's clock, with each op's HLO text cut after its opcode. Also prints
the count of Pallas calls in each execution.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def short(text: str) -> str:
    """An op's HLO text up to its opcode: what ``split_hlo`` and ``label``
    read."""
    from bench.core.trace import split_hlo
    name, opcode = split_hlo(text)
    head = text.split(" " + opcode + "(", 1)[0]
    return f"{head} {opcode}(...)" if opcode else text


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="det320-backlog")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default="bench_out")
    args = ap.parse_args()
    from bench import run
    from bench.core import spec, trace
    seen = {}
    load, idle = trace.load, trace.idle_by_host_state

    def keep_load(path):
        seen["tr"] = load(path)
        return seen["tr"]

    def keep_idle(tr, spans, lo, hi):
        seen["spans"], seen["window"] = spans, (lo, hi)
        return idle(tr, spans, lo, hi)

    trace.load, trace.idle_by_host_state = keep_load, keep_idle
    res, lines = run.run_cell(spec.benchmark(), args.workload, args.seed,
                              args.seconds, True)
    print("\n".join(lines))
    tr, (lo, hi) = seen["tr"], seen["window"]
    name = "jit__bundle"
    execs = trace.modules_named(tr, name, lo, hi)[:2]
    marker = [m for m in tr["modules"] if m[1].startswith(trace.MARKER)][:1]
    a, b = execs[0][1], execs[-1][2]
    small = {
        "window": [a, b],
        "modules": marker + [m for m in tr["modules"]
                             if a <= m[2] and m[2] + m[3] <= b],
        "ops": [[d, short(t), s, du] for d, t, s, du in tr["ops"]
                if a <= s and s + du <= b],
        "spans": [sp for sp in seen["spans"] if sp[1] <= b and
                  sp[1] + sp[2] >= a]}
    for ops in trace.ops_in(tr, execs):
        print("pallas calls in execution:",
              sum(trace.is_kernel(o) for _, o, _, _ in ops))
    out = ROOT / args.out / "trace_layout"
    out.mkdir(parents=True, exist_ok=True)
    (out / "slice.json").write_text(json.dumps(small))
    print(json.dumps(res)[:2000])


if __name__ == "__main__":
    main()
