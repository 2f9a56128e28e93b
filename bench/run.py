#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python bench/run.py --workload det320-backlog --seed 7 --seconds 20 --trace 0

Set-up (timed as ``setup_s``) builds the cell's system from the seed,
warms every shape the cell's traffic uses, and fills the frame pool.
The window then drives the system with the cell's traffic for
``--seconds``; answers still open at its close are waited for. With
``--trace 1`` the window runs under the profiler and the cell's
per-layer metrics are reported; otherwise its end-to-end metrics. A
traced run first serves the same traffic untraced for a few seconds, to
print what tracing costs the host path.

Afterwards the program's state is freed and a sample of the answers,
drawn from the seed, is compared with the configuration's plain
reference; ``correct`` says whether every compared number kept to its
limit. Diagnostics go to standard error, ending with each compared
number beside its limit; the last line of standard output is the JSON
result. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench.core import costs, loadgen, seeds, spec  # noqa: E402
from bench.core import trace as trace_mod  # noqa: E402
from bench.core.peaks import chip_peaks  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
PREROLL_S = 3.0          # untraced serving before a traced window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Counts:
    """Programs compiled or loaded from the persistent cache, counted
    through JAX's monitoring events; ``names`` keeps what each compile
    (or cache load) was of."""

    def __init__(self):
        import jax.monitoring as mon
        self.names = []
        self.misses = self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self) -> tuple:
        return len(self.names), self.misses, self.hits


class Run:
    """What the metric readers read: one run's log, counts and trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self) -> np.ndarray:
        """Requests due inside the window."""
        return self.due < self.seconds

    def served_in_window(self) -> np.ndarray:
        """Requests answered "ok" inside the window."""
        return self.ok & (self.done <= self.seconds)


def enable_cache(cache_dir) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``cache_dir``; every program is kept, however
    quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(cache_dir)
    pathlib.Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def profile_options():
    """Device ops only: no host or Python tracing (see core/trace.py)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    return opts


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def bench_marker(x):
    """The program whose first execution aligns host and trace clocks."""
    return x + 1


def tick_stats(log_) -> str:
    t = np.asarray(log_.tick_s) * 1e3
    if not len(t):
        return "no ticks"
    q = np.percentile(t, [50, 90, 99])
    i = int(t.argmax())
    return (f"tick_ms p50 {q[0]:.4f} p90 {q[1]:.4f} p99 {q[2]:.4f} "
            f"max {t[i]:.4f} at {log_.tick_start[i]:.3f} s "
            f"over {len(t)} ticks")


def check_answers(cfg, seed, pool, log_, served_fn, refmod) -> dict:
    """Compare a seeded sample of the window's answers with the
    reference; returns {number: its reading over the sample}."""
    import jax.numpy as jnp
    due = np.asarray(log_.due)
    ok = np.asarray(log_.ok)
    pick = np.flatnonzero(ok & (due < log_.end_s))
    n = min(int(cfg["check"]["sample"]), len(pick))
    rng = seeds.rng(seed, "check")
    sample = np.sort(rng.choice(pick, size=n, replace=False))
    frames = sorted({log_.frame[r] for r in sample})
    where = {f: i for i, f in enumerate(frames)}
    ref = refmod.Reference(cfg, seeds.jax_key(seed, "weights"),
                           jnp.asarray(pool[:1], jnp.float32) / 256.0)
    boxes, scores = ref.candidates(pool[frames])
    kept = [refmod.nms(cfg, b, s) for b, s in zip(boxes, scores)]
    worst = refmod.summarize([
        refmod.compare(cfg, served_fn(log_.output[r]), boxes[where[f]],
                       scores[where[f]], kept[where[f]])
        for r, f in ((r, log_.frame[r]) for r in sample)])
    worst["sampled"] = n
    return worst


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, peaks: dict = None,
             cache_dir=CACHE_DIR, t_start: float = None,
             system_hook=None) -> tuple:
    """One run of one cell; returns (result dict, stderr lines).
    ``system_hook(system)``, where given, may replace parts of the built
    system before the window (the fault tests use it)."""
    t_start = T_START if t_start is None else t_start
    cell = spec.cell(bench, workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    wanted = spec.metrics(bench, workload, trace)
    chips = int(cell["chips"])

    import jax
    devs = devices_for(chips, require_tpu)
    peaks = peaks or chip_peaks(devs[0].device_kind)
    cache = enable_cache(cache_dir)
    counts = Counts()
    lines = [f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)};"
             f" compile cache {cache}"]

    sysmod = spec.module("systems", cfg["system"])
    refmod = spec.module("reference", cfg["reference"])
    pool = sysmod.frames(cfg, seed)
    t_build = time.perf_counter()
    system = sysmod.System(cfg, seed, pool)
    t_warm = time.perf_counter()
    system.warm(loadgen.batch_sizes(traffic, system.width))
    marker = jax.jit(bench_marker)
    jax.block_until_ready(marker(np.zeros((8, 128), np.float32)))
    if system_hook is not None:
        system_hook(system)
    gc.collect()
    gc.freeze()          # set-up's objects are not scanned in the window
    t_ready = time.perf_counter()
    setup_s = t_ready - t_start
    c_setup = counts.snap()
    lines.append(
        f"setup_s {setup_s:.3f} (before build {t_build - t_start:.3f}, "
        f"build {t_warm - t_build:.3f}, compile+warm {t_ready - t_warm:.3f});"
        f" programs compiled {c_setup[1]}, loaded from the cache "
        f"{c_setup[2]}: {'cold' if c_setup[2] == 0 else 'warm'} cache")

    def new_feeder():
        d = loadgen.Feeder(None, system.request, len(pool), traffic,
                           system.width, seeds.rng(seed, "traffic"))
        d.sched = system.scheduler(d.on_result, d.span)
        return d

    if trace:
        pre = new_feeder()
        pre.window(min(PREROLL_S, seconds))
        pre.drain()
        lines.append(f"untraced pre-roll {min(PREROLL_S, seconds)} s: "
                     f"{tick_stats(pre.log)}")
        del pre
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=profile_options())
        t_marker = time.perf_counter()
        jax.block_until_ready(marker(np.zeros((8, 128), np.float32)))

    feeder = new_feeder()
    d0 = system.dispatches()
    c0 = counts.snap()
    wlog = feeder.window(seconds)
    c1 = counts.snap()
    dispatches = system.dispatches() - d0
    if trace:
        jax.profiler.stop_trace()
    drain_s = feeder.drain()
    in_window = counts.names[c0[0]:c1[0]]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    lines.append(f"compiles in window {len(in_window)}"
                 f"{' ' + repr(sorted(set(in_window))) if in_window else ''};"
                 f" peak_bytes_in_use {mem}; drain {drain_s:.3f} s")

    arr = wlog.arrays()
    ok = np.asarray(wlog.ok, bool)
    run = Run(cell=cell, cfg=cfg, traffic=traffic, seconds=float(seconds),
              chips=chips, peaks=peaks, width=system.width,
              setup_s=setup_s, due=arr["due"], submit=arr["submit"],
              done=arr["done"], ok=ok,
              tick_s=np.asarray(wlog.tick_s, np.float64),
              dispatches=dispatches, frame_ops=costs.frame_ops(cfg),
              kernel_calls=costs.w1a8_calls(cfg, system.width),
              bundle=system.program_name, trace=None)
    lines.append(f"window {seconds} s ({'traced' if trace else 'untraced'})"
                 f": {int(run.in_window().sum())} due, "
                 f"{int(run.served_in_window().sum())} served in window, "
                 f"{dispatches} dispatches; {tick_stats(wlog)}")

    served_fn = system.served
    spans = feeder.span.items
    window_host = (feeder.t0, feeder.t_end)
    system.close()
    del feeder, system
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    breakdown = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    if trace:
        t = time.perf_counter()
        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        tr = trace_mod.load(str(files[-1]))
        off = trace_mod.offset(tr, t_marker)
        lo, hi = window_host[0] + off, window_host[1] + off
        run.trace, run.trace_window = tr, (lo, hi)
        busy_s = trace_mod.busy(tr, lo, hi)
        device["busy_s"] = busy_s
        device["window_s"] = hi - lo
        spans = [(n, s + off, d) for n, s, d in spans]
        breakdown = {"device_ops": trace_mod.top_ops(tr, lo, hi),
                     "idle_gaps": trace_mod.idle_by_host_state(
                         tr, spans, lo, hi)[:10]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        lines.append(f"trace read in {time.perf_counter() - t:.3f} s: "
                     f"{len(tr['ops'])} device ops, busy {busy_s:.4f} s "
                     f"of {hi - lo:.4f} s")

    metrics = {}
    for m in wanted:
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t = time.perf_counter()
    worst = check_answers(cfg, seed, pool, wlog, served_fn, refmod)
    lines.append(f"check of {worst.pop('sampled')} sampled answers took "
                 f"{time.perf_counter() - t:.3f} s")
    limits = cfg["check"]["limits"]
    attempted = int(run.in_window().sum())
    failed = int((~ok[run.in_window()]).sum())
    numbers = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    numbers["failed"] = {"value": failed, "limit": 0}
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    for k in sorted(set(worst) - set(limits)):
        lines.append(f"{k} {worst[k]!r} (read, not compared)")
    for k, v in numbers.items():
        lines.append(f"{k} {v['value']!r} limit {v['limit']!r}")

    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, lines = run_cell(spec.benchmark(), args.workload, args.seed,
                             args.seconds, bool(args.trace))
    for line in lines:
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
