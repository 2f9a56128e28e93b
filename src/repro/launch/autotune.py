"""Roofline-driven autotune harness for the W1A8 Pallas kernels.

Per (op, layer shape, accum, device) cell — the structural cells are every
W1A8 layer of the paper's Table 1 network (`models.yolo.yolo_layer_cells`)
— sweep the launch-config space (`bm/bn` for matmul, row blocking for conv
and fused conv+pool, fused-vs-unfused pool routing), measure wall time,
and persist the winner in the committed autotune table
(``benchmarks/results/AUTOTUNE_kernels.json``) that
`kernels.config.resolve` serves at run time. Every candidate is bit-exact
vs the heuristic default (asserted during the sweep) — blocking changes
the launch grid, never the per-row dot operands — so the table is a pure
perf artifact.

Alongside the table, every cell's roofline accounting goes to
``BENCH_kernels.json``: FLOP/byte, the v5e roofline-model time
(peaks from `launch/peaks.py`), the achieved-vs-roofline fraction, and
the tuned-vs-default speedup.
On the CPU interpret-mode runner the achieved fraction is a
correctness-trajectory number, not a hardware claim (EXPERIMENTS.md
§Roofline); ``speedup_vs_default`` is the dimensionless, host-portable
metric the CI perf gate protects:

    python -m repro.launch.autotune                    # full sweep
    python -m repro.launch.autotune --bench --reduced --gate-bench

``--bench`` re-measures the committed winners (no sweep) and rewrites
BENCH entries; ``--gate-bench`` fails when a cell's measured speedup
regresses beyond the noise band vs the committed BENCH_kernels.json
(the PR 5 serve-gate mechanics).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

from repro.launch.peaks import V5E, chip_peaks

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results"
AUTOTUNE_OUT = RESULTS_DIR / "AUTOTUNE_kernels.json"
BENCH_OUT = RESULTS_DIR / "BENCH_kernels.json"

V5E_FLOPS = chip_peaks(V5E)["peak_flops_bf16"]
V5E_BW = chip_peaks(V5E)["hbm_bw"]

# Reduced (CI) cells: the cheap half of the table — every op class and
# both accum modes stay covered, keys identical to the full table's.
REDUCED_MAX_H = 40


# ---------------------------------------------------------------------------
# Cells + candidates
# ---------------------------------------------------------------------------

def yolo_cells(batch: int = 1) -> list:
    """Deduped structural cells [(op, dims)] over the YOLO layers."""
    from repro.models.yolo import yolo_layer_cells
    seen, cells = set(), []
    for _, op, dims in yolo_layer_cells(batch):
        if (op, dims) not in seen:
            seen.add((op, dims))
            cells.append((op, dims))
    return cells


def _divisors_leq(n: int, cap: int) -> list:
    return [d for d in range(1, min(n, cap) + 1) if n % d == 0]


def candidates(op: str, dims, accum: str) -> list:
    """Candidate KernelConfigs for one cell (always includes the heuristic
    default as candidate 0). bk stays at the heuristic pick so every
    matmul candidate accumulates over the same K blocking — bit-exactness
    vs the default is by construction, and the sweep asserts it anyway."""
    from repro.kernels.config import KernelConfig
    out = []
    if op == "matmul":
        m, k, n = dims
        base = KernelConfig(op=op, accum=accum, out_step=1.0)
        out.append(base)
        bms = sorted({8, 32, 128, 256, min(512, max(8, m // 8 * 8))})
        bns = sorted({128, 256})
        for bm in bms:
            for bn in bns:
                out.append(base.replace(bm=bm, bn=bn))
    else:
        h = dims[0] if op == "conv3x3" else dims[0] // 2
        base = KernelConfig(op=op, accum=accum, out_step=1.0)
        rows_opts = _divisors_leq(h, 16)
        if op == "conv3x3_pool":
            # both accum modes sweep both pool routes: the fused kernel has
            # dot AND popcount datapaths (kernels/w1a8_conv/fused_pool.py)
            out.append(base)        # dataclass default: fused=True
            for fused in (True, False):
                for r in rows_opts:
                    out.append(base.replace(fused=fused, rows=r))
        else:
            out.append(base)
            for r in rows_opts:
                out.append(base.replace(rows=r))
    # dedup, keep first occurrence (the default stays candidate 0)
    seen, uniq = set(), []
    for c in out:
        if c not in seen:
            seen.add(c)
            uniq.append(c)
    return uniq


def _cand_key(cfg) -> str:
    return json.dumps(cfg.to_dict(), sort_keys=True)


def select_winner(measurements: list) -> tuple:
    """(t_us, config) winner from [(t_us, config)] — deterministic: ties on
    time break on the canonical JSON of the config."""
    return min(measurements, key=lambda m: (m[0], _cand_key(m[1])))


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _operands(op: str, dims, seed: int = 0):
    """Seeded canonical operands for one cell. The activation step is
    uniform (per-tensor) so the same operands serve both accum modes and
    the dot/popcount outputs are directly comparable (bit-exact)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.w1a8_conv import ops as conv_ops
    from repro.kernels.w1a8_matmul import ops as mm_ops
    rng = np.random.default_rng(seed)
    if op == "matmul":
        m, k, n = dims
        a = jnp.asarray(rng.integers(0, 256, (m, k), np.uint8))
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        wp = mm_ops.w1a8_pack_weights(w)
        mul = jnp.full((k,), 0.05, jnp.float32)
        div = jnp.asarray(rng.uniform(0.5, 2.0, (n,)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((n,)), jnp.float32)
        return a, wp, mul, div, b, {"k": k}
    h, w_, cin, cout = dims
    a = jnp.asarray(rng.integers(0, 256, (1, h, w_, cin), np.uint8))
    w = jnp.asarray(rng.standard_normal((3, 3, cin, cout)), jnp.float32)
    wp = conv_ops.conv_pack_weights(w)
    mul = jnp.full((cin,), 0.05, jnp.float32)
    div = jnp.asarray(rng.uniform(0.5, 2.0, (cout,)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((cout,)), jnp.float32)
    return a, wp, mul, div, b, {"cin": cin}


def _call(op: str, operands, cfg):
    from repro.kernels.w1a8_conv import ops as conv_ops
    from repro.kernels.w1a8_matmul import ops as mm_ops
    a, wp, mul, div, b, kw = operands
    fn = {"matmul": mm_ops.w1a8_matmul,
          "conv3x3": conv_ops.w1a8_conv3x3,
          "conv3x3_pool": conv_ops.w1a8_conv3x3_pool}[op]
    return fn(a, wp, mul, div, b, config=cfg, **kw)


def time_config(op: str, operands, cfg, iters: int = 3) -> float:
    """Min-of-iters wall µs after one warmup/compile call."""
    import jax
    jax.block_until_ready(_call(op, operands, cfg))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(_call(op, operands, cfg))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def time_pair(op: str, operands, cfg_a, cfg_b, iters: int = 5):
    """Min-of-iters µs for two configs with *interleaved* iterations.

    Timing each config in its own back-to-back block lets any transient
    host load land entirely on one side and corrupt the ratio; alternating
    a/b per iteration exposes both configs to the same conditions, and
    min-of-iters then extracts each one's clean run. This is what the CI
    perf gate compares, so the ratio's stability matters more than either
    absolute time.
    """
    import jax
    jax.block_until_ready(_call(op, operands, cfg_a))
    jax.block_until_ready(_call(op, operands, cfg_b))
    best_a = best_b = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(_call(op, operands, cfg_a))
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(_call(op, operands, cfg_b))
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a * 1e6, best_b * 1e6


def roofline(op: str, dims) -> dict:
    """FLOP + byte accounting for one cell (binary MACs at face value,
    uint8 activations, 1-bit packed weights, f32 epilogue params)."""
    if op == "matmul":
        m, k, n = dims
        flops = 2 * m * k * n + 3 * m * n
        nbytes = m * k + k * n / 8 + m * n + 4 * (k + 2 * n)
    else:
        h, w, cin, cout = dims
        flops = 2 * 9 * cin * cout * h * w + 5 * cout * h * w
        out_elems = h * w * cout * (0.25 if op == "conv3x3_pool" else 1.0)
        if op == "conv3x3_pool":
            flops += 3 * cout * (h // 2) * (w // 2)       # 2×2 max = 3 cmp
        nbytes = h * w * cin + 9 * cin * cout / 8 + out_elems \
            + 4 * (cin + 2 * cout)
    t_c, t_m = flops / V5E_FLOPS, nbytes / V5E_BW
    return {"flops": int(flops), "bytes": int(nbytes),
            "flop_per_byte": round(flops / nbytes, 2),
            "t_model_us_v5e": round(max(t_c, t_m) * 1e6, 4),
            "bound": "compute" if t_c >= t_m else "memory"}


# ---------------------------------------------------------------------------
# Sweep / bench drivers
# ---------------------------------------------------------------------------

def sweep_cell(op: str, dims, accum: str, iters: int = 3) -> dict:
    """Sweep one cell; returns its AUTOTUNE entry. Asserts every candidate
    is bit-exact vs the heuristic default before timing it.

    Each candidate is timed *paired + interleaved* against the default
    (`time_pair`) and ranked by its time ratio, not its absolute time —
    separate-block timings let transient host load crown false winners
    whose "speedup" then fails the CI gate on every honest re-measure.
    """
    import numpy as np
    operands = _operands(op, dims)
    cands = candidates(op, dims, accum)
    ref = np.asarray(_call(op, operands, cands[0]))
    measured = [(1.0, cands[0])]
    pair_us = {}
    for cfg in cands[1:]:
        out = np.asarray(_call(op, operands, cfg))
        assert np.array_equal(out, ref), \
            f"candidate not bit-exact: {op}/{dims}/{accum} {cfg}"
        t_def, t_cand = time_pair(op, operands, cands[0], cfg,
                                  max(iters, 5))
        measured.append((t_cand / t_def, cfg))
        pair_us[_cand_key(cfg)] = (t_def, t_cand)
    ratio_best, best = select_winner(measured)
    if _cand_key(best) in pair_us:
        t_default, t_best = pair_us[_cand_key(best)]
    else:  # default won: one config, one timing
        t_default = t_best = time_config(op, operands, cands[0],
                                         max(iters, 5))
    return {"op": op, "dims": list(dims), "accum": accum,
            "config": best.replace(source="table").to_dict(),
            "t_us": round(t_best, 1), "t_default_us": round(t_default, 1),
            "speedup_vs_default": round(1.0 / ratio_best, 3),
            "candidates_tried": len(cands), "iters": iters}


def bench_cell(op: str, dims, accum: str, entry: dict,
               iters: int = 3) -> dict:
    """Re-measure one committed winner vs the heuristic default (no sweep);
    returns its BENCH entry."""
    from repro.kernels.config import KernelConfig
    operands = _operands(op, dims)
    default = candidates(op, dims, accum)[0]
    tuned = KernelConfig.from_dict(entry["config"])
    if tuned == default:  # source is compare=False, so provenance is ignored
        # winner IS the heuristic default: one config, one timing — a second
        # measurement would gate pure run-to-run noise against itself
        t_default = t_tuned = time_config(op, operands, default, iters)
    else:
        t_default, t_tuned = time_pair(op, operands, default, tuned,
                                       max(iters, 5))
    return {"t_us": round(t_tuned, 1), "t_default_us": round(t_default, 1),
            "speedup_vs_default": round(t_default / t_tuned, 3),
            **roofline(op, dims)}


def _bench_from(entry: dict) -> dict:
    op, dims = entry["op"], tuple(entry["dims"])
    return {"t_us": entry["t_us"], "t_default_us": entry["t_default_us"],
            "speedup_vs_default": entry["speedup_vs_default"],
            **roofline(op, dims)}


def _finish_bench(bench: dict, key: str, t_us: float) -> None:
    bench[key]["achieved_frac_v5e"] = round(
        bench[key]["t_model_us_v5e"] / max(t_us, 1e-9), 6)


def _is_reduced(op: str, dims) -> bool:
    return op == "matmul" or dims[0] <= REDUCED_MAX_H


def run(args) -> int:
    from repro.kernels import config as kc
    cells = yolo_cells(batch=args.batch)
    if args.reduced:
        cells = [(op, dims) for op, dims in cells if _is_reduced(op, dims)]
    dev = kc.device_key()
    committed_bench = {}
    if BENCH_OUT.exists():
        committed_bench = json.loads(BENCH_OUT.read_text()).get("entries", {})
    table = {}
    if AUTOTUNE_OUT.exists():
        table = json.loads(AUTOTUNE_OUT.read_text()).get("entries", {})

    bench, failures = {}, []
    for op, dims in cells:
        for accum in ("dot", "popcount"):
            key = kc.shape_key(op, dims, accum, dev)
            if args.bench:
                entry = table.get(key)
                if entry is None:
                    print(f"[skip] no committed entry for {key}")
                    continue
                bench[key] = bench_cell(op, dims, accum, entry,
                                        iters=args.iters)
            else:
                entry = sweep_cell(op, dims, accum, iters=args.iters)
                table[key] = entry
                bench[key] = _bench_from(entry)
            _finish_bench(bench, key, bench[key]["t_us"])
            b = bench[key]
            print(f"{key}: {b['t_us']:.0f}us tuned vs {b['t_default_us']:.0f}"
                  f"us default ({b['speedup_vs_default']:.2f}x), "
                  f"{b['flop_per_byte']:.0f} flop/B {b['bound']}-bound, "
                  f"roofline frac {b['achieved_frac_v5e']:.2e}")
            if args.gate_bench and key in committed_bench:
                band = args.band
                new_s = b["speedup_vs_default"]
                old_s = committed_bench[key]["speedup_vs_default"]
                if new_s < old_s * (1 - band) and new_s < 1 - band:
                    failures.append(
                        f"{key}: speedup_vs_default {new_s:.2f} < committed "
                        f"{old_s:.2f} beyond {band:.0%} noise band")

    if not args.bench:
        AUTOTUNE_OUT.parent.mkdir(parents=True, exist_ok=True)
        AUTOTUNE_OUT.write_text(json.dumps(
            {"version": 1, "device": dev, "entries": table}, indent=1,
            sort_keys=True) + "\n")
        print(f"wrote {AUTOTUNE_OUT} ({len(table)} entries)")
    # like the serve gate: the committed record was read above, so the
    # regenerated file can overwrite it (CI uploads it as an artifact)
    merged = dict(committed_bench)
    merged.update(bench)
    BENCH_OUT.write_text(json.dumps(
        {"version": 1, "device": dev,
         "roofline": {"peak_flops": V5E_FLOPS, "hbm_bw": V5E_BW,
                      "note": "v5e roofline model; measured wall is the "
                              "host runner (interpret mode on CPU) — "
                              "speedup_vs_default is the gated metric"},
         "entries": merged}, indent=1, sort_keys=True) + "\n")
    print(f"wrote {BENCH_OUT} ({len(merged)} entries)")
    if failures:
        print("PERF GATE FAILED:")
        for f in failures:
            print("  " + f)
        return 1
    if args.gate_bench:
        print(f"perf gate OK ({len(bench)} cells within the "
              f"{args.band:.0%} band)")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", action="store_true",
                    help="re-measure committed winners only (no sweep)")
    ap.add_argument("--reduced", action="store_true",
                    help=f"cheap cells only (conv h <= {REDUCED_MAX_H} "
                         f"+ matmul) — the CI subset")
    ap.add_argument("--gate-bench", action="store_true",
                    help="fail when a cell's speedup_vs_default regresses "
                         "beyond --band vs committed BENCH_kernels.json")
    ap.add_argument("--band", type=float, default=0.25,
                    help="noise band for --gate-bench (default 0.25)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    raise SystemExit(run(args))


if __name__ == "__main__":
    main()
