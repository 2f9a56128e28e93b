"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the 512-device flag before any jax import side effect:
"""
import os  # noqa: E402
import sys  # noqa: E402
if "jax" not in sys.modules:
    # Only force the 512-device pool when jax is still fresh (module
    # execution / dry-run scripts). Library imports from an already-running
    # jax process (tests, notebooks) must not repoison the device count.
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse            # noqa: E402
import json                # noqa: E402
import re                  # noqa: E402
import time                # noqa: E402
import traceback           # noqa: E402

import jax                 # noqa: E402
import jax.numpy as jnp    # noqa: E402
import numpy as np         # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                      # noqa: E402
from repro.configs.shapes import SHAPES, skip_reason       # noqa: E402
from repro.dist import sharding as shard_rules  # noqa: E402
from repro.dist.pipeline import (bubble_fraction,           # noqa: E402
                                 bubble_fraction_1f1b)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.peaks import V5E, chip_peaks  # noqa: E402
from repro.models.transformer import ShardCtx, init_lm_params, lm_forward  # noqa: E402
from repro.optim import adafactor, adamw       # noqa: E402
from repro.serve import engine as serve_engine  # noqa: E402
from repro.serve.packed import deploy_lm       # noqa: E402
from repro.train.step import make_train_step   # noqa: E402

HW = chip_peaks(V5E)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")

# archs whose optimizer state must be factored (≥398B params)
BIG = {"kimi-k2-1t-a32b", "jamba-1.5-large-398b", "internvl2-76b"}


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(arch: str, shape_name: str) -> dict:
    """Shardable, weak-type-correct stand-ins (no device allocation)."""
    cfg = configs.get_config(arch)
    spec = SHAPES[shape_name]
    b, s = spec.global_batch, spec.seq_len
    f32 = jnp.float32
    out = {}
    if spec.kind in ("train", "prefill"):
        toks = s - (cfg.prefix_len if cfg.frontend == "vision" else 0)
        out["tokens"] = jax.ShapeDtypeStruct((b, toks), jnp.int32)
        if spec.kind == "train":
            out["labels"] = jax.ShapeDtypeStruct((b, toks), jnp.int32)
        if cfg.family == "encdec":
            out["encoder_embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                         f32)
        if cfg.frontend == "vision":
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.prefix_len, cfg.d_model), f32)
    else:                                   # decode: one new token + cache
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    return out


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _batch_shardings(specs: dict, mesh, dp) -> dict:
    out = {}
    for k, v in specs.items():
        axes = dp if (dp and v.shape[0] % _axsize(mesh, dp) == 0) else ()
        out[k] = NamedSharding(mesh, P(axes if axes else None,
                                       *([None] * (v.ndim - 1))))
    return out


def _axsize(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _cache_shardings(cache_shapes, mesh, cfg, *, dp, long_ctx: bool,
                     seq_shard_fallback: bool = False):
    """KV/SSM cache sharding: batch over dp when divisible; for long-context
    (batch 1) the KV sequence dim shards over 'data' (SP).

    seq_shard_fallback (§Perf): archs whose kv_heads don't divide |model|
    (granite kv=1, chatglm kv=2, qwen/mixtral/jamba kv=8) replicate the KV
    cache across the model axis by default — the fallback shards the cache
    *sequence* over 'model' instead (XLA partitions the masked softmax with
    a max/sum reduce pair), cutting decode cache memory 16×.
    """
    model = "model"

    def spec_for(path, leaf):
        shp = leaf.shape
        name = jax.tree_util.keystr(path)
        if "lengths" in name:
            return P()
        batch_ok = dp and shp[1] % _axsize(mesh, dp) == 0
        bspec = dp if batch_ok else None
        if "'k'" in name or "'v'" in name:                # (st,B,L,KV,hd)
            seq = "data" if (long_ctx and shp[2] % mesh.shape["data"] == 0
                             and not batch_ok) else None
            kvs = model if shp[3] % mesh.shape[model] == 0 else None
            if kvs is None and seq is None and seq_shard_fallback and \
                    shp[2] % mesh.shape[model] == 0:
                seq = model
            return P(None, bspec, seq, kvs, None)
        if "'pos'" in name:                               # (st,B,L)
            seq = "data" if (long_ctx and shp[2] % mesh.shape["data"] == 0
                             and not batch_ok) else None
            kvs_possible = cfg.num_kv_heads % mesh.shape[model] == 0
            if not kvs_possible and seq is None and seq_shard_fallback and \
                    shp[2] % mesh.shape[model] == 0:
                seq = model
            return P(None, bspec, seq)
        if "conv" in name:                                # (st,B,W-1,C)
            c = model if shp[-1] % mesh.shape[model] == 0 else None
            return P(None, bspec, None, c)
        if "ssm" in name:                                 # (st,B,H,P,N)|(st,B,C,N)
            c = model if shp[2] % mesh.shape[model] == 0 else None
            return P(*([None, bspec, c] + [None] * (len(shp) - 3)))
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, spec_for(p, l)) for p, l in flat])


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------

def build_train_cell(arch: str, shape_name: str, mesh, *,
                     microbatches: int = 8, mode: str = "w1a8_train"):
    cfg = configs.get_config(arch)
    dp = shard_rules.dp_axes(mesh)
    dtype = jnp.bfloat16 if arch in BIG else jnp.float32
    params_sds = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), cfg, dtype))
    opt = adafactor(1e-3) if arch in BIG else adamw(1e-3)
    opt_sds = jax.eval_shape(opt[0], params_sds)
    ctx = ShardCtx(mesh=mesh, dp_axes=dp, tp_axis="model",
                   ep_axis="data" if cfg.num_experts else None)
    step = make_train_step(cfg, opt, mode=mode, microbatches=microbatches,
                           ctx=ctx, remat=True)
    batch_specs = input_specs(arch, shape_name)
    p_sh = shard_rules.tree_shardings(params_sds, cfg, mesh)
    o_sh = shard_rules.tree_shardings(opt_sds, cfg, mesh)
    b_sh = _batch_shardings(batch_specs, mesh, dp)
    jitted = jax.jit(step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
    return jitted, (params_sds, opt_sds, batch_specs)


def build_prefill_cell(arch: str, shape_name: str, mesh, *,
                       mode: str = "w1a8_eval", packed: bool = True):
    cfg = configs.get_config(arch)
    dp = shard_rules.dp_axes(mesh)
    params_sds = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    if packed and cfg.w1a8_body:
        params_sds = jax.eval_shape(deploy_lm, params_sds)
    ctx = ShardCtx(mesh=mesh, dp_axes=dp, tp_axis="model",
                   ep_axis="data" if cfg.num_experts else None)
    batch_specs = input_specs(arch, shape_name)

    def fwd(params, batch):
        kw = {k: v for k, v in batch.items() if k != "tokens"}
        return lm_forward(cfg, params, batch["tokens"], mode=mode, ctx=ctx,
                          remat=True, **kw)

    p_sh = shard_rules.tree_shardings(params_sds, cfg, mesh)
    b_sh = _batch_shardings(batch_specs, mesh, dp)
    jitted = jax.jit(fwd, in_shardings=(p_sh, b_sh))
    return jitted, (params_sds, batch_specs)


def build_decode_cell(arch: str, shape_name: str, mesh, *,
                      mode: str = "w1a8_eval", packed: bool = True):
    cfg = configs.get_config(arch)
    spec = SHAPES[shape_name]
    dp = shard_rules.dp_axes(mesh)
    long_ctx = spec.global_batch < _axsize(mesh, dp)
    params_sds = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    if packed and cfg.w1a8_body:
        params_sds = jax.eval_shape(deploy_lm, params_sds)
    cache_sds = jax.eval_shape(
        lambda: serve_engine.init_cache(cfg, spec.global_batch, spec.seq_len,
                                        jnp.bfloat16))
    # MoE: batch-replicated EP still works (DESIGN §6); dp only if divisible
    ctx = ShardCtx(mesh=mesh,
                   dp_axes=dp if not long_ctx else (),
                   tp_axis="model",
                   ep_axis="data" if cfg.num_experts else None)
    tok_specs = input_specs(arch, shape_name)

    def step(params, cache, batch):
        return serve_engine.decode_step(cfg, params, cache, batch["tokens"],
                                        mode=mode, ctx=ctx)

    p_sh = shard_rules.tree_shardings(params_sds, cfg, mesh)
    c_sh = _cache_shardings(cache_sds, mesh, cfg, dp=dp, long_ctx=long_ctx)
    b_sh = _batch_shardings(tok_specs, mesh, dp if not long_ctx else ())
    jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                     out_shardings=(None, c_sh), donate_argnums=(1,))
    return jitted, (params_sds, cache_sds, tok_specs)


def build_cell(arch: str, shape_name: str, mesh, **kw):
    kind = SHAPES[shape_name].kind
    if kind == "train":
        return build_train_cell(arch, shape_name, mesh, **kw)
    if kind == "prefill":
        return build_prefill_cell(arch, shape_name, mesh, **kw)
    return build_decode_cell(arch, shape_name, mesh, **kw)


# ---------------------------------------------------------------------------
# Collective parsing + roofline terms (§Roofline)
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"(\w[\w\.\-]*)\s*=\s*(?:\()?\s*((?:s|f|u|bf|pred|c)[\w\[\],{}\s]*)"
    r"\s*(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"\(")
_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|s64|u64|f64)"
                       r"\[([\d,]*)\]")

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}


def parse_collectives(hlo_text: str) -> dict:
    """Sum output-shape bytes per collective kind from optimized HLO."""
    out = {k: 0 for k in ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute")}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(\S+)\s+(all-reduce|all-gather|reduce-scatter|"
                      r"all-to-all|collective-permute)\(", line)
        if not m:
            continue
        kind = m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES.get(dt, 4)
        out[kind] += nbytes
        counts[kind] += 1
    out["counts"] = counts
    return out


def wire_bytes(coll: dict, n_chips: int) -> float:
    """Effective per-chip ICI traffic (ring formulas).

    all-reduce ≈ 2·size·(n−1)/n; ag/rs ≈ size·(n−1)/n (size = full tensor);
    a2a ≈ size·(n−1)/n; permute = size. HLO shapes are per-device, so
    all-gather outputs are already global-sized; for all-reduce the shape is
    the (replicated) full tensor.
    """
    f = (n_chips - 1) / max(n_chips, 1)
    return (2 * coll["all-reduce"] * f + coll["all-gather"] * f +
            coll["reduce-scatter"] * f + coll["all-to-all"] * f +
            coll["collective-permute"])


def roofline_terms(flops: float, bytes_acc: float, coll_bytes: float,
                   n_chips: int) -> dict:
    """Three §Roofline terms, in seconds (totals are whole-program)."""
    t_comp = flops / (n_chips * HW["peak_flops_bf16"])
    t_mem = bytes_acc / (n_chips * HW["hbm_bw"])
    t_coll = coll_bytes / HW["ici_bw"]        # coll_bytes is per-chip wire
    dom = max(("compute", t_comp), ("memory", t_mem),
              ("collective", t_coll), key=lambda kv: kv[1])
    return {"t_compute_s": t_comp, "t_memory_s": t_mem,
            "t_collective_s": t_coll, "bottleneck": dom[0]}


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference)
    + attention score/value FLOPs (standard MFU accounting; causal ⇒ S²/2,
    SWA ⇒ window-bounded, SSM mixers ⇒ no quadratic term)."""
    cfg = configs.get_config(arch)
    params = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        n = int(np.prod(leaf.shape))
        if "_packed" in name:
            n *= 32                              # 1-bit storage, real MACs
        total += n
        if "['moe']" in name and re.search(
                r"\['(up|gate|down)(_packed)?'\]", name):
            active += n * cfg.top_k // max(cfg.num_experts, 1)
        else:
            active += n
    spec = SHAPES[shape_name]
    tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode" else 1)
    mult = 6 if spec.kind == "train" else 2
    flops = mult * active * tokens

    # attention term: 4·H·hd FLOPs per (query, key) pair (QKᵀ + PV)
    n_attn = sum(1 for i in range(cfg.num_layers)
                 if cfg.mixer_kind(i).startswith("attn"))
    n_local = sum(1 for i in range(cfg.num_layers)
                  if cfg.mixer_kind(i) == "attn_local" or
                  (cfg.sliding_window and not cfg.local_global and
                   cfg.mixer_kind(i) == "attn"))
    s = spec.seq_len
    per_pair = 4 * cfg.num_heads * cfg.hd
    if spec.kind == "decode":
        ctx_w = min(s, cfg.sliding_window or s)
        flops += spec.global_batch * per_pair * (
            (n_attn - n_local) * s + n_local * ctx_w)
    else:
        pairs_full = s * s / 2
        pairs_win = min(s * s / 2, s * (cfg.sliding_window or s))
        attn = spec.global_batch * per_pair * (
            (n_attn - n_local) * pairs_full + n_local * pairs_win)
        flops += attn * (3 if spec.kind == "train" else 1)
    return flops


# ---------------------------------------------------------------------------
# Pipeline bubble accounting (dist/pipeline helpers)
# ---------------------------------------------------------------------------

def pipeline_bubble_record(cfg, *, microbatches: int = 8) -> dict:
    """Schedule idle fractions if this arch's stage stack were pipelined:
    n = the natural stage partition (num_layers / period), M = the train
    cell's microbatch count. Reported in every train cell so launch tooling
    can size num_micro; the schedules themselves live in dist/pipeline."""
    n = cfg.num_layers // cfg.period
    return {"stages": n, "num_micro": microbatches,
            "gpipe_bubble": round(bubble_fraction(n, microbatches), 4),
            "1f1b_bubble": round(bubble_fraction_1f1b(n, microbatches), 4)}


def bubble_table(stages=(4,), micro=(4, 8, 16)) -> list:
    """gpipe-vs-1f1b idle fractions over (n, M) — the CI-produced source
    for the BENCH_* bench trajectory (see EXPERIMENTS.md §Pipeline)."""
    rows = []
    for n in stages:
        for m in micro:
            rows.append({"stages": n, "num_micro": m,
                         "gpipe_bubble": round(bubble_fraction(n, m), 4),
                         "1f1b_bubble": round(bubble_fraction_1f1b(n, m), 4)})
    return rows


def write_bubble_table(out_path: str = None) -> str:
    out_path = out_path or os.path.join(RESULTS_DIR,
                                        "BENCH_bubble_fraction.json")
    rows = bubble_table()
    out_dir = os.path.dirname(out_path)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rows, f, indent=1)
    print("| n | M | gpipe | 1f1b |")
    print("|---|---|-------|------|")
    for r in rows:
        print(f"| {r['stages']} | {r['num_micro']} | {r['gpipe_bubble']:.3f}"
              f" | {r['1f1b_bubble']:.3f} |")
    return out_path


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             save_hlo: bool = False, **kw) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
           "chips": n_chips}
    skip = skip_reason(arch, shape_name)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec
    if SHAPES[shape_name].kind == "train":
        rec["pipeline_bubble"] = pipeline_bubble_record(
            configs.get_config(arch))
    t0 = time.time()
    with mesh:
        jitted, arg_sds = build_cell(arch, shape_name, mesh, **kw)
        lowered = jitted.lower(*arg_sds)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k, 0) or 0)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")}
        cost = compiled.cost_analysis() or {}
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        rec["cost"] = {"flops": flops, "bytes_accessed": bytes_acc}
        hlo = compiled.as_text()
        coll = parse_collectives(hlo)
        rec["collectives"] = coll
        cw = wire_bytes(coll, n_chips)
        rec["collective_wire_bytes_per_chip"] = cw
        # CPU cost analysis reports whole-program totals; per-chip = /chips
        rec["roofline"] = roofline_terms(flops, bytes_acc, cw, n_chips)
        mf = model_flops(arch, shape_name)
        rec["model_flops"] = mf
        rec["useful_flops_ratio"] = mf / flops if flops else None
        if save_hlo:
            os.makedirs(RESULTS_DIR, exist_ok=True)
            fn = os.path.join(RESULTS_DIR,
                              f"hlo_{arch}_{shape_name}_{rec['mesh']}.txt")
            with open(fn, "w") as f:
                f.write(hlo)
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--bubble-table", action="store_true",
                    help="write benchmarks/results/BENCH_bubble_fraction"
                         ".json (gpipe vs 1f1b idle fractions) and exit")
    args = ap.parse_args()

    if args.bubble_table:
        path = write_bubble_table(args.out)
        print(f"wrote {path}")
        return

    archs = list(configs.ARCH_NAMES) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = args.out or os.path.join(RESULTS_DIR, "dryrun.json")
    results = []
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skipped")}
    results = [r for r in results if r.get("status") in ("ok", "skipped")]

    for mp in meshes:
        mesh_name = "2x16x16" if mp else "16x16"
        for arch in archs:
            for shape in shapes:
                if (arch, shape, mesh_name) in done:
                    continue
                print(f"=== {arch} × {shape} × {mesh_name}", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod=mp,
                                   save_hlo=args.save_hlo)
                except Exception as e:                     # noqa: BLE001
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                results.append(rec)
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)
                stat = rec.get("status")
                extra = ""
                if stat == "ok":
                    r = rec["roofline"]
                    extra = (f" comp={r['t_compute_s']:.3g}s "
                             f"mem={r['t_memory_s']:.3g}s "
                             f"coll={r['t_collective_s']:.3g}s "
                             f"→ {r['bottleneck']}")
                elif stat == "error":
                    extra = " " + rec["error"][:200]
                print(f"    {stat}{extra}", flush=True)


if __name__ == "__main__":
    main()
