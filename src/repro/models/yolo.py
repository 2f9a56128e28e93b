"""The paper's W1A8 YOLOv3-tiny-like detector (Table 1), three datapaths:

  float   — QAT training / eval model (the "ONNX Runtime" oracle role),
  int     — numpy int64 bit-exact deployment pipeline (the "RTL" role):
            Q0.8 input, Q5.11/Q2.14 Conv1, sign-PE with fixed-point Mul_prev
            fused into accumulation, (mult, shift) Div_current post-processing,
            Q1.15/Q4.12 Conv11 emitting signed Q*.15 raw (int32/2^15),
  kernel  — Pallas streaming path (bit-packed weights, fused epilogues).

Input 320×320×3 → output 10×10×75 (y/x/channel), 0.74 M params, 0.098 GFLOPs
under the paper's full-precision-ops convention (binary ops discounted).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp
from repro.core.qtensor import QTensor
from repro.core.quant import (ACT_QMAX, binarize_ste, binarize_weight,
                              lsq_fake_quant, lsq_grad_scale, quantize_act)
from repro.kernels import config as _cfg
from repro.kernels.config import KernelConfig
from repro.kernels.w1a8_conv import ops as conv_ops
from repro.kernels.w1a8_matmul import ops as mm_ops

PROFILES = ("tuned", "default", "interpret")


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    name: str
    kind: str          # "std" | "w1a8"
    cin: int
    cout: int
    ksize: int
    pool: bool


# Table 1, exactly.
YOLO_LAYERS = (
    ConvSpec("conv1", "std", 3, 16, 3, True),
    ConvSpec("conv2", "w1a8", 16, 32, 3, True),
    ConvSpec("conv3", "w1a8", 32, 64, 3, True),
    ConvSpec("conv4", "w1a8", 64, 128, 3, True),
    ConvSpec("conv5", "w1a8", 128, 128, 3, False),
    ConvSpec("conv6", "w1a8", 128, 128, 3, False),
    ConvSpec("conv7", "w1a8", 128, 128, 3, True),
    ConvSpec("conv8", "w1a8", 128, 128, 3, False),
    ConvSpec("conv9", "w1a8", 128, 64, 1, False),
    ConvSpec("conv10", "w1a8", 64, 64, 3, False),
    ConvSpec("conv11", "std", 64, 75, 1, False),
)

INPUT_SIZE = 320
NUM_ANCHORS, NUM_CLASSES = 3, 20          # 75 = 3 * (5 + 20), VOC
GRID = 10


# ---------------------------------------------------------------------------
# Parameter init / counting
# ---------------------------------------------------------------------------

def init_yolo_params(key: jax.Array, dtype=jnp.float32) -> dict:
    params = {}
    for spec in YOLO_LAYERS:
        key, sub = jax.random.split(key)
        fan_in = spec.ksize * spec.ksize * spec.cin
        w = jax.random.normal(sub, (spec.ksize, spec.ksize, spec.cin,
                                    spec.cout), dtype) / np.sqrt(fan_in)
        layer = {"w": w, "b": jnp.zeros((spec.cout,), dtype)}
        if spec.kind == "w1a8":
            # per-input-channel LSQ step for this layer's input (Mul_prev)
            layer["act_step"] = jnp.full((spec.cin,), 0.05, dtype)
        params[spec.name] = layer
    # conv11's input quantizer (its Mul_prev); output stays raw (Q*.15)
    params["conv11"]["act_step"] = jnp.full((64,), 0.05, dtype)
    return params


def count_params() -> dict:
    """Parameter count (weights + biases), matching the paper's 0.74 M."""
    weights = sum(s.ksize ** 2 * s.cin * s.cout for s in YOLO_LAYERS)
    biases = sum(s.cout for s in YOLO_LAYERS)
    return {"weights": weights, "biases": biases, "total": weights + biases}


def spatial_sizes(input_size: int = INPUT_SIZE) -> dict:
    """Input H=W per layer (Table 2 progression) for one resolution bucket.

    Any multiple of 32 (= 2^5, one halving per pool) keeps every pooled
    plane even, so the same layer stack serves 256/320/416/... buckets."""
    if input_size <= 0 or input_size % 32:
        raise ValueError(f"input size must be a positive multiple of 32 "
                         f"(5 pools), got {input_size}")
    sizes, h = {}, input_size
    for s in YOLO_LAYERS:
        sizes[s.name] = h
        if s.pool:
            h //= 2
    return sizes


def count_gflops() -> dict:
    """FLOPs under both conventions.

    `paper` — full-precision ops only (the paper's 0.098 GFLOPs convention):
    Conv1/Conv11 MACs×2 + their bias adds + maxpool compares + W1A8
    post-processing (scale+round ≈ 2 ops/output) + Mul_prev prologue.
    `total` — everything at face value incl. binary-weight MACs×2.
    """
    sizes = spatial_sizes()
    full, binary, aux = 0, 0, 0
    for s in YOLO_LAYERS:
        hw = sizes[s.name] ** 2
        macs = s.ksize ** 2 * s.cin * s.cout * hw
        if s.kind == "std":
            full += 2 * macs + s.cout * hw          # MACs + bias
        else:
            binary += 2 * macs                       # sign-controlled add/sub
            aux += s.cin * hw                        # Mul_prev m_i·a_i (PE prologue)
            aux += 3 * s.cout * hw                   # post: scale, bias, round/clip
        if s.pool:
            aux += 3 * s.cout * (sizes[s.name] // 2) ** 2  # 2×2 max = 3 cmp
    return {"paper_gflops": (full + aux) / 1e9,
            "total_gflops": (full + binary + aux) / 1e9,
            "binary_discount64_gflops": (full + aux + binary / 64) / 1e9}


# ---------------------------------------------------------------------------
# Float forward (QAT train / eval oracle)
# ---------------------------------------------------------------------------

def _conv2d(x: jax.Array, w: jax.Array) -> jax.Array:
    # HIGHEST: the TPU's default precision rounds f32 operands to bf16,
    # which would drop bits of the Q5.11 conv1 and Q1.15 head weights.
    pad = "SAME" if w.shape[0] == 3 else "VALID"
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _maxpool2(x: jax.Array) -> jax.Array:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def yolo_forward_float(params: dict, images: jax.Array, *,
                       train: bool = False) -> jax.Array:
    """images: (B, 320, 320, 3) in [0, 1]. Returns (B, 10, 10, 75) raw head."""
    x = images
    for spec in YOLO_LAYERS:
        p = params[spec.name]
        if spec.kind == "std":
            if spec.name == "conv1":
                w = fxp.CONV1_W.roundtrip(p["w"]) if not train else p["w"]
                b = fxp.CONV1_B.roundtrip(p["b"]) if not train else p["b"]
                x = _conv2d(x, w) + b
                x = jax.nn.relu(x)
            else:  # conv11 detection head: quantize input, raw output
                if train:
                    gs = lsq_grad_scale(x.size // x.shape[-1])
                    xq = lsq_fake_quant(x, p["act_step"], jnp.asarray(gs, x.dtype))
                    x = _conv2d(xq, p["w"]) + p["b"]
                else:
                    xq = quantize_act(x, p["act_step"]) * p["act_step"]
                    w = fxp.CONV11_W.roundtrip(p["w"])
                    b = fxp.CONV11_B.roundtrip(p["b"])
                    x = _conv2d(xq, w) + b
        else:
            if train:
                gs = lsq_grad_scale(x.size // x.shape[-1])
                xq = lsq_fake_quant(x, p["act_step"], jnp.asarray(gs, x.dtype))
                wb = binarize_ste(p["w"])
            else:
                xq = quantize_act(x, p["act_step"]) * p["act_step"]
                wb = binarize_weight(p["w"])
            alpha = jax.lax.stop_gradient(
                jnp.mean(jnp.abs(p["w"]), axis=(0, 1, 2)))
            x = _conv2d(xq, wb) * alpha + p["b"]
            x = jax.nn.relu(x)
        if spec.pool:
            x = _maxpool2(x)
    return x


def calibrate_yolo(params: dict, images: jax.Array, *,
                   per_channel: bool = True) -> dict:
    """Range-calibrate every activation quantizer (LSQ init, per channel).

    Runs the float datapath layer by layer, setting each act_step so the
    observed per-channel max maps to code 255 — the deployment-time
    equivalent of LSQ's learned steps for an untrained/just-initialized net.

    ``per_channel=False`` calibrates one step per tensor (the scalar max,
    broadcast over channels) — the uniform-Mul_prev regime the FPGA PE
    actually implements (one fixed-point Mul_prev constant per layer ROM).
    Per-channel artifacts serve through every accum mode: the XNOR-popcount
    path folds the per-channel step ratio into the producer's epilogue
    (`yolo_forward_kernel`), so ``per_channel=True`` no longer restricts
    kernel selection.
    """
    params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    x = images
    for spec in YOLO_LAYERS:
        p = params[spec.name]
        if spec.kind == "w1a8" or spec.name == "conv11":
            axes = (0, 1, 2) if per_channel else None
            cmax = jnp.max(jnp.abs(x), axis=axes)
            step = jnp.maximum(cmax / ACT_QMAX, 1e-4)
            if not per_channel:
                step = jnp.broadcast_to(step, (x.shape[-1],))
            p = dict(p)
            p["act_step"] = step.astype(jnp.float32)
            params[spec.name] = p
        if spec.kind == "std":
            if spec.name == "conv1":
                x = jax.nn.relu(_conv2d(x, fxp.CONV1_W.roundtrip(p["w"]))
                                + fxp.CONV1_B.roundtrip(p["b"]))
            else:
                xq = quantize_act(x, p["act_step"]) * p["act_step"]
                x = _conv2d(xq, fxp.CONV11_W.roundtrip(p["w"])) \
                    + fxp.CONV11_B.roundtrip(p["b"])
        else:
            xq = quantize_act(x, p["act_step"]) * p["act_step"]
            alpha = jnp.mean(jnp.abs(p["w"]), axis=(0, 1, 2))
            x = jax.nn.relu(_conv2d(xq, binarize_weight(p["w"])) * alpha
                            + p["b"])
        if spec.pool:
            x = _maxpool2(x)
    return params


# ---------------------------------------------------------------------------
# Deployment: parameter extraction & fixed-point conversion (paper §4)
# ---------------------------------------------------------------------------

FM = 16  # fractional bits of the fixed-point Mul_prev inside the PE


def _requant_multshift(scale: np.ndarray, bits: int = 15):
    """scale → (mult int, rshift) with mult in [2^(bits-1), 2^bits):
    x·scale ≈ (x·mult) >> rshift  — the ONNX-style normalized requantizer."""
    scale = np.asarray(scale, np.float64)
    out_m = np.zeros(scale.shape, np.int64)
    out_s = np.zeros(scale.shape, np.int64)
    nz = scale > 0
    exp = np.floor(np.log2(scale[nz]))
    rshift = (bits - 1 - exp).astype(np.int64)
    mult = np.round(scale[nz] * (2.0 ** rshift)).astype(np.int64)
    # rounding may push mult to 2^bits; renormalize
    over = mult >= (1 << bits)
    mult[over] >>= 1
    rshift[over] -= 1
    out_m[nz], out_s[nz] = mult, rshift
    return out_m, out_s


def deploy_yolo(params: dict) -> dict:
    """Training params → integer deployment artifact (numpy, 'COE' role)."""
    art = {"layers": []}
    steps_next = {}  # step of each layer's *output* = next quant layer's input step
    for i, spec in enumerate(YOLO_LAYERS[:-1]):
        nxt = params[YOLO_LAYERS[i + 1].name]
        steps_next[spec.name] = np.asarray(
            jnp.broadcast_to(nxt["act_step"], (YOLO_LAYERS[i + 1].cin,)),
            np.float64)
    for spec in YOLO_LAYERS:
        p = {k: np.asarray(v, np.float64) for k, v in params[spec.name].items()}
        entry = {"spec": spec}
        if spec.name == "conv1":
            entry["w_raw"] = np.asarray(fxp.CONV1_W.quantize(
                jnp.asarray(p["w"], jnp.float32)), np.int64)
            entry["b_raw"] = np.asarray(fxp.CONV1_B.quantize(
                jnp.asarray(p["b"], jnp.float32)), np.int64)
            # acc scale 2^-19 (Q0.8 input × Q5.11 weights); bias at 2^-14 → <<5
            # post: /step_next ⇒ scale = 2^-19/step
            mult, shift = _requant_multshift(2.0 ** -19 / steps_next["conv1"])
            entry["post_mult"], entry["post_shift"] = mult, shift
        elif spec.name == "conv11":
            entry["w_raw"] = np.asarray(fxp.CONV11_W.quantize(
                jnp.asarray(p["w"], jnp.float32)), np.int64)
            entry["b_raw"] = np.asarray(fxp.CONV11_B.quantize(
                jnp.asarray(p["b"], jnp.float32)), np.int64)
            entry["m_raw"] = np.round(
                np.broadcast_to(p["act_step"], (spec.cin,)) * 2 ** FM
            ).astype(np.int64)
        else:
            w2 = p["w"].reshape(-1, spec.cout)
            entry["signs"] = np.where(w2 >= 0, 1, -1).astype(np.int64)
            alpha = np.mean(np.abs(w2), axis=0)
            entry["m_raw"] = np.round(
                np.broadcast_to(p["act_step"], (spec.cin,)) * 2 ** FM
            ).astype(np.int64)
            # post: y = acc·2^-FM·α + b, then /step_next — single fused
            # rounding: q = rshift(acc·mult + b_preshifted, shift)
            scale = alpha * 2.0 ** -FM / steps_next[spec.name]
            mult, shift = _requant_multshift(scale)
            entry["post_mult"], entry["post_shift"] = mult, shift
            entry["b_pre"] = np.round(
                p["b"] / steps_next[spec.name] * 2.0 ** shift).astype(np.int64)
        art["layers"].append(entry)
    return art


def _rshift_round(x: np.ndarray, shift: np.ndarray) -> np.ndarray:
    """Per-element rounding right-shift, half away from zero (RTL rounder)."""
    x = np.asarray(x, np.int64)
    half = np.where(shift > 0, np.int64(1) << np.maximum(shift - 1, 0), 0)
    mag = np.abs(x) + half
    return np.sign(x) * (mag >> shift)


def _im2col_np(x: np.ndarray, k: int) -> np.ndarray:
    b, h, w, c = x.shape
    if k == 1:
        return x.reshape(b, h, w, c)
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = [xp[:, dy:dy + h, dx:dx + w, :] for dy in range(3) for dx in range(3)]
    return np.concatenate(cols, axis=-1)


def yolo_forward_int(art: dict, images_u8: np.ndarray) -> np.ndarray:
    """Bit-exact integer pipeline (the RTL-analogue datapath).

    images_u8: (B, 320, 320, 3) uint8 raw pixels (Q0.8 codes, value = px/256).
    Returns (B, 10, 10, 75) int64 raw head output at Q*.15 (float = raw/2^15).
    """
    x = images_u8.astype(np.int64)                 # codes; scale 2^-8
    for entry in art["layers"]:
        spec: ConvSpec = entry["spec"]
        if spec.name == "conv1":
            cols = _im2col_np(x, 3)                                # (B,H,W,27)
            wf = entry["w_raw"].reshape(-1, spec.cout)             # (27,16) Q5.11
            acc = cols @ wf                                        # scale 2^-19
            acc = acc + (entry["b_raw"] << 5)                      # Q2.14 → 2^-19
            acc = np.maximum(acc, 0)                               # ReLU
            q = _rshift_round(acc * entry["post_mult"], entry["post_shift"])
            x = np.clip(q, 0, ACT_QMAX)
        elif spec.name == "conv11":
            cols = _im2col_np(x, spec.ksize)
            m9 = np.tile(entry["m_raw"], spec.ksize ** 2)
            wf = entry["w_raw"].reshape(-1, spec.cout)             # Q1.15
            acc = (cols * m9) @ wf                                 # 2^-(15+FM)
            raw = _rshift_round(acc, FM) + (entry["b_raw"] << 3)   # → Q*.15
            return raw
        else:
            cols = _im2col_np(x, spec.ksize)
            m9 = np.tile(entry["m_raw"], spec.ksize ** 2)
            acc = (cols * m9) @ entry["signs"]     # Eq. 3-4: fused Mul_prev PE
            q = _rshift_round(acc * entry["post_mult"] + entry["b_pre"],
                              entry["post_shift"])
            x = np.clip(q, 0, ACT_QMAX)            # post + ReLU-clip
        if spec.pool:
            b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
    raise AssertionError("unreachable")


# ---------------------------------------------------------------------------
# Pallas-kernel inference path (packed 1-bit weights, fused epilogues)
# ---------------------------------------------------------------------------

def deploy_yolo_kernel(params: dict) -> dict:
    """Training params → packed-weight artifact for the Pallas path."""
    art = {"layers": []}
    for i, spec in enumerate(YOLO_LAYERS):
        p = params[spec.name]
        entry = {"spec": spec}
        if spec.kind == "std":
            entry["w"] = jnp.asarray(p["w"], jnp.float32)
            entry["b"] = jnp.asarray(p["b"], jnp.float32)
            if spec.name == "conv11":
                entry["step_in"] = jnp.broadcast_to(p["act_step"], (spec.cin,))
        else:
            w2 = p["w"].reshape(-1, spec.cout)
            entry["w_packed"] = (conv_ops.conv_pack_weights(p["w"])
                                 if spec.ksize == 3 else
                                 mm_ops.w1a8_pack_weights(w2))
            entry["alpha"] = jnp.mean(jnp.abs(w2), axis=0).astype(jnp.float32)
            entry["step_in"] = jnp.broadcast_to(
                p["act_step"], (spec.cin,)).astype(jnp.float32)
            entry["b"] = jnp.asarray(p["b"], jnp.float32)
        if spec.name != "conv11":
            nxt = params[YOLO_LAYERS[i + 1].name]
            entry["step_out"] = jnp.broadcast_to(
                nxt["act_step"], (YOLO_LAYERS[i + 1].cin,)).astype(jnp.float32)
        art["layers"].append(entry)
    return art


def build_detector(key: jax.Array, calib_images: jax.Array, *,
                   per_channel: bool = None,
                   profile: str = None,
                   buckets=None) -> tuple:
    """Init + range-calibrate + pack: the serving-deployment recipe.

    calib_images (B, S, S, 3) float in [0, 1]. Returns
    (calibrated float params, deploy_yolo_kernel artifact) — the float
    params stay the verification oracle for the packed path
    (core.verify, DESIGN.md §10). ``per_channel`` defaults to True for
    every profile: per-channel calibration serves through all accum modes,
    including XNOR-popcount (the forward path folds the step ratio into
    the producer's epilogue — DESIGN.md §16), so calibration quality is
    never silently traded for kernel eligibility. ``profile`` names the
    tuning profile the artifact is destined for (recorded for callers; it
    no longer changes calibration).

    ``buckets`` declares the resolution buckets (image sides, each a
    multiple of 32) this artifact will serve, e.g. ``(256, 320, 416)``.
    The packed weights are resolution-independent — the buckets are
    recorded on the artifact (``art["buckets"]``) so `DetectionBackend`
    compiles one fixed-width executable per bucket, all sharing these
    weights. Default: the calibration image size.
    """
    if profile is not None and profile not in PROFILES:
        raise ValueError(f"profile must be one of {PROFILES}, got {profile!r}")
    if per_channel is None:
        per_channel = True
    if buckets is None:
        buckets = (int(calib_images.shape[1]),)
    buckets = tuple(dict.fromkeys(int(b) for b in buckets))
    for b in buckets:
        spatial_sizes(b)                 # validates the ×32 constraint
    params = init_yolo_params(key)
    params = calibrate_yolo(params, calib_images, per_channel=per_channel)
    art = deploy_yolo_kernel(params)
    art["buckets"] = buckets
    return params, art


def art_uniform_steps(art: dict) -> bool:
    """True iff every W1A8 layer's input steps are per-tensor uniform.

    Diagnostic only since the per-channel popcount fold landed: popcount
    is always eligible — uniform artifacts take the bit-exact identity
    fold, per-channel artifacts the producer-side uniformization."""
    for entry in art["layers"][1:-1]:
        steps = np.asarray(entry["step_in"])
        if not np.all(steps == steps.reshape(-1)[0]):
            return False
    return True


def yolo_layer_cells(batch: int = 1) -> list:
    """Structural autotune cells for every W1A8 layer.

    Returns [(layer name, op, dims)] with conv dims (h, w, cin, cout) of
    the input plane and matmul dims (m, k, n), m = batch·h·w. Pooled
    layers contribute both their ``conv3x3_pool`` cell (fused route) and
    the plain ``conv3x3`` cell (unfused route); duplicates across layers
    (conv5/6/8 share a shape) collapse by key.
    """
    sizes = spatial_sizes()
    cells = []
    for spec in YOLO_LAYERS:
        if spec.kind != "w1a8":
            continue
        h = sizes[spec.name]
        if spec.ksize == 3:
            if spec.pool:
                cells.append((spec.name, "conv3x3_pool",
                              (h, h, spec.cin, spec.cout)))
            cells.append((spec.name, "conv3x3", (h, h, spec.cin, spec.cout)))
        else:
            cells.append((spec.name, "matmul",
                          (batch * h * h, spec.cin, spec.cout)))
    return cells


def _layer_config(spec: ConvSpec, h: int, batch: int, *, profile: str,
                  accum, fuse_pool, interpret, table) -> KernelConfig:
    """Resolve one W1A8 layer's KernelConfig under the named profile.

    Explicit ``accum`` / ``fuse_pool`` / ``interpret`` kwargs override the
    profile's choice; "tuned" reads the autotune table (fastest accum —
    popcount is always eligible now that the per-channel fold exists —
    and fused-vs-unfused pool routing from the winning entry),
    "default"/"interpret" reproduce the historical heuristics.
    """
    if spec.ksize == 1:
        op, dims = "matmul", (batch * h * h, spec.cin, spec.cout)
    elif spec.pool:
        op, dims = "conv3x3_pool", (h, h, spec.cin, spec.cout)
    else:
        op, dims = "conv3x3", (h, h, spec.cin, spec.cout)
    if profile == "tuned":
        if accum is not None:
            cfg = _cfg.resolve(op, dims, accum=accum, table=table)
        else:
            cfg = _cfg.resolve_tuned(op, dims, table=table)
    else:
        cfg = KernelConfig(op=op, accum=accum or "dot", source=profile)
    if fuse_pool is not None:
        cfg = cfg.replace(fused=fuse_pool)
    elif profile != "tuned":
        cfg = cfg.replace(fused=False)     # historical default
    if interpret is not None:
        cfg = cfg.replace(interpret=interpret)
    elif profile == "interpret":
        cfg = cfg.replace(interpret=True)
    return cfg.replace(out_step=1.0)


def layer_configs(art: dict, size: int, batch: int, *,
                  profile: str = None, accum: str = None,
                  fuse_pool: bool = None, interpret: bool = None) -> list:
    """[(layer name, KernelConfig)] for every W1A8 layer of ``art`` at one
    (image side, batch) — exactly what `yolo_forward_kernel` launches, so
    callers can report the configs a served bundle runs with."""
    if profile is None:
        profile = "default"
    if profile not in PROFILES:
        raise ValueError(f"profile must be one of {PROFILES}, got {profile!r}")
    table = _cfg.load_table() if profile == "tuned" else None
    sizes = spatial_sizes(size)
    return [(e["spec"].name,
             _layer_config(e["spec"], sizes[e["spec"].name], batch,
                           profile=profile, accum=accum, fuse_pool=fuse_pool,
                           interpret=interpret, table=table))
            for e in art["layers"][1:-1]]


def yolo_forward_kernel(art: dict, images: jax.Array, *,
                        profile: str = None,
                        interpret: bool = None,
                        fuse_pool: bool = None,
                        accum: str = None) -> jax.Array:
    """Pallas streaming path. images (B,S,S,3) in [0,1] → (B,S/32,S/32,75)
    f32, for any bucket size S that is a multiple of 32 (default deployment
    S=320 → 10×10 grid). The layer stack, packed weights and per-layer
    configs are resolution-independent; only the spatial plan varies.

    Inter-layer tensors are uint8-code QTensors (requantized in each
    kernel's epilogue) — HBM activation traffic is 1 byte/elem, the
    streaming analogue; the codes+step pair crosses every layer boundary
    as one object.

    Per-layer launch configuration comes from ``profile``
    (`layer_configs`):

    * ``"default"`` (default) — heuristic tiles; Pallas compiled on a TPU
      and interpreted on any other backend.
    * ``"interpret"`` — heuristic tiles, interpret-mode Pallas everywhere.
    * ``"tuned"`` — per-layer winners from the committed autotune table
      (`kernels/config.resolve`, exact → nearest-shape → heuristic),
      including fastest-accum selection and the fused-vs-unfused pool
      routing the table measured.

    ``fuse_pool`` routes pooled W1A8 layers (conv2–4, conv7) through the
    fused conv+requant+MaxPool kernel (§5.2 Post+MaxPool stage chain) —
    bit-exact vs the unfused path, in both accum modes. ``accum="popcount"``
    contracts every W1A8 layer in the binary domain (XNOR-popcount); a
    per-channel-calibrated artifact serves through it via the producer-side
    step fold — when a layer's consumer contracts with popcount, the
    producer's epilogue requantizes onto the uniformized step
    s̄ = max_c s_c (div_eff = α/s̄, b_eff = b/s̄: one rounding, no extra
    clipping since s̄ ≥ s_c), so the codes reaching the bit-packed
    accumulation already sit on a per-tensor grid (DESIGN.md §16). All
    three kwargs override the profile.
    """
    layers = art["layers"]
    w1a8 = layers[1:-1]
    cfgs = [cfg for _, cfg in layer_configs(
        art, images.shape[1], images.shape[0], profile=profile, accum=accum,
        fuse_pool=fuse_pool, interpret=interpret)]

    def boundary_step(step_out, i):
        # the step the producer's epilogue quantizes ONTO; popcount
        # consumers get the uniformized s̄ = max_c s_c (producer-side fold)
        if i < len(cfgs) and cfgs[i].accum == "popcount":
            return jnp.broadcast_to(jnp.max(step_out), jnp.shape(step_out))
        return step_out

    # conv1 (std, fixed-point-rounded weights) in f32, then quantize to codes.
    # Every layer runs under a named scope of its name, so that a trace's
    # ops map to layers through their metadata.
    with jax.named_scope(layers[0]["spec"].name):
        w1 = fxp.CONV1_W.roundtrip(layers[0]["w"])
        b1 = fxp.CONV1_B.roundtrip(layers[0]["b"])
        x = jax.nn.relu(_conv2d(images, w1) + b1)
        x = _maxpool2(x)
        qx = QTensor.quantize_u8(x, boundary_step(layers[0]["step_out"], 0),
                                 axis=-1)

    for i, entry in enumerate(w1a8):
        spec: ConvSpec = entry["spec"]
        with jax.named_scope(spec.name):
            qx = _w1a8_layer(spec, entry, qx, cfgs[i],
                             boundary_step(entry["step_out"], i + 1))

    # conv11 detection head (std 1×1, fixed-point weights) on dequant codes.
    last = layers[-1]
    with jax.named_scope(last["spec"].name):
        xq = qx.dequantize()
        w11 = fxp.CONV11_W.roundtrip(last["w"])
        b11 = fxp.CONV11_B.roundtrip(last["b"])
        return _conv2d(xq, w11) + b11


def _w1a8_layer(spec: ConvSpec, entry: dict, qx: QTensor, cfg: KernelConfig,
                s_next: jax.Array) -> QTensor:
    """One W1A8 layer of `yolo_forward_kernel`: its Pallas kernel, named
    ``w1a8_<layer>``, on the input codes; ``s_next`` is the step its
    epilogue quantizes onto."""
    # Mul_prev = this layer's input steps (= qx.scale: the QTensor
    # carries exactly the dequant context the next kernel fuses);
    # per-channel requant is folded into the epilogue:
    # q = round(acc·(α/s_next) + b/s_next), out_step=1.
    mul_prev = qx.scale
    div_eff = entry["alpha"] / s_next
    b_eff = entry["b"] / s_next
    name = f"w1a8_{spec.name}"
    if spec.ksize == 3 and spec.pool:
        codes = conv_ops.w1a8_conv3x3_pool(
            qx.data, entry["w_packed"], mul_prev, div_eff, b_eff,
            cin=spec.cin, config=cfg, name=name)
    elif spec.ksize == 3:
        codes = conv_ops.w1a8_conv3x3(
            qx.data, entry["w_packed"], mul_prev, div_eff, b_eff,
            cin=spec.cin, config=cfg, name=name)
    else:
        b, h, w, _ = qx.data.shape
        codes = mm_ops.w1a8_matmul(
            qx.data.reshape(b * h * w, spec.cin), entry["w_packed"],
            mul_prev, div_eff, b_eff, k=spec.cin, config=cfg,
            name=name).reshape(b, h, w, spec.cout)
    return QTensor.from_codes(codes, s_next, axis=-1)
