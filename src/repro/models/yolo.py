"""The W1A8 detectors as layer graphs, three datapaths for the paper's:

  float   — QAT training / eval model (the "ONNX Runtime" oracle role),
  int     — numpy int64 bit-exact deployment pipeline (the "RTL" role):
            Q0.8 input, Q5.11/Q2.14 Conv1, sign-PE with fixed-point Mul_prev
            fused into accumulation, (mult, shift) Div_current post-processing,
            Q1.15/Q4.12 Conv11 emitting signed Q*.15 raw (int32/2^15),
  kernel  — Pallas streaming path (bit-packed weights, fused epilogues).

The paper's model (Table 1): input 320×320×3 → output 10×10×75
(y/x/channel), 0.74 M params, 0.098 GFLOPs under the paper's
full-precision-ops convention (binary ops discounted).

A model is a `Graph`: conv nodes (`ConvSpec`: standard or W1A8, kernel
size, stride, fused 2×2 pool) and `Node`s — darknet's ``shortcut`` (add a
named node's output to the previous node's), ``route`` (concatenate named
nodes' outputs), ``upsample`` (×2 nearest) and ``yolo`` (a detection head
over the previous node, with its anchors). The paper's model is the graph
`PAPER_GRAPH` (a chain with pools and one head); W1A8 YOLOv3
(`configs/yolov3_w1a8.py`) is another. Init, calibration, packing, counting
and the kernel path each walk the graph; the float and int datapaths are
the paper's chain.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

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
_LANES = 128                  # lanes of a TPU vector register


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    name: str
    kind: str          # "std" | "w1a8"
    cin: int
    cout: int
    ksize: int
    pool: bool
    stride: int = 1    # 3×3 convs: 1 or 2 (one pixel of padding a side)
    op = "conv"


@dataclasses.dataclass(frozen=True)
class Node:
    """A graph node that is not a conv: ``shortcut`` (``src`` = the node
    whose output is added to the previous node's), ``route`` (``src`` =
    the nodes whose outputs are concatenated on channels), ``upsample``
    (``factor``× nearest) or ``yolo`` (a head over the previous node;
    ``mask`` indexes the graph's anchors)."""
    name: str
    op: str
    src: tuple = ()
    mask: tuple = ()
    factor: int = 0


@dataclasses.dataclass(frozen=True)
class Graph:
    """A detector: nodes in order, anchors (w, h) as fractions of the input
    side, the classes, the published input side, and stage scopes
    ``((scope, first node), ...)`` that wrap runs of nodes in a trace."""
    nodes: tuple
    anchors: tuple
    num_classes: int = 20
    input_size: int = 320
    stages: tuple = ()

    @property
    def convs(self) -> tuple:
        return tuple(n for n in self.nodes if n.op == "conv")

    def head_anchors(self) -> tuple:
        """The anchors of each ``yolo`` node, in graph order."""
        return tuple(tuple(self.anchors[i] for i in n.mask)
                     for n in self.nodes if n.op == "yolo")

    def stage_of(self) -> dict:
        """{node name: its stage scope}, for nodes inside a stage."""
        firsts = dict((first, scope) for scope, first in self.stages)
        out, scope = {}, None
        for n in self.nodes:
            scope = firsts.get(n.name, scope)
            if scope is not None:
                out[n.name] = scope
        return out


def graph_rows(graph: Graph) -> list:
    """The graph as JSON-able rows, one per node: ``[name, "conv", kind,
    cin, cout, k, stride, pool]``, ``[name, "shortcut", from]``,
    ``[name, "route", [sources]]``, ``[name, "upsample", factor]``,
    ``[name, "yolo", [mask]]``."""
    rows = []
    for n in graph.nodes:
        if n.op == "conv":
            rows.append([n.name, "conv", n.kind, n.cin, n.cout, n.ksize,
                         n.stride, n.pool])
        elif n.op == "shortcut":
            rows.append([n.name, "shortcut", n.src[0]])
        elif n.op == "route":
            rows.append([n.name, "route", list(n.src)])
        elif n.op == "upsample":
            rows.append([n.name, "upsample", n.factor])
        else:
            rows.append([n.name, "yolo", list(n.mask)])
    return rows


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
# Anchor priors (fraction of image size), 3 anchors for the single head.
ANCHORS = ((0.12, 0.18), (0.32, 0.42), (0.72, 0.78))

PAPER_GRAPH = Graph(nodes=YOLO_LAYERS + (Node("yolo", "yolo",
                                              mask=(0, 1, 2)),),
                    anchors=ANCHORS, num_classes=NUM_CLASSES,
                    input_size=INPUT_SIZE)


def art_graph(art: dict) -> Graph:
    """The graph an artifact was packed for (the paper's by default)."""
    return art.get("graph", PAPER_GRAPH)


def _reads_images(graph: Graph, spec: ConvSpec) -> bool:
    return spec.name == graph.nodes[0].name


def _inputs(graph: Graph, i: int) -> tuple:
    """Names of the nodes whose outputs node ``i`` reads."""
    n = graph.nodes[i]
    if n.op == "route":
        return n.src
    prev = () if i == 0 else (graph.nodes[i - 1].name,)
    return prev + n.src if n.op == "shortcut" else prev


def _fused_shortcut(graph: Graph, i: int):
    """The shortcut node right after conv ``i``, which the conv's epilogue
    adds (`_segments` checks that it is the conv's only reader), or None."""
    nodes = graph.nodes
    if i + 1 < len(nodes) and nodes[i + 1].op == "shortcut":
        return nodes[i + 1]
    return None


@functools.lru_cache(maxsize=None)
def _segments(graph: Graph) -> dict:
    """{node: ((producer conv, channels), ...)}: which convs' quantized
    outputs make up each node's output channels, in order. A conv fused
    with the shortcut after it emits the shortcut's output; a head conv
    (a standard conv that reads codes) emits raw values and no segment."""
    seg = {}
    for i, n in enumerate(graph.nodes):
        if n.op == "conv":
            head = n.kind == "std" and not _reads_images(graph, n)
            seg[n.name] = () if head else ((n.name, n.cout),)
        elif n.op == "shortcut":
            conv = graph.nodes[i - 1]
            if conv.op != "conv" or conv.kind != "w1a8":
                raise ValueError(f"{n.name}: a shortcut follows the W1A8 "
                                 f"conv whose epilogue adds it")
            seg[n.name] = seg[conv.name]
        elif n.op == "route":
            seg[n.name] = sum((seg[s] for s in n.src), ())
        else:
            seg[n.name] = seg[graph.nodes[i - 1].name]
    for i, n in enumerate(graph.nodes):
        readers = [graph.nodes[j].name for j in range(len(graph.nodes))
                   if n.name in _inputs(graph, j)]
        if _fused_shortcut(graph, i) and readers != [graph.nodes[i + 1].name]:
            raise ValueError(f"{n.name} is fused with the shortcut after it"
                             f" and cannot be read by {readers}")
    return seg


@functools.lru_cache(maxsize=None)
def node_sides(graph: Graph, input_size: int) -> dict:
    """{node: (input side, output side)} at one input resolution; checks
    that every stride-2 conv and pool halves an even side, and that
    shortcuts and routes join planes of one side."""
    if input_size <= 0 or input_size % 32:
        raise ValueError(f"input size must be a positive multiple of 32 "
                         f"(5 pools), got {input_size}")
    out = {}
    for i, n in enumerate(graph.nodes):
        ins = [out[s][1] for s in _inputs(graph, i)] or [input_size]
        if len(set(ins)) != 1:
            raise ValueError(f"{n.name} joins planes of sides {ins}")
        h = ins[0]
        o = h
        if n.op == "conv":
            for halves in (n.stride == 2, n.pool):
                if halves:
                    if o % 2:
                        raise ValueError(f"{n.name} halves an odd side {o}")
                    o //= 2
        elif n.op == "upsample":
            o = h * n.factor
        out[n.name] = (h, o)
    return out


# ---------------------------------------------------------------------------
# Parameter init / counting
# ---------------------------------------------------------------------------

def init_yolo_params(key: jax.Array, dtype=jnp.float32,
                     graph: Graph = PAPER_GRAPH) -> dict:
    """Seeded init, one ``key, sub = split(key)`` per conv in graph order:
    w ~ normal / sqrt(fan_in), zero biases, and an input step (Mul_prev)
    for every conv that reads codes (W1A8 convs and heads)."""
    params = {}
    for spec in graph.convs:
        key, sub = jax.random.split(key)
        fan_in = spec.ksize * spec.ksize * spec.cin
        w = jax.random.normal(sub, (spec.ksize, spec.ksize, spec.cin,
                                    spec.cout), dtype) / np.sqrt(fan_in)
        layer = {"w": w, "b": jnp.zeros((spec.cout,), dtype)}
        if not _reads_images(graph, spec):
            # per-input-channel LSQ step for this layer's input (Mul_prev)
            layer["act_step"] = jnp.full((spec.cin,), 0.05, dtype)
        params[spec.name] = layer
    return params


def count_params(graph: Graph = PAPER_GRAPH) -> dict:
    """Parameter count (weights + biases), matching the paper's 0.74 M."""
    convs = graph.convs
    weights = sum(s.ksize ** 2 * s.cin * s.cout for s in convs)
    biases = sum(s.cout for s in convs)
    return {"weights": weights, "biases": biases, "total": weights + biases}


def spatial_sizes(input_size: int = None, graph: Graph = PAPER_GRAPH) -> dict:
    """Input H=W per conv (Table 2 progression) for one resolution bucket.

    Any multiple of 32 (= 2^5, one halving per pool or stride-2 conv)
    keeps every halved plane even, so the same graph serves
    256/320/416/... buckets."""
    sides = node_sides(graph, input_size or graph.input_size)
    return {s.name: sides[s.name][0] for s in graph.convs}


def count_gflops(graph: Graph = PAPER_GRAPH, input_size: int = None) -> dict:
    """FLOPs under both conventions.

    `paper` — full-precision ops only (the paper's 0.098 GFLOPs convention):
    Conv1/Conv11 MACs×2 + their bias adds + maxpool compares + W1A8
    post-processing (scale+round ≈ 2 ops/output) + Mul_prev prologue.
    `total` — everything at face value incl. binary-weight MACs×2.
    MACs count at a conv's output pixels (a stride-2 conv's quarter).
    """
    sides = node_sides(graph, input_size or graph.input_size)
    full, binary, aux = 0, 0, 0
    for s in graph.convs:
        h = sides[s.name][0]
        hw = h * h
        ohw = (h // s.stride) ** 2
        macs = s.ksize ** 2 * s.cin * s.cout * ohw
        if s.kind == "std":
            full += 2 * macs + s.cout * ohw         # MACs + bias
        else:
            binary += 2 * macs                       # sign-controlled add/sub
            aux += s.cin * hw                        # Mul_prev m_i·a_i (PE prologue)
            aux += 3 * s.cout * ohw                  # post: scale, bias, round/clip
        if s.pool:
            aux += 3 * s.cout * (h // 2) ** 2        # 2×2 max = 3 cmp
    return {"paper_gflops": (full + aux) / 1e9,
            "total_gflops": (full + binary + aux) / 1e9,
            "binary_discount64_gflops": (full + aux + binary / 64) / 1e9}


# ---------------------------------------------------------------------------
# Float forward (QAT train / eval oracle)
# ---------------------------------------------------------------------------

def _conv2d(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    # HIGHEST: the TPU's default precision rounds f32 operands to bf16,
    # which would drop bits of the Q5.11 conv1 and Q1.15 head weights.
    # A 3×3 conv pads one pixel a side (darknet's pad=1), at either stride.
    if w.shape[0] == 1:
        pad = "VALID"
    else:
        pad = "SAME" if stride == 1 else ((1, 1), (1, 1))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
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


def _upsample(x: jax.Array, factor: int) -> jax.Array:
    return jnp.repeat(jnp.repeat(x, factor, axis=1), factor, axis=2)


def calibrate_yolo(params: dict, images: jax.Array, *,
                   per_channel: bool = True,
                   graph: Graph = PAPER_GRAPH) -> dict:
    """Range-calibrate every activation quantizer (LSQ init, per channel).

    Runs the float datapath node by node, setting each act_step so the
    observed per-channel max maps to code 255 — the deployment-time
    equivalent of LSQ's learned steps for an untrained/just-initialized net.
    A shortcut adds its block input as the 8-bit codes the kernel path
    carries, on that tensor's own calibrated step.

    ``per_channel=False`` calibrates one step per tensor (the scalar max,
    broadcast over channels) — the uniform-Mul_prev regime the FPGA PE
    actually implements (one fixed-point Mul_prev constant per layer ROM).
    Per-channel artifacts serve through every accum mode: the XNOR-popcount
    path folds the per-channel step ratio into the producer's epilogue
    (`yolo_forward_kernel`), so ``per_channel=True`` no longer restricts
    kernel selection.
    """
    params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy

    def step_of(x):
        axes = (0, 1, 2) if per_channel else None
        cmax = jnp.max(jnp.abs(x), axis=axes)
        step = jnp.maximum(cmax / ACT_QMAX, 1e-4)
        if not per_channel:
            step = jnp.broadcast_to(step, (x.shape[-1],))
        return step.astype(jnp.float32)

    vals = {}
    for i, n in enumerate(graph.nodes):
        ins = [vals[s] for s in _inputs(graph, i)] or [images]
        x = ins[0]
        if n.op == "conv":
            p = params[n.name]
            if _reads_images(graph, n):
                x = jax.nn.relu(
                    _conv2d(x, fxp.CONV1_W.roundtrip(p["w"]), n.stride)
                    + fxp.CONV1_B.roundtrip(p["b"]))
            else:
                p = dict(p)
                p["act_step"] = step_of(x)
                params[n.name] = p
                xq = quantize_act(x, p["act_step"]) * p["act_step"]
                if n.kind == "std":
                    x = _conv2d(xq, fxp.CONV11_W.roundtrip(p["w"])) \
                        + fxp.CONV11_B.roundtrip(p["b"])
                else:
                    alpha = jnp.mean(jnp.abs(p["w"]), axis=(0, 1, 2))
                    x = jax.nn.relu(
                        _conv2d(xq, binarize_weight(p["w"]), n.stride)
                        * alpha + p["b"])
            if n.pool:
                x = _maxpool2(x)
        elif n.op == "shortcut":
            skip_step = step_of(ins[1])
            x = x + quantize_act(ins[1], skip_step) * skip_step
        elif n.op == "route":
            x = jnp.concatenate(ins, axis=-1)
        elif n.op == "upsample":
            x = _upsample(x, n.factor)
        vals[n.name] = x
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


def conv1_pooled_int(images_u8: np.ndarray, w, b, step) -> tuple:
    """The served conv1 on uint8 pixels (3×3 conv, ReLU, 2×2 max pool,
    codes on ``step``) with its integers in int64: the pooled accumulator
    max Σ a·n (pixel = a/256, Q5.11 weight = n/2048), then, in float32 as
    the kernel path does, x = acc·2^-19 + b (Q2.14), ReLU and
    ``clip(round(x / step), 0, 255)``. Returns (acc int64, codes uint8),
    each (B, S/2, S/2, cout)."""
    n = np.asarray(fxp.CONV1_W.quantize(jnp.asarray(w)), np.int64)
    acc = _im2col_np(np.asarray(images_u8, np.int64), 3) \
        @ n.reshape(-1, n.shape[-1])
    bsz, h, wd, c = acc.shape
    acc = acc.reshape(bsz, h // 2, 2, wd // 2, 2, c).max(axis=(2, 4))
    frac = fxp.INPUT_Q.frac_bits + fxp.CONV1_W.frac_bits
    x = acc.astype(np.float32) * np.float32(2.0 ** -frac) \
        + np.asarray(fxp.CONV1_B.roundtrip(jnp.asarray(b)), np.float32)
    v = np.maximum(x, np.float32(0)) / np.asarray(step, np.float32)
    q = np.trunc(v + np.where(v >= 0, np.float32(0.5), np.float32(-0.5)))
    return acc, np.clip(q, 0, ACT_QMAX).astype(np.uint8)


# ---------------------------------------------------------------------------
# Pallas-kernel inference path (packed 1-bit weights, fused epilogues)
# ---------------------------------------------------------------------------

def _producer_steps(graph: Graph, params: dict) -> dict:
    """{producer conv: the step its epilogue quantizes onto}: the slice of
    a reader's input step (act_step) that covers the producer's channels."""
    seg = _segments(graph)
    steps = {}
    for i, n in enumerate(graph.nodes):
        if n.op != "conv" or _reads_images(graph, n):
            continue
        step_in = jnp.broadcast_to(params[n.name]["act_step"], (n.cin,))
        off = 0
        for prod, ch in seg[graph.nodes[i - 1].name]:
            if prod not in steps:
                steps[prod] = step_in[off:off + ch].astype(jnp.float32)
            off += ch
    return steps


def deploy_yolo_kernel(params: dict, graph: Graph = PAPER_GRAPH) -> dict:
    """Training params → packed-weight artifact for the Pallas path: one
    entry per conv in graph order, and the graph."""
    art = {"layers": [], "graph": graph}
    steps_out = _producer_steps(graph, params)
    for spec in graph.convs:
        p = params[spec.name]
        entry = {"spec": spec}
        if spec.kind == "std":
            entry["w"] = jnp.asarray(p["w"], jnp.float32)
            entry["b"] = jnp.asarray(p["b"], jnp.float32)
            if not _reads_images(graph, spec):
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
        if spec.name in steps_out:
            entry["step_out"] = steps_out[spec.name]
        art["layers"].append(entry)
    return art


def build_detector(key: jax.Array, calib_images: jax.Array, *,
                   per_channel: bool = None,
                   profile: str = None,
                   buckets=None, graph: Graph = PAPER_GRAPH) -> tuple:
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
    no longer changes calibration). ``graph`` is the model (the paper's
    by default).

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
        node_sides(graph, b)             # validates the ×32 constraint
    params = init_yolo_params(key, graph=graph)
    params = calibrate_yolo(params, calib_images, per_channel=per_channel,
                            graph=graph)
    art = deploy_yolo_kernel(params, graph)
    art["buckets"] = buckets
    return params, art


def art_uniform_steps(art: dict) -> bool:
    """True iff every W1A8 layer's input steps are per-tensor uniform.

    Diagnostic only since the per-channel popcount fold landed: popcount
    is always eligible — uniform artifacts take the bit-exact identity
    fold, per-channel artifacts the producer-side uniformization."""
    for entry in art["layers"]:
        if entry["spec"].kind != "w1a8":
            continue
        steps = np.asarray(entry["step_in"])
        if not np.all(steps == steps.reshape(-1)[0]):
            return False
    return True


# The line-buffer conv kernels unpack a layer's whole sign matrix in every
# grid step; past the paper's widest layer (9·128·128 signs) a 3×3 conv
# runs as an im2col view through the tiled matmul kernel, as do stride-2
# convs and convs with a fused shortcut, which only that route computes.
ROW_KERNEL_MAX_SIGNS = 9 * 128 * 128


def _gemm_conv(spec: ConvSpec, skip: bool) -> bool:
    """Whether a 3×3 W1A8 conv runs through `conv_ops.w1a8_conv3x3_gemm`."""
    return (spec.stride != 1 or skip
            or spec.ksize ** 2 * spec.cin * spec.cout > ROW_KERNEL_MAX_SIGNS)


def _w1a8_plan(graph: Graph, size: int) -> list:
    """[(spec, input side, fused shortcut or None)] of each W1A8 conv."""
    sides = node_sides(graph, size)
    return [(n, sides[n.name][0], _fused_shortcut(graph, i))
            for i, n in enumerate(graph.nodes)
            if n.op == "conv" and n.kind == "w1a8"]


def _op_dims(spec: ConvSpec, h: int, batch: int, skip: bool) -> tuple:
    """The kernel op a W1A8 conv launches and its structural dims."""
    if spec.ksize == 1:
        return "matmul", (batch * h * h, spec.cin, spec.cout)
    if _gemm_conv(spec, skip):
        ho = h // spec.stride
        return "matmul", (batch * ho * ho, 9 * spec.cin, spec.cout)
    if spec.pool:
        return "conv3x3_pool", (h, h, spec.cin, spec.cout)
    return "conv3x3", (h, h, spec.cin, spec.cout)


def yolo_layer_cells(batch: int = 1, graph: Graph = PAPER_GRAPH,
                     input_size: int = None) -> list:
    """Structural autotune cells for every W1A8 layer.

    Returns [(layer name, op, dims)] with conv dims (h, w, cin, cout) of
    the input plane and matmul dims (m, k, n), m = batch·h·w (a 3×3 conv
    on the im2col route: m = batch·output pixels, k = 9·cin). Pooled
    layers contribute both their ``conv3x3_pool`` cell (fused route) and
    the plain ``conv3x3`` cell (unfused route); duplicates across layers
    (conv5/6/8 share a shape) collapse by key.
    """
    cells = []
    for spec, h, skip in _w1a8_plan(graph, input_size or graph.input_size):
        op, dims = _op_dims(spec, h, batch, skip is not None)
        cells.append((spec.name, op, dims))
        if op == "conv3x3_pool":
            cells.append((spec.name, "conv3x3", dims))
    return cells


def _layer_config(spec: ConvSpec, h: int, batch: int, *, profile: str,
                  accum, fuse_pool, interpret, table,
                  skip: bool = False) -> KernelConfig:
    """Resolve one W1A8 layer's KernelConfig under the named profile.

    Explicit ``accum`` / ``fuse_pool`` / ``interpret`` kwargs override the
    profile's choice; "tuned" reads the autotune table (fastest accum —
    popcount is always eligible now that the per-channel fold exists —
    and fused-vs-unfused pool routing from the winning entry),
    "default"/"interpret" reproduce the historical heuristics.
    """
    op, dims = _op_dims(spec, h, batch, skip)
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
    return [(spec.name,
             _layer_config(spec, h, batch, profile=profile, accum=accum,
                           fuse_pool=fuse_pool, interpret=interpret,
                           table=table, skip=skip is not None))
            for spec, h, skip in _w1a8_plan(art_graph(art), size)]


def _emit_steps(graph: Graph, entries: dict, cfgs: dict) -> dict:
    """{producer conv: the step its epilogue quantizes onto}. A popcount
    consumer contracts on one uniform step, so the producers of its input
    (several, through a route; joined where one feeds two such consumers)
    quantize onto the group's s̄ = max of their steps (producer-side fold,
    DESIGN.md §16); every other producer keeps its calibrated steps."""
    steps = {name: e["step_out"] for name, e in entries.items()
             if "step_out" in e}
    seg = _segments(graph)
    groups = []
    for i, n in enumerate(graph.nodes):
        if n.op == "conv" and n.kind == "w1a8" \
                and cfgs[n.name].accum == "popcount":
            group = [p for p, _ in seg[graph.nodes[i - 1].name]]
            for g in [g for g in groups if set(g) & set(group)]:
                groups.remove(g)
                group = g + [p for p in group if p not in g]
            groups.append(group)
    for group in groups:
        top = functools.reduce(jnp.maximum, [jnp.max(steps[p])
                                             for p in group])
        for p in group:
            steps[p] = jnp.broadcast_to(top, jnp.shape(steps[p]))
    return steps


def graph_forward_kernel(art: dict, images: jax.Array, *,
                         profile: str = None, interpret: bool = None,
                         fuse_pool: bool = None, accum: str = None) -> tuple:
    """Pallas streaming path over the artifact's graph: images (B,S,S,3)
    in [0,1], or uint8 pixels (value = px/256) → the raw output of each
    ``yolo`` head, in graph order, f32 (B, G, G, anchors·(5 + classes)).
    A pooled first conv reads uint8 pixels exactly at pooled resolution
    (`_conv1_pooled_exact`); any other first conv, and float images, take
    the f32 path. Every node runs under a
    ``jax.named_scope`` of its name, inside its stage scope where the
    graph has stages, so that a trace's ops map to nodes through their
    metadata; each W1A8 conv is a Pallas call named ``w1a8_<layer>``.
    Keyword arguments as `yolo_forward_kernel`."""
    graph = art_graph(art)
    entries = {e["spec"].name: e for e in art["layers"]}
    cfgs = dict(layer_configs(
        art, images.shape[1], images.shape[0], profile=profile, accum=accum,
        fuse_pool=fuse_pool, interpret=interpret))
    steps = _emit_steps(graph, entries, cfgs)
    stage = graph.stage_of()
    vals, heads = {}, []
    for i, n in enumerate(graph.nodes):
        ins = [vals[s] for s in _inputs(graph, i)]
        with contextlib.ExitStack() as scopes:
            if n.name in stage:
                scopes.enter_context(jax.named_scope(stage[n.name]))
            scopes.enter_context(jax.named_scope(n.name))
            if n.op == "conv":
                out = _conv_node(graph, i, entries[n.name], images, ins,
                                 vals, cfgs.get(n.name), steps.get(n.name))
            elif n.op == "route":
                out = ins[0] if len(ins) == 1 else QTensor.from_codes(
                    jnp.concatenate([q.data for q in ins], axis=-1),
                    jnp.concatenate([q.scale for q in ins]), axis=-1)
            elif n.op == "upsample":
                out = QTensor.from_codes(_upsample(ins[0].data, n.factor),
                                         ins[0].scale, axis=-1)
            else:            # a shortcut (added in the conv's epilogue), yolo
                out = ins[0]
                if n.op == "yolo":
                    heads.append(out)
        vals[n.name] = out
    return tuple(heads)


def _conv_node(graph: Graph, i: int, entry: dict, images, ins: list,
               vals: dict, cfg, s_next):
    """One conv node of `graph_forward_kernel`."""
    spec = graph.nodes[i]
    if _reads_images(graph, spec):
        if images.dtype == jnp.uint8 and spec.pool and spec.ksize == 3 \
                and spec.stride == 1:
            with jax.named_scope("pooled_exact"):
                return _conv1_pooled_exact(images, entry["w"], entry["b"],
                                           s_next)
        if images.dtype == jnp.uint8:       # pixels / 256 is exact
            images = images.astype(jnp.float32) / 256.0
        # conv1 (std, fixed-point-rounded weights) in f32, then quantize
        # to codes
        w1 = fxp.CONV1_W.roundtrip(entry["w"])
        b1 = fxp.CONV1_B.roundtrip(entry["b"])
        x = jax.nn.relu(_conv2d(images, w1, spec.stride) + b1)
        if spec.pool:
            x = _maxpool2(x)
        return QTensor.quantize_u8(x, s_next, axis=-1)
    if spec.kind == "std":
        # a detection head (std 1×1, fixed-point weights) on dequant codes
        xq = ins[0].dequantize()
        w11 = fxp.CONV11_W.roundtrip(entry["w"])
        b11 = fxp.CONV11_B.roundtrip(entry["b"])
        return _conv2d(xq, w11) + b11
    fused = _fused_shortcut(graph, i)
    skip = vals[fused.src[0]] if fused is not None else None
    return _w1a8_layer(spec, entry, ins[0], cfg, s_next, skip)


def _conv1_pool_blocks(pixels: jax.Array, w: jax.Array) -> jax.Array:
    """The 2×2 max pool of a 3×3, stride-1 conv (one pixel of padding a
    side) over uint8 pixels with Q5.11 weights, as the exact integers
    max Σ a·n (pixel = a/256, weight = n/2048), in lane blocks: int32
    (B, S/2, S/(2q), q·cout), whose block j holds pooled columns qj to
    qj + q - 1 — the order of a row-major (B, S/2, S/2, cout) array.

    Exactness: n = 256·h + l with h in -256..255 and l in 0..255, so the
    pixels, h and l are exact in bf16, each product is exact on the MXU
    and every partial sum stays below 2^24: bf16 contractions with f32
    accumulation give Σ a·h and Σ a·l exactly, in any order.

    Layout: one contraction over the padded pixels, a 4 × (2q + 2) window
    at stride (2, 2q), computes the four sub-positions (dy, dx) of q
    neighbouring pool windows, each holding the 3×3 kernel at its offset.
    Its output channels are eight blocks of q·cout, one per (sub-position,
    half), so that joining the halves and the pool's max are elementwise
    between whole blocks of a vector register's lanes."""
    n = fxp.CONV1_W.quantize(w)                     # (3, 3, cin, cout) int32
    cout = n.shape[3]
    q = math.gcd(pixels.shape[2] // 2, max(1, _LANES // cout))
    halves = jnp.stack([n >> 8, n & 255], axis=3)   # (3, 3, cin, 2, cout)
    k = jnp.stack([jnp.stack([
        jnp.pad(halves, ((dy, 1 - dy), (2 * j + dx, 2 * q - 1 - 2 * j - dx),
                         (0, 0), (0, 0), (0, 0)))
        for j in range(q)], axis=4) for dy in range(2) for dx in range(2)],
        axis=3)                                # (4, 2q+2, cin, 4, 2, q, cout)
    xp = jnp.pad(pixels, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(jnp.bfloat16),
        k.reshape(k.shape[:3] + (-1,)).astype(jnp.bfloat16), (2, 2 * q),
        "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    blk = q * cout

    def part(i):
        return out[..., i * blk:(i + 1) * blk].astype(jnp.int32)
    return functools.reduce(jnp.maximum, [256 * part(2 * s) + part(2 * s + 1)
                                          for s in range(4)])


def _conv1_pooled_exact(pixels: jax.Array, w: jax.Array, b: jax.Array,
                        s_next: jax.Array) -> QTensor:
    """conv1 + ReLU + 2×2 max pool on uint8 pixels, requantized to codes
    on ``s_next``, computed at pooled resolution: the pool's max commutes
    with the bias and the ReLU (both monotone), so only the pooled integer
    accumulator (`_conv1_pool_blocks`) is scaled by 2^-19, biased and
    quantized, in its lane blocks, and no full-resolution map is written."""
    acc = _conv1_pool_blocks(pixels, w)
    cout = b.shape[0]
    reps = acc.shape[-1] // cout
    frac = fxp.INPUT_Q.frac_bits + fxp.CONV1_W.frac_bits
    x = acc.astype(jnp.float32) * 2.0 ** -frac \
        + jnp.tile(fxp.CONV1_B.roundtrip(b), reps)
    step = jnp.tile(jnp.broadcast_to(s_next, (cout,)), reps)
    codes = QTensor.quantize_u8(jax.nn.relu(x), step, axis=-1).data
    return QTensor.from_codes(codes.reshape(acc.shape[:2] + (-1, cout)),
                              s_next, axis=-1)


def yolo_forward_kernel(art: dict, images: jax.Array, *,
                        profile: str = None,
                        interpret: bool = None,
                        fuse_pool: bool = None,
                        accum: str = None):
    """Pallas streaming path. images (B,S,S,3) in [0,1] → (B,S/32,S/32,75)
    f32, for any bucket size S that is a multiple of 32 (default deployment
    S=320 → 10×10 grid). The layer stack, packed weights and per-layer
    configs are resolution-independent; only the spatial plan varies. A
    graph with several heads (W1A8 YOLOv3) gives the tuple of their raw
    outputs (`graph_forward_kernel`).

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
    heads = graph_forward_kernel(art, images, profile=profile,
                                 interpret=interpret, fuse_pool=fuse_pool,
                                 accum=accum)
    return heads[0] if len(heads) == 1 else heads


def _w1a8_layer(spec: ConvSpec, entry: dict, qx: QTensor, cfg: KernelConfig,
                s_next: jax.Array, skip: QTensor = None) -> QTensor:
    """One W1A8 layer of `graph_forward_kernel`: its Pallas kernel, named
    ``w1a8_<layer>``, on the input codes; ``s_next`` is the step its
    epilogue quantizes onto, ``skip`` a residual block's input, added in
    the epilogue."""
    # Mul_prev = this layer's input steps (= qx.scale: the QTensor
    # carries exactly the dequant context the next kernel fuses);
    # per-channel requant is folded into the epilogue:
    # q = round(acc·(α/s_next) + b/s_next [+ q_skip·s_skip/s_next]),
    # out_step=1.
    mul_prev = qx.scale
    div_eff = entry["alpha"] / s_next
    b_eff = entry["b"] / s_next
    name = f"w1a8_{spec.name}"
    res = {}
    if skip is not None:
        res = {"skip": skip.data, "skip_ratio": skip.scale / s_next}
    if spec.ksize == 3 and cfg.op == "matmul":
        codes = conv_ops.w1a8_conv3x3_gemm(
            qx.data, entry["w_packed"], mul_prev, div_eff, b_eff,
            cin=spec.cin, stride=spec.stride, config=cfg, name=name, **res)
    elif spec.ksize == 3 and spec.pool:
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
            name=name, **res).reshape(b, h, w, spec.cout)
    return QTensor.from_codes(codes, s_next, axis=-1)
