"""Plain reference of W1A8 YOLOv3 (darknet's ``cfg/yolov3-voc.cfg``,
arXiv:1804.02767), under the paper's W1A8 scheme (arXiv 2605.03396).

Written from the cfg's layer graph, as the configuration file lists it
under ``graph``, and the scheme's equations alone; it imports nothing of
the program under test and takes nothing it made, only helpers of the
paper's reference (``yolo_w1a8``):

- weights: per conv, in graph order, ``key, sub = split(key)`` and
  ``normal(sub, (k, k, cin, cout)) / sqrt(k * k * cin)`` times the
  configuration's ``init`` gain: ``head_gain`` for a head (a standard conv
  that reads activations), ``res_gain`` for the 3×3 conv that ends a
  residual block (the one a shortcut follows), ``gain`` for every other
  conv; biases zero (batch norm folded into zero biases);
- activation steps: range calibration on one frame, the step of every
  activation a conv reads = max(per-channel max / qmax, 1e-4);
- first conv and heads: weights and biases rounded to their fixed-point
  formats (half away from zero, saturating); the first conv reads the
  frame in [0, 1) and is followed by ReLU, the heads are linear;
- W1A8 convs: ``relu(conv(quant(x), sign(w)) * alpha + b)`` with inputs
  quantized to ``clip(round(x / s), 0, qmax) * s``, ``sign(w)`` (0 -> +1)
  and alpha = the per-output-channel mean |w|; 3×3 convs pad one pixel a
  side (darknet's ``pad=1``), at stride 1 or 2;
- shortcut: ``y = relu(conv(...)) + x_skip``, darknet's order (activation
  on the conv, linear add), where ``x_skip`` is the block input as the
  8-bit activation it is (quantized on its own calibrated step);
- route: concatenation on channels; upsample: x2 nearest;
- decode per head with its ``mask`` into the cfg's anchors (6,7,8 at 13;
  3,4,5 at 26; 0,1,2 at 52): sigmoid offsets, anchor-scaled exponent sizes
  (anchors in pixels over the input side), objectness x class
  probability; heads concatenated in graph order, each head's cells
  row-major with its anchors innermost; then per-class greedy NMS.

Departures from the cfg, also listed under ``assumed`` in the
configuration: ReLU with unsigned 8-bit codes in place of leaky 0.1 (the
W1A8 scheme's activations), batch norm folded into zero biases, random
weights. Everything runs in float32 with convolutions at
``Precision.HIGHEST``. ``act_bits`` sets qmax = 2**bits - 1: 8 is the
configuration, 4 is the control (the next precision below int8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.yolo_w1a8 import (MATCH_IOU, MATCH_SCORE, _fixed,
                                       _quant, iou, nms, summarize)

__all__ = ["Reference", "nms", "compare", "summarize", "graph", "heads"]

HIGHEST = jax.lax.Precision.HIGHEST


def graph(cfg: dict) -> list:
    """The configuration's nodes as dicts, with each node's inputs."""
    out = []
    for i, row in enumerate(cfg["graph"]):
        name, op = row[0], row[1]
        prev = [cfg["graph"][i - 1][0]] if i else []
        n = {"name": name, "op": op, "inputs": prev}
        if op == "conv":
            n.update(zip(("kind", "cin", "cout", "k", "stride", "pool"),
                         row[2:]))
        elif op == "shortcut":
            n["inputs"] = prev + [row[2]]
        elif op == "route":
            n["inputs"] = list(row[2])
        elif op == "upsample":
            n["factor"] = row[2]
        else:
            n["mask"] = list(row[2])
        out.append(n)
    return out


def _role(nodes: list, i: int) -> str:
    """"first", "head", "res" (a block's last conv) or "w1a8"."""
    n = nodes[i]
    if i == 0:
        return "first"
    if n["kind"] == "std":
        return "head"
    if i + 1 < len(nodes) and nodes[i + 1]["op"] == "shortcut":
        return "res"
    return "w1a8"


def init_weights(cfg: dict, key) -> dict:
    """{conv: (w, b)}, drawn from ``key`` as the module docstring says."""
    gains = {"first": "gain", "w1a8": "gain", "res": "res_gain",
             "head": "head_gain"}
    nodes = graph(cfg)
    out = {}
    for i, n in enumerate(nodes):
        if n["op"] != "conv":
            continue
        key, sub = jax.random.split(key)
        fan_in = n["k"] * n["k"] * n["cin"]
        w = jax.random.normal(sub, (n["k"], n["k"], n["cin"], n["cout"]),
                              jnp.float32)
        gain = cfg["init"][gains[_role(nodes, i)]]
        out[n["name"]] = (w / np.sqrt(fan_in) * gain,
                          jnp.zeros((n["cout"],), jnp.float32))
    return out


def _conv(x, w, stride=1):
    pad = ((1, 1), (1, 1)) if w.shape[0] == 3 else ((0, 0), (0, 0))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _step(x, qmax):
    return jnp.maximum(jnp.max(jnp.abs(x), axis=(0, 1, 2)) / qmax, 1e-4)


def _walk(cfg, weights, images, qmax, steps=None):
    """The forward pass; with ``steps`` None it calibrates: returns
    ({node: step of the activation it quantizes}, [head outputs])."""
    fx = cfg["fixed_point"]
    nodes = graph(cfg)
    got = {} if steps is None else steps
    vals, heads = {}, []
    for i, n in enumerate(nodes):
        ins = [vals[s] for s in n["inputs"]] or [images]
        x = ins[0]
        if n["op"] == "conv":
            w, b = weights[n["name"]]
            role = _role(nodes, i)
            if role == "first":
                x = jax.nn.relu(_conv(x, _fixed(w, *fx["first_w"]),
                                      n["stride"])
                                + _fixed(b, *fx["first_b"]))
            else:
                if steps is None:
                    got[n["name"]] = _step(x, qmax)
                xq = _quant(x, got[n["name"]], qmax)
                if role == "head":
                    x = _conv(xq, _fixed(w, *fx["head_w"])) \
                        + _fixed(b, *fx["head_b"])
                else:
                    alpha = jnp.mean(jnp.abs(w), axis=(0, 1, 2))
                    sign = jnp.where(w >= 0, 1.0, -1.0)
                    x = jax.nn.relu(_conv(xq, sign, n["stride"]) * alpha + b)
        elif n["op"] == "shortcut":
            if steps is None:
                got[n["name"]] = _step(ins[1], qmax)
            x = x + _quant(ins[1], got[n["name"]], qmax)
        elif n["op"] == "route":
            x = jnp.concatenate(ins, axis=-1)
        elif n["op"] == "upsample":
            f = n["factor"]
            x = jnp.repeat(jnp.repeat(x, f, axis=1), f, axis=2)
        else:
            heads.append(x)
        vals[n["name"]] = x
    return got, heads


def calibrate(cfg: dict, weights: dict, frame, act_bits: int) -> dict:
    """The step of every quantized activation, from one (1, S, S, 3) frame
    in [0, 1]."""
    return _walk(cfg, weights, frame, float(2 ** act_bits - 1))[0]


def forward(cfg: dict, weights: dict, steps: dict, images, act_bits: int):
    """(B, S, S, 3) in [0, 1] -> [raw output of each head]."""
    return _walk(cfg, weights, images, float(2 ** act_bits - 1), steps)[1]


def heads(cfg: dict) -> list:
    """[(grid side, anchors (3, 2) as fractions)] per head, in order."""
    s = int(cfg["input_size"])
    px = np.asarray(cfg["anchors_px"], np.float64)
    side, out = {}, []
    for n in graph(cfg):
        h = side[n["inputs"][0]] if n["inputs"] else s
        if n["op"] == "conv":
            h //= n["stride"] * (2 if n["pool"] else 1)
        elif n["op"] == "upsample":
            h *= n["factor"]
        elif n["op"] == "yolo":
            out.append((h, px[n["mask"]] / s))
        side[n["name"]] = h
    return out


def decode(cfg: dict, raws):
    """Head outputs -> (boxes (B, N, 4) cx cy w h, class scores (B, N, C))."""
    nc = cfg["num_classes"]
    boxes, scores = [], []
    for raw, (g, anchors) in zip(raws, heads(cfg)):
        b, na = raw.shape[0], len(anchors)
        r = raw.reshape(b, g, g, na, 5 + nc)
        cy, cx = jnp.meshgrid(jnp.arange(g, dtype=jnp.float32),
                              jnp.arange(g, dtype=jnp.float32),
                              indexing="ij")
        a = jnp.asarray(anchors, jnp.float32)
        bx = (jax.nn.sigmoid(r[..., 0]) + cx[None, :, :, None]) / g
        by = (jax.nn.sigmoid(r[..., 1]) + cy[None, :, :, None]) / g
        bw = a[:, 0] * jnp.exp(jnp.clip(r[..., 2], -8, 8))
        bh = a[:, 1] * jnp.exp(jnp.clip(r[..., 3], -8, 8))
        sc = jax.nn.sigmoid(r[..., 4])[..., None] * jax.nn.sigmoid(r[..., 5:])
        boxes.append(jnp.stack([bx, by, bw, bh], -1).reshape(b, -1, 4))
        scores.append(sc.reshape(b, -1, nc))
    return jnp.concatenate(boxes, 1), jnp.concatenate(scores, 1)


class Reference:
    """The reference for one configuration and seed: weights, steps, and a
    jitted forward + decode that takes them as arguments (so one compile
    serves every seed)."""

    def __init__(self, cfg: dict, key, calib_frame, act_bits: int = None):
        self.cfg = cfg
        self.act_bits = int(act_bits or cfg["act_bits"])
        self.weights = jax.jit(functools.partial(init_weights, cfg))(key)
        self.steps = jax.jit(functools.partial(
            calibrate, cfg, act_bits=self.act_bits))(self.weights,
                                                     calib_frame)
        self._run = jax.jit(functools.partial(self._candidates, cfg,
                                              act_bits=self.act_bits))

    @staticmethod
    def _candidates(cfg, weights, steps, frames_u8, act_bits):
        images = frames_u8.astype(jnp.float32) / 256.0
        return decode(cfg, forward(cfg, weights, steps, images, act_bits))

    def candidates(self, frames_u8: np.ndarray, block: int = 8):
        """Decoded candidates of uint8 frames, in blocks of ``block``
        frames: (boxes (F, N, 4), scores (F, N, C)) as numpy float32."""
        boxes, scores = [], []
        for i in range(0, len(frames_u8), block):
            chunk = np.asarray(frames_u8[i:i + block])
            pad = block - len(chunk)
            if pad:                      # one shape, one compile
                chunk = np.concatenate(
                    [chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
            bx, sc = jax.device_get(self._run(self.weights, self.steps,
                                              jnp.asarray(chunk)))
            boxes.append(bx[:block - pad])
            scores.append(sc[:block - pad])
        return np.concatenate(boxes), np.concatenate(scores)


def _decoded(box: np.ndarray, grid) -> np.ndarray:
    """cx cy w h -> (cx, cy) in cells of ``grid`` and (ln w, ln h): the
    raw head's offsets and log sizes, up to the sigmoid and the anchor."""
    grid = np.asarray(grid, np.float64)[..., None]
    return np.concatenate([box[..., :2] * grid, np.log(box[..., 2:])], -1)


def slots(cfg: dict, served_boxes: np.ndarray, boxes: np.ndarray
          ) -> tuple:
    """The reference candidate each served box was decoded from, across the
    three grids: on each head, the anchors in the cell that holds the
    box's centre or a neighbour of it (the float16 wire may round a centre
    across a cell edge), measured in that head's decoded terms; the
    nearest of all. Returns (candidate indices, their grid sides)."""
    got_all = served_boxes.astype(np.float64)
    best_d = np.full(len(got_all), np.inf)
    best_c = np.zeros(len(got_all), np.int64)
    best_g = np.ones(len(got_all), np.int64)
    base = 0
    for g, anchors in heads(cfg):
        na = len(anchors)
        ref = _decoded(boxes[base:base + g * g * na].astype(np.float64), g)
        got = _decoded(got_all, g)
        cell = np.clip(np.floor(got[:, :2]).astype(np.int64), 0, g - 1)
        near = np.arange(-1, 2)
        ys = cell[:, 1, None, None] + near[None, :, None]
        xs = cell[:, 0, None, None] + near[None, None, :]
        inside = (ys >= 0) & (ys < g) & (xs >= 0) & (xs < g)
        at = (np.clip(ys, 0, g - 1) * g + np.clip(xs, 0, g - 1)) * na
        cand = (at.reshape(len(got), -1, 1)
                + np.arange(na)[None, None, :]).reshape(len(got), -1)
        ok = np.repeat(inside.reshape(len(got), -1), na, axis=1)
        d = np.where(ok, np.abs(ref[cand] - got[:, None, :]).sum(-1), np.inf)
        j = d.argmin(-1)
        dj = d[np.arange(len(got)), j]
        better = dj < best_d
        best_d = np.where(better, dj, best_d)
        best_c = np.where(better, base + cand[np.arange(len(got)), j],
                          best_c)
        best_g = np.where(better, g, best_g)
        base += g * g * na
    return best_c, best_g


def compare(cfg: dict, served: dict, boxes: np.ndarray, scores: np.ndarray,
            kept: dict) -> dict:
    """How far one frame's served detections lie from the reference.

    ``boxes``/``scores`` are the reference's decoded candidates of the
    frame (all three heads), ``kept`` its NMS output (``nms``). Each served
    detection is traced to the candidate it was decoded from (``slots``);
    then:

    - ``box_err``: the mean, over the served detections, of the largest
      decoded-term gap to that candidate's box on its own grid: centre
      offsets in cells, sizes in ln;
    - ``score_err``: the mean gap between the served score and the
      reference's score of that candidate in the served class, over the
      spread (standard deviation) of the frame's reference scores;
    - ``matched`` of ``served`` and ``kept`` detections: a served and a
      kept detection match, each at most once, when their classes agree,
      their IoU is ``MATCH_IOU`` or more and their scores lie within
      ``MATCH_SCORE``. This holds the valid count, the classes, the scores
      and NMS's choice to the reference's.

    A served list longer than ``max_out``, a class out of range or a box
    that is not finite and positive reads as infinitely far.
    """
    kv = int(kept["valid"])
    valid = int(served["valid"])
    far = {"box_err": float("inf"), "score_err": float("inf"),
           "matched": 0, "served": cfg["nms"]["max_out"], "kept": kv}
    if not 0 <= valid <= cfg["nms"]["max_out"]:
        return far
    sb = np.asarray(served["boxes"][:valid], np.float64)
    sc = np.asarray(served["classes"][:valid], np.int64)
    ss = np.asarray(served["scores"][:valid], np.float64)
    if (np.any((sc < 0) | (sc >= scores.shape[-1]))
            or not np.all(np.isfinite(sb)) or np.any(sb[:, 2:] <= 0)):
        return far
    out = {"box_err": 0.0, "score_err": 0.0, "served": valid, "kept": kv}
    if valid:
        j, g = slots(cfg, sb, boxes)
        gap = np.abs(_decoded(boxes[j].astype(np.float64), g)
                     - _decoded(sb, g)).max(-1)
        out["box_err"] = float(gap.mean())
        out["score_err"] = float(np.abs(scores[j, sc] - ss).mean()
                                 / max(float(np.std(scores)), 1e-12))
    kb, kc = kept["boxes"][:kv].astype(np.float64), kept["classes"][:kv]
    ks = kept["scores"][:kv].astype(np.float64)
    taken = np.zeros(kv, bool)
    for i in range(valid):
        hit = ((iou(sb[i], kb) >= MATCH_IOU) & (kc == sc[i])
               & (np.abs(ks - ss[i]) <= MATCH_SCORE) & ~taken)
        if hit.any():
            taken[int(np.flatnonzero(hit)[0])] = True
    out["matched"] = int(taken.sum())
    return out
