"""Plain reference of the W1A8 detector (arXiv 2605.03396, Table 1).

Written from the paper's equations and the configuration file alone; it
imports nothing of the program under test and takes nothing it made:

- weights: per layer, in table order, ``key, sub = split(key)`` and
  ``normal(sub, (k, k, cin, cout)) / sqrt(k * k * cin)`` times the
  configuration's ``init`` gain (``head_gain`` for the last layer),
  biases zero;
- activation steps: range calibration on one frame, each quantized
  layer's input step = max(per-channel max / qmax, 1e-4);
- conv1 / conv11 weights and biases rounded to their fixed-point formats
  (half away from zero, saturating);
- W1A8 layers: inputs quantized to ``clip(round(x / s), 0, qmax) * s``,
  weights ``sign(w)`` (0 -> +1) times the per-output-channel mean |w|;
- head decode (YOLOv3: sigmoid offsets, anchor-scaled exponent sizes,
  objectness x class probability) and per-class greedy NMS.

Everything runs in float32 with convolutions at ``Precision.HIGHEST``.
``act_bits`` sets qmax = 2**bits - 1: 8 is the configuration, 4 is the
control (the next precision below int8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def layers(cfg: dict) -> list:
    return [dict(zip(("name", "kind", "cin", "cout", "k", "pool"), row))
            for row in cfg["layers"]]


def init_weights(cfg: dict, key) -> list:
    """[(w, b)] per layer, drawn from ``key`` as the module docstring says."""
    out = []
    specs = layers(cfg)
    for i, spec in enumerate(specs):
        key, sub = jax.random.split(key)
        fan_in = spec["k"] * spec["k"] * spec["cin"]
        w = jax.random.normal(sub, (spec["k"], spec["k"], spec["cin"],
                                    spec["cout"]), jnp.float32)
        gain = cfg["init"]["head_gain" if i == len(specs) - 1 else "gain"]
        out.append((w / np.sqrt(fan_in) * gain,
                    jnp.zeros((spec["cout"],), jnp.float32)))
    return out


def _fixed(x, int_bits: int, frac_bits: int):
    """Round to signed Q<int>.<frac> (half away from zero), saturating."""
    scale = float(1 << frac_bits)
    lim = float(1 << (int_bits + frac_bits))
    raw = jnp.trunc(x * scale + jnp.where(x >= 0, 0.5, -0.5))
    return jnp.clip(raw, -lim, lim - 1) / scale


def _conv(x, w):
    pad = "SAME" if w.shape[0] == 3 else "VALID"
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def _quant(x, step, qmax):
    v = x / step
    q = jnp.clip(jnp.trunc(v + jnp.where(v >= 0, 0.5, -0.5)), 0, qmax)
    return q * step


def _layer(cfg, spec, wb, x, step, qmax):
    """One layer; ``step`` is this layer's input step (None for conv1)."""
    w, b = wb
    fx = cfg["fixed_point"]
    if spec["name"] == "conv1":
        x = jax.nn.relu(_conv(x, _fixed(w, *fx["conv1_w"]))
                        + _fixed(b, *fx["conv1_b"]))
    elif spec["kind"] == "std":                      # the conv11 head
        x = _conv(_quant(x, step, qmax), _fixed(w, *fx["conv11_w"])) \
            + _fixed(b, *fx["conv11_b"])
    else:
        alpha = jnp.mean(jnp.abs(w), axis=(0, 1, 2))
        sign = jnp.where(w >= 0, 1.0, -1.0)
        x = jax.nn.relu(_conv(_quant(x, step, qmax), sign) * alpha + b)
    return _pool(x) if spec["pool"] else x


def calibrate(cfg: dict, weights: list, frame, act_bits: int) -> list:
    """Input step of every quantized layer (None for conv1), from one
    (1, S, S, 3) frame in [0, 1]."""
    qmax = float(2 ** act_bits - 1)
    steps, x = [], frame
    for spec, wb in zip(layers(cfg), weights):
        step = None
        if spec["name"] != "conv1":
            step = jnp.maximum(jnp.max(jnp.abs(x), axis=(0, 1, 2)) / qmax,
                               1e-4)
        steps.append(step)
        x = _layer(cfg, spec, wb, x, step, qmax)
    return steps


def forward(cfg: dict, weights: list, steps: list, images, act_bits: int):
    """(B, S, S, 3) in [0, 1] -> (B, S/32, S/32, anchors * (5 + classes))."""
    qmax = float(2 ** act_bits - 1)
    x = images
    for spec, wb, step in zip(layers(cfg), weights, steps):
        x = _layer(cfg, spec, wb, x, step, qmax)
    return x


def decode(cfg: dict, raw):
    """raw head -> (boxes (B, N, 4) cx cy w h, class scores (B, N, C))."""
    b, g = raw.shape[0], raw.shape[1]
    na, nc = cfg["num_anchors"], cfg["num_classes"]
    r = raw.reshape(b, g, g, na, 5 + nc)
    cy, cx = jnp.meshgrid(jnp.arange(g, dtype=jnp.float32),
                          jnp.arange(g, dtype=jnp.float32), indexing="ij")
    anchors = jnp.asarray(cfg["anchors"], jnp.float32)
    bx = (jax.nn.sigmoid(r[..., 0]) + cx[None, :, :, None]) / g
    by = (jax.nn.sigmoid(r[..., 1]) + cy[None, :, :, None]) / g
    bw = anchors[:, 0] * jnp.exp(jnp.clip(r[..., 2], -8, 8))
    bh = anchors[:, 1] * jnp.exp(jnp.clip(r[..., 3], -8, 8))
    scores = jax.nn.sigmoid(r[..., 4])[..., None] * jax.nn.sigmoid(r[..., 5:])
    return (jnp.stack([bx, by, bw, bh], -1).reshape(b, -1, 4),
            scores.reshape(b, -1, nc))


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

    def candidates(self, frames_u8: np.ndarray, block: int = 16):
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


def iou(box, boxes) -> np.ndarray:
    """IoU of one cx cy w h box against (N, 4) boxes."""
    ax1, ay1 = box[0] - box[2] / 2, box[1] - box[3] / 2
    ax2, ay2 = box[0] + box[2] / 2, box[1] + box[3] / 2
    bx1, by1 = boxes[:, 0] - boxes[:, 2] / 2, boxes[:, 1] - boxes[:, 3] / 2
    bx2, by2 = boxes[:, 0] + boxes[:, 2] / 2, boxes[:, 1] + boxes[:, 3] / 2
    iw = np.maximum(np.minimum(ax2, bx2) - np.maximum(ax1, bx1), 0)
    ih = np.maximum(np.minimum(ay2, by2) - np.maximum(ay1, by1), 0)
    inter = iw * ih
    union = (ax2 - ax1) * (ay2 - ay1) + (bx2 - bx1) * (by2 - by1) - inter
    return inter / np.maximum(union, 1e-9)


def nms(cfg: dict, boxes: np.ndarray, scores: np.ndarray) -> dict:
    """Per-class greedy NMS of one frame's candidates, emitted as the
    detection wire: float16 boxes and scores, int8 classes, a valid count."""
    p = cfg["nms"]
    cls = scores.argmax(-1)
    rem = scores.max(-1).astype(np.float64)
    rem = np.where(rem >= p["score_thresh"], rem, 0.0)
    out_b = np.zeros((p["max_out"], 4), np.float32)
    out_s = np.zeros((p["max_out"],), np.float32)
    out_c = np.full((p["max_out"],), -1, np.int64)
    for i in range(p["max_out"]):
        j = int(np.argmax(rem))
        if rem[j] <= 0:
            break
        out_b[i], out_s[i], out_c[i] = boxes[j], rem[j], cls[j]
        hit = (iou(boxes[j], boxes) > p["iou_thresh"]) & (cls == cls[j])
        rem = np.where(hit, 0.0, rem)
        rem[j] = 0.0
    valid = int(np.sum(out_s > 0))
    return {"boxes": out_b.astype(np.float16).astype(np.float32),
            "scores": out_s.astype(np.float16).astype(np.float32),
            "classes": out_c.astype(np.int8).astype(np.int64),
            "valid": valid}


MATCH_IOU = 0.8          # a served and a kept detection of one class are
MATCH_SCORE = 0.03       # one object at this IoU or more, scores this close


def _decoded(box: np.ndarray, grid: int) -> np.ndarray:
    """cx cy w h -> (cx, cy) in cells and (ln w, ln h): the raw head's
    offsets and log sizes, up to the sigmoid and the anchor."""
    return np.concatenate([box[..., :2] * grid, np.log(box[..., 2:])], -1)


def slots(cfg: dict, served_boxes: np.ndarray, boxes: np.ndarray
          ) -> np.ndarray:
    """The reference candidate each served box was decoded from: the
    anchor, in the cell that holds the box's centre or a neighbour of it
    (the float16 wire may round a centre across a cell edge), whose box
    lies nearest in decoded terms."""
    g = int(cfg["input_size"]) // 32
    na = int(cfg["num_anchors"])
    ref = _decoded(boxes.astype(np.float64), g)
    got = _decoded(served_boxes, g)
    cell = np.clip(np.floor(got[:, :2]).astype(np.int64), 0, g - 1)
    near = np.arange(-1, 2)
    ys = cell[:, 1, None, None] + near[None, :, None]
    xs = cell[:, 0, None, None] + near[None, None, :]
    inside = (ys >= 0) & (ys < g) & (xs >= 0) & (xs < g)
    base = (np.clip(ys, 0, g - 1) * g + np.clip(xs, 0, g - 1)) * na
    cand = (base.reshape(len(got), -1, 1)
            + np.arange(na)[None, None, :]).reshape(len(got), -1)
    ok = np.repeat(inside.reshape(len(got), -1), na, axis=1)
    d = np.abs(ref[cand] - got[:, None, :]).sum(-1)
    d = np.where(ok, d, np.inf)
    return cand[np.arange(len(got)), d.argmin(-1)]


def compare(cfg: dict, served: dict, boxes: np.ndarray, scores: np.ndarray,
            kept: dict) -> dict:
    """How far one frame's served detections lie from the reference.

    ``boxes``/``scores`` are the reference's decoded candidates of the
    frame, ``kept`` its NMS output (``nms``). Each served detection is
    traced to the candidate it was decoded from (``slots``); then:

    - ``box_err``: the mean, over the served detections, of the largest
      decoded-term gap to that candidate's box: centre offsets in cells,
      sizes in ln;
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
        g = int(cfg["input_size"]) // 32
        j = slots(cfg, sb, boxes)
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


def summarize(frames: list) -> dict:
    """A run's numbers from its sampled frames' ``compare`` readings:

    - ``box_err``, ``score_err``: the worst frame's;
    - ``set_miss``: 1 - F1 of all the served detections against all the
      kept ones, over the whole sample (0 when both are empty). One
      frame's list swings with near-tied scores at NMS's ``max_out`` cut;
      the sample's does not.
    """
    out = {k: max((f[k] for f in frames), default=0.0)
           for k in ("box_err", "score_err")}
    n = sum(f["served"] + f["kept"] for f in frames)
    out["set_miss"] = (1.0 - 2.0 * sum(f["matched"] for f in frames) / n
                       if n else 0.0)
    return out
