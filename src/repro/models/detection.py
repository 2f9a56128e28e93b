"""Detection-head decode + NMS (paper §6.2 post-processing, the "PS side").

A head emits (B, G, G, A·(5 + C)) raw values = A anchors × (tx, ty, tw, th,
obj, C cls) per cell, y/x/channel order (the paper's: 10×10×75, 3 anchors,
20 VOC classes). Decode follows YOLOv3:
  bx = (σ(tx) + cx)/G, by = (σ(ty) + cy)/G, bw = pw·e^tw, bh = ph·e^th,
confidence = σ(obj)·max σ(cls). A model with several heads (YOLOv3's 13,
26 and 52 grids) decodes each with its own anchors into one candidate
list, heads in graph order, each head's cells row-major with its anchors
innermost. NMS is class-wise greedy IoU suppression, implemented with a
fixed-iteration lax.fori_loop (jit-safe, static shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import yolo


def decode_head(raw: jax.Array, anchors=yolo.ANCHORS) -> dict:
    """raw (B, G, G, A·(5+C)) → boxes (B, G·G·A, 4) cxcywh in [0,1] and
    class scores (B, G·G·A, C); ``anchors`` ((w, h), ...) per anchor.

    G is read off the raw head (10 for the deployment 320×320 input; a
    resolution bucket of side S decodes a G = S/32 grid) — box coordinates
    stay image-relative fractions, so every bucket shares one decode."""
    b, grid = raw.shape[0], raw.shape[1]
    na = len(anchors)
    nc = raw.shape[-1] // na - 5
    anchors = jnp.asarray(anchors, jnp.float32)
    r = raw.reshape(b, grid, grid, na, 5 + nc)
    cy, cx = jnp.meshgrid(jnp.arange(grid, dtype=jnp.float32),
                          jnp.arange(grid, dtype=jnp.float32), indexing="ij")
    bx = (jax.nn.sigmoid(r[..., 0]) + cx[None, :, :, None]) / grid
    by = (jax.nn.sigmoid(r[..., 1]) + cy[None, :, :, None]) / grid
    bw = anchors[None, None, None, :, 0] * jnp.exp(jnp.clip(r[..., 2], -8, 8))
    bh = anchors[None, None, None, :, 1] * jnp.exp(jnp.clip(r[..., 3], -8, 8))
    obj = jax.nn.sigmoid(r[..., 4])
    cls_prob = jax.nn.sigmoid(r[..., 5:])
    boxes = jnp.stack([bx, by, bw, bh], axis=-1).reshape(b, -1, 4)
    scores = (obj[..., None] * cls_prob).reshape(b, -1, nc)
    return {"boxes": boxes, "scores": scores}


def decode_heads(raws, head_anchors) -> dict:
    """Several heads' raw outputs → one candidate list (`decode_head` per
    head with its anchors, concatenated in order)."""
    dec = [decode_head(r, a) for r, a in zip(raws, head_anchors)]
    return {k: jnp.concatenate([d[k] for d in dec], axis=1)
            for k in ("boxes", "scores")}


def iou_cxcywh(a: jax.Array, b: jax.Array) -> jax.Array:
    """IoU between (..., 4) and (..., 4) cxcywh boxes."""
    ax1, ay1 = a[..., 0] - a[..., 2] / 2, a[..., 1] - a[..., 3] / 2
    ax2, ay2 = a[..., 0] + a[..., 2] / 2, a[..., 1] + a[..., 3] / 2
    bx1, by1 = b[..., 0] - b[..., 2] / 2, b[..., 1] - b[..., 3] / 2
    bx2, by2 = b[..., 0] + b[..., 2] / 2, b[..., 1] + b[..., 3] / 2
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0)
    inter = iw * ih
    union = (ax2 - ax1) * (ay2 - ay1) + (bx2 - bx1) * (by2 - by1) - inter
    return inter / jnp.maximum(union, 1e-9)


def nms(boxes: jax.Array, scores: jax.Array, *, iou_thresh: float = 0.45,
        score_thresh: float = 0.25, max_out: int = 50):
    """Greedy class-agnostic-per-class NMS, static shapes (jit-safe).

    boxes (N, 4), scores (N, C) → (max_out, 4), (max_out,), (max_out,) int32
    class ids; empty slots have score 0 and class -1.
    """
    cls_id = jnp.argmax(scores, axis=-1)
    score = jnp.max(scores, axis=-1)
    score = jnp.where(score >= score_thresh, score, 0.0)

    def body(i, state):
        sc, out_b, out_s, out_c = state
        j = jnp.argmax(sc)
        best = sc[j]
        out_b = out_b.at[i].set(boxes[j])
        out_s = out_s.at[i].set(best)
        out_c = out_c.at[i].set(jnp.where(best > 0, cls_id[j], -1))
        ious = iou_cxcywh(boxes[j][None, :], boxes)
        same_cls = cls_id == cls_id[j]
        suppress = (ious > iou_thresh) & same_cls
        sc = jnp.where(suppress, 0.0, sc).at[j].set(0.0)
        return sc, out_b, out_s, out_c

    init = (score, jnp.zeros((max_out, 4)), jnp.zeros((max_out,)),
            jnp.full((max_out,), -1, jnp.int32))
    _, ob, os_, oc = jax.lax.fori_loop(0, max_out, body, init)
    os_ = jnp.where(os_ > 0, os_, 0.0)
    return ob, os_, oc


@functools.partial(jax.jit, static_argnames=(
    "anchors", "iou_thresh", "score_thresh", "max_out"))
def postprocess(raw, *, anchors=None, iou_thresh: float = 0.45,
                score_thresh: float = 0.25, max_out: int = 50):
    """Full post-processing for a batch of raw heads (named scopes
    ``decode`` and ``nms``): ``raw`` is one head's output, with the
    paper's anchors unless ``anchors`` (a tuple of one ``((w, h), ...)``
    per head) says otherwise, or a tuple of heads with their ``anchors``.
    NMS runs over every head's candidates together."""
    heads = tuple(raw) if isinstance(raw, (tuple, list)) else (raw,)
    if anchors is None:
        anchors = (yolo.ANCHORS,)
    with jax.named_scope("decode"):
        dec = decode_heads(heads, anchors)
    with jax.named_scope("nms"):
        return jax.vmap(lambda b, s: nms(b, s, iou_thresh=iou_thresh,
                                         score_thresh=score_thresh,
                                         max_out=max_out))(dec["boxes"],
                                                           dec["scores"])


def compact_detections(boxes: jax.Array, scores: jax.Array,
                       classes: jax.Array):
    """Static-shape NMS output for ONE image → the device-side emission wire.

    (max_out, 4) f32 boxes, (max_out,) f32 scores, (max_out,) int32 class
    ids → (fp16 boxes, fp16 scores, int8 classes, int32 valid-count).
    Greedy NMS emits kept boxes in descending-score order, so the positive
    slots are a prefix and one int32 prefix length stands in for a mask.
    9 bytes/slot instead of 28 — and a backend shipping this instead of the
    raw head drops the 4·G·G·75-byte tensor from every device→host sync.
    fp16 is lossless for the set structure (the NMS ran in f32; only the
    emitted values round: boxes in [0,1] to ~2⁻¹¹, scores to ~1e-3)."""
    valid = jnp.sum((scores > 0).astype(jnp.int32))
    return (boxes.astype(jnp.float16), scores.astype(jnp.float16),
            classes.astype(jnp.int8), valid)


def detections_to_list(boxes, scores, classes) -> list:
    """Static-shape NMS output for ONE image → host-side list of dicts
    (empty slots dropped) — the wire form of a detection ServeResult."""
    import numpy as np
    boxes, scores, classes = (np.asarray(boxes), np.asarray(scores),
                              np.asarray(classes))
    keep = scores > 0
    return [{"box_cxcywh": boxes[i].tolist(), "score": float(scores[i]),
             "class_id": int(classes[i])} for i in np.flatnonzero(keep)]
