"""Operations and bytes the detector's algorithm needs, from its shapes.

Counted at face value: every multiply-accumulate is two operations, the
binary-weight ones included, whatever datapath computes them. Bytes are
those a W1A8 layer cannot avoid moving: uint8 activations in and out
(pooled output where the layer pools), 1-bit packed weights, and its f32
per-channel scales and biases. The arithmetic is that of the program's
``launch/autotune.roofline`` and ``models/yolo.count_gflops``, kept here
so that no later change to the program moves the yardstick.
"""
from __future__ import annotations


def layer_planes(cfg: dict) -> list:
    """[(layer dict, input side h)] at the configuration's input size."""
    from bench.reference.yolo_w1a8 import layers
    out, h = [], int(cfg["input_size"])
    for spec in layers(cfg):
        out.append((spec, h))
        if spec["pool"]:
            h //= 2
    return out


def frame_ops(cfg: dict) -> float:
    """Operations for one frame through every layer."""
    total = 0.0
    for s, h in layer_planes(cfg):
        hw = h * h
        macs = s["k"] ** 2 * s["cin"] * s["cout"] * hw
        total += 2 * macs
        if s["kind"] == "std":
            total += s["cout"] * hw                       # bias
        else:
            total += s["cin"] * hw + 3 * s["cout"] * hw   # Mul_prev, post
        if s["pool"]:
            total += 3 * s["cout"] * (h // 2) ** 2        # 2x2 max
    return total


def w1a8_calls(cfg: dict, batch: int) -> list:
    """[(layer name, ops, bytes)] of each W1A8 kernel call on ``batch``
    frames, in the order the forward pass launches them."""
    calls = []
    for s, h in layer_planes(cfg):
        if s["kind"] != "w1a8":
            continue
        hw, cin, cout = h * h, s["cin"], s["cout"]
        params = 4 * (cin + 2 * cout)
        if s["k"] == 1:
            m = batch * hw
            ops = 2 * m * cin * cout + 3 * m * cout
            nbytes = m * cin + cin * cout / 8 + m * cout + params
        else:
            ops = batch * (2 * 9 * cin * cout * hw + 5 * cout * hw)
            out = cout * hw
            if s["pool"]:
                ops += batch * 3 * cout * (h // 2) ** 2
                out /= 4
            nbytes = batch * (hw * cin + out) + 9 * cin * cout / 8 + params
        calls.append((s["name"], float(ops), float(nbytes)))
    return calls


def least_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound, at the 8-bit peak (the W1A8 contraction)."""
    return max(ops / peaks["peak_ops_int8"], nbytes / peaks["hbm_bw"])
