"""Operations and bytes of a detector given as a layer graph (the
configuration's ``graph``), from its shapes, and the roofline share of
its W1A8 kernel calls in a trace.

Counted as ``costs.py`` counts the paper's chain: every multiply-accumulate
is two operations, the binary-weight ones included, at a conv's output
pixels (a stride-2 conv's quarter); a W1A8 layer's epilogue is three
operations an output (scale, bias, round/clip) and its Mul_prev one an
input; a fused shortcut two more an output (its step ratio and the add).
Bytes are those a W1A8 call cannot avoid moving: its uint8 input plane
once, its uint8 output, the residual input's codes where it adds one,
1-bit packed weights and its f32 per-channel operands. The im2col view
that the wide convs go through is not counted: the least time is that of
the layer, not of the route the program takes.
"""
from __future__ import annotations

from bench.core import costs, trace


def conv_planes(cfg: dict) -> list:
    """[(conv node dict, input side, adds a shortcut)] in graph order."""
    from bench.reference.yolov3_w1a8 import graph
    nodes = graph(cfg)
    side, out = {}, []
    for i, n in enumerate(nodes):
        h = side[n["inputs"][0]] if n["inputs"] else int(cfg["input_size"])
        o = h
        if n["op"] == "conv":
            skip = i + 1 < len(nodes) and nodes[i + 1]["op"] == "shortcut"
            out.append((n, h, skip))
            o = h // n["stride"] // (2 if n["pool"] else 1)
        elif n["op"] == "upsample":
            o = h * n["factor"]
        side[n["name"]] = o
    return out


def _conv_ops(n: dict, h: int, skip: bool) -> float:
    ho = h // n["stride"]
    out = n["cout"] * ho * ho
    ops = 2.0 * n["k"] ** 2 * n["cin"] * out
    if n["kind"] == "std":
        ops += out                                  # bias
    else:
        ops += n["cin"] * h * h + 3 * out           # Mul_prev, post
    if skip:
        ops += 2 * out                              # ratio, add
    if n["pool"]:
        ops += 3 * n["cout"] * (ho // 2) ** 2       # 2x2 max
    return ops


def frame_ops(cfg: dict) -> float:
    """Operations for one frame through every conv of the graph."""
    return sum(_conv_ops(n, h, skip) for n, h, skip in conv_planes(cfg))


def w1a8_calls(cfg: dict, batch: int) -> list:
    """[(layer name, ops, bytes, {"res": adds a shortcut, "s2": stride
    2})] of each W1A8 kernel call on ``batch`` frames, in graph order."""
    calls = []
    for n, h, skip in conv_planes(cfg):
        if n["kind"] != "w1a8":
            continue
        ho = h // n["stride"] // (2 if n["pool"] else 1)
        cin, cout = n["cin"], n["cout"]
        nbytes = (batch * (h * h * cin + ho * ho * cout * (2 if skip else 1))
                  + n["k"] ** 2 * cin * cout / 8
                  + 4 * (cin + (3 if skip else 2) * cout))
        calls.append((n["name"], batch * _conv_ops(n, h, skip),
                      float(nbytes), {"res": skip, "s2": n["stride"] == 2}))
    return calls


def kernel_share(run, calls: list):
    """Percent of roofline of ``calls`` [(layer, ops, bytes, ...)] in the
    traced window: over the executions of the served program, the least
    time of the calls found (the ``w1a8_<layer>`` Pallas call, matched by
    its HLO name up to the first ``.``) over their device time; None
    where the trace holds none of them."""
    if run.trace is None or not calls:
        return None
    least = {f"w1a8_{name}": costs.least_seconds(ops, nbytes, run.peaks)
             for name, ops, nbytes, *_ in calls}
    lo, hi = run.trace_window
    need = spent = 0.0
    for ops in trace.ops_in(run.trace, trace.modules_named(
            run.trace, run.bundle, lo, hi)):
        for name, opcode, _, dur in ops:
            call = name.split(".", 1)[0]
            if call in least and trace.is_kernel(opcode):
                need += least[call]
                spent += dur
    return 100.0 * need / spent if spent > 0 else None
