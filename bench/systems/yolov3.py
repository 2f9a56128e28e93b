"""The system under test for W1A8 YOLOv3: the program's detector server
over the YOLOv3 layer graph.

Set-up builds what a deployment would, by the recipe of
``models.yolo.build_detector`` over the graph of
``configs.yolov3_w1a8`` (seeded init, range calibration on one frame,
weight packing) run as one jitted call on the device, with the initial
weights scaled by the configuration's ``init`` gains: ``head_gain`` on the
three heads, ``res_gain`` on the 3×3 conv that ends each residual block,
``gain`` on every other conv. It first checks that the program's graph,
anchors and fixed-point formats are the configuration's. Serving is the
same as the paper's detector (``systems/detector.py``): a
``serve.DetectionBackend`` with the configuration's width, dispatch depth,
device-NMS wire and kernel profile, driven by a ``serve.Scheduler``, whose
one jitted bundle per dispatch holds the first conv (XLA), the 71 W1A8
Pallas kernels, the three heads, the decode of 10647 candidates a frame,
NMS and the compact detection wire.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.core import seeds
from bench.systems import detector
from bench.systems.detector import frames  # noqa: F401  (the frame pool)


def program_graph(cfg: dict):
    """The program's graph at the configuration's widths and depth, after
    checking that it is the one the configuration lists."""
    from repro.configs import yolov3_w1a8
    from repro.core import fixedpoint as fxp
    from repro.models import yolo
    graph = yolov3_w1a8.graph(base=int(cfg["base_width"]),
                              blocks=tuple(cfg["blocks"]),
                              num_classes=int(cfg["num_classes"]),
                              input_size=int(cfg["input_size"]))
    if yolo.graph_rows(graph) != cfg["graph"]:
        raise ValueError("the program's YOLOv3 graph is not the "
                         "configuration's")
    size = int(cfg["input_size"])
    anchors = tuple((w / size, h / size) for w, h in cfg["anchors_px"])
    if graph.anchors != anchors:
        raise ValueError(f"the program's anchors {graph.anchors} are not "
                         f"the configuration's {anchors}")
    formats = {"first_w": fxp.CONV1_W, "first_b": fxp.CONV1_B,
               "head_w": fxp.CONV11_W, "head_b": fxp.CONV11_B}
    for key, fmt in formats.items():
        if [fmt.int_bits, fmt.frac_bits] != list(cfg["fixed_point"][key]):
            raise ValueError(f"the program's {key} format is {fmt}, not "
                             f"the configuration's {cfg['fixed_point'][key]}")
    return graph


def gains(cfg: dict, graph) -> dict:
    """{conv: its init gain}, by the rule in the module docstring."""
    init, out = cfg["init"], {}
    nodes = graph.nodes
    for i, n in enumerate(nodes):
        if n.op != "conv":
            continue
        if i and n.kind == "std":
            out[n.name] = init["head_gain"]
        elif i + 1 < len(nodes) and nodes[i + 1].op == "shortcut":
            out[n.name] = init["res_gain"]
        else:
            out[n.name] = init["gain"]
    return out


class System(detector.System):
    """One built W1A8 YOLOv3 detector server for a configuration and seed."""

    def __init__(self, cfg: dict, seed: int, pool):
        from repro.models import yolo
        from repro.serve import DetectionBackend, Scheduler, ServeRequest
        graph = program_graph(cfg)
        self.cfg, self.pool = cfg, pool
        self._Scheduler, self._Request = Scheduler, ServeRequest
        size = int(cfg["input_size"])
        serving = cfg["serving"]
        self.width = int(serving["width"])
        gain = gains(cfg, graph)

        def build(key, calib):
            params = yolo.init_yolo_params(key, graph=graph)
            for name, p in params.items():
                p["w"] = p["w"] * gain[name]
            params = yolo.calibrate_yolo(params, calib, graph=graph)
            return [{k: v for k, v in e.items() if k != "spec"}
                    for e in yolo.deploy_yolo_kernel(params, graph)["layers"]]

        calib = jnp.asarray(pool[:1], jnp.float32) / 256.0
        arrays = jax.block_until_ready(
            jax.jit(build)(seeds.jax_key(seed, "weights"), calib))
        art = {"layers": [{"spec": spec, **a} for spec, a in
                          zip(graph.convs, arrays)],
               "graph": graph, "buckets": (size,)}
        nms = cfg["nms"]
        self.backend = DetectionBackend(
            art, slots=self.width, depth=int(serving["depth"]),
            profile=serving["profile"], device_nms=True, buckets=(size,),
            iou_thresh=nms["iou_thresh"], score_thresh=nms["score_thresh"],
            max_out=nms["max_out"])
