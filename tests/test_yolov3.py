"""The detector graph walker: the paper's chain through it, bit for bit, and
W1A8 YOLOv3 (``configs/yolov3_w1a8.py``) against its plain reference
(``bench/reference/yolov3_w1a8.py``) at a small size on the CPU.

The small graph is YOLOv3's layout at input 64 with every width divided
by 16 and one residual block per stage: all three heads (grids 2, 4, 8),
both routes, both upsamples, five stride-2 convs and five fused
shortcuts. Weights are seeded and scaled by the benchmark
configuration's ``init`` gains, as the benchmark's system scales them.
"""
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import yolov3_w1a8
from repro.core import fixedpoint as fxp
from repro.core.qtensor import QTensor
from repro.kernels.w1a8_conv import ops as conv_ops
from repro.kernels.w1a8_matmul import ops as mm_ops
from repro.models import detection, yolo
from repro.serve import DetectionBackend, Scheduler, ServeRequest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import yolo_w1a8 as ref_paper  # noqa: E402
from bench.reference import yolov3_w1a8 as ref  # noqa: E402
from bench.systems import yolov3 as yolov3_system  # noqa: E402

CFG = json.loads((ROOT / "bench" / "configs"
                  / "yolov3-w1a8-416.json").read_text())
SMALL = dict(base=2, blocks=(1, 1, 1, 1, 1), input_size=64)


# -- the paper's model through the walker --------------------------------

def chain_forward(art, images, **kw):
    """The paper's kernel path as a fixed chain of layers, before the
    graph walker: conv1 in f32, conv2..conv10 each quantizing onto the
    next layer's input step (uniformized for a popcount consumer), the
    conv11 head on dequantized codes."""
    layers = art["layers"]
    cfgs = [c for _, c in yolo.layer_configs(art, images.shape[1],
                                             images.shape[0], **kw)]

    def boundary_step(step_out, i):
        if i < len(cfgs) and cfgs[i].accum == "popcount":
            return jnp.broadcast_to(jnp.max(step_out), jnp.shape(step_out))
        return step_out

    w1 = fxp.CONV1_W.roundtrip(layers[0]["w"])
    b1 = fxp.CONV1_B.roundtrip(layers[0]["b"])
    x = jax.nn.relu(yolo._conv2d(images, w1) + b1)
    qx = QTensor.quantize_u8(yolo._maxpool2(x),
                             boundary_step(layers[0]["step_out"], 0), axis=-1)
    for i, e in enumerate(layers[1:-1]):
        spec, cfg = e["spec"], cfgs[i]
        s_next = boundary_step(e["step_out"], i + 1)
        args = (qx.scale, e["alpha"] / s_next, e["b"] / s_next)
        name = f"w1a8_{spec.name}"
        if spec.ksize == 3 and spec.pool:
            codes = conv_ops.w1a8_conv3x3_pool(qx.data, e["w_packed"], *args,
                                               cin=spec.cin, config=cfg,
                                               name=name)
        elif spec.ksize == 3:
            codes = conv_ops.w1a8_conv3x3(qx.data, e["w_packed"], *args,
                                          cin=spec.cin, config=cfg, name=name)
        else:
            b, h, w, _ = qx.data.shape
            codes = mm_ops.w1a8_matmul(
                qx.data.reshape(b * h * w, spec.cin), e["w_packed"], *args,
                k=spec.cin, config=cfg, name=name).reshape(b, h, w, -1)
        qx = QTensor.from_codes(codes, s_next, axis=-1)
    last = layers[-1]
    return yolo._conv2d(qx.dequantize(), fxp.CONV11_W.roundtrip(last["w"])) \
        + fxp.CONV11_B.roundtrip(last["b"])


@pytest.mark.parametrize("size", [320, 608])
@pytest.mark.parametrize("mode", [{"profile": "tuned"},
                                  {"profile": "default", "accum": "popcount"}],
                         ids=["tuned", "popcount"])
def test_paper_graph_is_the_chain_bit_for_bit(size, mode):
    rng = np.random.default_rng(size)
    frames = rng.integers(0, 256, (2, size, size, 3), np.uint8)
    calib = jnp.asarray(frames[:1], jnp.float32) / 256.0
    _, art = yolo.build_detector(jax.random.PRNGKey(7), calib)
    assert art["graph"] is yolo.PAPER_GRAPH
    imgs = jnp.asarray(frames, jnp.float32) / 256.0
    got = jax.jit(lambda x: yolo.yolo_forward_kernel(art, x, **mode))(imgs)
    want = jax.jit(lambda x: chain_forward(art, x, **mode))(imgs)
    assert got.shape == (2, size // 32, size // 32, 75)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_paper_graph_keeps_its_layers_and_counts():
    assert yolo.PAPER_GRAPH.convs == yolo.YOLO_LAYERS
    assert yolo.count_params()["total"] == yolo.count_params(
        yolo.PAPER_GRAPH)["total"]
    assert [n for n, _, _ in yolo.yolo_layer_cells()][::2][:4] == \
        ["conv2", "conv3", "conv4", "conv5"]
    assert {c.op for _, c in yolo.layer_configs(
        {"layers": []}, 320, 32, profile="default")} == \
        {"conv3x3_pool", "conv3x3", "matmul"}


# -- W1A8 YOLOv3 ------------------------------------------------------------

def test_yolov3_graph_is_the_cfg_layout():
    g = yolov3_w1a8.GRAPH
    convs = g.convs
    assert len(g.nodes) == 107 and len(convs) == 75
    assert sum(c.kind == "w1a8" for c in convs) == 71
    assert [c.name for c in convs if c.kind == "std"] == \
        ["conv1", "conv59", "conv67", "conv75"]
    assert sum(n.op == "shortcut" for n in g.nodes) == 23
    assert [c.name for c in convs if c.stride == 2] == \
        ["conv2", "conv5", "conv10", "conv27", "conv44"]
    sides = yolo.node_sides(g, 416)
    assert [sides[n.name][1] for n in g.nodes if n.op == "yolo"] == \
        [13, 26, 52]
    assert g.head_anchors()[0] == tuple(
        (w / 416, h / 416) for w, h in yolov3_w1a8.ANCHORS_PX[6:])
    assert round(yolo.count_params(g)["total"] / 1e6, 1) == 61.6
    # the 75 head channels and the routes' joins
    assert {c.cout for c in convs if c.kind == "std"} - {32} == {75}
    rows = {r[0]: r for r in yolo.graph_rows(g)}
    assert rows["route2"][2] == ["upsample1", "shortcut19"]
    assert rows["route4"][2] == ["upsample2", "shortcut11"]
    assert rows["conv61"][3] == 768 and rows["conv69"][3] == 384
    # the benchmark's configuration lists this graph, node for node
    assert yolo.graph_rows(g) == CFG["graph"]


def test_fused_shortcut_with_another_reader_is_refused():
    g = yolov3_w1a8.graph(**SMALL)
    nodes = list(g.nodes)
    i = next(j for j, n in enumerate(nodes) if n.op == "shortcut")
    nodes.insert(i + 1, yolo.Node("route9", "route", src=(nodes[i - 1].name,)))
    bad = yolo.Graph(nodes=tuple(nodes), anchors=g.anchors,
                     input_size=g.input_size)
    with pytest.raises(ValueError, match="fused with the shortcut"):
        yolo._segments(bad)


def small_cfg() -> dict:
    g = yolov3_w1a8.graph(**SMALL)
    return dict(CFG, input_size=SMALL["input_size"], base_width=2,
                blocks=list(SMALL["blocks"]), graph=yolo.graph_rows(g))


def build_small(seed: int, frames: np.ndarray):
    """The program's artifact of the small graph, with the configuration's
    init gains (heads, residual blocks' last convs, the rest)."""
    g = yolov3_w1a8.graph(**SMALL)
    params = yolo.init_yolo_params(jax.random.PRNGKey(seed), graph=g)
    for name, gain in yolov3_system.gains(CFG, g).items():
        params[name]["w"] = params[name]["w"] * gain
    calib = jnp.asarray(frames[:1], jnp.float32) / 256.0
    params = yolo.calibrate_yolo(params, calib, graph=g)
    art = yolo.deploy_yolo_kernel(params, g)
    art["buckets"] = (SMALL["input_size"],)
    return art


@functools.lru_cache(maxsize=None)
def small_case(seed: int) -> dict:
    frames = np.random.default_rng(seed).integers(
        0, 256, (4, 64, 64, 3), np.uint8)
    art = build_small(seed, frames)
    cfg = small_cfg()
    calib = jnp.asarray(frames[:1], jnp.float32) / 256.0
    imgs = jnp.asarray(frames, jnp.float32) / 256.0
    raws = {}
    for bits in (8, 4):
        r = ref.Reference(cfg, jax.random.PRNGKey(seed), calib,
                          act_bits=bits)
        raws[bits] = [np.asarray(x) for x in
                      ref.forward(cfg, r.weights, r.steps, imgs, bits)]
    return {"frames": frames, "art": art, "cfg": cfg, "raw_ref": raws[8],
            "raw_int4": raws[4]}


@pytest.fixture(scope="module")
def small():
    return small_case(11)


def head_errors(heads, want) -> list:
    """(mean, max) of |heads - want| over the reference head's spread, per
    head."""
    out = []
    for got, w in zip(heads, want):
        e = np.abs(np.asarray(got, np.float64) - w) / w.std()
        out.append((e.mean(), e.max()))
    return out


# Raw heads: the program's W1A8 convs contract bf16 operands (each code
# times its step rounded to 8 bits of mantissa) where the reference uses
# float32, and a code that lands on the other side of a rounding boundary
# moves by one step; through 14 W1A8 layers that leaves the heads at a
# mean gap under 0.02 of their spread and a largest gap under 0.16 (seeds
# 11 to 13). The same reference at int4 activations reads a mean of 0.086
# to 0.23 and a largest gap of 0.52 to 2.9, so both bounds separate it.
RAW_MEAN_TOL = 0.04
RAW_MAX_TOL = 0.3
BOX_TOL = 0.02           # decoded centres (image fractions), ln sizes, scores


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_small_graph_matches_reference(seed):
    case = small_case(seed)
    imgs = jnp.asarray(case["frames"], jnp.float32) / 256.0
    heads = jax.jit(lambda x: yolo.graph_forward_kernel(
        case["art"], x, profile="tuned"))(imgs)
    assert [h.shape[1] for h in heads] == [2, 4, 8]
    assert [h.shape for h in heads] == [r.shape for r in case["raw_ref"]]
    for mean, top in head_errors(heads, case["raw_ref"]):
        assert mean < RAW_MEAN_TOL and top < RAW_MAX_TOL, (mean, top)
    for mean, top in head_errors(case["raw_int4"], case["raw_ref"]):
        assert mean > RAW_MEAN_TOL or top > RAW_MAX_TOL, (mean, top)
    # decoded candidates: the program's decode of its heads against the
    # reference's decode of its own, all three grids in one list
    dec = detection.decode_heads(heads, yolo.art_graph(
        case["art"]).head_anchors())
    boxes, scores = ref.decode(case["cfg"], case["raw_ref"])
    assert dec["boxes"].shape == (4, 3 * (4 + 16 + 64), 4)
    got_b = np.asarray(dec["boxes"], np.float64)
    want_b = np.asarray(boxes, np.float64)
    assert np.abs(got_b[..., :2] - want_b[..., :2]).max() < BOX_TOL
    assert np.abs(np.log(got_b[..., 2:] / want_b[..., 2:])).max() < BOX_TOL
    assert np.abs(np.asarray(dec["scores"]) - np.asarray(scores)).max() \
        < BOX_TOL


def test_popcount_consumers_read_one_step_through_routes(small):
    """Under accum="popcount" every W1A8 conv contracts on one uniform
    input step: the producers of a routed input (an upsampled neck conv
    and a backbone shortcut) quantize onto one shared s̄."""
    art = small["art"]
    g = yolo.art_graph(art)
    entries = {e["spec"].name: e for e in art["layers"]}
    cfgs = dict(yolo.layer_configs(art, 64, 4, profile="default",
                                   accum="popcount"))
    steps = yolo._emit_steps(g, entries, cfgs)
    seg = yolo._segments(g)
    joined = 0
    for i, n in enumerate(g.nodes):
        if n.op == "conv" and n.kind == "w1a8":
            prods = [p for p, _ in seg[g.nodes[i - 1].name]]
            step = np.concatenate([np.asarray(steps[p]) for p in prods])
            assert np.all(step == step[0]), n.name
            top = max(float(np.max(entries[p]["step_out"])) for p in prods)
            assert step[0] == np.float32(top)
            joined += len(prods) > 1
    assert joined == 2                        # after route2 and route4
    heads = yolo.graph_forward_kernel(
        art, jnp.asarray(small["frames"], jnp.float32) / 256.0,
        profile="default", accum="popcount")
    assert all(np.isfinite(np.asarray(h)).all() for h in heads)


def test_device_nms_equals_reference_nms_over_three_heads(small):
    """The bundle's decode + NMS, on the reference's own raw heads, keeps
    what the reference's greedy NMS keeps over all 336 candidates."""
    cfg = small["cfg"]
    post = dict(cfg["nms"])
    anchors = yolo.art_graph(small["art"]).head_anchors()
    boxes, scores, classes = detection.postprocess(
        tuple(jnp.asarray(r) for r in small["raw_ref"]), anchors=anchors,
        **post)
    cb, cs = ref.decode(cfg, small["raw_ref"])
    kept_any = 0
    for i in range(len(small["frames"])):
        b, s, c, valid = (np.asarray(x) for x in
                          detection.compact_detections(boxes[i], scores[i],
                                                       classes[i]))
        kept = ref_paper.nms(cfg, np.asarray(cb[i]), np.asarray(cs[i]))
        assert int(valid) == kept["valid"]
        kept_any += kept["valid"]
        n = kept["valid"]
        assert np.array_equal(c[:n].astype(np.int64), kept["classes"][:n])
        assert np.array_equal(b[:n].astype(np.float32), kept["boxes"][:n])
        assert np.array_equal(s[:n].astype(np.float32), kept["scores"][:n])
    assert kept_any > 0


def test_small_graph_serves_through_the_backend(small):
    """build_detector's artifact of the graph, through DetectionBackend and
    the Scheduler, answers what the bundle computes."""
    frames = small["frames"]
    g = yolov3_w1a8.graph(**SMALL)
    _, art = yolo.build_detector(
        jax.random.PRNGKey(3), jnp.asarray(frames[:1], jnp.float32) / 256.0,
        graph=g)
    assert art["buckets"] == (64,) and art["graph"] is g
    assert [e["spec"] for e in art["layers"]] == list(g.convs)
    backend = DetectionBackend(art, slots=4, depth=2, profile="tuned",
                               device_nms=True)
    assert backend.buckets == (64,)
    out = {}
    sched = Scheduler(backend, result_sink=lambda r: out.setdefault(
        r.rid, r))
    for i in range(len(frames)):
        sched.submit(ServeRequest(rid=i, image=frames[i]))
    while sched.queue or sched.active:
        sched.tick()
    assert sorted(out) == list(range(len(frames)))
    want = jax.device_get(backend._fwd(jnp.asarray(frames)))
    for i, res in out.items():
        assert res.finish_reason == "ok"
        got = res.detections
        assert got["valid"] == int(want[3][i])
        assert np.array_equal(got["boxes"],
                              np.asarray(want[0][i], np.float32))
    raw = DetectionBackend(art, slots=4, profile="tuned")._fwd(
        jnp.asarray(frames))[0]
    assert isinstance(raw, tuple) and [r.shape[1] for r in raw] == [2, 4, 8]
