"""W1A8 YOLOv3 at 416: darknet's ``cfg/yolov3-voc.cfg`` (arXiv:1804.02767).

The Darknet-53 backbone (conv 32, then five stages, each opened by a 3×3
stride-2 conv, with 1/2/8/8/4 residual blocks of 1×1 c/2 → 3×3 c →
shortcut), and a three-scale head: at 13, 26 and 52 a five-conv neck, a
3×3 conv and a 1×1 linear head of 3·(5 + 20) = 75 channels, joined by
route → 1×1 conv → ×2 nearest upsample → route with the backbone's 26×26
(512-channel) and 52×52 (256-channel) outputs. 75 convs: the first and
the three heads are fixed-point standard convs, the other 71 W1A8.

Nodes are listed in the cfg's order and named by kind and count (conv1 …
conv75, shortcut1 …, route1 …, upsample1 …, yolo1 …), so every W1A8 conv's
Pallas call is ``w1a8_conv<n>``. `graph` builds the same layout at other
widths and depths for tests.
"""
from repro.models.yolo import ConvSpec, Graph, Node

NAME = "yolov3-w1a8"
INPUT_SIZE = 416
NUM_CLASSES = 20
BLOCKS = (1, 2, 8, 8, 4)
BASE_WIDTH = 32
# yolov3-voc.cfg's anchors, in pixels at 416; each head's mask picks three
ANCHORS_PX = ((10, 13), (16, 30), (33, 23), (30, 61), (62, 45), (59, 119),
              (116, 90), (156, 198), (373, 326))
MASKS = ((6, 7, 8), (3, 4, 5), (0, 1, 2))       # heads at 13, 26, 52


def graph(base: int = BASE_WIDTH, blocks=BLOCKS,
          num_classes: int = NUM_CLASSES,
          input_size: int = INPUT_SIZE) -> Graph:
    """The yolov3-voc.cfg layer graph with first width ``base`` (32
    published) and ``blocks`` residual blocks per backbone stage."""
    nodes, stages = [], []
    count = {}

    def name(kind):
        count[kind] = count.get(kind, 0) + 1
        return f"{kind}{count[kind]}"

    def conv(cin, cout, k, stride=1, kind="w1a8"):
        nodes.append(ConvSpec(name("conv"), kind, cin, cout, k, False,
                              stride))
        return nodes[-1].name

    def node(op, **kw):
        nodes.append(Node(name(op), op, **kw))
        return nodes[-1].name

    head = 3 * (5 + num_classes)
    stages.append(("backbone.s1", conv(3, base, 3, kind="std")))
    c = base
    saved = []                       # the outputs the routes read
    for s, n in enumerate(blocks):
        first = conv(c, 2 * c, 3, stride=2)
        if s:
            stages.append((f"backbone.s{s + 1}", first))
        c *= 2
        out = first
        for _ in range(n):
            conv(c, c // 2, 1)
            conv(c // 2, c, 3)
            out = node("shortcut", src=(out,))
        saved.append(out)
    tail = None                      # the neck conv each next scale routes
    for j, grid in enumerate((13, 26, 52)):
        w = c // 2 ** j                # 1024, 512, 256 at published widths
        if j:
            stages.append((f"neck.{grid}", node("route", src=(tail,))))
            conv(w, w // 2, 1)
            up = node("upsample", factor=2)
            node("route", src=(up, saved[-1 - j]))
            first = conv(w // 2 + w, w // 2, 1)
        else:
            first = conv(w, w // 2, 1)
            stages.append((f"neck.{grid}", first))
        conv(w // 2, w, 3)
        conv(w, w // 2, 1)
        conv(w // 2, w, 3)
        tail = conv(w, w // 2, 1)
        conv(w // 2, w, 3)
        conv(w, head, 1, kind="std")
        node("yolo", mask=MASKS[j])
    anchors = tuple((w / input_size, h / input_size) for w, h in ANCHORS_PX)
    return Graph(nodes=tuple(nodes), anchors=anchors,
                 num_classes=num_classes, input_size=input_size,
                 stages=tuple(stages))


GRAPH = graph()
