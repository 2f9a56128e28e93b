"""conv1's pooled route (`models.yolo._conv1_pooled_exact`): the 3×3 conv,
ReLU and 2×2 max pool of uint8 pixels computed at pooled resolution from
exact integers. Checked bit for bit against the int64 reference
(`yolo.conv1_pooled_int`), against the f32 path it replaces on ordinary
frames, and for the inputs that keep the f32 path: float pixels and an
unpooled first conv."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import yolov3_w1a8
from repro.core import fixedpoint as fxp
from repro.core.qtensor import QTensor
from repro.models import yolo


def f32_path(images, w, b, step, pool=True):
    """conv1 as the kernel path computes it from float pixels."""
    x = jax.nn.relu(yolo._conv2d(images, fxp.CONV1_W.roundtrip(w))
                    + fxp.CONV1_B.roundtrip(b))
    return QTensor.quantize_u8(yolo._maxpool2(x) if pool else x, step,
                               axis=-1).data


def rail_weights(rng, cout: int = 16):
    """Random Q5.11 weights with the format's rails (raw -65536 and
    65535) and raw -32768 and 32767 planted."""
    w = rng.normal(size=(3, 3, 3, cout)).astype(np.float32) * 0.6
    scale = fxp.CONV1_W.scale
    w[0, 0, 0, 0] = fxp.CONV1_W.raw_min / scale
    w[1, 1, 1, 1] = fxp.CONV1_W.raw_max / scale
    w[2, 2, 2, 2] = -32768 / scale
    w[0, 2, 1, 3] = 32767 / scale
    b = rng.normal(size=(cout,)).astype(np.float32) * 0.3
    return jnp.asarray(w), jnp.asarray(b)


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("size", [32, 96])
def test_route_is_the_int64_reference_bit_for_bit(size, batch):
    rng = np.random.default_rng(size + batch)
    px = rng.integers(0, 256, (batch, size, size, 3), np.uint8)
    px[0, :4, :4] = 255
    px[-1, 4:8, :4] = 0
    w, b = rail_weights(rng)
    n = np.asarray(fxp.CONV1_W.quantize(w))
    assert {-65536, 65535, -32768, 32767} <= set(n.ravel().tolist())
    acc, _ = yolo.conv1_pooled_int(px, w, b, 1.0)
    # steps that spread the codes over 0..255, with some at each rail
    top = (acc.max(axis=(0, 1, 2)) * 2.0 ** -19).astype(np.float32)
    step = jnp.asarray(np.maximum(top, 1.0) / 300, jnp.float32)
    want_acc, want = yolo.conv1_pooled_int(px, w, b, step)
    assert np.abs(want_acc).max() >= 2 ** 24     # past f32's exact integers
    assert want.min() == 0 and want.max() == 255

    got_acc = jax.jit(yolo._conv1_pool_blocks)(px, w)
    got = jax.jit(yolo._conv1_pooled_exact)(px, w, b, step)
    assert np.array_equal(
        np.asarray(got_acc).reshape(want_acc.shape), want_acc)
    assert got.data.dtype == jnp.uint8
    assert np.array_equal(np.asarray(got.data), want)
    assert np.array_equal(np.asarray(got.scale), np.asarray(step))


@pytest.mark.parametrize("size", [96, 320])
def test_route_codes_match_the_f32_path(size):
    """Calibrated conv1 of the paper's model on random frames: the route
    and the f32 path it replaces give the same codes, up to one code in a
    few places where the f32 path's rounding lands across a boundary."""
    rng = np.random.default_rng(size)
    px = rng.integers(0, 256, (2, size, size, 3), np.uint8)
    imgs = jnp.asarray(px, jnp.float32) / 256.0
    params = yolo.calibrate_yolo(
        yolo.init_yolo_params(jax.random.PRNGKey(size)), imgs[:1])
    c1 = yolo.deploy_yolo_kernel(params)["layers"][0]
    got = jax.jit(yolo._conv1_pooled_exact)(px, c1["w"], c1["b"],
                                            c1["step_out"]).data
    old = jax.jit(f32_path)(imgs, c1["w"], c1["b"], c1["step_out"])
    d = np.abs(np.asarray(got, np.int32) - np.asarray(old, np.int32))
    share = float(np.mean(d > 0))
    print(f"conv1 codes off the f32 path at {size}: {share:.3g} of "
          f"{d.size}, largest {d.max()}")
    assert np.asarray(got).max() > 0
    assert d.max() <= 1
    assert share <= 1e-4


@pytest.mark.parametrize("graph,dtype", [
    (yolo.PAPER_GRAPH, np.float32),
    (yolov3_w1a8.GRAPH, np.float32),
    (yolov3_w1a8.GRAPH, np.uint8),
], ids=["paper-float", "yolov3-float", "yolov3-uint8"])
def test_float_pixels_and_unpooled_conv1_keep_the_f32_path(graph, dtype):
    """Float pixels (which need not sit on the 1/256 grid) and YOLOv3's
    unpooled first conv give exactly the f32 path's codes; uint8 pixels
    there are converted as the serve bundle used to convert them."""
    spec = graph.nodes[0]
    rng = np.random.default_rng(3)
    px = rng.integers(0, 256, (2, 32, 32, 3), np.uint8)
    imgs = jnp.asarray(px, jnp.float32) / 256.0
    if dtype == np.float32:
        imgs = imgs + jnp.float32(1e-3)        # off the 1/256 grid
    w = jnp.asarray(rng.normal(size=(3, 3, 3, spec.cout)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(spec.cout,)) * 0.1, jnp.float32)
    step = jnp.full((spec.cout,), 0.01, jnp.float32)
    entry = {"spec": spec, "w": w, "b": b}
    x = imgs if dtype == np.float32 else jnp.asarray(px)
    got = jax.jit(lambda x: yolo._conv_node(graph, 0, entry, x, [], {}, None,
                                            step).data)(x)
    want = jax.jit(lambda x: f32_path(x, w, b, step, spec.pool))(imgs)
    assert np.array_equal(np.asarray(got), np.asarray(want))
