"""Compile the W1A8 Pallas kernels and the detector serve bundle for a
described TPU v5e (``v5e:2x2``, no chip attached).

Interpret-mode tests cannot see what Mosaic refuses (casts, reductions,
reshapes and relayouts the chip does not support); these compiles do.
Nothing runs, so results are checked elsewhere. The topology is described
inside a fixture, never at import: only one process at a time may load the
TPU compiler library, and every xdist worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import config as kcfg
from repro.kernels.config import KernelConfig
from repro.kernels.w1a8_conv import ops as conv_ops
from repro.kernels.w1a8_matmul import ops as mm_ops
from repro.models import yolo

BATCH = 8
SERVE_SLOTS = 32
N_W1A8_LAYERS = 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # else the compiler logs
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — any failure means "no TPU"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _custom_calls(compiled) -> int:
    return sum('custom_call_target="tpu_custom_call"' in line
               for line in compiled.as_text().splitlines())


def _kernel_call(op, dims, accum, sharding):
    """(fn, argument shapes) for one W1A8 layer cell on one chip."""
    cfg = KernelConfig(op=op, accum=accum, interpret=False, out_step=1.0)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    if op == "matmul":
        m, k, n = dims

        def fn(a, w, mul, div, b):
            return mm_ops.w1a8_matmul(a, w, mul, div, b, k=k, config=cfg)
        return fn, (sds((m, k), jnp.uint8),
                    sds(((k + 31) // 32, n), jnp.uint32),
                    sds((k,), jnp.float32), sds((n,), jnp.float32),
                    sds((n,), jnp.float32))
    h, w, cin, cout = dims
    entry = (conv_ops.w1a8_conv3x3 if op == "conv3x3"
             else conv_ops.w1a8_conv3x3_pool)

    def fn(a, wp, mul, div, b):
        return entry(a, wp, mul, div, b, cin=cin, config=cfg)
    return fn, (sds((BATCH, h, w, cin), jnp.uint8),
                sds(((9 * cin + 31) // 32, cout), jnp.uint32),
                sds((cin,), jnp.float32), sds((cout,), jnp.float32),
                sds((cout,), jnp.float32))


@pytest.mark.parametrize("accum", ["dot", "popcount"])
@pytest.mark.parametrize(
    "name,op,dims", yolo.yolo_layer_cells(batch=BATCH),
    ids=[f"{n}-{op}" for n, op, _ in yolo.yolo_layer_cells(batch=BATCH)])
def test_w1a8_kernel_compiles_for_v5e(name, op, dims, accum, one_chip,
                                      no_persistent_cache):
    fn, args = _kernel_call(op, dims, accum, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) == 1, (name, op, accum)


@pytest.fixture(scope="module")
def serve_bundle(topo, one_chip, no_persistent_cache):
    """The DetectionBackend bundle (Pallas convs + head + device NMS) at
    320 x 32 slots, as `chip_smoke.py` serves it, compiled for one chip.
    The configs are steered to what a v5e resolves: the chip's autotune
    key, and compiled (not interpreted) kernels."""
    from repro.serve import DetectionBackend
    kind = topo.devices[0].device_kind
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kcfg, "device_key",
                   lambda: kind.strip().lower().replace(" ", "-"))
        mp.setattr(KernelConfig, "resolved_interpret",
                   lambda self: bool(self.interpret))
        rng = np.random.default_rng(0)
        calib = jnp.asarray(rng.integers(0, 256, (1, 320, 320, 3), np.uint8),
                            jnp.float32) / 256
        _, art = yolo.build_detector(jax.random.PRNGKey(0), calib)
        backend = DetectionBackend(art, slots=SERVE_SLOTS, depth=2,
                                   profile="tuned", device_nms=True)
        return backend.lower(320, sharding=one_chip).compile()


def test_serve_bundle_compiles_for_v5e(serve_bundle):
    """One Pallas kernel per W1A8 layer."""
    assert _custom_calls(serve_bundle) >= N_W1A8_LAYERS


def test_serve_bundle_names_kernels_and_layers(serve_bundle):
    """Each custom call is named after its layer (``w1a8_conv2`` ..
    ``w1a8_conv10``), and the layers' named scopes reach the compiled
    HLO's ``op_name`` metadata, where a trace's ops are mapped to layers."""
    text = serve_bundle.as_text()
    calls = [line.split(" = ", 1)[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == sorted(f"w1a8_conv{i}" for i in range(2, 11))
    for scope in ("conv1", "conv11", "decode", "nms", "wire"):
        assert f'op_name="jit(_bundle)/{scope}/' in text \
            or f'op_name="jit(_bundle)/jit(postprocess)/{scope}/' in text, \
            scope


def _yolov3_cells():
    """W1A8 YOLOv3-416's distinct kernel shapes at real widths: the
    stride-2 convs out of 416 and 26, the residual 3×3 convs at 208 and
    13 (shortcut in the epilogue), a 1×1 at 13 and the routed 1×1 at 26."""
    from repro.configs import yolov3_w1a8
    g = yolov3_w1a8.GRAPH
    sides = yolo.node_sides(g, 416)
    keep = ("conv2", "conv4", "conv44", "conv52", "conv53", "conv61")
    specs = {n.name: n for n in g.convs}
    return [(name, specs[name], sides[name][0],
             yolo._fused_shortcut(g, g.nodes.index(specs[name])) is not None)
            for name in keep]


@pytest.mark.parametrize("accum", ["dot", "popcount"])
@pytest.mark.parametrize("name,spec,h,skip", _yolov3_cells(),
                         ids=[c[0] for c in _yolov3_cells()])
def test_yolov3_kernel_compiles_for_v5e(name, spec, h, skip, accum,
                                        one_chip, no_persistent_cache):
    op, dims = yolo._op_dims(spec, h, BATCH, skip)
    assert op == "matmul"
    cfg = KernelConfig(op=op, accum=accum, interpret=False, out_step=1.0)
    ho = h // spec.stride

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    k = spec.ksize ** 2 * spec.cin
    args = [sds((BATCH, h, h, spec.cin), jnp.uint8),
            sds(((k + 31) // 32, spec.cout), jnp.uint32),
            sds((spec.cin,), jnp.float32), sds((spec.cout,), jnp.float32),
            sds((spec.cout,), jnp.float32)]
    if skip:
        args += [sds((BATCH, ho, ho, spec.cout), jnp.uint8),
                 sds((spec.cout,), jnp.float32)]

    def fn(a, wp, mul, div, b, *res):
        kw = dict(zip(("skip", "skip_ratio"), res))
        if spec.ksize == 3:
            return conv_ops.w1a8_conv3x3_gemm(a, wp, mul, div, b,
                                              cin=spec.cin,
                                              stride=spec.stride,
                                              config=cfg, **kw)
        return mm_ops.w1a8_matmul(a, wp, mul, div, b, k=spec.cin,
                                  config=cfg, **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) == 1, (name, accum)
