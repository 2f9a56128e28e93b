"""KernelConfig resolution + autotune harness + deprecation shim.

Covers the PR's API-redesign acceptance criteria: autotune table
round-trip (sweep → persist → load → identical winner), deterministic
tie-breaking, nearest-shape fallback on a miss, bit-exactness of every
tuned candidate vs the reference path on the kernel test shapes, and the
legacy-kwarg DeprecationWarning shim on all three kernel entry points and
DetectionBackend.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import config as kc
from repro.kernels.config import KernelConfig
from repro.kernels.w1a8_conv import ops as conv_ops
from repro.kernels.w1a8_matmul import ops as mm_ops
from repro.launch import autotune


# ---------------------------------------------------------------------------
# KernelConfig object semantics
# ---------------------------------------------------------------------------

def test_config_hashable_and_source_excluded():
    a = KernelConfig(op="conv3x3", rows=2, source="table")
    b = KernelConfig(op="conv3x3", rows=2, source="heuristic")
    assert a == b and hash(a) == hash(b)
    assert hash(a) != hash(a.replace(rows=4))
    jax.jit(lambda x, *, config: x, static_argnames=("config",))(
        jnp.zeros(()), config=a)          # static jit arg works


def test_config_validation():
    with pytest.raises(ValueError):
        KernelConfig(op="conv9x9")
    with pytest.raises(ValueError):
        KernelConfig(accum="fma")
    with pytest.raises(ValueError):
        KernelConfig(bk=48)               # not a PACK multiple
    with pytest.raises(ValueError):
        KernelConfig(rows=0)


def test_heuristic_tiles_match_legacy_pick():
    cfg = KernelConfig()
    assert cfg.matmul_tiles(300, 1152, 75) == (256, 512, 128)
    assert cfg.matmul_tiles(5, 70, 12) == (8, 96, 128)
    assert KernelConfig(bm=32).matmul_tiles(300, 1152, 75)[0] == 32
    assert KernelConfig(rows=4).conv_rows(10) == 2   # divisor clipping
    assert KernelConfig(rows=16).conv_rows(20) == 10


# ---------------------------------------------------------------------------
# Resolution: exact → nearest → heuristic
# ---------------------------------------------------------------------------

def _mini_table(tmp_path, entries):
    p = tmp_path / "AUTOTUNE_kernels.json"
    p.write_text(json.dumps({"version": 1, "entries": entries}))
    kc.clear_table_cache()
    return p


def test_resolve_exact_nearest_heuristic(tmp_path):
    dev = kc.device_key()
    key = kc.shape_key("conv3x3", (8, 8, 8, 16), "dot", dev)
    cfg = KernelConfig(op="conv3x3", rows=4, out_step=1.0)
    table = {key: {"config": cfg.to_dict(), "t_us": 10.0}}
    p = _mini_table(tmp_path, table)
    t = kc.load_table(p)
    exact = kc.resolve("conv3x3", (8, 8, 8, 16), accum="dot", table=t)
    assert exact.rows == 4 and exact.source == "table"
    near = kc.resolve("conv3x3", (10, 10, 8, 16), accum="dot", table=t)
    assert near.rows == 4 and near.source == "nearest"
    miss = kc.resolve("matmul", (100, 128, 64), accum="dot", table=t)
    assert miss.source == "heuristic" and miss.bm is None


def test_resolve_nearest_is_deterministic_on_ties(tmp_path):
    dev = kc.device_key()
    # two entries equidistant from the query; the smaller key must win
    e = {kc.shape_key("conv3x3", (8, 8, 8, 16), "dot", dev):
         {"config": KernelConfig(op="conv3x3", rows=2).to_dict()},
         kc.shape_key("conv3x3", (32, 32, 8, 16), "dot", dev):
         {"config": KernelConfig(op="conv3x3", rows=8).to_dict()}}
    p = _mini_table(tmp_path, e)
    t = kc.load_table(p)
    got = kc.resolve("conv3x3", (16, 16, 8, 16), accum="dot", table=t)
    want_key = min(kc.shape_key("conv3x3", (8, 8, 8, 16), "dot", dev),
                   kc.shape_key("conv3x3", (32, 32, 8, 16), "dot", dev))
    assert got.rows == KernelConfig.from_dict(
        e[want_key]["config"]).rows


def test_resolve_tuned_picks_fastest_accum():
    dev = kc.device_key()
    dims = (8, 8, 8, 16)
    t = {kc.shape_key("conv3x3", dims, "dot", dev):
         {"config": KernelConfig(op="conv3x3").to_dict(), "t_us": 20.0},
         kc.shape_key("conv3x3", dims, "popcount", dev):
         {"config": KernelConfig(op="conv3x3", accum="popcount").to_dict(),
          "t_us": 10.0}}
    got = kc.resolve_tuned("conv3x3", dims, table=t)
    assert got.accum == "popcount"
    got = kc.resolve_tuned("conv3x3", dims, allow_popcount=False, table=t)
    assert got.accum == "dot"


def test_table_env_override_and_missing_file(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE",
                       str(tmp_path / "nope.json"))
    kc.clear_table_cache()
    assert kc.load_table() == {}
    cfg = kc.resolve("conv3x3", (8, 8, 8, 16), accum="dot")
    assert cfg.source == "heuristic"
    monkeypatch.delenv("REPRO_AUTOTUNE_TABLE")
    kc.clear_table_cache()


# ---------------------------------------------------------------------------
# Autotune harness: round-trip + tie-break
# ---------------------------------------------------------------------------

def test_select_winner_tie_breaks_on_canonical_key():
    a = KernelConfig(op="conv3x3", rows=4)
    b = KernelConfig(op="conv3x3", rows=2)
    # equal times: winner must be the canonically-smaller config,
    # independent of measurement order
    w1 = autotune.select_winner([(5.0, a), (5.0, b)])
    w2 = autotune.select_winner([(5.0, b), (5.0, a)])
    assert w1 == w2
    assert w1[1] == min((a, b), key=lambda c: json.dumps(
        c.to_dict(), sort_keys=True))


def test_sweep_persist_load_roundtrip(tmp_path):
    """sweep → persist → load → resolve returns the identical winner."""
    dev = kc.device_key()
    op, dims, accum = "conv3x3", (8, 8, 8, 16), "dot"
    entry = autotune.sweep_cell(op, dims, accum, iters=1)
    key = kc.shape_key(op, dims, accum, dev)
    p = tmp_path / "AUTOTUNE_kernels.json"
    p.write_text(json.dumps({"version": 1, "entries": {key: entry}}))
    kc.clear_table_cache()
    loaded = kc.resolve(op, dims, accum=accum, table=kc.load_table(p))
    assert loaded == KernelConfig.from_dict(entry["config"])
    assert loaded.source == "table"


def test_roofline_accounting():
    r = autotune.roofline("matmul", (100, 128, 64))
    assert r["flops"] == 2 * 100 * 128 * 64 + 3 * 100 * 64
    assert r["bound"] in ("compute", "memory")
    assert r["t_model_us_v5e"] > 0
    rp = autotune.roofline("conv3x3_pool", (40, 40, 64, 128))
    rc = autotune.roofline("conv3x3", (40, 40, 64, 128))
    assert rp["bytes"] < rc["bytes"]      # pooled output writes 1/4 the plane


# ---------------------------------------------------------------------------
# Bit-exactness of tuned configs vs the reference path (kernel test shapes)
# ---------------------------------------------------------------------------

MM_SHAPES = [(5, 70, 12), (16, 64, 128), (257, 96, 130)]
CONV_SHAPES = [(2, 8, 8, 16, 32), (1, 10, 10, 64, 75), (3, 6, 10, 24, 40)]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
def test_matmul_candidates_bit_exact(m, k, n):
    """Every candidate config matches its accum mode's reference path
    bit-for-bit (blocking changes the launch grid, not the math); dot vs
    popcount differ only by the dot path's bf16 prologue noise, which the
    kernel tests bound separately under canonical operands."""
    ops = autotune._operands("matmul", (m, k, n))
    for accum in ("dot", "popcount"):
        ref = None
        for cfg in autotune.candidates("matmul", (m, k, n), accum):
            out = np.asarray(autotune._call("matmul", ops, cfg))
            if ref is None:
                ref = out
            assert np.array_equal(out, ref), (accum, cfg)


@pytest.mark.parametrize("b,h,w,cin,cout", CONV_SHAPES)
def test_conv_candidates_bit_exact(b, h, w, cin, cout):
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 256, (b, h, w, cin), np.uint8))
    wt = jnp.asarray(rng.standard_normal((3, 3, cin, cout)), jnp.float32)
    wp = conv_ops.conv_pack_weights(wt)
    mul = jnp.full((cin,), 0.07, jnp.float32)
    div = jnp.asarray(rng.uniform(0.5, 2.0, (cout,)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((cout,)), jnp.float32)
    for accum in ("dot", "popcount"):
        ref = None
        for cfg in autotune.candidates("conv3x3", (h, w, cin, cout), accum):
            out = np.asarray(conv_ops.w1a8_conv3x3(
                a, wp, mul, div, bias, cin=cin, config=cfg))
            if ref is None:
                ref = out
            assert np.array_equal(out, ref), (accum, cfg)


def test_pool_candidates_bit_exact():
    b, h, w, cin, cout = 2, 8, 8, 16, 32
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.integers(0, 256, (b, h, w, cin), np.uint8))
    wt = jnp.asarray(rng.standard_normal((3, 3, cin, cout)), jnp.float32)
    wp = conv_ops.conv_pack_weights(wt)
    mul = jnp.full((cin,), 0.07, jnp.float32)
    div = jnp.asarray(rng.uniform(0.5, 2.0, (cout,)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((cout,)), jnp.float32)
    for accum in ("dot", "popcount"):
        ref = None
        for cfg in autotune.candidates("conv3x3_pool", (h, w, cin, cout),
                                       accum):
            out = np.asarray(conv_ops.w1a8_conv3x3_pool(
                a, wp, mul, div, bias, cin=cin, config=cfg))
            if ref is None:
                ref = out
            assert np.array_equal(out, ref), (accum, cfg)


def test_pool_fused_popcount_accepted():
    """fused=True + accum="popcount" is a valid cell: the fused conv+pool
    kernel has a popcount datapath, so the config constructs cleanly,
    dispatches without rejection, and matches the unfused
    popcount-conv→reduce_window route bit-for-bit. (This used to raise a
    dot-path-only ValueError at dispatch — the config/dispatch split the
    KernelConfig redesign was meant to remove.)"""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.integers(0, 256, (1, 4, 4, 8), np.uint8))
    wp = conv_ops.conv_pack_weights(
        jnp.asarray(rng.standard_normal((3, 3, 8, 16)), jnp.float32))
    mul = jnp.full((8,), 0.05, jnp.float32)
    div = jnp.asarray(rng.uniform(0.5, 2.0, (16,)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    base = KernelConfig(op="conv3x3_pool", accum="popcount", out_step=1.0,
                        interpret=True)
    got = conv_ops.w1a8_conv3x3_pool(a, wp, mul, div, bias, cin=8,
                                     config=base.replace(fused=True))
    want = conv_ops.w1a8_conv3x3_pool(a, wp, mul, div, bias, cin=8,
                                      config=base.replace(fused=False))
    assert got.dtype == jnp.uint8
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Deprecation shim
# ---------------------------------------------------------------------------

def _mm_operands():
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.integers(0, 256, (4, 32), np.uint8))
    w = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    wp = mm_ops.w1a8_pack_weights(w)
    mul = jnp.full((32,), 0.05, jnp.float32)
    div = jnp.ones((8,), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    return a, wp, mul, div, b


def test_legacy_kwargs_warn_once_and_match_config():
    a, wp, mul, div, b = _mm_operands()
    kc._deprecation_warned = False        # re-arm (warn-once pattern)
    with pytest.warns(DeprecationWarning, match="KernelConfig"):
        y_legacy = mm_ops.w1a8_matmul(a, wp, mul, div, b, k=32,
                                      interpret=True, accum="dot")
    y_cfg = mm_ops.w1a8_matmul(a, wp, mul, div, b, k=32,
                               config=KernelConfig(interpret=True))
    assert np.array_equal(np.asarray(y_legacy), np.asarray(y_cfg))
    # second legacy call must NOT re-warn
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        mm_ops.w1a8_matmul(a, wp, mul, div, b, k=32, interpret=True)


def test_config_plus_legacy_kwargs_is_type_error():
    a, wp, mul, div, b = _mm_operands()
    with pytest.raises(TypeError, match="not both"):
        mm_ops.w1a8_matmul(a, wp, mul, div, b, k=32,
                           config=KernelConfig(), interpret=True)


def test_config_op_mismatch_raises():
    a, wp, mul, div, b = _mm_operands()
    with pytest.raises(ValueError, match="entry point"):
        mm_ops.w1a8_matmul(a, wp, mul, div, b, k=32,
                           config=KernelConfig(op="conv3x3"))


def test_detection_backend_legacy_kwargs_warn(tiny_detector):
    from repro.serve import backends
    art = tiny_detector
    backends._detect_kwargs_warned = False
    with pytest.warns(DeprecationWarning, match="profile"):
        be = backends.DetectionBackend(art, slots=1, fuse_pool=False)
    assert be.profile == "default"
    with pytest.raises(TypeError, match="not both"):
        backends.DetectionBackend(art, slots=1, profile="tuned",
                                  interpret=True)
    be2 = backends.DetectionBackend(art, slots=1)
    assert be2.profile == "tuned"


@pytest.fixture(scope="module")
def tiny_detector():
    from repro.models import yolo
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.integers(0, 256, (1, yolo.INPUT_SIZE,
                                             yolo.INPUT_SIZE, 3), np.uint8),
                       jnp.float32) / 256.0
    _, art = yolo.build_detector(jax.random.PRNGKey(0), imgs,
                                 profile="tuned")
    return art


# ---------------------------------------------------------------------------
# Profile plumbing: tuned == interpret bit-for-bit on the model forward
# ---------------------------------------------------------------------------

def test_yolo_profiles_bit_exact(tiny_detector):
    from repro.models import yolo
    art = tiny_detector
    rng = np.random.default_rng(1)
    img = jnp.asarray(rng.integers(0, 256, (1, yolo.INPUT_SIZE,
                                            yolo.INPUT_SIZE, 3), np.uint8),
                      jnp.float32) / 256.0
    base = np.asarray(yolo.yolo_forward_kernel(art, img,
                                               profile="interpret"))
    tuned = np.asarray(yolo.yolo_forward_kernel(art, img, profile="tuned"))
    assert np.array_equal(base, tuned)
    with pytest.raises(ValueError, match="profile"):
        yolo.yolo_forward_kernel(art, img, profile="fastest")
