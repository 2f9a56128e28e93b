"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

Every kernel runs in interpret mode (CPU) and is asserted allclose against
ref.py; the exact-int path is asserted bit-equal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.kernels.config import KernelConfig
from repro.kernels.w1a8_conv import ops as conv_ops
from repro.kernels.w1a8_conv import ref as conv_ref
from repro.kernels.w1a8_matmul import kernel as mm_kernel
from repro.kernels.w1a8_matmul import ops as mm_ops
from repro.kernels.w1a8_matmul import ref as mm_ref


def _mm_case(m, k, n, seed):
    kw, ka, km = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = jax.random.normal(kw, (k, n))
    wp = packing.pack_signs(w, axis=0)
    a = jax.random.randint(ka, (m, k), 0, 256, jnp.int32).astype(jnp.uint8)
    mul = jax.random.uniform(km, (k,), jnp.float32, 0.01, 0.1)
    div = jax.random.uniform(km, (n,), jnp.float32, 0.5, 1.5)
    b = jax.random.normal(km, (n,), jnp.float32)
    return a, wp, mul, div, b


MM_SHAPES = [(1, 32, 8), (5, 70, 12), (16, 64, 128), (128, 512, 256),
             (300, 1152, 75), (2, 4608, 192), (257, 96, 130)]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
def test_w1a8_matmul_matches_ref(m, k, n):
    a, wp, mul, div, b = _mm_case(m, k, n, seed=m * 31 + k + n)
    y_ref = mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, b)
    y_ker = mm_ops.w1a8_matmul(a, wp, mul, div, b, k=k, interpret=True)
    scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               atol=6e-3 * scale)


@pytest.mark.parametrize("m,k,n", [(16, 64, 128), (300, 1152, 75)])
def test_w1a8_matmul_requant_within_1lsb(m, k, n):
    a, wp, mul, div, b = _mm_case(m, k, n, seed=7)
    y = mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, b)
    # realistic LSQ step: matched to the activation range (as training learns)
    step = float(jnp.max(jnp.abs(y))) / 255.0
    q_ref = mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, b,
                                   out_step=jnp.float32(step))
    q_ker = mm_ops.w1a8_matmul(a, wp, mul, div, b, k=k, out_step=step,
                               interpret=True)
    diff = np.abs(np.asarray(q_ker, np.int32) - np.asarray(q_ref, np.int32))
    assert (diff <= 1).mean() > 0.995, f"1-LSB agreement {(diff <= 1).mean()}"
    assert diff.mean() < 0.3


@pytest.mark.parametrize("m,k,n", [(8, 64, 128), (256, 512, 256), (32, 1024, 128)])
def test_w1a8_matmul_int_path_bit_exact(m, k, n):
    a, wp, *_ = _mm_case(m, k, n, seed=k)
    signs = packing.unpack_signs(wp, k, axis=0, dtype=jnp.int32)
    colsum = jnp.sum(signs, axis=0, dtype=jnp.int32).reshape(1, n)
    bm = max(8, min(m, 256))
    bk = min(k, 512)
    bn = min(n, 256)
    y = mm_kernel.w1a8_matmul_int_pallas(a, wp, colsum, bm=bm, bk=bk, bn=bn,
                                         interpret=True)
    y_ref = a.astype(jnp.int32) @ signs
    assert bool(jnp.all(y == y_ref)), "exact-int kernel must be bit-exact"


def test_w1a8_matmul_batched_leading_dims():
    a, wp, mul, div, b = _mm_case(12, 96, 40, seed=3)
    a3 = a.reshape(3, 4, 96)
    y = mm_ops.w1a8_matmul(a3, wp, mul, div, b, k=96, interpret=True)
    assert y.shape == (3, 4, 40)
    y2 = mm_ops.w1a8_matmul(a, wp, mul, div, b, k=96, interpret=True)
    np.testing.assert_allclose(np.asarray(y).reshape(12, 40), np.asarray(y2),
                               rtol=0, atol=1e-5)


CONV_SHAPES = [(1, 4, 4, 8, 16), (2, 8, 8, 16, 32), (1, 10, 10, 64, 75),
               (1, 20, 20, 128, 128), (3, 7, 9, 24, 40)]


@pytest.mark.parametrize("b,h,w,cin,cout", CONV_SHAPES)
def test_w1a8_conv_matches_ref(b, h, w, cin, cout):
    kw, ka, km = jax.random.split(jax.random.PRNGKey(b * 100 + cin), 3)
    wgt = jax.random.normal(kw, (3, 3, cin, cout))
    wp = conv_ops.conv_pack_weights(wgt)
    a = jax.random.randint(ka, (b, h, w, cin), 0, 256, jnp.int32).astype(jnp.uint8)
    mul = jax.random.uniform(km, (cin,), jnp.float32, 0.01, 0.1)
    div = jax.random.uniform(km, (cout,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(km, (cout,), jnp.float32)
    y_ref = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias)
    y_ker = conv_ops.w1a8_conv3x3(a, wp, mul, div, bias, cin=cin,
                                  interpret=True)
    scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               atol=6e-3 * scale)


def test_w1a8_conv_requant_uint8():
    b, h, w, cin, cout = 1, 6, 6, 16, 24
    kw, ka, km = jax.random.split(jax.random.PRNGKey(0), 3)
    wgt = jax.random.normal(kw, (3, 3, cin, cout))
    wp = conv_ops.conv_pack_weights(wgt)
    a = jax.random.randint(ka, (b, h, w, cin), 0, 256, jnp.int32).astype(jnp.uint8)
    mul = jnp.full((cin,), 0.05, jnp.float32)
    div = jnp.ones((cout,), jnp.float32)
    bias = jnp.zeros((cout,), jnp.float32)
    y = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias)
    step = float(jnp.max(jnp.abs(y))) / 255.0
    q_ref = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias,
                                      out_step=jnp.float32(step))
    q_ker = conv_ops.w1a8_conv3x3(a, wp, mul, div, bias, cin=cin,
                                  out_step=step, interpret=True)
    assert q_ker.dtype == jnp.uint8
    diff = np.abs(np.asarray(q_ker, np.int32) - np.asarray(q_ref, np.int32))
    assert (diff <= 1).mean() > 0.995


@pytest.mark.parametrize("make_case", ["matmul", "conv"])
def test_requant_epilogue_rounding_matches_ref_across_zero(make_case):
    """Regression: kernel and ref epilogues must agree **bit-exact** on
    pre-clip values that straddle zero (incl. exact ±half-integers, the
    rounding boundary). Both now call core.quant.round_half_away; note the
    uint8 clip rail at 0 makes the old trunc(x+0.5) form observationally
    identical below zero, so what this locks is the shared rounding helper
    plus exact positive-side agreement — any future epilogue drift (ties,
    offsets, clip order) breaks the equality.

    The arithmetic is made exact on purpose: mul ≡ 1 keeps the bf16 MXU
    operands integral, so the only freedom left is the epilogue.
    """
    if make_case == "matmul":
        m, k, n = 16, 64, 128
        a, wp, *_ = _mm_case(m, k, n, seed=11)
        mul = jnp.ones((k,), jnp.float32)
        div = jnp.ones((n,), jnp.float32)
        # half-integer biases centred so pre-clip y/step straddles zero
        bias = (jnp.arange(n, dtype=jnp.float32) - n / 2) * 7.0 + 0.5
        y = mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, bias)
        step = float(jnp.max(jnp.abs(y))) / 64.0          # many values < 0
        q_ref = mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, bias,
                                       out_step=jnp.float32(step))
        q_ker = mm_ops.w1a8_matmul(a, wp, mul, div, bias, k=k,
                                   out_step=step, interpret=True)
    else:
        b, h, w, cin, cout = 1, 6, 6, 16, 24
        kw, ka = jax.random.split(jax.random.PRNGKey(12), 2)
        wgt = jax.random.normal(kw, (3, 3, cin, cout))
        wp = conv_ops.conv_pack_weights(wgt)
        a = jax.random.randint(ka, (b, h, w, cin), 0, 256,
                               jnp.int32).astype(jnp.uint8)
        mul = jnp.ones((cin,), jnp.float32)
        div = jnp.ones((cout,), jnp.float32)
        bias = (jnp.arange(cout, dtype=jnp.float32) - cout / 2) * 9.0 + 0.5
        y = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias)
        step = float(jnp.max(jnp.abs(y))) / 64.0
        q_ref = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias,
                                          out_step=jnp.float32(step))
        q_ker = conv_ops.w1a8_conv3x3(a, wp, mul, div, bias, cin=cin,
                                      out_step=step, interpret=True)
    q_ref, q_ker = np.asarray(q_ref, np.int32), np.asarray(q_ker, np.int32)
    assert (q_ref == 0).any() and (q_ref > 0).any(), "inputs must straddle 0"
    assert np.array_equal(q_ker, q_ref), \
        f"epilogue rounding drifted from ref ({np.abs(q_ker - q_ref).max()} LSB)"


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("out_step", [None, "auto"])
def test_w1a8_matmul_popcount_bit_exact_vs_dot(m, k, n, out_step):
    """XNOR-popcount accumulation vs the unpack-dot path, bit for bit, on
    every existing matmul test shape. Canonical operands (mul ≡ 1 folded
    into div) keep the dot path's bf16 operands exactly-representable
    integers, so both paths compute the same integer Σ s·a and run the
    same f32 epilogue — any deviation is a popcount bug, not noise."""
    a, wp, _, div, b = _mm_case(m, k, n, seed=m + 2 * k + 3 * n)
    m0 = 0.013
    mul = jnp.full((k,), m0, jnp.float32)
    ones = jnp.ones((k,), jnp.float32)
    if out_step == "auto":
        y = mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, b)
        out_step = float(jnp.max(jnp.abs(y))) / 255.0
    y_pc = mm_ops.w1a8_matmul(a, wp, mul, div, b, k=k, out_step=out_step,
                              accum="popcount", interpret=True)
    y_dot = mm_ops.w1a8_matmul(a, wp, ones, div * m0, b, k=k,
                               out_step=out_step, accum="dot", interpret=True)
    assert np.array_equal(np.asarray(y_pc), np.asarray(y_dot))
    # vs the jnp oracle: identical math, but XLA may contract the epilogue's
    # mul+add into an FMA differently outside Pallas — allow 1 ulp / 1 LSB.
    y_ref = mm_ref.w1a8_matmul_ref(
        a, wp, k, ones, div * m0, b,
        None if out_step is None else jnp.float32(out_step))
    diff = np.abs(np.asarray(y_pc, np.float64) - np.asarray(y_ref, np.float64))
    if out_step is None:
        assert diff.max() <= 4e-6 * (np.abs(np.asarray(y_ref)).max() + 1)
    else:
        assert diff.max() <= 1


@pytest.mark.parametrize("b,h,w,cin,cout", CONV_SHAPES)
@pytest.mark.parametrize("out_step", [None, "auto"])
def test_w1a8_conv_popcount_bit_exact_vs_dot(b, h, w, cin, cout, out_step):
    """Conv analogue of the popcount bit-exactness sweep, incl. the K9p
    padding lanes (9·Cin not a multiple of 32 for most shapes)."""
    kw, ka, km = jax.random.split(jax.random.PRNGKey(b * 7 + cin), 3)
    wgt = jax.random.normal(kw, (3, 3, cin, cout))
    wp = conv_ops.conv_pack_weights(wgt)
    a = jax.random.randint(ka, (b, h, w, cin), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    m0 = 0.05
    mul = jnp.full((cin,), m0, jnp.float32)
    ones = jnp.ones((cin,), jnp.float32)
    div = jax.random.uniform(km, (cout,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(km, (cout,), jnp.float32)
    if out_step == "auto":
        y = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias)
        out_step = float(jnp.max(jnp.abs(y))) / 255.0
    y_pc = conv_ops.w1a8_conv3x3(a, wp, mul, div, bias, cin=cin,
                                 out_step=out_step, accum="popcount",
                                 interpret=True)
    y_dot = conv_ops.w1a8_conv3x3(a, wp, ones, div * m0, bias, cin=cin,
                                  out_step=out_step, accum="dot",
                                  interpret=True)
    assert np.array_equal(np.asarray(y_pc), np.asarray(y_dot))
    # 1-ulp FMA slack vs the jnp oracle (see matmul variant for rationale)
    y_ref = conv_ref.w1a8_conv3x3_ref(
        a, wp, cin, ones, div * m0, bias,
        None if out_step is None else jnp.float32(out_step))
    diff = np.abs(np.asarray(y_pc, np.float64) - np.asarray(y_ref, np.float64))
    if out_step is None:
        assert diff.max() <= 4e-6 * (np.abs(np.asarray(y_ref)).max() + 1)
    else:
        assert diff.max() <= 1


def test_popcount_recovers_exact_integer_accumulation():
    """Neutral epilogue (div ≡ 1, bias ≡ 0, mul ≡ 1): the popcount path's
    output IS the integer Σ_k s_k·a_k — the binary-domain contraction is
    exact, not an approximation (where the dot path's bf16 prologue rounds
    as soon as mul ≠ 1)."""
    m, k, n = 32, 96, 64
    a, wp, *_ = _mm_case(m, k, n, seed=99)
    ones_k = jnp.ones((k,), jnp.float32)
    ones_n = jnp.ones((n,), jnp.float32)
    zeros_n = jnp.zeros((n,), jnp.float32)
    signs = packing.unpack_signs(wp, k, axis=0, dtype=jnp.int32)
    want = np.asarray(a, np.int64) @ np.asarray(signs, np.int64)
    got = mm_ops.w1a8_matmul(a, wp, ones_k, ones_n, zeros_n, k=k,
                             accum="popcount", interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_packing_roundtrip_axes():
    for axis, shape in [(0, (70, 12)), (1, (12, 70)), (0, (32, 5)), (0, (33, 4))]:
        w = jax.random.normal(jax.random.PRNGKey(axis + shape[0]), shape)
        pk = packing.pack_signs(w, axis=axis)
        un = packing.unpack_signs(pk, shape[axis], axis=axis)
        expect = np.where(np.asarray(w) >= 0, 1, -1)
        assert np.array_equal(np.asarray(un), expect)


POOL_SHAPES = [(1, 4, 4, 8, 16), (2, 8, 8, 16, 32), (1, 10, 10, 64, 75),
               (3, 6, 10, 24, 40)]


@pytest.mark.parametrize("b,h,w,cin,cout", POOL_SHAPES)
def test_fused_pool_popcount_bit_exact(b, h, w, cin, cout):
    """Fused conv+pool popcount datapath, on every even-plane kernel test
    shape (incl. ragged Cout=75 and the K9p-padded Cin=24): bit-exact vs
    (a) the fused DOT datapath under canonical operands (mul ≡ m0 folded
    into div keeps the dot prologue's bf16 operands exact integers — both
    paths compute the same Σ s·a and run the same requant+2×2-max
    epilogue) and (b) the unfused popcount-conv→reduce_window route under
    the original operands."""
    from repro.kernels.config import KernelConfig
    kw, ka, km = jax.random.split(jax.random.PRNGKey(b * 13 + cin), 3)
    wgt = jax.random.normal(kw, (3, 3, cin, cout))
    wp = conv_ops.conv_pack_weights(wgt)
    a = jax.random.randint(ka, (b, h, w, cin), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    m0 = 0.05
    mul = jnp.full((cin,), m0, jnp.float32)
    ones = jnp.ones((cin,), jnp.float32)
    div = jax.random.uniform(km, (cout,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(km, (cout,), jnp.float32)
    y = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias)
    step = float(jnp.max(jnp.abs(y))) / 255.0
    base = KernelConfig(op="conv3x3_pool", accum="popcount", out_step=step,
                        interpret=True)
    y_pc = conv_ops.w1a8_conv3x3_pool(a, wp, mul, div, bias, cin=cin,
                                      config=base.replace(fused=True))
    y_dot = conv_ops.w1a8_conv3x3_pool(
        a, wp, ones, div * m0, bias, cin=cin,
        config=base.replace(fused=True, accum="dot"))
    y_unf = conv_ops.w1a8_conv3x3_pool(a, wp, mul, div, bias, cin=cin,
                                       config=base.replace(fused=False))
    assert y_pc.dtype == jnp.uint8
    assert y_pc.shape == (b, h // 2, w // 2, cout)
    assert np.array_equal(np.asarray(y_pc), np.asarray(y_dot))
    assert np.array_equal(np.asarray(y_pc), np.asarray(y_unf))


def test_fused_conv_pool_matches_unfused():
    """Paper §5.2 Post+MaxPool fusion: one kernel == conv→requant→pool."""
    from repro.kernels.w1a8_conv.fused_pool import w1a8_conv3x3_pool2
    b, h, w, cin, cout = 1, 8, 8, 16, 32
    kw, ka, km = jax.random.split(jax.random.PRNGKey(5), 3)
    wgt = jax.random.normal(kw, (3, 3, cin, cout))
    wp = conv_ops.conv_pack_weights(wgt)
    a = jax.random.randint(ka, (b, h, w, cin), 0, 256, jnp.int32).astype(jnp.uint8)
    mul = jax.random.uniform(km, (cin,), jnp.float32, 0.01, 0.1)
    div = jax.random.uniform(km, (cout,), jnp.float32, 0.5, 1.5)
    bias = jax.random.normal(km, (cout,), jnp.float32)
    y = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias)
    step = float(jnp.max(jnp.abs(y))) / 255.0
    q = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias,
                                  out_step=jnp.float32(step))
    want = jax.lax.reduce_window(q, jnp.uint8(0), jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    got = w1a8_conv3x3_pool2(a, wp, mul, div, bias, cin=cin, out_step=step,
                             interpret=True)
    assert got.shape == (b, h // 2, w // 2, cout)
    diff = np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32))
    assert (diff <= 1).mean() > 0.995 and diff.max() <= 2


def _exact_epilogue_case(cout, seed):
    """Operands that make the epilogue exact in f32 (a power-of-two Div,
    half-integer biases, a power-of-two shortcut ratio), so that the
    kernel and the oracle must agree bit for bit in either accum mode."""
    div = jnp.full((cout,), 0.125, jnp.float32)
    bias = (jnp.arange(cout, dtype=jnp.float32) - cout / 2) * 3.0 + 0.5
    ratio = jnp.full((cout,), 0.25, jnp.float32)
    return div, bias, ratio


@pytest.mark.parametrize("accum", ["dot", "popcount"])
@pytest.mark.parametrize("stride,with_skip", [(2, False), (1, True),
                                              (2, True)],
                         ids=["stride2", "shortcut", "stride2-shortcut"])
def test_w1a8_conv_gemm_bit_exact_vs_ref(stride, with_skip, accum):
    """The im2col route of the 3×3 conv: stride 2 computed at the output
    pixels only (darknet's one pixel of padding a side), and the residual
    input added after the ReLU in the epilogue, against the oracle."""
    b, h, w, cin, cout = 2, 10, 10, 12, 40
    kw, ka, ks = jax.random.split(jax.random.PRNGKey(stride * 10 + cin), 3)
    wp = conv_ops.conv_pack_weights(jax.random.normal(kw, (3, 3, cin, cout)))
    a = jax.random.randint(ka, (b, h, w, cin), 0, 256,
                           jnp.int32).astype(jnp.uint8)
    ho = (h - 1) // stride + 1
    div, bias, ratio = _exact_epilogue_case(cout, stride)
    res = {}
    if with_skip:
        res = {"skip": jax.random.randint(ks, (b, ho, ho, cout), 0, 256,
                                          jnp.int32).astype(jnp.uint8),
               "skip_ratio": ratio}
    mul = jnp.ones((cin,), jnp.float32)
    q_ref = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias,
                                      jnp.float32(1.0), stride=stride, **res)
    cfg = KernelConfig(op="matmul", accum=accum, out_step=1.0,
                       interpret=True)
    q = conv_ops.w1a8_conv3x3_gemm(a, wp, mul, div, bias, cin=cin,
                                   stride=stride, config=cfg, **res)
    assert q.dtype == jnp.uint8 and q.shape == (b, ho, ho, cout)
    q, q_ref = np.asarray(q), np.asarray(q_ref)
    assert 0.05 < np.mean((q > 0) & (q < 255)), "codes must not all clip"
    assert np.array_equal(q, q_ref)
    # a stride-2 conv is the stride-1 conv at every other pixel
    if stride == 2 and not with_skip:
        full = conv_ref.w1a8_conv3x3_ref(a, wp, cin, mul, div, bias,
                                         jnp.float32(1.0))
        assert np.array_equal(q, np.asarray(full)[:, ::2, ::2])


@pytest.mark.parametrize("accum", ["dot", "popcount"])
def test_w1a8_matmul_shortcut_epilogue(accum):
    """The residual epilogue adds the shortcut after the ReLU (darknet's
    order): y = max(acc·div + bias, 0) + skip·ratio, then round/clip."""
    m, k, n = 48, 96, 136
    a, wp, *_ = _mm_case(m, k, n, seed=5)
    div, bias, ratio = _exact_epilogue_case(n, 5)
    skip = jax.random.randint(jax.random.PRNGKey(6), (m, n), 0, 256,
                              jnp.int32).astype(jnp.uint8)
    mul = jnp.ones((k,), jnp.float32)
    cfg = KernelConfig(op="matmul", accum=accum, out_step=1.0,
                       interpret=True)
    q = np.asarray(mm_ops.w1a8_matmul(a, wp, mul, div, bias, k=k,
                                      config=cfg, skip=skip,
                                      skip_ratio=ratio))
    y = np.asarray(mm_ref.w1a8_matmul_ref(a, wp, k, mul, div, bias),
                   np.float64)
    want = np.clip(np.trunc(np.maximum(y, 0) + np.asarray(skip) * 0.25
                            + 0.5), 0, 255)
    assert (y < 0).mean() > 0.1, "some convs must be cut by the ReLU"
    assert np.array_equal(q, want)
    with pytest.raises(ValueError, match="quantizing epilogue"):
        mm_ops.w1a8_matmul(a, wp, mul, div, bias, k=k,
                           config=cfg.replace(out_step=None), skip=skip,
                           skip_ratio=ratio)
