"""Property-based tests (hypothesis) on the system's invariants.

hypothesis is an optional dev dependency (declared in pyproject's ``dev``
extra); when absent the whole module skips instead of erroring collection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402
hnp = pytest.importorskip("hypothesis.extra.numpy")

from repro.core import fixedpoint as fxp
from repro.core import packing
from repro.core.quant import (ACT_QMAX, binarize_weight, quantize_act,
                              round_half_away, sign_accumulate_fused)
from repro.launch.mesh import make_mesh

SET = dict(deadline=None, max_examples=25)


@settings(**SET)
@given(hnp.arrays(np.float32, hnp.array_shapes(min_dims=2, max_dims=2,
                                               min_side=1, max_side=80),
                  elements=st.floats(-4, 4, width=32,
                                     allow_subnormal=False)))
def test_pack_unpack_roundtrip(w):
    pk = packing.pack_signs(jnp.asarray(w), axis=0)
    un = np.asarray(packing.unpack_signs(pk, w.shape[0], axis=0))
    assert np.array_equal(un, np.where(w >= 0, 1, -1))
    # storage: exactly ceil(K/32) words per column
    assert pk.shape == ((w.shape[0] + 31) // 32, w.shape[1])


@settings(**SET)
@given(hnp.arrays(np.float32, (13,), elements=st.floats(-1e4, 1e4,
                                                        width=32)))
def test_round_half_away_matches_python(x):
    got = np.asarray(round_half_away(jnp.asarray(x)))
    import math
    want = np.asarray([math.floor(v + 0.5) if v >= 0 else math.ceil(v - 0.5)
                       for v in x], np.float32)
    assert np.array_equal(got, want)


@settings(**SET)
@given(hnp.arrays(np.float32, (4, 7), elements=st.floats(-100, 100,
                                                         width=32)),
       st.floats(1e-3, 2.0))
def test_quantize_act_bounds_and_idempotence(x, step):
    q = np.asarray(quantize_act(jnp.asarray(x), jnp.float32(step)))
    assert q.min() >= 0 and q.max() <= ACT_QMAX
    assert np.array_equal(q, np.round(q))            # integer codes
    # quantizing a dequantized value is a fixed point
    q2 = np.asarray(quantize_act(jnp.asarray(q * step), jnp.float32(step)))
    assert np.array_equal(q, q2)


@settings(**SET)
@given(st.integers(0, 2 ** 40), st.integers(1, 2 ** 16), st.integers(4, 20))
def test_fixed_mul_rshift_is_rounded_product(x, m, f):
    got = int(fxp.fixed_mul_rshift(np.int64(x), np.int64(m), f))
    want = int(np.floor(x * m / 2 ** f + 0.5))
    assert got == want


@settings(**SET)
@given(st.floats(-30, 30, width=32))
def test_qformat_roundtrip_error_bound(v):
    qf = fxp.CONV1_W                                  # Q5.11
    rt = float(qf.roundtrip(jnp.float32(v)))
    if -32 <= v <= 31.999:                            # in range
        assert abs(rt - v) <= 2 ** -11 / 2 + 1e-9
    assert qf.raw_min / qf.scale <= rt <= qf.raw_max / qf.scale


@settings(**SET)
@given(hnp.arrays(np.float32, (3, 24), elements=st.floats(0, 255, width=32)),
       hnp.arrays(np.float32, (24, 8), elements=st.floats(-2, 2, width=32)),
       hnp.arrays(np.float32, (24,), elements=st.floats(0.0078125, 1.0,
                                                        width=32)))
def test_eq34_fusion_equals_two_step(a, w, m):
    """Eq. 3-4: Σ s(m·a) == (a ⊙ m) @ sign(w) — fusion is exact algebra."""
    signs = binarize_weight(jnp.asarray(w))
    fused = np.asarray(sign_accumulate_fused(jnp.asarray(a), jnp.asarray(m),
                                             signs))
    # numpy accumulates in f64; tolerate f32 summation-order differences
    twostep = np.asarray((a * m) @ np.asarray(signs))
    scale = np.abs(twostep).max() + 1.0
    np.testing.assert_allclose(fused, twostep, atol=2e-5 * scale)


@settings(deadline=None, max_examples=10)
@given(st.integers(1, 3), st.integers(8, 40), st.integers(1, 2),
       st.integers(0, 1000))
def test_blockwise_attention_equals_dense(b, s, kvh_pow, seed):
    from repro.models.layers import _blockwise_attention, _attn_weights
    kv = 2 * kvh_pow
    h, hd = kv * 2, 8
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kv, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    probs, g = _attn_weights(q, k, causal=True, window=0, softcap=0.0,
                             q_pos=pos, k_pos=pos)
    dense = jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(b, s, h, hd)
    block = _blockwise_attention(q, k, v, causal=True, window=0, softcap=0.0,
                                 q_pos=pos, k_pos=pos, block=16)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=2e-5)


@settings(deadline=None, max_examples=10)
@given(st.integers(4, 32), st.integers(0, 100))
def test_moe_no_drop_when_cf_equals_experts(t, seed):
    """cap ≥ T·k ⇒ every assignment survives ⇒ Σ gates recovered exactly."""
    from repro.models.layers import ModelConfig
    from repro.models import moe as moe_mod
    cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=8,
                      num_experts=4, top_k=2, capacity_factor=4.0,
                      w1a8_body=False)
    p = moe_mod.init_moe(jax.random.PRNGKey(seed), cfg)
    # identity-ish experts: y should equal Σ_k gate_k · expert_k(x)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, 16))
    y = moe_mod.moe_ffn(p, cfg, x, mode="float")
    # brute-force reference over all experts
    import numpy as _np
    logits = np.asarray(x @ p["router"])
    top = _np.argsort(-logits, axis=1)[:, :2]
    gates = jax.nn.softmax(jnp.take_along_axis(jnp.asarray(logits),
                                               jnp.asarray(top), 1), -1)
    want = _np.zeros((t, 16), _np.float32)
    for e in range(4):
        up = np.asarray(x @ p["up"][e])
        gt = np.asarray(x @ p["gate"][e])
        h = up * (gt / (1 + _np.exp(-gt)))
        out_e = h @ np.asarray(p["down"][e])
        for kk in range(2):
            mask = (top[:, kk] == e)
            want[mask] += _np.asarray(gates)[mask, kk, None] * out_e[mask]
    _np.testing.assert_allclose(np.asarray(y), want, atol=3e-4)


@settings(deadline=None, max_examples=40)
@given(st.data())
def test_scheduler_trace_fifo_within_deadline_no_slot_leak(data):
    """serve v3 scheduler property: random arrival traces — bursts of 1–4B
    requests, mixed lm/detect lifetimes, deadlines, priority classes,
    bounded queue — must admit (priority, deadline, arrival-seq) order,
    never leak slots, and end with an empty wait queue (checked against the
    pure-python reference model in tests/test_serve_stream.py; a failing
    example's trace is printed in the assertion message, and hypothesis
    shrinks it)."""
    from test_serve_stream import assert_trace_ok
    capacity = data.draw(st.integers(1, 4), label="capacity")
    admit_width = data.draw(st.one_of(st.none(), st.integers(1, capacity)),
                            label="admit_width")
    rid = 0
    trace = []
    for _ in range(data.draw(st.integers(1, 4), label="n_bursts")):
        idle = data.draw(st.integers(0, 2))
        burst = []
        for _ in range(data.draw(st.integers(1, 4 * capacity))):  # 1..4B
            burst.append((rid,
                          data.draw(st.sampled_from(["lm", "detect"])),
                          data.draw(st.integers(1, 3)),        # lifetime
                          data.draw(st.one_of(st.none(),
                                              st.integers(0, 6))),
                          data.draw(st.integers(0, 2))))       # priority
            rid += 1
        trace.append((idle, burst))
    max_queue = data.draw(st.one_of(st.none(),
                                    st.integers(1, 3 * capacity)),
                          label="max_queue")
    assert_trace_ok(capacity, admit_width, trace, max_queue)


@settings(deadline=None, max_examples=30)
@given(st.data())
def test_fleet_router_conserves_requests_and_replays_deterministically(data):
    """Fleet property: random arrival traces through a Router (random
    replica count, queue bound, scripted scale events) — no request lost or
    duplicated (completed + every drop cause = submitted, each rid surfaces
    exactly once), scale-down never strands queued or in-flight work, and
    an identical replay produces the identical result stream (checked
    against the pure-python fleet reference in tests/test_fleet.py)."""
    from test_fleet import assert_fleet_trace_ok
    n_replicas = data.draw(st.integers(1, 3), label="replicas")
    width = data.draw(st.integers(1, 3), label="width")
    service = data.draw(st.integers(1, 3), label="service_ticks")
    max_queue = data.draw(st.one_of(st.none(), st.integers(1, 6)),
                          label="max_queue")
    rid = 0
    trace = []
    for _ in range(data.draw(st.integers(1, 5), label="n_bursts")):
        idle = data.draw(st.integers(0, 3))
        burst = []
        for _ in range(data.draw(st.integers(0, 4 * width))):
            burst.append((rid,
                          data.draw(st.one_of(st.none(),
                                              st.integers(0, 6))),  # dl
                          data.draw(st.integers(0, 2))))            # prio
            rid += 1
        trace.append((idle, burst))
    # scripted scale events: (tick, +1|-1) — exercises drain/retire paths
    scale_script = data.draw(
        st.lists(st.tuples(st.integers(0, 12), st.sampled_from([+1, -1])),
                 max_size=3), label="scale_script")
    assert_fleet_trace_ok(n_replicas, width, service, trace,
                          max_queue=max_queue, scale_script=dict(scale_script))


@settings(deadline=None, max_examples=25)
@given(hnp.arrays(np.float32, (4, 6),
                  elements=st.floats(-4, 4, width=32, allow_subnormal=False)),
       st.sampled_from([1e-6, 1e-3, 1.0, 1e3, 1e6]),
       st.booleans())
def test_int8_wire_permute_roundtrip_within_envelope(x, mag, flip):
    """The pipeline stage wire: quantize → ppermute(int8 codes + f32 scale)
    → dequantize round-trips within the documented envelope |x̂ − x| ≤
    max|x|/254 per element per hop (collectives.permute_quantized), across
    magnitudes and sign mixes including rows that straddle zero; devices
    outside the permutation dequantize to exactly 0 (the f32-ppermute
    boundary semantics the 1F1B schedule relies on)."""
    from repro.dist.collectives import permute_quantized
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (see conftest.py)")
    x = x * np.float32(mag) * (np.float32(-1.0) if flip else np.float32(1.0))
    mesh = make_mesh((4,), ("d",))
    spec = jax.sharding.PartitionSpec("d")
    shift = [(i, i + 1) for i in range(3)]        # ring edge stays dark
    fn = jax.jit(jax.shard_map(lambda s: permute_quantized(s, "d", shift),
                               mesh=mesh, in_specs=spec, out_specs=spec))
    out = np.asarray(fn(jnp.asarray(x)))
    np.testing.assert_array_equal(out[0], 0.0)    # boundary device: exact 0
    for row in range(3):                          # device row → row+1
        envelope = np.abs(x[row]).max() / 254 + 1e-30
        err = np.abs(out[row + 1] - x[row]).max()
        assert err <= envelope * (1 + 1e-6), (row, err, envelope)


@settings(deadline=None, max_examples=25)
@given(hnp.arrays(np.float32, (4, 64),
                  elements=st.floats(-4, 4, width=32, allow_subnormal=False)),
       st.sampled_from([1e-6, 1e-3, 1.0, 1e3, 1e6]),
       st.booleans(), st.booleans())
def test_b1_roundtrip_sign_exact_alpha_clamped(x, mag, flip, per_slice):
    """The b1 activation wire (QTensor.quantize_b1 → dequantize): signs
    survive the round trip exactly (x̂ = sign(x)·α with the x ≥ 0 → +1
    packing convention), |x̂| ≡ α = mean|x| (per tensor, or per row under
    per_slice=True) across six orders of magnitude and global sign flips,
    and an all-zero row — forced into every example — hits the 1e-20 α
    clamp instead of NaN-poisoning the dequantize."""
    from repro.core.qtensor import QTensor
    x = x * np.float32(mag) * (np.float32(-1.0) if flip else np.float32(1.0))
    x[1] = 0.0                                    # guaranteed all-zero row
    qt = QTensor.quantize_b1(jnp.asarray(x), axis=-1, per_slice=per_slice)
    xh = np.asarray(qt.dequantize())
    alpha = np.asarray(qt.scale)
    assert np.all(np.isfinite(xh)) and np.all(alpha >= 1e-20)
    assert np.array_equal(np.sign(xh), np.where(x >= 0, 1.0, -1.0))
    np.testing.assert_array_equal(np.abs(xh), np.broadcast_to(alpha, xh.shape))
    want = np.abs(x).mean(axis=-1, keepdims=True) if per_slice \
        else np.abs(x).mean()
    np.testing.assert_allclose(alpha, np.maximum(want, 1e-20).astype(
        np.float32), rtol=1e-5)
    if per_slice:                                 # the clamp, observably
        assert alpha.reshape(-1)[1] == np.float32(1e-20)
        assert np.abs(xh[1]).max() <= 1e-20


@settings(deadline=None, max_examples=8)
@given(st.integers(2, 12), st.integers(0, 50))
def test_nms_kept_boxes_are_mutually_distant(n, seed):
    from repro.models.detection import iou_cxcywh, nms
    key = jax.random.PRNGKey(seed)
    boxes = jnp.stack([jax.random.uniform(key, (n,), minval=0.2, maxval=0.8),
                       jax.random.uniform(jax.random.fold_in(key, 1), (n,),
                                          minval=0.2, maxval=0.8),
                       jnp.full((n,), 0.2), jnp.full((n,), 0.2)], -1)
    scores = jax.random.uniform(jax.random.fold_in(key, 2), (n, 20),
                                minval=0.3, maxval=1.0)
    ob, osc, oc = nms(boxes, scores, iou_thresh=0.45, max_out=n)
    kept = [(np.asarray(ob[i]), int(oc[i])) for i in range(n)
            if float(osc[i]) > 0]
    for i in range(len(kept)):
        for j in range(i + 1, len(kept)):
            if kept[i][1] == kept[j][1]:
                iou = float(iou_cxcywh(jnp.asarray(kept[i][0]),
                                       jnp.asarray(kept[j][0])))
                assert iou <= 0.45 + 1e-6
