"""Single-process 1F1B/GPipe pipelined-training guards (fast CPU).

Runs on the 16 forced host devices set up by conftest.py -- no subprocess,
no second jax runtime. The heavyweight end-to-end checks live in
tests/dist_main.py; these cover the schedule algebra (bubble fraction,
stash depth), the sequential-oracle match, and the int8-wire gradient
envelope established in PR 1 (~1.4% rel err on unit-normal grads,
asserted < 3%).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.dist.collectives import dequantize_wire, quantize_wire
from repro.dist.pipeline import (
    _schedule_constants,
    bubble_fraction,
    bubble_fraction_1f1b,
    pipeline_train_reference,
    pipeline_train_step,
)
from repro.launch.mesh import make_mesh

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 16,
    reason="needs the forced 16-device host platform (see conftest.py)",
)


def _stage_fn(w, x):
    return jnp.tanh(x @ w["w"] + w["b"])


def _loss_fn(top, y, aux):
    return jnp.mean((y @ top["head"] - aux["tgt"]) ** 2)


def _toy(n, num_micro, mb, d=16):
    key = jax.random.PRNGKey(0)
    ws = {
        "w": jax.random.normal(key, (n, d, d)) * 0.3,
        "b": jax.random.normal(jax.random.fold_in(key, 1), (n, d)) * 0.1,
    }
    head = jax.random.normal(jax.random.fold_in(key, 2), (d, d))
    x = jax.random.normal(jax.random.fold_in(key, 3), (num_micro, mb, d))
    tgt = jax.random.normal(jax.random.fold_in(key, 4), (num_micro, mb, d))
    return ws, {"head": head * 0.2}, x, {"tgt": tgt}


def _toy_sat(n, num_micro, mb, d=16):
    """Sign-dominated variant: weights scaled so every tanh saturates to
    ~±1 — the b1-wire contract (|out| ≈ const, information in the sign
    plane). Built on PRNGKey(0) like `_toy` but with wscale 3.0 / x×2."""
    key = jax.random.PRNGKey(0)
    ws = {
        "w": jax.random.normal(key, (n, d, d)) * 3.0,
        "b": jax.random.normal(jax.random.fold_in(key, 1), (n, d)) * 0.1,
    }
    head = jax.random.normal(jax.random.fold_in(key, 2), (d, d))
    x = jax.random.normal(jax.random.fold_in(key, 3), (num_micro, mb, d)) * 2.0
    tgt = jax.random.normal(jax.random.fold_in(key, 4), (num_micro, mb, d))
    return ws, {"head": head * 0.2}, x, {"tgt": tgt}


def _qdq(x, qtype):
    return dequantize_wire(quantize_wire(x, qtype), x.dtype)


def _b1_wire_reference(stage_fn, loss_fn, ws, x, aux, top):
    """Sequential oracle with the b1 wire noise at every stage boundary.

    Emulates exactly what `pipeline_train_step(act_wire="b1")` computes,
    minus the schedule: forward activations cross each boundary as
    quantize→dequantize b1 (sign·α), backward cotangents as int8, and
    each stage's VJP runs at the dequantized stashed input. The pipelined
    schedules must match THIS reference tightly — the wire noise is the
    documented envelope, the schedule algebra must add nothing."""
    tm = jax.tree_util.tree_map
    n = jax.tree_util.tree_leaves(ws)[0].shape[0]
    num_m = x.shape[0]
    gw = tm(jnp.zeros_like, ws)
    gtop = tm(jnp.zeros_like, top)
    dxs = jnp.zeros_like(x)
    loss_acc = 0.0
    for m in range(num_m):
        h, fns = x[m], []
        for s in range(n):
            out, f = jax.vjp(stage_fn, tm(lambda le: le[s], ws), h)
            fns.append(f)
            if s < n - 1:
                h = _qdq(out, "b1")
        aux_m = tm(lambda a: a[m], aux)
        loss_m, (dtop_m, ct) = jax.value_and_grad(
            lambda tp, yy: loss_fn(tp, yy, aux_m), argnums=(0, 1)
        )(top, out)
        loss_acc += loss_m
        gtop = tm(lambda a, g: a + g, gtop, dtop_m)
        for s in reversed(range(n)):
            dw_s, dx = fns[s](ct)
            gw = tm(lambda a, g, s=s: a.at[s].add(g), gw, dw_s)
            if s > 0:
                ct = _qdq(dx, "s8")
        dxs = dxs.at[m].set(dx)
    inv = 1.0 / num_m
    return (
        loss_acc * inv,
        tm(lambda g: g * inv, gw),
        tm(lambda g: g * inv, gtop),
        dxs * inv,
    )


def _rel(got, want):
    got_l = jax.tree_util.tree_leaves(got)
    want_l = jax.tree_util.tree_leaves(want)
    d = jnp.sqrt(sum(jnp.sum((a - b) ** 2) for a, b in zip(got_l, want_l)))
    nrm = jnp.sqrt(sum(jnp.sum(b**2) for b in want_l))
    return float(d / nrm)


def test_bubble_fraction_drops_vs_gpipe():
    # same (n, M): the 1F1B span is M+2n-1 ticks vs GPipe's 2(M+n-1)
    for num_micro in (4, 8, 16):
        gp = bubble_fraction(4, num_micro)
        ob = bubble_fraction_1f1b(4, num_micro)
        assert ob < gp, (num_micro, ob, gp)
    assert bubble_fraction_1f1b(1, 8) == 0.0
    assert bubble_fraction_1f1b(4, 32) < bubble_fraction_1f1b(4, 8)


def test_1f1b_stash_depth_is_o_n_not_o_m():
    assert _schedule_constants(4, 64, "1f1b")["ring"] == 7
    assert _schedule_constants(4, 64, "gpipe")["ring"] == 64
    assert _schedule_constants(4, 4, "1f1b")["ring"] == 4
    with pytest.raises(ValueError):
        _schedule_constants(4, 4, "zb-h1")


@needs_devices
@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_pipeline_train_matches_oracle(schedule):
    n, num_micro = 4, 8
    ws, top, x, aux = _toy(n, num_micro, mb=2)
    loss_ref, gws_ref, gtop_ref, dx_ref = pipeline_train_reference(
        _stage_fn, _loss_fn, ws, x, aux=aux, top=top
    )
    mesh = make_mesh((n,), ("stage",))
    step = pipeline_train_step(
        _stage_fn,
        _loss_fn,
        mesh=mesh,
        axis="stage",
        num_micro=num_micro,
        schedule=schedule,
    )
    with mesh:
        loss, gws, gtop, dx = step(ws, x, aux=aux, top=top)
    assert abs(float(loss) - float(loss_ref)) / abs(float(loss_ref)) < 1e-5
    assert _rel(gws, gws_ref) < 1e-5
    assert _rel(gtop, gtop_ref) < 1e-5
    assert _rel(dx, dx_ref) < 1e-5


@needs_devices
@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_act_wire_int8_envelope(schedule):
    """int8 stage-boundary wire (activations fwd + cotangents bwd, each hop
    quantize→permute→dequantize at ≤ max|x|/254 per element): the 1F1B/
    GPipe training oracle match degrades from 1e-5 to a bounded few-percent
    envelope — the ICI-bandwidth/precision trade, asserted both ways
    (close to the oracle, but alive: the wire is actually quantized)."""
    n, num_micro = 4, 8
    ws, top, x, aux = _toy(n, num_micro, mb=2)
    loss_ref, gws_ref, gtop_ref, dx_ref = pipeline_train_reference(
        _stage_fn, _loss_fn, ws, x, aux=aux, top=top
    )
    mesh = make_mesh((n,), ("stage",))
    step = pipeline_train_step(
        _stage_fn,
        _loss_fn,
        mesh=mesh,
        axis="stage",
        num_micro=num_micro,
        schedule=schedule,
        act_wire="int8",
    )
    with mesh:
        loss, gws, gtop, dx = step(ws, x, aux=aux, top=top)
    assert abs(float(loss) - float(loss_ref)) / abs(float(loss_ref)) < 0.02
    assert _rel(gws, gws_ref) < 0.05
    assert _rel(gtop, gtop_ref) < 0.05
    assert _rel(dx, dx_ref) < 0.05
    assert _rel(gws, gws_ref) > 1e-7          # quantization actually on wire


@needs_devices
@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_act_wire_b1_envelope(schedule):
    """b1 stage-boundary wire (packed signs + α forward, int8 cotangents
    backward), asserted both directions twice over: (1) the pipelined
    schedules match the b1-wire sequential reference at oracle tightness —
    schedule algebra adds nothing on top of the wire noise; (2) vs the
    CLEAN fp32 oracle the loss sits inside the documented few-percent
    envelope on a sign-dominated (saturated-tanh) toy, yet measurably off
    it — the 1-bit wire is actually on. Gradients vs the clean oracle are
    deliberately NOT enveloped: saturated-tanh VJPs are exponentially
    sensitive to the sign·α forward perturbation (see DESIGN.md §16)."""
    n, num_micro = 4, 8
    ws, top, x, aux = _toy_sat(n, num_micro, mb=2)
    loss_c, gws_c, _, _ = pipeline_train_reference(
        _stage_fn, _loss_fn, ws, x, aux=aux, top=top
    )
    loss_ref, gws_ref, gtop_ref, dx_ref = _b1_wire_reference(
        _stage_fn, _loss_fn, ws, x, aux, top
    )
    mesh = make_mesh((n,), ("stage",))
    step = pipeline_train_step(
        _stage_fn,
        _loss_fn,
        mesh=mesh,
        axis="stage",
        num_micro=num_micro,
        schedule=schedule,
        act_wire="b1",
    )
    with mesh:
        loss, gws, gtop, dx = step(ws, x, aux=aux, top=top)
    # (1) schedule correctness under the b1 wire: oracle-tight
    assert abs(float(loss) - float(loss_ref)) / abs(float(loss_ref)) < 1e-5
    assert _rel(gws, gws_ref) < 1e-4
    assert _rel(gtop, gtop_ref) < 1e-4
    assert _rel(dx, dx_ref) < 1e-4
    # (2) documented envelope vs the clean oracle — and alive
    assert abs(float(loss) - float(loss_c)) / abs(float(loss_c)) < 0.05
    assert abs(float(loss) - float(loss_c)) / abs(float(loss_c)) > 1e-7
    assert _rel(gws, gws_c) > 1e-7            # 1-bit wire actually on


def test_act_wire_validated():
    with pytest.raises(ValueError, match="act_wire"):
        pipeline_train_step(_stage_fn, _loss_fn,
                            mesh=make_mesh((2,), ("stage",)),
                            axis="stage", num_micro=2, act_wire="fp16")


@needs_devices
@pytest.mark.parametrize("wire,tol", [("fp32", 1e-5), ("int8", 0.03)])
def test_dp_grad_wire_envelope(wire, tol):
    n, num_micro = 2, 4
    ws, top, x, aux = _toy(n, num_micro, mb=8)
    ref = pipeline_train_reference(_stage_fn, _loss_fn, ws, x, aux=aux, top=top)
    mesh = make_mesh((n, 8), ("stage", "data"))
    step = pipeline_train_step(
        _stage_fn,
        _loss_fn,
        mesh=mesh,
        axis="stage",
        num_micro=num_micro,
        dp_axis="data",
        grad_wire=wire,
    )
    with mesh:
        loss, gws, gtop, _ = step(ws, x, aux=aux, top=top)
    assert abs(float(loss) - float(ref[0])) < 1e-5
    assert _rel(gws, ref[1]) < tol
    assert _rel(gtop, ref[2]) < tol
