"""Pipeline parallelism over a mesh axis (DESIGN.md §9).

Forward-only GPipe plus full **pipelined training** with 1F1B and GPipe
schedules. ``gpipe(stage_fn, mesh=m, axis='pod', num_micro=M)`` maps
``n = |axis|`` pipeline stages onto the devices of ``axis``. Stage weights
shard over the axis (device s holds stage s); microbatches stream through
with the classic GPipe schedule: ``M + n − 1`` ticks, tick ``t`` has device
``s`` processing microbatch ``t − s``, activations hop one device per tick
via ``collective_permute`` (nearest-neighbour ICI traffic only — no gather
of the full activation set anywhere). Bubble fraction is the usual
``(n−1)/(M+n−1)``; utilisation is reported by :func:`bubble_fraction` so
launch tooling can size ``num_micro``.

Training (:func:`pipeline_train_step`) runs the same lockstep-SPMD style
with a *backward wave* flowing in the opposite direction: activations hop
right (stage s → s+1), cotangents hop left (s+1 → s), both via
``collective_permute``. Two schedules share one implementation, differing
only in when device ``s`` runs the backward of microbatch ``m``:

  1F1B   fwd(m,s) at tick m+s,  bwd(m,s) at tick m + 2n−1−s
  GPipe  fwd(m,s) at tick m+s,  bwd(m,s) at tick m + M+2n−2−s

Under 1F1B device ``s`` holds at most ``min(M, 2(n−s)−1)`` stashed
activations (O(n), independent of M — the memory point of 1F1B; the stash
is a ``min(M, 2n−1)``-deep ring buffer vs GPipe's M). 1F1B also packs the
two waves into ``M+2n−1`` ticks against GPipe training's ``2(M+n−1)``, so
each device sits idle for fewer schedule ticks: see
:func:`bubble_fraction_1f1b`.

Results are numerically identical to sequentially applying the ``n``
stages to every microbatch and calling ``jax.grad`` (the backward pass
recomputes each stage forward from the stashed stage *input* — the same
ops in the same order as the oracle's VJP), which is what the dist suite
asserts against :func:`gpipe_reference` / :func:`pipeline_train_reference`.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import (permute_quantized,
                                    tree_quantized_allreduce)

tmap = jax.tree_util.tree_map


def bubble_fraction(num_stages: int, num_micro: int) -> float:
    """GPipe idle fraction: (n−1) / (M+n−1).

    Holds for forward-only GPipe (M+n−1 ticks, M useful per device) and for
    GPipe *training* as implemented here (a forward sweep then a backward
    sweep, 2(M+n−1) ticks, 2M useful — the ratio is unchanged).
    """
    return (num_stages - 1) / (num_micro + num_stages - 1)


def bubble_fraction_1f1b(num_stages: int, num_micro: int) -> float:
    """1F1B idle-tick fraction of the lockstep schedule: (n−1) / (M+2n−1).

    Accounting: the 1F1B schedule spans ``M+2n−1`` permute-synchronised
    ticks. Device ``s`` has a valid forward on M of them (ticks s..s+M−1)
    and a valid backward on M (ticks 2n−1−s .. 2n−2−s+M); the two ranges
    overlap on ``M−|2n−1−2s|`` ticks, so it sits fully idle on
    ``n−1+...`` ticks — averaged over stages, ``n−1`` of ``M+2n−1``.
    GPipe training spans ``2(M+n−1)`` ticks with *disjoint* forward and
    backward ranges per device, giving the classic ``(n−1)/(M+n−1)`` —
    strictly worse for every M ≥ 1, n ≥ 2. (Total compute emitted is the
    same; 1F1B wins by keeping devices busy on more ticks and by the O(n)
    activation stash.)
    """
    n, m = num_stages, num_micro
    if n <= 1:
        return 0.0
    return (n - 1) / (m + 2 * n - 1)


def gpipe_reference(stage_fn: Callable, ws, x: jax.Array) -> jax.Array:
    """Sequential oracle: run every stage over every microbatch in order."""
    n = jax.tree_util.tree_leaves(ws)[0].shape[0]
    for i in range(n):
        w = tmap(lambda l: l[i], ws)
        x = jax.vmap(lambda xm, w=w: stage_fn(w, xm))(x)
    return x


def gpipe(stage_fn: Callable, *, mesh, axis: str, num_micro: int,
          act_wire: str = "fp32") -> Callable:
    """Build ``f(ws, x)``: the pipelined equivalent of sequentially applying
    ``n = mesh.shape[axis]`` stages to ``num_micro`` microbatches.

    stage_fn(w, x_mb) → y_mb  (same shape/dtype as x_mb — pipeline stages
    must be shape-preserving so activations can hop between devices).
    ws: pytree of stage-stacked weights, every leaf shaped (n, ...).
    x: (num_micro, mb, ...) microbatched input, replicated.
    ``act_wire="int8"`` ships the stage-hop activations as int8 codes +
    f32 scale (``dist.collectives.permute_quantized``) instead of f32;
    ``act_wire="b1"`` ships packed sign bits + one α scale (1 bit/element
    — for sign-dominated stage outputs).
    """
    if act_wire not in ("fp32", "int8", "b1"):
        raise ValueError(f"unknown act_wire {act_wire!r}")
    n = int(mesh.shape[axis])
    ticks = num_micro + n - 1
    shift_right = [(i, i + 1) for i in range(n - 1)]
    cache = {}      # (ws treedef, leaf ndims) → jitted shard_map'd program

    def local(ws_l, x_all):
        idx = jax.lax.axis_index(axis)
        w = tmap(lambda l: l[0], ws_l)           # this device's stage
        carry = jnp.zeros_like(x_all[0])         # activation from s−1
        ys = jnp.zeros_like(x_all)
        for t in range(ticks):                   # static schedule
            feed = x_all[min(t, num_micro - 1)]  # stage-0 intake
            out = stage_fn(w, jnp.where(idx == 0, feed, carry))
            m = t - (n - 1)                      # microbatch leaving
            if 0 <= m < num_micro:
                ys = ys.at[m].set(jnp.where(idx == n - 1, out, ys[m]))
            if t < ticks - 1:
                carry = (jax.lax.ppermute(out, axis, shift_right)
                         if act_wire == "fp32" else
                         permute_quantized(out, axis, shift_right,
                                           wire=act_wire))
        # only the last stage holds results; psum replicates them
        return jax.lax.psum(ys, axis)

    def run(ws, x):
        leaves, treedef = jax.tree_util.tree_flatten(ws)
        key = (treedef, tuple(l.ndim for l in leaves))
        fn = cache.get(key)
        if fn is None:
            w_specs = tmap(lambda l: P(axis, *([None] * (l.ndim - 1))), ws)
            fn = jax.jit(jax.shard_map(local, mesh=mesh,
                                       in_specs=(w_specs, P()),
                                       out_specs=P(), check_vma=False))
            cache[key] = fn                      # repeat calls reuse the jit
        return fn(ws, x)

    return run


# ---------------------------------------------------------------------------
# Pipelined training (1F1B / GPipe schedules) — DESIGN.md §9
# ---------------------------------------------------------------------------

def _schedule_constants(num_stages: int, num_micro: int,
                        schedule: str) -> dict:
    """Static tick table. fwd(m,s) runs at tick m+s under both schedules;
    bwd(m,s) at tick m + base − s. Validity is masked per device; whole
    phases with no valid work anywhere are statically elided via the
    lo/hi ranges. ``ring`` is the activation-stash depth."""
    n, m = num_stages, num_micro
    if schedule == "1f1b":
        return {"ticks": m + 2 * n - 1, "ring": min(m, 2 * n - 1),
                "base": 2 * n - 1, "bwd_lo": n, "bwd_hi": m + 2 * n - 2,
                "fwd_hi": m + n - 2}
    if schedule == "gpipe":
        return {"ticks": 2 * (m + n - 1), "ring": m,
                "base": m + 2 * n - 2, "bwd_lo": m + n - 1,
                "bwd_hi": 2 * m + 2 * n - 3, "fwd_hi": m + n - 2}
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


def pipeline_train_local(stage_fn: Callable, loss_fn: Callable, *,
                         axis: str, num_stages: int, num_micro: int,
                         schedule: str = "1f1b",
                         act_wire: str = "fp32") -> Callable:
    """Per-device pipelined fwd+bwd, for use *inside* a ``shard_map``.

    Returns ``local(ws_l, top, x_all, aux) → (loss, dw, dtop, dx)`` where
    ``ws_l`` is this device's stage-weight slice (leaves ``(1, ...)``),
    ``top`` a replicated pytree consumed by the loss (LM head / final norm;
    ``{}`` if unused), ``x_all`` the ``(M, mb, ...)`` microbatched input and
    ``aux`` a pytree of per-microbatch loss inputs with leading dim M
    (``{}`` if unused). ``loss_fn(top, y_mb, aux_mb) → scalar``.

    Outputs are device-local: ``dw`` is the grad of this device's stage,
    ``loss``/``dtop`` are nonzero only on the last stage and ``dx`` (the
    cotangent of ``x_all``) only on stage 0 — callers psum them over
    ``axis``. All grads are for the *mean* loss over microbatches.

    The backward recomputes each stage's forward from the stashed stage
    input (rather than stashing VJP residuals), so the stash is one
    activation per in-flight microbatch — a ``min(M, 2n−1)`` ring under
    1F1B — and the math is op-for-op the oracle's VJP.
    """
    n, num_m = num_stages, num_micro
    if act_wire not in ("fp32", "int8", "b1"):
        raise ValueError(f"unknown act_wire {act_wire!r}")
    sc = _schedule_constants(n, num_m, schedule)
    # the b1 wire applies to the rightward *activation* wave only: stage
    # outputs can be sign-dominated (saturated nonlinearities), cotangents
    # never are — the leftward wave degrades to the int8 wire instead of
    # losing its magnitudes entirely.
    fwd_wire = act_wire
    bwd_wire = "int8" if act_wire == "b1" else act_wire

    def hop(x, perm, wire):
        # the stage-boundary wire: both the rightward activation wave and
        # the leftward cotangent wave cross it (quantized codes + f32
        # scale when the wire is int8/b1 — ≤1 byte/elem of ICI, like
        # every other boundary in the W1A8 dataflow)
        if wire == "fp32":
            return jax.lax.ppermute(x, axis, perm)
        return permute_quantized(x, axis, perm, wire=wire)
    shift_right = [(i, i + 1) for i in range(n - 1)]
    shift_left = [(i + 1, i) for i in range(n - 1)]

    def local(ws_l, top, x_all, aux):
        idx = jax.lax.axis_index(axis)
        first, last = idx == 0, idx == n - 1
        w = tmap(lambda l: l[0], ws_l)
        mb_shape = x_all.shape[1:]
        carry = jnp.zeros(mb_shape, x_all.dtype)    # activation from s−1
        ct_in = jnp.zeros(mb_shape, x_all.dtype)    # cotangent from s+1
        stash = jnp.zeros((sc["ring"],) + mb_shape, x_all.dtype)
        gw = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), w)
        gtop = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), top)
        dxs = jnp.zeros_like(x_all)
        loss_acc = jnp.zeros((), jnp.float32)

        for t in range(sc["ticks"]):                # static schedule
            # backward half-tick runs first: when the ring is at capacity
            # the forward half of the same tick reuses the slot read here
            if sc["bwd_lo"] <= t <= sc["bwd_hi"]:
                m_b = t - (sc["base"] - idx)
                valid = (m_b >= 0) & (m_b < num_m)
                m_c = jnp.clip(m_b, 0, num_m - 1)
                x_saved = jax.lax.dynamic_index_in_dim(
                    stash, jnp.mod(m_c, sc["ring"]), 0, keepdims=False)
                aux_m = tmap(lambda a: jax.lax.dynamic_index_in_dim(
                    a, m_c, 0, keepdims=False), aux)
                y, f_stage = jax.vjp(stage_fn, w, x_saved)

                def head(y_, aux_m=aux_m):
                    return jax.value_and_grad(
                        lambda tp, yy: loss_fn(tp, yy, aux_m),
                        argnums=(0, 1))(top, y_)

                # only the last stage owns the loss head: cond (on the
                # per-device predicate) skips the head fwd+bwd — e.g. the
                # vocab-sized unembed — on the other n−1 stages entirely
                head_sds = jax.eval_shape(head, y)
                zeros = tmap(lambda s: jnp.zeros(s.shape, s.dtype),
                             head_sds)
                loss_m, (dtop_m, ct_last) = jax.lax.cond(
                    last, head, lambda y_: zeros, y)
                dw_m, dx_m = f_stage(jnp.where(last, ct_last, ct_in))
                gw = tmap(lambda a, g: a + jnp.where(valid, g, 0.0),
                          gw, dw_m)
                gtop = tmap(lambda a, g: a + jnp.where(valid & last, g, 0.0),
                            gtop, dtop_m)
                loss_acc = loss_acc + jnp.where(valid & last, loss_m, 0.0)
                prev = jax.lax.dynamic_index_in_dim(dxs, m_c, 0,
                                                    keepdims=False)
                dxs = jax.lax.dynamic_update_index_in_dim(
                    dxs, jnp.where(valid & first, dx_m, prev), m_c, 0)
                if t < sc["bwd_hi"]:
                    ct_in = hop(dx_m, shift_left, bwd_wire)
            if t <= sc["fwd_hi"]:
                m_f = t - idx
                valid = (m_f >= 0) & (m_f < num_m)
                x_in = jnp.where(first, x_all[min(t, num_m - 1)], carry)
                out = stage_fn(w, x_in)
                slot = jnp.mod(jnp.clip(m_f, 0, num_m - 1), sc["ring"])
                prev = jax.lax.dynamic_index_in_dim(stash, slot, 0,
                                                    keepdims=False)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, jnp.where(valid, x_in, prev), slot, 0)
                if t < sc["fwd_hi"]:
                    carry = hop(out, shift_right, fwd_wire)

        inv = 1.0 / num_m                           # grads of the MEAN loss
        gw = tmap(lambda g, p: (g * inv).astype(p.dtype), gw, w)
        gtop = tmap(lambda g, p: (g * inv).astype(p.dtype), gtop, top)
        return loss_acc * inv, gw, gtop, dxs * inv

    return local


def reduce_pipeline_outputs(loss, gw, gtop, dxs, *, axis: str,
                            dp_axis: Optional[str] = None,
                            grad_wire: str = "fp32"):
    """Shared post-processing for :func:`pipeline_train_local` outputs,
    inside the enclosing shard_map: replicate the stage-local pieces over
    the pipeline ``axis`` (last stage holds loss/dtop, stage 0 holds dx),
    then reduce grads/loss across ``dp_axis`` — over the int8 wire
    (``dist.collectives``) when ``grad_wire == 'int8'``, else an exact
    pmean. ``dxs`` stays batch-sharded, rescaled to be the cotangent of
    the dp-mean loss."""
    loss = jax.lax.psum(loss, axis)
    gtop = tmap(lambda g: jax.lax.psum(g, axis), gtop)
    dxs = jax.lax.psum(dxs, axis)
    if dp_axis is not None:
        if grad_wire == "int8":
            gw = tree_quantized_allreduce(gw, dp_axis)
            gtop = tree_quantized_allreduce(gtop, dp_axis)
        else:
            gw = tmap(lambda g: jax.lax.pmean(g, dp_axis), gw)
            gtop = tmap(lambda g: jax.lax.pmean(g, dp_axis), gtop)
        loss = jax.lax.pmean(loss, dp_axis)
        dxs = dxs / jax.lax.axis_size(dp_axis)
    return loss, gw, gtop, dxs


def pipeline_train_step(stage_fn: Callable, loss_fn: Callable, *, mesh,
                        axis: str, num_micro: int, schedule: str = "1f1b",
                        dp_axis: Optional[str] = None,
                        grad_wire: str = "fp32",
                        act_wire: str = "fp32") -> Callable:
    """Build ``f(ws, x, aux=None, top=None)``: pipelined training over
    ``n = mesh.shape[axis]`` stages, numerically matching the sequential
    :func:`pipeline_train_reference` oracle.

    ws: stage-stacked weights, every leaf ``(n, ...)`` (sharded over axis).
    x: ``(num_micro, mb, ...)`` microbatched input; with ``dp_axis`` the mb
    dim additionally shards over the data axis and grads/loss reduce across
    it — over the int8 wire (``dist.collectives``) when
    ``grad_wire == 'int8'``, else an exact ``pmean``.
    loss_fn(top, y_mb, aux_mb) → scalar mean-reduced per microbatch.
    ``act_wire == 'int8'`` additionally carries the stage-boundary
    ``collective_permute`` payloads — forward activations *and* backward
    cotangents — as int8 codes + f32 scale (4× less ICI per hop; adds the
    per-hop quantization noise the dist tests bound). ``act_wire == 'b1'``
    carries the *forward* activations as packed sign bits + one α scale
    (1 bit/element, ~8× less than int8 on the code payload) while the
    backward cotangents stay on the int8 wire — sign-dominated stage
    outputs keep their information, cotangents keep their magnitudes. The
    loss/grad envelope vs the fp32 wire is documented in EXPERIMENTS.md
    and asserted by tests/test_pipeline_unit.py; it is tight only when
    stage outputs saturate (|out| ≈ const), the b1 contract.

    Returns ``(loss, grads)``; with ``top`` given, ``(loss, grads,
    grads_top, dx)`` where ``dx`` is the cotangent of ``x`` (so callers can
    continue the backward into an embedding front-end).
    """
    if grad_wire not in ("fp32", "int8"):
        raise ValueError(f"unknown grad_wire {grad_wire!r}")
    n = int(mesh.shape[axis])
    local = pipeline_train_local(stage_fn, loss_fn, axis=axis, num_stages=n,
                                 num_micro=num_micro, schedule=schedule,
                                 act_wire=act_wire)
    cache = {}

    def run(ws, x, aux=None, top=None):
        has_top = top is not None
        top_in = {} if top is None else top
        aux_in = {} if aux is None else aux
        leaves, treedef = jax.tree_util.tree_flatten((ws, top_in, aux_in))
        key = (treedef, tuple(l.ndim for l in leaves), x.ndim)
        fn = cache.get(key)
        if fn is None:
            w_specs = tmap(lambda l: P(axis, *([None] * (l.ndim - 1))), ws)
            t_specs = tmap(lambda l: P(), top_in)
            x_spec = P(None, dp_axis) if dp_axis else P()
            a_specs = tmap(lambda l: x_spec, aux_in)

            def prog(ws_l, top_l, x_l, aux_l):
                out = local(ws_l, top_l, x_l, aux_l)
                loss, gw, gtop, dxs = reduce_pipeline_outputs(
                    *out, axis=axis, dp_axis=dp_axis, grad_wire=grad_wire)
                return loss, tmap(lambda g: g[None], gw), gtop, dxs

            fn = jax.jit(jax.shard_map(
                prog, mesh=mesh,
                in_specs=(w_specs, t_specs, x_spec, a_specs),
                out_specs=(P(), w_specs, t_specs, x_spec),
                check_vma=False))
            cache[key] = fn
        loss, gws, gtop, dxs = fn(ws, top_in, x, aux_in)
        if has_top:
            return loss, gws, gtop, dxs
        return loss, gws

    return run


def pipeline_train_reference(stage_fn: Callable, loss_fn: Callable, ws, x,
                             aux=None, top=None):
    """Sequential ``jax.grad`` oracle for :func:`pipeline_train_step`:
    apply every stage to every microbatch in order, mean the losses,
    differentiate. Returns ``(loss, grads)`` — plus ``(grads_top, dx)``
    when ``top`` is given — with the same conventions as the pipelined
    version."""
    has_top = top is not None
    top_in = {} if top is None else top
    aux_in = {} if aux is None else aux
    n = jax.tree_util.tree_leaves(ws)[0].shape[0]
    num_m = x.shape[0]

    def total(ws_, top_, x_):
        losses = []
        for m in range(num_m):
            h = x_[m]
            for i in range(n):
                h = stage_fn(tmap(lambda l: l[i], ws_), h)
            losses.append(loss_fn(top_, h,
                                  tmap(lambda a: a[m], aux_in)))
        return jnp.mean(jnp.stack(losses))

    loss, (gws, gtop, dx) = jax.value_and_grad(
        total, argnums=(0, 1, 2))(ws, top_in, x)
    if has_top:
        return loss, gws, gtop, dx
    return loss, gws
