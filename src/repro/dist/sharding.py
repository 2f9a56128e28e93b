"""Sharding rules: param path + shape → PartitionSpec (DESIGN.md §9).

One function, ``param_spec``, maps every parameter leaf of every arch in
``configs.ARCH_NAMES`` (and the optimizer/packed-deploy trees derived from
them) to a legal ``PartitionSpec`` on a ('data', 'model') — or
('pod', 'data', 'model') — mesh:

  * attention / dense-FFN / SSM projections: **tensor-parallel** over
    ``model`` — column-parallel (wq/wk/wv/up/gate/in_proj: output dim),
    row-parallel (wo/down/out_proj: contraction dim). Bit-packed deploy
    weights (``w_packed``) shard the same dims (the /32 word dim stands in
    for K), so the W1A8 scale split (alpha per output channel, act_step per
    tensor) is preserved shard-locally — the REQ-YOLO/FracBNN lesson that
    compensation arithmetic must survive the parallel mapping.
  * MoE expert stacks (E, K, N): **expert-parallel** over ``data`` on E and
    tensor-parallel over ``model`` inside the expert (up/gate: hidden F
    columns; down: hidden F rows) — matching the shard_map specs used by
    ``models.transformer._apply_moe``.
  * embedding / LM head: vocab-sharded over ``model`` (the z-loss softmax
    partitions cleanly).
  * norms, biases of row-parallel projections, scalar LSQ steps, router:
    replicated.

An axis is only placed when the dim is divisible by the mesh axis size, so
every spec is legal for every (arch × mesh) cell; optimizer trees (adamw
mu/nu mirror params; adafactor vr/vc are reduced) inherit rules by path and
keep whatever placements still divide.
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


# leaf names of column-parallel projections (shard output dim over model)
_COL_PARALLEL = ("wq", "wk", "wv", "up", "gate", "in_proj", "x_proj",
                 "dt_proj", "shared_up", "shared_gate")
# leaf names of row-parallel projections (shard contraction dim over model)
_ROW_PARALLEL = ("wo", "down", "out_proj", "shared_down")

_KEY_RE = re.compile(r"\['([^']+)'\]")


def dp_axes(mesh) -> tuple:
    """Mesh axes the batch shards over (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def _axsize(mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def _fits(mesh, shape, dim: int, axis: str) -> bool:
    """True iff `axis` exists and divides shape[dim] (dim may be negative)."""
    if axis not in mesh.axis_names:
        return False
    if not (-len(shape) <= dim < len(shape)):
        return False
    return shape[dim] % _axsize(mesh, axis) == 0


def _spec(ndim: int, placements: dict) -> P:
    """Build a PartitionSpec from {dim (may be negative): axis}."""
    entries = [None] * ndim
    for dim, axis in placements.items():
        entries[dim % ndim] = axis
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _moe_spec(keys, shape, mesh) -> P:
    """Expert stacks: leaves under a ['moe'] node (or a bare MoE param dict).

    Canonical shapes (an optional leading stage dim rides along replicated):
      up/gate[_packed]   (E, K[/32], F)   → ep on E, model on F (columns)
      down[_packed]      (E, F[/32], D)   → ep on E, model on F (rows)
      up/gate_alpha      (E, 1, F)        → ep on E, model on F
      down_alpha         (E, 1, D)        → ep on E
      router (D, E), act_step (), shared_* (dense rules) → see param_spec
    """
    leaf = keys[-1]
    ndim = len(shape)
    placements = {}
    # E is third-from-last for the 3D+ expert stacks; for reduced optimizer
    # leaves (adafactor vr/vc drop a trailing dim) fall back to dim 0.
    e_dim = (-3 if ndim >= 3 else 0) % ndim
    if _fits(mesh, shape, e_dim, "data"):
        placements[e_dim] = "data"
    if leaf.startswith(("up", "gate")):
        tp_dim = (-1) % ndim
    elif leaf.startswith("down") and not leaf.endswith("alpha") and ndim >= 2:
        tp_dim = (-2) % ndim
    else:
        tp_dim = None
    if tp_dim is not None and tp_dim != e_dim \
            and _fits(mesh, shape, tp_dim, "model"):
        placements[tp_dim] = "model"
    return _spec(ndim, placements)


def param_spec(path: str, shape, cfg, mesh) -> P:
    """PartitionSpec for one param leaf.

    path: ``jax.tree_util.keystr``-style string, e.g.
    ``"['slots'][0]['attn']['wq']['w']"`` (optimizer prefixes like ['mu']
    are ignored — rules match on the innermost module keys).
    shape: the leaf's shape (with or without the stacked stage dim).
    """
    keys = _KEY_RE.findall(path)
    ndim = len(shape)
    if ndim == 0 or not keys:
        return P()

    # ---- MoE expert tensors: (data, model) ---------------------------------
    if "moe" in keys:
        leaf = keys[-1]
        if leaf == "router" or leaf == "act_step":
            return P()
        if leaf.startswith("shared_"):
            dim = -1 if leaf in ("shared_up", "shared_gate") else -2
            if _fits(mesh, shape, dim, "model") and ndim >= 2:
                return _spec(ndim, {dim: "model"})
            return P()
        return _moe_spec(keys, shape, mesh)

    # ---- embedding / LM head: vocab over model -----------------------------
    if keys[-1] == "emb":
        if ndim >= 2 and _fits(mesh, shape, -2, "model"):
            return _spec(ndim, {-2: "model"})
        return P()
    if keys[-1] == "head":
        if _fits(mesh, shape, -1, "model"):
            return _spec(ndim, {-1: "model"})
        return P()

    # ---- projections (attn / dense mlp / mamba), incl. packed deploy -------
    proj = next((k for k in reversed(keys) if k in _COL_PARALLEL
                 or k in _ROW_PARALLEL), None)
    if proj is not None:
        leaf = keys[-1]
        col = proj in _COL_PARALLEL
        if leaf in ("w", "w_packed", "vr", "vc", "v", proj):
            # weight matrix (…, K[/32], N) or a same-/reduced-shape moment
            if col and _fits(mesh, shape, -1, "model"):
                return _spec(ndim, {-1: "model"})
            if not col and ndim >= 2 and _fits(mesh, shape, -2, "model"):
                return _spec(ndim, {-2: "model"})
            return P()
        if leaf in ("b", "alpha") and col and _fits(mesh, shape, -1, "model"):
            # output-channel vectors follow the column shards
            return _spec(ndim, {-1: "model"})
        return P()

    # ---- depthwise conv / SSM channel vectors ------------------------------
    if keys[-1] in ("conv_w", "conv_b") and _fits(mesh, shape, -1, "model"):
        return _spec(ndim, {-1: "model"})

    # norms, scalar steps, A_log/D/dt_bias, step counters: replicate
    return P()


def tree_shardings(tree, cfg, mesh):
    """Map every leaf of a param/optimizer/cache-free tree to a
    ``NamedSharding`` built from :func:`param_spec`.

    Accepts concrete arrays or ``ShapeDtypeStruct`` leaves (eval_shape
    trees); returns a tree of identical structure.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [NamedSharding(mesh, param_spec(jax.tree_util.keystr(p),
                                          leaf.shape, cfg, mesh))
           for p, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def pipeline_tree_shardings(tree, mesh, num_layers: int,
                            axis: str = "stage"):
    """Placement for pipelined training (``launch/train.py --pipeline``):
    every layer-stacked leaf (leading dim == num_layers, which the stage
    partition later reshapes to ``(n, L/n, ...)``) shards over the pipeline
    ``axis`` — so each device's params *and optimizer state* live on their
    stage shard — and everything else (embed, final norm, step counters)
    replicates. Applies to params and any optimizer tree derived from them
    (adamw mu/nu mirror shapes; adafactor vr/vc keep the leading L)."""
    n = _axsize(mesh, axis)

    def one(leaf):
        shape = leaf.shape
        if len(shape) >= 1 and shape[0] == num_layers \
                and num_layers % n == 0:
            return NamedSharding(mesh, _spec(len(shape), {0: axis}))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(one, tree)


def spec_report(tree, cfg, mesh, *, only_sharded: bool = False) -> str:
    """Human-readable leaf → spec table (debugging / DESIGN.md audits)."""
    lines = []
    for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        spec = param_spec(jax.tree_util.keystr(p), leaf.shape, cfg, mesh)
        if only_sharded and all(s is None for s in spec):
            continue
        lines.append(f"{jax.tree_util.keystr(p):70s} {str(leaf.shape):24s} "
                     f"{spec}")
    return "\n".join(lines)
