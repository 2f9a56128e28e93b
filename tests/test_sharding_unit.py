"""Single-process sharding-rule guard (fast CPU — no subprocess, no second
jax runtime). Catches sharding regressions that would otherwise only show up
in the 16-device subprocess suite (tests/test_dist.py).

Covers, for every config in ``configs.ARCH_NAMES``:
  * tree_shardings assigns a NamedSharding to every param leaf (1-device mesh)
  * every spec is *legal* on the production-sized 16×16 mesh: a mesh axis is
    only placed on a dim it divides, and used at most once per spec
  * the model axis actually lands on the big projections (not all-replicate)
  * optimizer (adamw) and packed-deploy trees inherit legal specs
"""
import jax
import pytest
from conftest import FakeProdMesh

from repro import configs
from repro.dist import sharding as shard_rules
from repro.dist.sharding import dp_axes, param_spec
from repro.launch.mesh import make_mesh
from repro.models.transformer import init_lm_params


def _params_sds(name):
    cfg = configs.get_config(name)
    return cfg, jax.eval_shape(
        lambda c=cfg: init_lm_params(jax.random.PRNGKey(0), c))


def _assert_legal(path, shape, spec, mesh):
    used = []
    entries = tuple(spec)
    assert len(entries) <= len(shape), (path, shape, spec)
    for dim, ax in enumerate(entries):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        for a in axes:
            assert a in mesh.axis_names, (path, spec)
            assert shape[dim] % mesh.shape[a] == 0, \
                f"{path}: dim {dim} of {shape} not divisible by |{a}|"
            used.append(a)
    assert len(used) == len(set(used)), f"{path}: axis reused in {spec}"


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_every_param_leaf_gets_a_sharding(name):
    cfg, sds = _params_sds(name)
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = shard_rules.tree_shardings(sds, cfg, mesh)
    n_params = len(jax.tree_util.tree_leaves(sds))
    shardings = jax.tree_util.tree_leaves(sh)
    assert len(shardings) == n_params
    assert all(isinstance(s, jax.sharding.NamedSharding) for s in shardings)


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_specs_legal_on_production_mesh(name):
    cfg, sds = _params_sds(name)
    mesh = FakeProdMesh()
    for p, leaf in jax.tree_util.tree_flatten_with_path(sds)[0]:
        path = jax.tree_util.keystr(p)
        spec = param_spec(path, leaf.shape, cfg, mesh)
        _assert_legal(path, leaf.shape, spec, mesh)


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_model_axis_lands_on_projections(name):
    """At least one weight matrix per arch must be model-sharded; MoE archs
    must additionally shard an expert stack over (data, model)."""
    cfg, sds = _params_sds(name)
    mesh = FakeProdMesh()
    specs = {jax.tree_util.keystr(p):
             param_spec(jax.tree_util.keystr(p), leaf.shape, cfg, mesh)
             for p, leaf in jax.tree_util.tree_flatten_with_path(sds)[0]}
    assert any("model" in str(s) for s in specs.values()), \
        f"{name}: everything replicated"
    if cfg.num_experts:
        moe = {k: s for k, s in specs.items() if "['moe']" in k}
        assert any("model" in str(s) for s in moe.values()), \
            f"{name}: expert hidden dims not TP sharded"
        if cfg.num_experts % mesh.shape["data"] == 0:
            assert any("data" in str(s) and "model" in str(s)
                       for s in moe.values()), \
                f"{name}: experts not EP+TP sharded"


def test_optimizer_and_packed_trees_inherit_legal_specs():
    from repro.optim import adamw
    from repro.serve.packed import deploy_lm

    cfg, sds = _params_sds("mixtral-8x7b")
    mesh = FakeProdMesh()
    opt_sds = jax.eval_shape(adamw(1e-3)[0], sds)
    packed_sds = jax.eval_shape(deploy_lm, sds)
    for tree in (opt_sds, packed_sds):
        for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            path = jax.tree_util.keystr(p)
            spec = param_spec(path, leaf.shape, cfg, mesh)
            _assert_legal(path, leaf.shape, spec, mesh)
    # packed column-parallel weights stay model-sharded on the word dim's N
    flat = {jax.tree_util.keystr(p): leaf for p, leaf
            in jax.tree_util.tree_flatten_with_path(packed_sds)[0]}
    wq_packed = next(k for k in flat if "['wq']['w_packed']" in k)
    assert "model" in str(param_spec(wq_packed, flat[wq_packed].shape,
                                     cfg, mesh))


def test_dp_axes():
    mesh1 = make_mesh((1, 1), ("data", "model"))
    assert dp_axes(mesh1) == ("data",)

    class Pod:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}
    assert dp_axes(Pod()) == ("pod", "data")
