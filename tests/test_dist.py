"""Distribution-layer tests. The multi-device checks need their own process
(XLA device count is fixed at first jax init), so they run via subprocess."""
import os
import subprocess
import sys

import jax

from repro.launch.mesh import make_mesh


def test_multi_device_suite():
    """EP MoE, TP-in-expert, GPipe, int8 all-reduce, sharded train, SP attn,
    1F1B/GPipe pipelined training vs jax.grad oracle, pipelined LM step."""
    script = os.path.join(os.path.dirname(__file__), "dist_main.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    assert "ALL DIST CHECKS PASSED" in res.stdout
    assert "1F1B/GPipe pipelined training" in res.stdout
    assert "pipelined LM train step OK" in res.stdout


def test_sharding_rules_cover_all_archs():
    """Every param leaf of every full config gets a legal PartitionSpec."""
    from repro import configs
    from repro.dist import sharding as shard_rules
    from repro.models.transformer import init_lm_params

    mesh = make_mesh((1, 1), ("data", "model"))
    for name in configs.ARCH_NAMES:
        cfg = configs.get_config(name)
        sds = jax.eval_shape(
            lambda c=cfg: init_lm_params(jax.random.PRNGKey(0), c))
        sh = shard_rules.tree_shardings(sds, cfg, mesh)
        n = len(jax.tree_util.tree_leaves(sh))
        assert n == len(jax.tree_util.tree_leaves(sds))


def test_sharding_rules_shard_the_big_tensors():
    """On a (4,4) devices=1 stand-in mesh the spec strings must place the
    model axis on FFN/attention projections (not replicate everything)."""
    from conftest import FakeProdMesh as FakeMesh
    from repro import configs
    from repro.dist.sharding import param_spec

    cfg = configs.get_config("qwen2.5-14b")

    spec = param_spec("['slots'][0]['attn']['wq']['w']",
                      (5120, 5120), cfg, FakeMesh())
    assert "model" in str(spec)
    spec = param_spec("['slots'][0]['mlp']['down']['w']",
                      (13824, 5120), cfg, FakeMesh())
    assert "model" in str(spec)
    cfg_moe = configs.get_config("kimi-k2-1t-a32b")
    spec = param_spec("['slots'][0]['moe']['up']",
                      (384, 7168, 2048), cfg_moe, FakeMesh())
    assert "data" in str(spec) and "model" in str(spec)
