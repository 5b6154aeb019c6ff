"""Distribution: sharding rules, MoE shard_map on a real (1-device) mesh,
dry-run machinery on a small forced-device-count subprocess."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.dist.sharding import (MeshContext, ShardingPolicy, param_specs,
                                 use_policy)
from repro.models import Model

from conftest import tiny_config

ROOT = Path(__file__).resolve().parent.parent


def _mesh11():
    from repro.dist.sharding import make_mesh
    return make_mesh((1, 1), ("data", "model"))


def test_param_specs_rules(key):
    cfg = get_config("mixtral-8x22b")
    mesh = _mesh11()
    policy = ShardingPolicy(mesh)
    model = Model(cfg)
    shapes = jax.eval_shape(model.init, key)
    specs = param_specs(shapes, cfg, policy)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    as_dict = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp): s for kp, s in flat}
    # moe expert weights: TP over d_ff (mixtral E=8 can't divide model)
    moe_w1 = [v for p, v in as_dict.items() if "moe" in p and p.endswith("w1")]
    assert all(v[-1] == "model" or v[-1] is None for v in moe_w1)
    # attention wq sharded over heads on the "model" axis
    wq = [v for p, v in as_dict.items() if p.endswith("wq")]
    assert all(len(v) == 4 for v in wq)  # stacked scan + 3 dims


def test_moe_shard_map_matches_local(key):
    """shard_map MoE on a 1x1 mesh == meshless local MoE."""
    cfg = tiny_config(get_config("mixtral-8x22b"))
    mesh = _mesh11()
    policy = ShardingPolicy(mesh)
    mctx = MeshContext(mesh)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)

    m_local = Model(cfg, mesh_ctx=None)
    params = m_local.init(key)
    out_local = m_local.forward(params, {"tokens": tokens}, use_remat=False)

    m_dist = Model(cfg, mesh_ctx=mctx)
    with use_policy(policy, mctx):
        out_dist = m_dist.forward(params, {"tokens": tokens}, use_remat=False)
    np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_dist),
                               rtol=1e-4, atol=1e-4)


def test_ep_moe_shard_map_matches_local(key):
    cfg = tiny_config(get_config("llama4-scout-17b-a16e"))
    mesh = _mesh11()
    mctx = MeshContext(mesh)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    m_local = Model(cfg, mesh_ctx=None)
    params = m_local.init(key)
    out_local = m_local.forward(params, {"tokens": tokens}, use_remat=False)
    m_dist = Model(cfg, mesh_ctx=mctx)
    with use_policy(ShardingPolicy(mesh), mctx):
        out_dist = m_dist.forward(params, {"tokens": tokens}, use_remat=False)
    np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_dist),
                               rtol=1e-4, atol=1e-4)


def test_policy_drops_nondivisible_axes():
    from types import SimpleNamespace
    mesh = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 4, "model": 16})
    pol = ShardingPolicy(mesh)
    spec = pol.spec_for((7, 64), ("batch", "heads"))
    assert spec[0] is None          # 7 % 4 != 0 -> dropped
    assert spec[1] == "model"       # 64 % 16 == 0 -> sharded
    spec2 = pol.spec_for((24, 64), ("heads", "heads"))
    # 24 doesn't divide -> dropped; 64 takes the axis; never used twice
    assert spec2[0] is None and spec2[1] == "model"
    spec3 = pol.spec_for((32, 64), ("heads", "heads"))
    assert spec3[0] == "model" and spec3[1] is None


@pytest.mark.slow
def test_dryrun_subprocess_small_mesh():
    """End-to-end dry-run machinery on a forced 8-device CPU mesh."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/tmp"}
    code = (
        "import repro.launch.dryrun as dr, repro.launch.mesh as lm;"
        "lm.make_production_mesh = (lambda *, multi_pod=False: "
        "lm.make_mesh((2,2,2),('pod','data','model')) if multi_pod else "
        "lm.make_mesh((4,2),('data','model')));"
        "r = dr.run_cell('olmo-1b','train_4k','single',"
        "overrides={'n_layers':2,'d_model':128,'n_heads':4,'n_kv_heads':4,"
        "'d_ff':256,'vocab_size':512});"
        "assert r['status']=='ok', r;"
        "assert r['hlo']['flops'] > 0 and r['hlo']['collective_bytes'] > 0;"
        "r2 = dr.run_cell('olmo-1b','decode_32k','multi',"
        "overrides={'n_layers':2,'d_model':128,'n_heads':4,'n_kv_heads':4,"
        "'d_ff':256,'vocab_size':512});"
        "assert r2['status']=='ok', r2;"
        "print('SUBPROCESS_OK')"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]


def test_chip_peaks_known_and_unknown_kinds():
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND, chip_peaks
    assert chip_peaks(PRODUCTION_DEVICE_KIND).hbm_bytes == 16e9
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v4")


def test_compile_cache_follows_env_var_else_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv(cc.ENV_VAR)
        fixed = str(ROOT / ".jax_cache")
        assert cc.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
