"""Partition-spec assignment + divisibility fitting + failure domains."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.blocks import partition_pytree
from repro.models import get_model
from repro.sharding.partition import (DistContext, _fit_spec,
                                      blocks_on_failed_devices,
                                      make_dist_ctx, param_partition_specs,
                                      single_device_ctx,
                                      state_partition_specs)


@pytest.fixture(scope="module")
def mesh():
    # 1-device mesh (1,1) — spec logic is shape-only, works on CPU
    from repro.launch.mesh import make_mesh_compat
    return make_mesh_compat((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def fake16():
    """DistContext that *claims* a 16x16 mesh for pure spec logic tests."""
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    return DistContext(mesh=FakeMesh(), dp=("data",), tp="model")


def test_fit_spec_drops_nondivisible(fake16):
    # 2 kv heads cannot shard over model=16
    spec = _fit_spec((28, 1536, 2, 128), P(None, "data", "model", None), fake16)
    assert spec == P(None, "data", None, None)
    # 96 heads can
    spec = _fit_spec((64, 12288, 96, 128), P(None, "data", "model", None), fake16)
    assert spec == P(None, "data", "model", None)
    # odd vocab cannot shard
    spec = _fit_spec((51865, 1024), P("model", "data"), fake16)
    assert spec == P(None, "data")


def test_param_specs_cover_all_leaves(fake16):
    cfg = get_config("qwen3-moe-235b-a22b")
    ops = get_model(cfg)
    p_shape = jax.eval_shape(lambda: ops.init_params(jax.random.PRNGKey(0), cfg))
    specs = param_partition_specs(p_shape, fake16)
    leaves_p = jax.tree_util.tree_leaves(p_shape)
    leaves_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    assert len(leaves_p) == len(leaves_s)
    # expert weights must be expert-parallel over model
    assert specs["layers"]["moe"]["w_gate_experts"][1] == "model"
    # embeddings vocab-parallel
    assert specs["embed"][0] == "model"


def test_state_specs_decode(fake16):
    cfg = get_config("yi-9b")
    ops = get_model(cfg)
    ctx = fake16
    state_shape = jax.eval_shape(lambda: ops.init_cache(cfg, 128, 4096,
                                                        single_device_ctx()))
    specs = state_partition_specs(state_shape, ctx)
    assert specs["k"][1] == "data"     # batch over data
    # kpos replicated (trailing Nones are semantically P())
    assert all(e is None for e in specs["kpos"])


def test_dp_spec_not_batch_shardable(fake16):
    import dataclasses
    ctx = dataclasses.replace(fake16, batch_shardable=False)
    assert ctx.dp_spec is None
    assert ctx.raw_dp_spec == "data"


def test_topology_aware_failure_mask(fake16):
    params = {"w": jnp.zeros((1600, 4), jnp.float32)}
    part = partition_pytree(params, 100)
    mask = blocks_on_failed_devices(part, params, fake16, 0.25,
                                    np.random.default_rng(0))
    # 4/16 data slices fail -> roughly a quarter of the blocks
    assert 0.1 <= mask.mean() <= 0.45


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    without it the cache goes to the fixed ``<repo>/.jax_cache``."""
    import pathlib
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
            assert compile_cache.enable_compile_cache() == str(want)
            assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_real_1x1_mesh_constraint_roundtrip(mesh):
    ctx = make_dist_ctx(mesh)
    x = jnp.ones((4, 8))
    y = ctx.shard(x, "dp", None)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
