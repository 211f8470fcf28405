"""Compile every Pallas kernel of the fault-tolerance path for a TPU v5e
chip, at the arena sizes of qwen2-1.5b (published widths, 4 layers), and
the arena kernels also at mamba2-370m's.

Interpret mode (every other kernel test) runs the kernel body in Python
and never checks what the TPU compiler refuses: block shapes that are not
(8, 128)-tiled, rank-1 blocks, VMEM over-use. These tests lower and
compile for a *described* chip (``jax.experimental.topologies``), so they
need no accelerator; each asserts that the compiled program holds the
Mosaic kernel (``tpu_custom_call``) rather than a fallback.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and test workers
import every test file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

LAYERS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _arena_case(name):
    """Partition, f32 arena layout and parity striping of ``name`` at its
    published widths (f32 leaves: the compiled arena sweep is an f32
    program), built from shapes only."""
    from repro.configs import get_config
    from repro.core.arena import build_arena_layout
    from repro.core.blocks import partition_pytree
    from repro.fabric.domains import FailureDomainMap
    from repro.fabric.parity import ParityCodec
    from repro.fabric.placement import ClusterView
    from repro.kernels.fused_maintain.ops import arena_routing
    from repro.models import get_model
    from repro.sharding.partition import block_device_homes
    cfg = dataclasses.replace(get_config(name), n_layers=LAYERS,
                              dtype="float32")
    ops = get_model(cfg)
    shapes = jax.eval_shape(lambda r: ops.init_params(r, cfg),
                            jax.random.PRNGKey(0))
    part = partition_pytree(shapes, 128)
    layout = build_arena_layout(part, tail_pack=False)
    view = ClusterView(FailureDomainMap(8, 2, 2), block_device_homes(part, 8))
    codec = ParityCodec(part, view, group_size=4, use_pallas=False)
    routing = arena_routing(layout, codec.layout, codec.group_of)
    embed = next(l for l in part.leaves if l.name == "['embed']")
    return dict(part=part, layout=layout, codec=codec, routing=routing,
                embed=embed)


@pytest.fixture(scope="module")
def qwen():
    return _arena_case("qwen2-1.5b")


@pytest.fixture(scope="module")
def mamba2():
    return _arena_case("mamba2-370m")


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_arena_maintain(one_chip, case):
    from repro.kernels.fused_maintain.kernel import arena_maintain_pallas
    lay, r = case["layout"], case["routing"]
    arena = _spec(one_chip, (lay.total_words // 128, 128), jnp.float32)
    _compile(functools.partial(arena_maintain_pallas, perm=r.perm,
                               dest=r.dest, first=r.first,
                               n_dest_tiles=int(r.touched.size),
                               interpret=False),
             arena, arena)


def _compile_arena_scatter(one_chip, case):
    from repro.kernels.fused_maintain.kernel import arena_scatter_pallas
    lay = case["layout"]
    arena = _spec(one_chip, (lay.total_words // 128, 128), jnp.float32)
    k = 1 << int(np.ceil(np.log2(lay.n_tiles // 8)))   # a 1/8 save, bucketed
    _compile(functools.partial(arena_scatter_pallas, interpret=False),
             arena, arena, _spec(one_chip, (k,), jnp.int32))


def test_arena_maintain_compiles(one_chip, qwen):
    _compile_arena_maintain(one_chip, qwen)


def test_arena_scatter_compiles(one_chip, qwen):
    _compile_arena_scatter(one_chip, qwen)


def test_arena_maintain_compiles_mamba2(one_chip, mamba2):
    _compile_arena_maintain(one_chip, mamba2)


def test_arena_scatter_compiles_mamba2(one_chip, mamba2):
    _compile_arena_scatter(one_chip, mamba2)


def test_scatter_save_compiles(one_chip, qwen):
    from repro.kernels.fused_maintain.kernel import scatter_save_pallas
    leaf = qwen["embed"]
    mat = _spec(one_chip, (leaf.rows, leaf.row_width), jnp.float32)
    k = 1 << int(np.ceil(np.log2(leaf.n_blocks // 8)))
    _compile(functools.partial(scatter_save_pallas, block_rows=128,
                               interpret=False),
             mat, mat, _spec(one_chip, (k,), jnp.int32))


def _grouped(one_chip, qwen):
    codec = qwen["codec"]
    n, g, f = codec.n_groups, codec.group_size, codec.layout.frame_elems
    return (_spec(one_chip, (n, g, f), jnp.int32),
            _spec(one_chip, (n, f), jnp.int32),
            _spec(one_chip, (n, g), jnp.int32))


def test_parity_xor_compiles(one_chip, qwen):
    from repro.kernels.parity_xor.kernel import parity_xor_pallas
    _compile(functools.partial(parity_xor_pallas, interpret=False),
             *_grouped(one_chip, qwen))


def test_gf256_mac_compiles(one_chip, qwen):
    from repro.kernels.gf256_mac.kernel import gf256_mac_pallas
    _compile(functools.partial(gf256_mac_pallas, interpret=False),
             *_grouped(one_chip, qwen))


def test_masked_restore_compiles(one_chip, qwen):
    from repro.kernels.masked_restore.kernel import masked_restore_pallas
    leaf = qwen["embed"]
    view = _spec(one_chip, (leaf.n_blocks, 128 * leaf.row_width),
                 jnp.float32)
    _compile(functools.partial(masked_restore_pallas, interpret=False),
             view, view, _spec(one_chip, (leaf.n_blocks,), jnp.bool_))
