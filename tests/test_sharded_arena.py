"""Sharded parameter arena: layout math, elastic relayout, and SPMD
end-to-end equivalence.

Two halves. The in-process tests cover the host-side sharded-layout
arithmetic (pad tiles, data-region invariance, relayout round-trip, span
ownership) and the explicit misconfiguration paths. The SPMD tests need
more than one device, which tier-1 runs without (conftest forbids
XLA_FLAGS in-process so smoke tests see the real single CPU), so they
shell out to a driver with ``--xla_force_host_platform_device_count=8``.

Equivalence scope, stated honestly: arena-vs-PyTree bit-equality holds on
the SAME mesh (identical shardings → identical reduction orders). Across
topologies (1 device vs 8, 8 shards vs 4) the sharded RNG in param init
and the different all-reduce association orders change low bits, so
cross-topology claims are allclose at best and not asserted here.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.arena import (ARENA_TILE, arena_block_homes,
                              build_arena_layout, pack_arena, relayout_arena,
                              unpack_arena)
from repro.core.blocks import partition_pytree
from repro.core.policy import CheckpointPolicy
from repro.data.pipeline import ShardedLMDataset
from repro.fabric import CheckpointFabric, FabricConfig
from repro.launch.mesh import mesh_devices, survivor_mesh
from repro.sharding import single_device_ctx
from repro.telemetry.recorder import Recorder
from repro.training import TrainLoop, TrainLoopConfig, TrainState

RNG = np.random.default_rng(11)


def _params():
    return {"w": jnp.asarray(RNG.normal(size=(96, 40)), jnp.float32),
            "emb": jnp.asarray(RNG.normal(size=(65, 24)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(33,)), jnp.float32),
            "s": jnp.float32(1.5)}


# ---------------------------------------------------------------------------
# sharded layout math (in-process, host-side)
# ---------------------------------------------------------------------------

def test_sharded_layout_invariants():
    """Sharding only appends zero pad tiles: the data region is byte-wise
    identical across shard counts, every shard owns whole tiles, and the
    pad is the minimal amount that makes the tile count divide."""
    part = partition_pytree(_params(), block_rows=8)
    base = build_arena_layout(part)               # shards=1
    for shards in (1, 2, 4, 8):
        lay = build_arena_layout(part, shards=shards)
        assert lay.shards == shards
        assert lay.data_words == base.data_words
        assert lay.n_tiles % shards == 0
        assert lay.shard_words * shards == lay.total_words
        assert lay.shard_words % ARENA_TILE == 0
        # minimal pad: removing one pad tile per shard would break I1
        assert lay.total_words - base.data_words < shards * ARENA_TILE
        # pad tiles report gid 0 — bit-neutral because pad words are zero
        # in every arena (I4), so per-gid reductions see an exact +0.0
        gids = lay.tile_gids()
        assert gids.shape == (lay.n_tiles,)
        n_pad_tiles = (lay.total_words - lay.data_words) // ARENA_TILE
        if n_pad_tiles:
            assert (gids[-n_pad_tiles:] == 0).all()

    with pytest.raises(ValueError):
        build_arena_layout(part, shards=0)


def test_relayout_arena_bit_exact_roundtrip():
    """shards=1 → 4 → 1 round-trips bit-exactly, pad tail is zero, and
    the decoded tree is unchanged at every shard count."""
    values = _params()
    part = partition_pytree(values, block_rows=8)
    l1 = build_arena_layout(part, shards=1)
    l4 = build_arena_layout(part, shards=4)
    a1 = pack_arena(values, l1)
    a4 = relayout_arena(a1, l1, l4)
    assert a4.shape == (l4.total_words,)
    np.testing.assert_array_equal(np.asarray(a4)[:l4.data_words],
                                  np.asarray(a1)[:l1.data_words])
    assert not np.asarray(a4)[l4.data_words:].any()
    for lay, arena in ((l1, a1), (l4, a4)):
        for x, y in zip(jax.tree_util.tree_leaves(values),
                        jax.tree_util.tree_leaves(
                            unpack_arena(jnp.asarray(arena), lay))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    back = relayout_arena(a4, l4, l1)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(a1))

    # different partitions must refuse to relayout into each other
    other = partition_pytree({"w": jnp.zeros((16, 8), jnp.float32)},
                             block_rows=8)
    with pytest.raises(ValueError):
        relayout_arena(a1, l1, build_arena_layout(other, shards=4))


def test_arena_block_homes_span_ownership():
    """Each gid's home is the shard whose contiguous word span holds the
    first tile of its first arena block (checked against brute force)."""
    part = partition_pytree(_params(), block_rows=8)
    for shards in (1, 2, 4):
        lay = build_arena_layout(part, shards=shards)
        homes = arena_block_homes(lay)
        assert homes.shape == (part.total_blocks,)
        assert homes.min() >= 0 and homes.max() < shards
        sw = lay.shard_words
        for ab in lay.blocks:
            assert homes[ab.gid] == ab.offset // sw
    # shards=1: everything home 0
    assert (arena_block_homes(build_arena_layout(part)) == 0).all()
    # asking for a device count that doesn't divide the tiles is an error
    lay = build_arena_layout(part, shards=2)   # 28 tiles
    with pytest.raises(ValueError):
        arena_block_homes(lay, n_devices=5)


def test_survivor_mesh_and_mesh_devices():
    dev = jax.devices()[0]
    m = survivor_mesh([dev])
    assert m.devices.shape == (1, 1)
    assert m.axis_names == ("data", "model")
    assert mesh_devices(m) == [dev]


def test_meshed_fabric_size_mismatch_raises():
    """A mesh whose device count disagrees with cfg.n_devices is a
    misconfiguration, not a fallback."""
    part = partition_pytree(_params(), block_rows=8)
    m = survivor_mesh([jax.devices()[0]])
    with pytest.raises(ValueError, match="mesh"):
        CheckpointFabric(part, FabricConfig(n_devices=8), mesh=m)


def test_arena_gated_fallback_warns_and_records():
    """arena_state=True with a fabric that can't build an arena layout
    must not fall back silently: a warning fires and the recorder gets a
    ``fabric/arena_gated`` event (satellite: no silent PyTree fallback)."""
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    rec = Recorder()
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=CheckpointPolicy.scar(fraction=0.25, interval=2),
        fabric=FabricConfig(parity=False),      # no arena layout: one tier
        arena_state=True, recorder=rec))
    with pytest.warns(UserWarning, match="not arena-capable"):
        state = loop.init_state()
    assert isinstance(state, TrainState)        # fell back, loudly
    assert any(e["kind"] == "fabric/arena_gated" for e in rec.events)


# ---------------------------------------------------------------------------
# SPMD end-to-end (subprocess: forced 8-device CPU topology)
# ---------------------------------------------------------------------------

def _run_spmd(driver: str, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(driver)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, (
        f"SPMD driver failed\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr}")
    return proc.stdout


_COMMON = """
import numpy as np
import jax
from repro.configs import get_config
from repro.core.policy import CheckpointPolicy
from repro.data.pipeline import ShardedLMDataset
from repro.fabric import FabricConfig
from repro.launch.mesh import make_mesh_compat
from repro.sharding.partition import make_dist_ctx
from repro.training import ArenaTrainState, TrainLoop, TrainLoopConfig

cfg = get_config("qwen2-1.5b", reduced=True)
mesh = make_mesh_compat((4, 2), ("data", "model"))
ctx = make_dist_ctx(mesh)
"""


def test_spmd_sharded_arena_bit_equal_to_pytree_same_mesh():
    """The acceptance criterion: on the SAME (4, 2) mesh the arena loop
    and the PyTree loop produce bit-identical losses, running checkpoint
    and final params — while the arena loop runs pack-free with the
    replica shipped over a genuinely rotated anti-affine placement."""
    out = _run_spmd(_COMMON + """
def run(arena_state):
    pol = CheckpointPolicy.scar(fraction=0.25, interval=2)
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=pol, fabric=FabricConfig(), arena_state=arena_state))
    state = loop.init_state()
    ds = ShardedLMDataset(cfg, batch=8, seq=32, ctx=ctx)
    return loop, loop.run(state, iter(ds), 5)

la, sa = run(True)
lt, st = run(False)
assert isinstance(sa, ArenaTrainState), type(sa)
assert sa.layout.shards == 8
assert [m["loss"] for m in la.metrics] == [m["loss"] for m in lt.metrics]
assert (np.asarray(la.controller._ckpt_arena)
        == np.asarray(lt.controller._ckpt_arena)).all()
assert all(bool((np.asarray(x) == np.asarray(y)).all())
           for x, y in zip(jax.tree_util.tree_leaves(sa.params),
                           jax.tree_util.tree_leaves(st.params)))
fab = la.controller.fabric
assert fab.stats["live_packs"] == 0
assert fab.stats["arena_resident_maintains"] == fab.stats["arena_maintains"]
# GSPMD cannot partition a Mosaic kernel: the meshed sweep and codecs
# take their jnp paths, and the stats say so
assert fab.stats["arena_sweep"] == "jnp"
assert "sharded" in fab.stats["arena_sweep_reason"], fab.stats
assert fab.parity.use_pallas is False
# the replica landed on a rotated device order (anti-affinity is real)
rot = [d.id for d in fab._replica_sharding.mesh.devices.reshape(-1)]
assert rot != sorted(rot), rot
assert fab.stats["ici_bytes_moved"] + fab.stats["dcn_bytes_moved"] > 0
print("SPMD-EQ-OK")
""")
    assert "SPMD-EQ-OK" in out


def test_spmd_elastic_shrink_heal_regrow():
    """Host loss at step 4 shrinks the mesh to the survivors (8 → 4
    shards, honoring batch divisibility), training continues with finite
    losses, and the heal at step 9 re-grows to the full mesh — the loop
    never leaves the arena representation and never packs."""
    out = _run_spmd(_COMMON + """
pol = CheckpointPolicy.scar(fraction=0.25, interval=2)
loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
    policy=pol, fabric=FabricConfig(elastic=True),
    fail_schedule=[(4, "host", 1)], heal_after=5))
state = loop.init_state()
assert isinstance(state, ArenaTrainState)
ds = ShardedLMDataset(cfg, batch=8, seq=32, ctx=ctx)
state = loop.run(state, iter(ds), 12)
resizes = [(m["step"], m["mesh_resize"]) for m in loop.metrics
           if "mesh_resize" in m]
fab = loop.controller.fabric
assert all(np.isfinite(m["loss"]) for m in loop.metrics)
# 6 alive after host loss; batch=8 -> largest divisor k<=6 is 4
assert resizes[0][1]["shards"] == 4, resizes
assert resizes[1][1]["shards"] == 8, resizes
assert fab.view.n_alive_devices == 8
assert fab.arena_layout.shards == 8
assert fab.stats["mesh_resizes"] == 2
assert fab.stats["live_packs"] == 0
assert state.layout.shards == 8
assert all(np.isfinite(np.asarray(l)).all()
           for l in jax.tree_util.tree_leaves(state.params))
print("SPMD-ELASTIC-OK")
""")
    assert "SPMD-ELASTIC-OK" in out


def test_spmd_meshed_fabric_arena_gate_raises():
    """On a mesh the fabric cannot silently drop to the tree pipeline —
    an arena-incapable config plus a mesh is a hard ValueError."""
    out = _run_spmd(_COMMON + """
from repro.core.blocks import partition_pytree
from repro.fabric import CheckpointFabric
import jax.numpy as jnp
part = partition_pytree({"w": jnp.zeros((64, 8), jnp.float32)}, block_rows=8)
try:
    CheckpointFabric(part, FabricConfig(parity=False), mesh=mesh)
except ValueError as e:
    assert "arena" in str(e).lower(), e
    print("SPMD-GATE-OK")
else:
    raise AssertionError("meshed non-arena fabric did not raise")
""")
    assert "SPMD-GATE-OK" in out
