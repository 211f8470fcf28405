"""Flat parameter arena: layout invariants, pack/unpack round-trip,
single-dispatch maintenance/save/restore equivalence vs the tree paths,
and the arena-segment persistent store.

Kernel checks run interpret=True on CPU (TPU is the compile target);
replica/parity are bit-exact vs the tree-path oracles, scores get a tight
allclose (different association order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.arena import (ARENA_TILE, ArenaLayout, arena_compatible,
                              arena_drift_scores, arena_restore,
                              build_arena_layout,
                              frames_from_arena, frames_gather_index,
                              pack_arena, unpack_arena)
from repro.core.blocks import (block_scores, partition_pytree, select_blocks,
                               tree_sq_norm)
from repro.core.controller import FTController
from repro.core.norms import get_norm
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.fabric import CheckpointFabric, FabricConfig
from repro.fabric.domains import FailureDomainMap
from repro.fabric.parity import ParityCodec, pack_frames
from repro.fabric.placement import ClusterView
from repro.kernels.fused_maintain.ops import (ArenaMaintainProgram,
                                              arena_routing,
                                              arena_scatter_save)
from repro.sharding.partition import block_device_homes

RNG = np.random.default_rng(23)


def _tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _params():
    return {"w": jnp.asarray(RNG.normal(size=(50, 6)), jnp.float32),
            "emb": jnp.asarray(RNG.normal(size=(33, 8)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(5,)), jnp.float32),
            "s": jnp.float32(2.5)}


def _drift(tree, scale=1.0):
    return jax.tree_util.tree_map(
        lambda x: x + jnp.asarray(RNG.normal(size=x.shape) * scale,
                                  x.dtype), tree)


def _codec(params, part, group_size=3):
    view = ClusterView(FailureDomainMap(8, 2, 2),
                       block_device_homes(part, 8))
    codec = ParityCodec(part, view, group_size=group_size, use_pallas=False)
    codec.encode(0, params)
    return codec


# ---------------------------------------------------------------------------
# layout invariants
# ---------------------------------------------------------------------------

def test_layout_invariants():
    params = _params()
    part = partition_pytree(params, 16)
    lay = build_arena_layout(part)
    # I1 (word-level): the data region and the whole buffer are tile
    # multiples; main-region segments are tile-aligned, tail-packed
    # segments word-contiguous and pad-free
    assert lay.total_words % ARENA_TILE == 0
    assert lay.data_words % ARENA_TILE == 0
    assert lay.has_tail     # _params has sub-tile leaves ("b", "s")
    prev_end = 0
    for ab in lay.blocks:                       # I2: disjoint, covering,
        if ab.offset < lay.tail_start:          # offset-ascending
            assert ab.offset % ARENA_TILE == 0
            assert ab.words % ARENA_TILE == 0
        else:
            assert ab.words == ab.payload       # tail: no intra-seg pad
        assert 0 < ab.payload <= ab.words
        assert ab.offset == prev_end
        prev_end = ab.offset + ab.words
    assert prev_end == lay.tail_end <= lay.data_words
    assert lay.n_tiles == lay.total_words // ARENA_TILE
    # tail packing strictly shrinks the buffer vs the aligned layout
    loose = build_arena_layout(part, tail_pack=False)
    assert lay.total_words < loose.total_words
    assert lay.padding_ratio < loose.padding_ratio
    assert not loose.has_tail
    gids = lay.tile_gids()
    assert gids.shape == (lay.n_tiles,)
    main_gids = {ab.gid for ab in lay.blocks if ab.offset < lay.tail_start}
    tail_tiles = set(range(lay.tail_start // ARENA_TILE,
                           lay.data_words // ARENA_TILE))
    assert {int(g) for g in gids if g >= 0} == main_gids
    assert {i for i, g in enumerate(gids) if g < 0} == tail_tiles


def test_layout_colocated_leaves_get_separate_segments():
    tree = {"net": {"w": jnp.zeros((16, 3), jnp.float32)},
            "mu": {"w": jnp.zeros((16, 3), jnp.float32)}}
    part = partition_pytree(tree, 8, colocate=("net", "mu"))
    lay = build_arena_layout(part)
    assert len(lay.blocks) == 2 * part.total_blocks
    # both leaves' segments for gid 0 are selected together
    tiles = lay.tiles_for_blocks([0])
    assert tiles.size == 2 * (lay.seg_words[0] // ARENA_TILE)


def test_arena_compatible_gates_dtypes():
    # word-packable dtypes — incl. the quantized set — are arena-native
    good = partition_pytree({"a": jnp.zeros((4,), jnp.bfloat16),
                             "b": jnp.zeros((4,), jnp.float32),
                             "c": jnp.zeros((4,), jnp.int8),
                             "d": jnp.zeros((4,), jnp.int32)}, 4)
    # only truly word-unpackable dtypes gate (f64/int64/bool/complex);
    # np array: jnp would silently downcast f64 -> f32 without x64 mode
    bad = partition_pytree({"a": np.zeros((4,), np.float64)}, 4)
    assert arena_compatible(good)
    assert not arena_compatible(bad)
    fab = CheckpointFabric(bad, FabricConfig())
    assert fab.arena_layout is None             # component passes


# ---------------------------------------------------------------------------
# pack/unpack round trip (I3) — hypothesis property
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip_property():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dtypes = [jnp.float32, jnp.bfloat16, jnp.float16]

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(
        st.sampled_from([(), (1,), (7,), (13, 3), (16, 4), (33, 5),
                         (128, 2), (130, 3)]),
        st.integers(0, 2)), min_size=1, max_size=5),
        st.sampled_from([4, 8, 16, 128]),
        st.integers(0, 2 ** 31 - 1))
    def prop(leaf_specs, block_rows, seed):
        r = np.random.default_rng(seed)
        tree = {f"l{i}": jnp.asarray(r.normal(size=shape) * 100,
                                     dtypes[d])
                for i, (shape, d) in enumerate(leaf_specs)}
        part = partition_pytree(tree, block_rows)
        lay = build_arena_layout(part)
        arena = pack_arena(tree, lay)
        assert arena.shape == (lay.total_words,)
        back = unpack_arena(arena, lay)
        for x, y in zip(jax.tree_util.tree_leaves(back),
                        jax.tree_util.tree_leaves(tree)):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        # I4: every pad word is exactly 0.0f
        a = np.asarray(arena)
        for ab in lay.blocks:
            assert not a[ab.offset + ab.payload:ab.offset + ab.words].any()

    prop()


def test_arena_restore_matches_select_blocks_property():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    params = _params()
    part = partition_pytree(params, 16)
    lay = build_arena_layout(part)

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, part.total_blocks - 1), min_size=1,
                    max_size=part.total_blocks),
           st.integers(0, 2 ** 31 - 1))
    def prop(ids, seed):
        r = np.random.default_rng(seed)
        src = jax.tree_util.tree_map(
            lambda x: x + jnp.asarray(r.normal(size=x.shape), x.dtype),
            params)
        mask = np.zeros((part.total_blocks,), bool)
        mask[np.unique(ids)] = True
        got = arena_restore(params, pack_arena(src, lay), mask, lay)
        want = select_blocks(params, src, jnp.asarray(mask), part)
        _tree_equal(got, want)

    prop()


# ---------------------------------------------------------------------------
# arena maintain: single dispatch vs tree-path reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_arena_maintain_matches_tree_reference(use_pallas):
    params = _params()
    ck = _drift(params)
    part = partition_pytree(params, 16)
    codec = _codec(params, part)
    lay = build_arena_layout(part)
    prog = ArenaMaintainProgram(part, lay, codec.layout, codec.group_of,
                                codec.n_groups, use_pallas=use_pallas,
                                interpret=True)
    rep, sc, par = prog(params, pack_arena(ck, lay))
    np.testing.assert_array_equal(np.asarray(rep),
                                  np.asarray(pack_arena(params, lay)))
    np.testing.assert_array_equal(np.asarray(par), np.asarray(codec.parity))
    want = block_scores(params, ck, part, get_norm("l2"))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # scoreless variant still produces the same replica + parity
    rep2, sc2, par2 = prog(params, None)
    np.testing.assert_array_equal(np.asarray(rep2), np.asarray(rep))
    np.testing.assert_array_equal(np.asarray(par2), np.asarray(par))
    assert not np.asarray(sc2).any()


@pytest.mark.parametrize("shape", [(1, 1), (5, 100), (8, 512), (33, 777),
                                   (128, 2048), (7, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_arena_drift_scores_sweep(shape, dtype):
    """The sweep's word scorer against ``block_scores`` on one leaf of
    ``shape`` cut into 4-row blocks, beside a small leaf of the other
    dtype (a mixed-dtype arena, as mamba2's is)."""
    other = jnp.float32 if dtype == jnp.bfloat16 else jnp.bfloat16
    tree = {"x": jnp.asarray(RNG.normal(size=shape), dtype),
            "y": jnp.asarray(RNG.normal(size=(3, 5)), other)}
    ref = _drift(tree)
    part = partition_pytree(tree, 4)
    lay = build_arena_layout(part)
    got = arena_drift_scores(pack_arena(tree, lay), pack_arena(ref, lay),
                             lay)
    want = block_scores(tree, ref, part, get_norm("l2"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_arena_drift_scores_zero_distance():
    tree = {"x": jnp.asarray(RNG.normal(size=(16, 300)), jnp.float32),
            "y": jnp.asarray(RNG.normal(size=(9, 7)), jnp.bfloat16)}
    part = partition_pytree(tree, 4)
    lay = build_arena_layout(part)
    a = pack_arena(tree, lay)
    got = np.asarray(arena_drift_scores(a, a, lay))
    assert got.shape == (part.total_blocks,)
    assert not got.any()


def test_arena_maintain_colocated_leaves():
    tree = {"net": {"w": jnp.asarray(RNG.normal(size=(16, 3)), jnp.float32)},
            "mu": {"w": jnp.asarray(RNG.normal(size=(16, 3)), jnp.float32)},
            "t": jnp.float32(1.0)}
    ck = _drift(tree)
    part = partition_pytree(tree, 8, colocate=("net", "mu"))
    codec = _codec(tree, part, group_size=2)
    lay = build_arena_layout(part)
    for use_pallas in (False, True):
        prog = ArenaMaintainProgram(part, lay, codec.layout, codec.group_of,
                                    codec.n_groups, use_pallas=use_pallas,
                                    interpret=True)
        rep, sc, par = prog(tree, pack_arena(ck, lay))
        np.testing.assert_array_equal(np.asarray(par),
                                      np.asarray(codec.parity))
        want = block_scores(tree, ck, part, get_norm("l2"))
        np.testing.assert_allclose(np.asarray(sc), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_arena_routing_covers_every_tile_once():
    params = _params()
    part = partition_pytree(params, 16)
    codec = _codec(params, part)
    lay = build_arena_layout(part)
    r = arena_routing(lay, codec.layout, codec.group_of)
    # routing covers exactly the main-region tiles, each once; tail tiles
    # are swept by the word-granular epilogue instead
    main_tiles = list(range(lay.tail_start // ARENA_TILE))
    assert sorted(r.perm.tolist()) == main_tiles
    assert r.first[0] == 1
    listed = r.members[r.members >= 0]
    assert sorted(listed.tolist()) == main_tiles
    # the aligned (tail_pack=False) layout routes every tile
    loose = build_arena_layout(part, tail_pack=False)
    r2 = arena_routing(loose, codec.layout, codec.group_of)
    assert sorted(r2.perm.tolist()) == list(range(loose.n_tiles))


def test_frames_from_arena_matches_pack_frames():
    params = _params()
    part = partition_pytree(params, 16)
    codec = _codec(params, part)
    lay = build_arena_layout(part)
    idx = frames_gather_index(lay, codec.layout)
    got = frames_from_arena(pack_arena(params, lay), idx)
    want = pack_frames(params, part, codec.layout)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_parity_reconstruct_from_arena_matches_tree_path():
    params = _params()
    part = partition_pytree(params, 16)
    codec = _codec(params, part)
    lay = build_arena_layout(part)
    arena = pack_arena(params, lay)
    # lose one member of the tail group (single erasure, no device dead)
    tail = codec.members[-1]
    victim = int(tail[tail >= 0][-1])
    lost = np.zeros((part.total_blocks,), bool)
    lost[victim] = True
    rec_mask = codec.reconstructable(lost, ~lost, np.empty((0,), np.int32),
                                     step=0)
    assert rec_mask[victim]
    want = codec.reconstruct(params, rec_mask, ~lost)
    got = codec.reconstruct_from_arena(arena, lay, rec_mask, ~lost)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_recover_routes_parity_through_arena_frames(monkeypatch):
    """When the sweep's snapshot arena matches the parity encode step,
    recovery must source member frames from the arena gather — the
    full-tree pack_frames path must not run."""
    params = _params()
    part = partition_pytree(params, 16)
    fab = CheckpointFabric(part, FabricConfig())
    fab.maintain(5, params)
    # kill block 0's primary home AND its replica home: the block must
    # fall to the PARITY tier (its group's other members survive)
    failed = np.unique(np.asarray(
        [fab.view.homes[0], fab.replicas.replica_homes[0]], np.int32))
    lost = np.isin(fab.view.homes, failed)
    plan = fab.planner.plan(lost, failed, step=5)
    if not plan.counts["PARITY"]:
        pytest.skip("striping left no parity-tier block for this seed")
    monkeypatch.setattr(
        ParityCodec, "reconstruct",
        lambda *a, **k: pytest.fail("tree-path pack_frames used despite "
                                    "fresh snapshot arena"))
    ck = jax.tree_util.tree_map(jnp.array, params)
    recovered, stats = fab.planner.recover(params, ck, plan)
    assert float(tree_sq_norm(recovered, params)) == 0.0


# ---------------------------------------------------------------------------
# arena save path: controller equivalence + recovery
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", [SelectionStrategy.PRIORITY,
                                      SelectionStrategy.ROUND_ROBIN,
                                      SelectionStrategy.RANDOM])
def test_controller_arena_save_matches_rewrite(strategy):
    """Arena-mode saves are bit-equivalent to the seed jnp.where fold,
    strategy by strategy, over a multi-save run with maintenance."""
    params = _params()
    pol = CheckpointPolicy(fraction=0.25, full_interval=1,
                          strategy=strategy,
                          recovery=RecoveryMode.PARTIAL, block_rows=16)
    a = FTController(params, pol, fabric=FabricConfig(),
                     rng=jax.random.PRNGKey(5))
    b = FTController(params, pol, inplace_save=False,
                     rng=jax.random.PRNGKey(5))
    assert a._arena_layout is not None
    live = params
    for step in (1, 2, 3):
        live = _drift(live, scale=step)
        a.maintain(step, live)
        ma = a.checkpoint_now(step, live)
        mb = b.checkpoint_now(step, live)
        np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
    _tree_equal(a.ckpt.values, b.ckpt.values)
    np.testing.assert_array_equal(np.asarray(a.ckpt.saved_iter),
                                  np.asarray(b.ckpt.saved_iter))
    assert a.stats["save_bytes_moved"] > 0
    assert a.fabric.stats["arena_maintains"] == 3


def test_arena_scatter_save_is_single_program():
    params = _params()
    part = partition_pytree(params, 16)
    lay = build_arena_layout(part)
    src = pack_arena(params, lay)
    dst = jnp.zeros_like(src)
    ids = np.asarray([1, 4, part.total_blocks - 1])
    out, moved = arena_scatter_save(dst, src, lay, ids, use_pallas=False)
    out_p, moved_p = arena_scatter_save(jnp.zeros_like(src), src, lay, ids,
                                        use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_p))
    assert moved == moved_p == lay.seg_bytes_for_blocks(ids)
    # untouched tiles stayed zero
    touched = lay.tiles_for_blocks(ids)
    o2 = np.asarray(out).reshape(-1, ARENA_TILE)
    untouched = np.setdiff1d(np.arange(lay.n_tiles), touched)
    assert not o2[untouched].any()


def test_arena_recovery_from_replica_and_ckpt_is_exact():
    """Domain loss with arena tiers: replica tier restores live values
    through contiguous arena slices; a degraded fallback recovers from
    the (arena-backed) running checkpoint."""
    params = _params()
    pol = CheckpointPolicy(fraction=0.5, full_interval=1,
                          strategy=SelectionStrategy.PRIORITY,
                          recovery=RecoveryMode.PARTIAL, block_rows=16)
    ctl = FTController(params, pol, fabric=FabricConfig(elastic=True),
                       rng=jax.random.PRNGKey(0))
    live = _drift(params)
    ctl.maintain(1, live)
    ctl.checkpoint_now(1, live)
    live2, info = ctl.on_domain_event(live, "host", 0, step=1)
    assert float(tree_sq_norm(live2, live)) == 0.0
    assert info["tier_counts"]["PEER_REPLICA"] > 0
    assert ctl.fabric.replicas.arena is not None


def test_arena_ckpt_tree_materialization_is_lazy_and_correct():
    params = _params()
    pol = CheckpointPolicy(fraction=0.25, full_interval=1,
                          strategy=SelectionStrategy.ROUND_ROBIN,
                          recovery=RecoveryMode.PARTIAL, block_rows=16)
    ctl = FTController(params, pol, fabric=FabricConfig())
    live = _drift(params)
    ctl.maintain(1, live)
    ctl.checkpoint_now(1, live)
    assert ctl._ckpt_dirty                      # hot path left it lazy
    vals = ctl.ckpt.values                      # decoded on demand ...
    assert ctl._ckpt_dirty and ctl._ckpt.values is None   # ... not kept
    _tree_equal(vals, unpack_arena(ctl._ckpt_arena, ctl._arena_layout))


# ---------------------------------------------------------------------------
# arena-segment store
# ---------------------------------------------------------------------------

def test_arena_store_roundtrip_and_rekey(tmp_path):
    import os

    from repro.checkpoint_io import ShardedCheckpointStore

    params = _params()
    pol = CheckpointPolicy(fraction=0.25, full_interval=1,
                          strategy=SelectionStrategy.ROUND_ROBIN,
                          recovery=RecoveryMode.PARTIAL, block_rows=16)
    store = ShardedCheckpointStore(str(tmp_path))
    ctl = FTController(params, pol, store=store,
                       fabric=FabricConfig(elastic=True))
    assert store.arena_layout is not None
    live = params
    for step in (1, 2, 3):
        live = _drift(live)
        ctl.maintain(step, live)
        ctl.checkpoint_now(step, live)
    store.flush()
    _tree_equal(store.read_all(), ctl.ckpt.values)
    # partial read touches only the masked blocks
    mask = np.zeros((ctl.partition.total_blocks,), bool)
    mask[0] = True
    part_vals = store.read_blocks(mask)
    w = jax.tree_util.tree_leaves(part_vals)[0]
    want_w = jax.tree_util.tree_leaves(ctl.ckpt.values)[0]
    np.testing.assert_array_equal(np.asarray(w)[:16], np.asarray(want_w)[:16])
    # degrade placement, then re-key the mirror during compaction
    live, _ = ctl.on_domain_event(live, "host", 0, step=3)
    reclaimed = store.compact(rekey_homes=ctl.fabric.view.homes,
                              domains=ctl.fabric.domains)
    assert reclaimed >= 0
    _tree_equal(store.read_all(), ctl.ckpt.values)
    # every live segment now sits on its block's CURRENT home host
    want_hosts = ctl.fabric.domains.host_of(ctl.fabric.view.homes)
    np.testing.assert_array_equal(store.host_of_block, want_hosts)
    # a fresh save after the re-key lands in the new keying and reads back
    live = _drift(live)
    ctl.maintain(4, live)
    ctl.checkpoint_now(4, live)
    store.flush()
    _tree_equal(store.read_all(), ctl.ckpt.values)


def test_arena_store_one_append_write_per_host(tmp_path, monkeypatch):
    from repro.checkpoint_io import ShardedCheckpointStore

    params = _params()
    pol = CheckpointPolicy(fraction=0.5, full_interval=1,
                          strategy=SelectionStrategy.ROUND_ROBIN,
                          recovery=RecoveryMode.PARTIAL, block_rows=16)
    store = ShardedCheckpointStore(str(tmp_path))
    ctl = FTController(params, pol, store=store, fabric=FabricConfig())
    live = _drift(params)
    writes = []
    orig = ShardedCheckpointStore._do_write

    def spy(self, jobs, step):
        by_shard = {}
        for seg, _ in jobs:
            by_shard.setdefault(self._shard_path(seg), []).append(seg)
        writes.append(len(by_shard))
        return orig(self, jobs, step)

    monkeypatch.setattr(ShardedCheckpointStore, "_do_write", spy)
    ctl.maintain(1, live)
    ctl.checkpoint_now(1, live)
    store.flush()
    assert writes and all(n <= 4 for n in writes)   # ≤ one per host shard


@pytest.mark.parametrize("smem_steps", [3, 8])
def test_arena_kernels_split_by_smem_capacity(monkeypatch, smem_steps):
    """A sweep or scatter longer than the SMEM routing capacity runs as
    several calls (cut at parity-destination starts for the sweep) with
    the same outputs as one call."""
    from repro.kernels.fused_maintain import kernel as fk
    params = _params()
    part = partition_pytree(params, 16)
    codec = _codec(params, part)
    lay = build_arena_layout(part, tail_pack=False)
    r = arena_routing(lay, codec.layout, codec.group_of)
    x2d = pack_arena(params, lay).reshape(-1, 128)
    z2d = pack_arena(_drift(params), lay).reshape(-1, 128)
    tiles = jnp.asarray(np.arange(lay.n_tiles, dtype=np.int32)[::-1])
    n_dest = int(r.touched.size)
    want_sc, want_par = fk.arena_maintain_pallas(
        x2d, z2d, r.perm, r.dest, r.first, n_dest, interpret=True)
    want_dst = fk.arena_scatter_pallas(jnp.zeros_like(z2d), x2d, tiles,
                                       interpret=True)
    monkeypatch.setattr(fk, "SMEM_STEPS", smem_steps)
    assert len(fk._dest_chunks(r.first)) > 1
    sc, par = fk.arena_maintain_pallas(x2d, z2d, r.perm, r.dest, r.first,
                                       n_dest, interpret=True)
    np.testing.assert_array_equal(np.asarray(par), np.asarray(want_par))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(want_sc))
    dst = fk.arena_scatter_pallas(jnp.zeros_like(z2d), x2d, tiles,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(dst), np.asarray(want_dst))
    np.testing.assert_array_equal(np.asarray(dst), np.asarray(x2d))


@pytest.mark.parametrize("model,want", [
    ("reduced_f32", ("jnp", "has_tail")),
    ("reduced_bf16", ("jnp", "not uniform_f32, has_tail")),
    ("aligned_f32", ("pallas", "")),
])
def test_fabric_records_which_arena_sweep_runs(model, want):
    """The fabric's stats say which arena sweep runs and why the Pallas
    kernel was passed over (reduced qwen2: its sub-tile k/v biases put a
    tail region in the layout)."""
    import dataclasses
    from repro.models import get_model
    if model == "aligned_f32":
        params = {"w": jnp.asarray(RNG.normal(size=(64, 128)), jnp.float32)}
    else:
        cfg = get_config("qwen2-1.5b", reduced=True)
        if model == "reduced_bf16":
            cfg = dataclasses.replace(cfg, dtype="bfloat16")
        params = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    part = partition_pytree(params, 16)
    fab = CheckpointFabric(part, FabricConfig(use_pallas=True))
    fab.maintain(1, params)
    assert (fab.stats["arena_sweep"], fab.stats["arena_sweep_reason"]) == want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cut_leaf_round_trips_bit_exact(monkeypatch, tmp_path, dtype):
    """A layer-stacked leaf wider than ``MAX_BLOCK_ELEMS`` is viewed with
    narrower rows (``leaf_view_shape``). Pack/unpack, the arena and kernel
    partial restores, and the arena store's write/read must all treat
    block ``b`` as view rows ``[b·br, (b+1)·br)``, bit for bit."""
    from repro.checkpoint_io import ShardedCheckpointStore
    from repro.core import blocks
    from repro.kernels.masked_restore.ops import tree_masked_restore
    br = 16
    monkeypatch.setattr(blocks, "MAX_BLOCK_ELEMS", br * 128)
    blocks.leaf_view_shape.cache_clear()
    try:
        def tree(scale):
            return {"stack": jnp.asarray(scale * RNG.normal(size=(3, 24, 256)),
                                         dtype),
                    "emb": jnp.asarray(scale * RNG.normal(size=(40, 128)),
                                       dtype),
                    "b": jnp.asarray(scale * RNG.normal(size=(5,)), dtype)}
        params, other = tree(1.0), tree(3.0)
        part = partition_pytree(params, br)
        stack = part.leaves[[l.name for l in part.leaves].index("['stack']")]
        assert stack.rows != stack.shape[0] and stack.n_blocks > 1, stack
        lay = build_arena_layout(part)
        arena = jax.jit(lambda p: pack_arena(p, lay))(params)
        _tree_equal(unpack_arena(arena, lay), params)

        mask = np.zeros((part.total_blocks,), bool)
        mask[stack.offset + np.arange(0, stack.n_blocks, 2)] = True
        mask[part.leaves[0].offset] = True

        def reference(dst, src):
            """Per view row: src where the row's block is masked."""
            out = {}
            for leaf, (k, d) in zip(part.leaves, sorted(dst.items())):
                rows = max(leaf.rows, 1)
                keep = np.repeat(mask[leaf.offset:leaf.offset
                                      + leaf.n_blocks], br)[:rows]
                dv = np.asarray(d).reshape(rows, -1)
                sv = np.asarray(src[k]).reshape(rows, -1)
                out[k] = np.where(keep[:, None], sv, dv).reshape(leaf.shape)
            return out

        want = reference(other, params)
        _tree_equal(arena_restore(other, arena, mask, lay), want)
        _tree_equal(tree_masked_restore(other, params, mask, part,
                                        interpret=True), want)

        store = ShardedCheckpointStore(str(tmp_path))
        other_arena = jax.jit(lambda p: pack_arena(p, lay))(other)
        store.init(other, part, arena_layout=lay,
                   arena_values=np.asarray(other_arena))
        _tree_equal(store.read_all(), other)
        tiles = lay.tiles_for_blocks(np.nonzero(mask)[0])
        data = np.asarray(arena).reshape(-1, ARENA_TILE)[tiles]
        store.write_arena(mask, tiles, data, step=1, background=False)
        _tree_equal(store.read_all(), want)
    finally:
        monkeypatch.undo()
        blocks.leaf_view_shape.cache_clear()
