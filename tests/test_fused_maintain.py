"""Single-pass maintenance: the arena sweep against the seed oracles, the
component passes of fabrics without an arena layout, fabric + controller
integration, and the donation-based in-place partial save.

Kernels run in interpret=True mode on CPU (the kernel body executes in
Python) — the TPU is the compile target, interpret validates semantics.
Replica and parity outputs must be *bit-exact* vs the seed oracles (copy
and XOR are exact operations); scores are float reductions with a
different association order, so they get a tight allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.arena import build_arena_layout, pack_arena
from repro.core.blocks import (block_scores, partition_pytree, select_blocks,
                               tree_sq_norm)
from repro.core.checkpoint import init_running_checkpoint
from repro.core.controller import FTController
from repro.core.norms import get_norm
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.fabric import CheckpointFabric, FabricConfig
from repro.fabric.domains import FailureDomainMap
from repro.fabric.parity import ParityCodec
from repro.fabric.placement import ClusterView
from repro.fabric.replica import ReplicaSet
from repro.kernels.fused_maintain.kernel import scatter_save_pallas
from repro.kernels.fused_maintain.ref import scatter_save_ref
from repro.kernels.fused_maintain.ops import (ArenaMaintainProgram,
                                              arena_routing,
                                              maintain_traffic,
                                              tree_scatter_save)
from repro.sharding.partition import block_device_homes

RNG = np.random.default_rng(11)


def _tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _params():
    return {"w": jnp.asarray(RNG.normal(size=(50, 6)), jnp.float32),
            "emb": jnp.asarray(RNG.normal(size=(33, 8)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(5,)), jnp.float32),
            "s": jnp.float32(2.5)}


def _codec(params, part, group_size=3):
    view = ClusterView(FailureDomainMap(8, 2, 2),
                       block_device_homes(part, 8))
    codec = ParityCodec(part, view, group_size=group_size, use_pallas=False)
    codec.encode(0, params)
    return codec


# ---------------------------------------------------------------------------
# kernel-level sweeps vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 1), (5, 100), (8, 512), (13, 777)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_arena_maintain_leaf_sweep(shape, dtype):
    """One leaf of ``shape`` (a block per row) through the arena sweep:
    the replica is the packed live values, the parity is bit-equal to the
    seed encode and the scores match ``block_scores``. Both layouts: the
    default tail-packed one (the jnp word sweep) and the tile-aligned one
    (the Pallas kernel in interpret mode, where the leaf is f32)."""
    tree = {"x": jnp.asarray(RNG.normal(size=shape), dtype)}
    ck = {"x": jnp.asarray(RNG.normal(size=shape), dtype)}
    part = partition_pytree(tree, 1)
    codec = _codec(tree, part, group_size=2)
    want = block_scores(tree, ck, part, get_norm("l2"))
    for tail_pack in (True, False):
        lay = build_arena_layout(part, tail_pack=tail_pack)
        prog = ArenaMaintainProgram(part, lay, codec.layout, codec.group_of,
                                    codec.n_groups, use_pallas=True,
                                    interpret=True)
        aligned_f32 = not lay.has_tail and dtype == jnp.float32
        assert prog.sweep == ("pallas" if aligned_f32 else "jnp")
        rep, sc, par = prog(tree, pack_arena(ck, lay))
        np.testing.assert_array_equal(np.asarray(rep),
                                      np.asarray(pack_arena(tree, lay)))
        np.testing.assert_array_equal(np.asarray(par),
                                      np.asarray(codec.parity))
        np.testing.assert_allclose(np.asarray(sc), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,block_rows", [((50, 6), 16), ((7, 3), 4),
                                              ((128, 520), 64)])
def test_scatter_save_kernel_sweep(shape, block_rows):
    dst = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    src = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    n_blocks = -(-shape[0] // block_rows)
    k = max(1, n_blocks // 2)
    rows = np.sort(RNG.choice(n_blocks, k, replace=False)).astype(np.int32)
    got = scatter_save_pallas(jnp.array(dst), src, jnp.asarray(rows),
                              block_rows, interpret=True)
    want = scatter_save_ref(dst, src, rows, block_rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_save_kernel_duplicate_rows_idempotent():
    dst = jnp.asarray(RNG.normal(size=(20, 8)), jnp.float32)
    src = jnp.asarray(RNG.normal(size=(20, 8)), jnp.float32)
    rows = jnp.asarray([1, 1, 3, 3], jnp.int32)   # bucket-padding pattern
    got = scatter_save_pallas(jnp.array(dst), src, rows, 4, interpret=True)
    want = scatter_save_ref(dst, src, np.asarray([1, 3]), 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# traffic model
# ---------------------------------------------------------------------------

def test_maintain_traffic_model_fused_wins():
    """The arena sweep moves fewer bytes than the seed's three passes, and
    feeding it the live arena (no pack) fewer still; its compact staging
    is smaller than the seed's frames + gather."""
    params = _params()
    part = partition_pytree(params, 16)
    codec = _codec(params, part)
    lay = build_arena_layout(part)
    t = maintain_traffic(part, codec.layout, codec.n_groups,
                         codec.members.shape[1], arena_layout=lay,
                         routing=arena_routing(lay, codec.layout,
                                               codec.group_of))
    assert t["arena_resident"] < t["arena"] < t["seed"]
    assert t["staging_arena"] < t["staging_seed"]


# ---------------------------------------------------------------------------
# fabric integration
# ---------------------------------------------------------------------------

def test_fabric_fused_matches_seed_maintain():
    """The arena fabric's replica and parity equal ``replicas.refresh`` +
    ``ParityCodec.encode`` of the same values, at fewer bytes."""
    params = _params()
    part = partition_pytree(params, 16)
    fab = CheckpointFabric(part, FabricConfig())
    fab.maintain(3, params)
    assert fab.stats["arena_maintains"] == 1
    seed = ReplicaSet(part, fab.view)
    seed.refresh(3, params)
    _tree_equal(fab.replicas.values, seed.values)
    codec = ParityCodec(part, fab.view, group_size=fab.cfg.parity_group,
                        use_pallas=False)
    codec.encode(3, params)
    np.testing.assert_array_equal(np.asarray(fab.parity.parity),
                                  np.asarray(codec.parity))
    assert fab.replicas.is_fresh(3) and fab.parity.is_fresh(3)
    t = fab._traffic_model()
    assert fab.stats["maintain_bytes_moved"] == t["arena"]
    assert t["arena"] < t["replica_pass"] + t["parity_pass"]


def test_fabric_fused_recovery_after_domain_loss():
    """A host loss recovered from arena-maintained tiers is exact, and the
    sweep program rebuilds against the re-striped topology."""
    params = _params()
    part = partition_pytree(params, 16)
    fab = CheckpointFabric(part, FabricConfig(elastic=True))
    ck = init_running_checkpoint(params, part)
    fab.maintain(5, params)
    lost, failed = fab.domain_failure("host", 0)
    assert failed.size
    recovered, stats = fab.on_failure(params, ck.values, lost,
                                      failed_devices=failed, step=5)
    assert float(tree_sq_norm(recovered, params)) == 0.0
    # elastic replan re-striped: next maintain must rebuild and stay
    # bit-consistent with a fresh seed encode on the same topology
    fab.maintain(6, params, force=True)
    want = jnp.array(fab.parity.parity)
    fab.parity.encode(6, params)
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(fab.parity.parity))


@pytest.mark.parametrize("case", ["replicate_only", "parity_only",
                                  "off_interval"])
def test_component_passes_match_the_oracles(case):
    """Fabrics that cannot take the arena sweep — one tier off (no arena
    layout), or tree input on a step where only one tier is due — run the
    seed's component passes, and their tiers equal ``ReplicaSet.refresh``
    / ``ParityCodec.encode`` of the same values."""
    params = _params()
    part = partition_pytree(params, 16)
    kw = {"replicate_only": dict(parity=False),
          "parity_only": dict(replicate=False),
          "off_interval": dict(parity_interval=2)}[case]
    fab = CheckpointFabric(part, FabricConfig(use_pallas=False, **kw))
    assert (fab.arena_layout is None) == (case != "off_interval")
    live = jax.tree_util.tree_map(lambda x: x + 1, params)
    fab.maintain(2, params)       # both tiers due on step 2 everywhere
    fab.maintain(3, live)         # off_interval: only the replica is due
    assert fab.stats["arena_maintains"] == (case == "off_interval")
    t = fab._traffic_model()
    if case != "parity_only":
        seed = ReplicaSet(part, fab.view)
        seed.refresh(3, live)
        _tree_equal(fab.replicas.values, seed.values)
        assert fab.replicas.is_fresh(3)
    if case == "off_interval":
        # the parity still holds step 2's sweep; the replica pass alone ran
        assert fab.parity.encoded_step == 2
        assert fab.stats["maintain_bytes_moved"] == \
            t["arena"] + t["replica_pass"]
    elif case == "parity_only":
        codec = ParityCodec(part, fab.view, group_size=fab.cfg.parity_group,
                            use_pallas=False)
        codec.encode(3, live)
        np.testing.assert_array_equal(np.asarray(fab.parity.parity),
                                      np.asarray(codec.parity))
        assert fab.stats["maintain_bytes_moved"] == 2 * t["parity_pass"]
    else:
        assert fab.stats["maintain_bytes_moved"] == 2 * t["replica_pass"]


def test_arena_routing_runs_once_per_striping(monkeypatch):
    """The arena routing is built once per parity striping and read by
    both the sweep program and the traffic model: three maintains, a loss
    and a heal of a non-elastic fabric (the striping never changes)
    build it exactly once."""
    from repro.kernels.fused_maintain import ops
    calls = []
    real = ops.arena_routing

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(ops, "arena_routing", counting)
    params = _params()
    part = partition_pytree(params, 16)
    fab = CheckpointFabric(part, FabricConfig(use_pallas=False))
    ck = init_running_checkpoint(params, part)
    fab._traffic_model()
    for step in (1, 2, 3):
        fab.maintain(step, params, ck.values)
    lost, failed = fab.domain_failure("host", 0)
    fab.on_failure(params, ck.values, lost, failed, step=3,
                   persist_failure=True)
    fab.maintain(4, params, ck.values)
    fab.heal_domain("host", 0, params, step=4)
    fab.maintain(5, params, ck.values)
    fab.redundancy_nbytes()
    assert len(calls) == 1
    assert fab.stats["maintain_program_builds"] == 1
    assert fab._arena_maintain_fn().routing is fab._arena_routing()


def test_fabric_scores_cache_lifecycle():
    params = _params()
    part = partition_pytree(params, 16)
    fab = CheckpointFabric(part, FabricConfig())
    ck = init_running_checkpoint(params, part)
    drifted = jax.tree_util.tree_map(lambda x: x + 1, params)
    fab.maintain(2, drifted, ckpt_values=ck.values)
    assert fab.last_scores_step == 2
    want = block_scores(drifted, ck.values, part, get_norm("l2"))
    np.testing.assert_allclose(np.asarray(fab.last_scores),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    fab.invalidate_scores()
    assert fab.last_scores is None and fab.last_scores_step == -1
    # without ckpt_values the sweep still maintains but caches no scores
    fab.maintain(3, drifted, force=True)
    assert fab.last_scores is None


def test_checkpoint_forces_freshness_despite_off_interval_maintain():
    """An off-interval maintain() must not mask the post-checkpoint force
    refresh: with replicate_interval=2 a checkpoint at an odd step still
    leaves every tier fresh (regression: the force was skipped whenever
    maintain ran the same step, even as a no-op)."""
    params = _params()
    pol = CheckpointPolicy(fraction=0.25, full_interval=1,
                           strategy=SelectionStrategy.ROUND_ROBIN,
                           recovery=RecoveryMode.PARTIAL, block_rows=16)
    ctl = FTController(params, pol,
                       fabric=FabricConfig(replicate_interval=2,
                                           parity_interval=2))
    live = jax.tree_util.tree_map(lambda x: x + 1, params)
    ctl.maintain(3, live)                      # 3 % 2 != 0: refreshes nothing
    assert not ctl.fabric.is_fresh(3)
    ctl.maybe_checkpoint(3, live)
    assert ctl.fabric.is_fresh(3)
    assert ctl.fabric.replicas.is_fresh(3)
    assert ctl.fabric.parity.is_fresh(3)


# ---------------------------------------------------------------------------
# in-place partial save
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_tree_scatter_save_matches_select_blocks(use_pallas):
    params = _params()
    part = partition_pytree(params, 16)
    ck = jax.tree_util.tree_map(
        lambda x: x + jnp.asarray(RNG.normal(size=x.shape), x.dtype), params)
    mask = np.asarray(RNG.random(part.total_blocks) < 0.4)
    mask[0] = True
    want = select_blocks(ck, params, jnp.asarray(mask), part)
    got, moved = tree_scatter_save(
        jax.tree_util.tree_map(jnp.array, ck), params,
        np.nonzero(mask)[0], part, use_pallas=use_pallas, interpret=True)
    _tree_equal(got, want)
    assert 0 < moved < sum(x.size * x.dtype.itemsize
                           for x in jax.tree_util.tree_leaves(params))


def test_tree_scatter_save_untouched_leaves_pass_through():
    params = _params()
    part = partition_pytree(params, 16)
    ck = jax.tree_util.tree_map(jnp.array, params)
    w_leaf = next(l for l in part.leaves if l.name == "['w']")
    idx = np.asarray([w_leaf.offset])
    got, moved = tree_scatter_save(ck, params, idx, part, use_pallas=False)
    # only w was touched; every other leaf is the same buffer object
    for leaf, a, b in zip(part.leaves, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(ck)):
        if leaf.name != "['w']":
            assert a is b
    assert moved == 16 * w_leaf.row_width * 4


def test_controller_inplace_save_matches_rewrite_path():
    """The donation-scatter save path is bit-equivalent to the seed
    jnp.where rewrite over a multi-save PRIORITY run."""
    params = _params()
    pol = CheckpointPolicy(fraction=0.25, full_interval=4,
                           strategy=SelectionStrategy.PRIORITY,
                           recovery=RecoveryMode.PARTIAL, block_rows=16)
    a = FTController(params, pol, inplace_save=True,
                     rng=jax.random.PRNGKey(3))
    b = FTController(params, pol, inplace_save=False,
                     rng=jax.random.PRNGKey(3))
    live = params
    for step in (1, 2, 3):
        live = jax.tree_util.tree_map(
            lambda x: x + jnp.asarray(RNG.normal(size=x.shape) * step,
                                      x.dtype), live)
        ma = a.checkpoint_now(step, live)
        mb = b.checkpoint_now(step, live)
        np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
    _tree_equal(a.ckpt.values, b.ckpt.values)
    np.testing.assert_array_equal(np.asarray(a.ckpt.saved_iter),
                                  np.asarray(b.ckpt.saved_iter))
    assert a.stats["save_bytes_moved"] > 0
    assert b.stats["save_bytes_moved"] == 0


def test_controller_fused_scores_reused_for_priority():
    """maintain() before a PRIORITY save caches the sweep's scores; the save
    consumes them (no third pass) and still selects the same blocks."""
    params = _params()
    pol = CheckpointPolicy(fraction=0.25, full_interval=1,
                           strategy=SelectionStrategy.PRIORITY,
                           recovery=RecoveryMode.PARTIAL, block_rows=16)
    fab = FabricConfig()
    ctl = FTController(params, pol, fabric=fab, rng=jax.random.PRNGKey(0))
    plain = FTController(params, pol, rng=jax.random.PRNGKey(0))
    live = jax.tree_util.tree_map(lambda x: x + 1, params)
    ctl.maintain(1, live)
    assert ctl.fabric.last_scores_step == 1
    m1 = ctl.checkpoint_now(1, live)
    m2 = plain.checkpoint_now(1, live)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    _tree_equal(ctl.ckpt.values, plain.ckpt.values)
    assert ctl.fabric.last_scores is None   # consumed + invalidated


def test_incremental_inplace_save_property():
    """Hypothesis: a sequence of random partial saves applied through the
    in-place scatter equals the seed select_blocks fold, mask by mask."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    params = _params()
    part = partition_pytree(params, 16)

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.lists(st.integers(0, part.total_blocks - 1),
                             min_size=1, max_size=part.total_blocks),
                    min_size=1, max_size=4),
           st.integers(0, 2 ** 31 - 1))
    def prop(mask_seq, seed):
        r = np.random.default_rng(seed)
        inplace = jax.tree_util.tree_map(jnp.array, params)
        fold = jax.tree_util.tree_map(jnp.array, params)
        for ids in mask_seq:
            src = jax.tree_util.tree_map(
                lambda x: x + jnp.asarray(r.normal(size=x.shape), x.dtype),
                params)
            idx = np.unique(np.asarray(ids))
            mask = np.zeros((part.total_blocks,), bool)
            mask[idx] = True
            inplace, _ = tree_scatter_save(inplace, src, idx, part,
                                           use_pallas=False)
            fold = select_blocks(fold, src, jnp.asarray(mask), part)
        _tree_equal(inplace, fold)

    prop()
