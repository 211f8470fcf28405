"""Elastic placement engine: cluster view, re-homing, degraded-mode soak.

Covers the elastic invariants the placement layer must hold after every
re-plan:
- no block is homed on a dead device,
- replicas stay anti-affine (replica host ≠ primary host) while ≥2 hosts
  survive,
- every parity group keeps ≥ 2 members with live homes,
and the headline behavior: after a host loss with ``elastic=True``, a
*subsequent* failure of a different host still recovers every lost block
from PEER_REPLICA or PARITY — never RUNNING_CKPT/DISK — while the
recover-in-place fabric falls through on the degraded topology.
"""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint_io import ShardedCheckpointStore
from repro.core.blocks import partition_pytree, tree_sq_norm
from repro.core.checkpoint import init_running_checkpoint
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.fabric import (CheckpointFabric, ClusterView, FabricConfig,
                          FailureDomainMap, FailureEvent, ParityCodec)
from repro.fabric.parity import pack_frames
from repro.fabric.placement import (anti_affine_replica_homes,
                                    parity_group_homes, rebalance_homes,
                                    rehome_blocks, stripe_parity_groups)
from repro.sharding.partition import block_device_homes

RNG = np.random.default_rng(23)


def _params(rows=256, width=6):
    return {"w": jnp.asarray(RNG.normal(size=(rows, width)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(8,)), jnp.float32)}


def _dm():
    return FailureDomainMap(n_devices=8, devices_per_host=2, hosts_per_rack=2)


def _view(part):
    dm = _dm()
    return ClusterView(dm, block_device_homes(part, dm.n_devices))


def _fabric(part, **kw):
    kw.setdefault("elastic", True)
    cfg = FabricConfig(n_devices=8, devices_per_host=2, hosts_per_rack=2,
                       use_pallas=False, **kw)
    return CheckpointFabric(part, cfg)


def _noisy(params, seed=0):
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda x: x + jnp.asarray(rng.normal(size=x.shape), jnp.float32),
        params)


def _assert_elastic_invariants(fab):
    """The three placement invariants every elastic re-plan must restore."""
    view = fab.view
    assert view.alive[view.homes].all(), "block homed on a dead device"
    if fab.replicas is not None:
        assert view.alive[fab.replicas.replica_homes].all()
        if view.n_alive_hosts >= 2:
            assert np.all(
                np.asarray(view.host_of(fab.replicas.replica_homes))
                != np.asarray(view.host_of(view.homes))), \
                "replica shares its primary's host"
    if fab.parity is not None:
        # a 1-member XOR group is a bare copy, so XOR folds the tail; with
        # m >= 2 parity rows RS keeps a 1-member tail group as it is
        least = 2 if fab.parity.n_parity < 2 else 1
        for j, row in enumerate(fab.parity.members):
            ids = row[row >= 0]
            assert ids.size >= least, f"parity group {j} has < {least} members"
            assert view.alive[view.homes[ids]].all(), \
                f"parity group {j} has a dead member home"
        assert view.alive[fab.parity.parity_homes].all()


# ---------------------------------------------------------------------------
# ClusterView + placement primitives
# ---------------------------------------------------------------------------

def test_cluster_view_mutation_and_healing():
    part = partition_pytree(_params(), 16)
    view = _view(part)
    assert view.n_alive_devices == 8 and view.n_alive_hosts == 4
    newly = view.mark_failed([0, 1])
    assert newly.tolist() == [0, 1] and view.version == 1
    assert view.mark_failed([1]).size == 0        # already dead: no-op
    assert view.n_alive_hosts == 3
    assert view.displaced_blocks().size > 0
    healed = view.heal([0, 1, 2])                 # 2 was never dead
    assert healed.tolist() == [0, 1]
    assert view.n_alive_devices == 8


def test_rehome_moves_displaced_blocks_balanced():
    part = partition_pytree(_params(), 16)
    view = _view(part)
    view.mark_failed(view.domains.devices_in("host", 0))
    displaced = rehome_blocks(view)
    assert displaced.size > 0
    assert view.alive[view.homes].all()
    load = view.load()[view.alive_devices()]
    assert load.max() - load.min() <= 1, "re-homing left load unbalanced"
    # idempotent: nothing left to move
    assert rehome_blocks(view).size == 0


def test_replica_homes_anti_affine_in_degraded_view():
    part = partition_pytree(_params(), 16)
    view = _view(part)
    view.mark_failed(view.domains.devices_in("host", 0))
    rehome_blocks(view)
    rep = anti_affine_replica_homes(view)
    assert view.alive[rep].all()
    assert np.all(np.asarray(view.host_of(rep))
                  != np.asarray(view.host_of(view.homes)))


def test_parity_restripe_in_degraded_view():
    part = partition_pytree(_params(), 16)
    view = _view(part)
    view.mark_failed(view.domains.devices_in("host", 0))
    rehome_blocks(view)
    members = stripe_parity_groups(view, 2)   # 3 alive hosts → width ≤ 2
    hosts = np.asarray(view.host_of(view.homes))
    for row in members:
        ids = row[row >= 0]
        assert ids.size >= 2
        assert len(set(hosts[ids].tolist())) == ids.size
    homes = parity_group_homes(members, view)
    assert view.alive[homes].all()
    n_alive_hosts = view.n_alive_hosts
    for j, row in enumerate(members):
        ids = row[row >= 0]
        m_hosts = set(hosts[ids].tolist())
        if len(m_hosts) < n_alive_hosts:
            # a member-free host exists → parity must sit on one
            assert int(view.host_of(homes[j])) not in m_hosts
        else:
            # group as wide as the topology (the folded tail group):
            # fall back to a device holding no member
            assert int(homes[j]) not in set(view.homes[ids].tolist())


def test_rebalance_after_heal_levels_load():
    part = partition_pytree(_params(), 16)
    view = _view(part)
    view.mark_failed(view.domains.devices_in("host", 0))
    rehome_blocks(view)
    view.heal(view.domains.devices_in("host", 0))
    moved = rebalance_homes(view)
    assert moved.size > 0, "healed devices attracted no load"
    load = view.load()[view.alive_devices()]
    assert load.max() - load.min() <= 1


# ---------------------------------------------------------------------------
# FabricConfig validation + ragged parity groups
# ---------------------------------------------------------------------------

def test_fabric_config_rejects_degenerate_parity_group():
    with pytest.raises(ValueError):
        FabricConfig(parity_group=1)
    with pytest.raises(ValueError):
        FabricConfig(parity_group=0)


def test_ragged_last_parity_group_folds_and_recovers():
    # 17 blocks (16 of w + 1 of b), group_size 4 → 17 % 4 == 1: the lone
    # tail member must fold into the previous group, not form a 1-group
    params = _params()
    part = partition_pytree(params, 16)
    assert part.total_blocks % 4 == 1
    dm = FailureDomainMap(n_devices=8, devices_per_host=1)  # no width clamp < 4
    view = ClusterView(dm, block_device_homes(part, 8))
    codec = ParityCodec(part, view, group_size=4, use_pallas=False)
    sizes = [(row >= 0).sum() for row in codec.members]
    assert min(sizes) >= 2
    assert sum(sizes) == part.total_blocks
    assert (codec.group_of >= 0).all()
    # single erasure inside the widened tail group reconstructs bit-exactly
    codec.encode(3, params)
    tail = codec.members[-1]
    victim = int(tail[tail >= 0][-1])
    lost = np.zeros((part.total_blocks,), bool)
    lost[victim] = True
    rec_mask = codec.reconstructable(lost, ~lost, np.empty((0,), np.int32),
                                     step=3)
    assert rec_mask[victim]
    frames = codec.reconstruct(params, rec_mask, ~lost)
    want = pack_frames(params, part, codec.layout)
    np.testing.assert_array_equal(np.asarray(frames)[victim],
                                  np.asarray(want)[victim])


# ---------------------------------------------------------------------------
# Elastic fabric: invariants + the second-failure acceptance criterion
# ---------------------------------------------------------------------------

def test_elastic_failure_replans_and_keeps_invariants():
    params = _params()
    part = partition_pytree(params, 16)
    fab = _fabric(part)
    live = _noisy(params)
    ckpt = init_running_checkpoint(params, part)
    fab.maintain(1, live)
    lost, failed = fab.domain_failure("host", 0)
    rec, info = fab.on_failure(live, ckpt.values, lost, failed, step=1)
    assert info["placement"]["rehomed_blocks"] == int(lost.sum()) > 0
    assert fab.view.n_alive_hosts == 3
    _assert_elastic_invariants(fab)
    assert float(tree_sq_norm(rec, live)) < 1e-12


def test_elastic_subsequent_failures_never_hit_ckpt_tiers():
    """Acceptance: after a host loss with elastic=True, every later loss of
    a different host recovers from PEER_REPLICA or PARITY only."""
    params = _params()
    part = partition_pytree(params, 16)
    fab = _fabric(part)
    ckpt = init_running_checkpoint(params, part)
    live = _noisy(params)
    for step, host in ((1, 0), (2, 1), (3, 2)):
        fab.maintain(step, live, force=True)
        lost, failed = fab.domain_failure("host", host)
        assert lost.any()
        rec, info = fab.on_failure(live, ckpt.values, lost, failed,
                                   step=step)
        tc = info["tier_counts"]
        assert tc["RUNNING_CKPT"] == 0 and tc["DISK"] == 0, \
            f"event {step} (host {host}) fell through: {tc}"
        assert tc["PEER_REPLICA"] + tc["PARITY"] == int(lost.sum())
        assert float(tree_sq_norm(rec, live)) < 1e-12
        _assert_elastic_invariants(fab)


def test_inplace_fabric_falls_through_on_degraded_topology():
    """The contrast case: recover-in-place (elastic=False) leaves replicas
    pointing at dead devices, so a later failure in the other rack falls
    through to RUNNING_CKPT/DISK."""
    params = _params()
    part = partition_pytree(params, 16)
    fab = _fabric(part, elastic=False, parity=False)
    ckpt = init_running_checkpoint(params, part)
    live = _noisy(params)
    last = None
    for step, host in ((1, 0), (2, 1), (3, 2)):
        fab.maintain(step, live, force=True)
        lost, failed = fab.domain_failure("host", host)
        _, last = fab.on_failure(live, ckpt.values, lost, failed,
                                 step=step, persist_failure=True)
    # host 2 sits in rack 1; its replicas were seeded in rack 0 — both of
    # whose hosts are already dead — and were never re-seeded
    tc = last["tier_counts"]
    assert tc["PEER_REPLICA"] == 0
    assert tc["RUNNING_CKPT"] + tc["DISK"] > 0


def test_inplace_parity_cannot_use_long_dead_members():
    """Regression: parity availability must respect view liveness — a group
    member homed on a device dead since an *earlier* persisted event is
    physically gone and cannot serve as an XOR survivor, even though the
    simulation still holds its value."""
    params = _params()
    part = partition_pytree(params, 16)
    fab = _fabric(part, elastic=False)     # replicas + parity, in-place
    ckpt = init_running_checkpoint(params, part)
    live = _noisy(params)
    last = lost = None
    for step, host in ((1, 0), (2, 1), (3, 2)):
        fab.maintain(step, live, force=True)
        lost, failed = fab.domain_failure("host", host)
        _, last = fab.on_failure(live, ckpt.values, lost, failed,
                                 step=step, persist_failure=True)
    # by event 3, every parity group containing a host-2 member has lost a
    # second member (or its parity home) to the earlier host-0/1 deaths,
    # and every replica of a host-2 block sat in the dead rack 0: nothing
    # cheap survives
    tc = last["tier_counts"]
    assert tc["PEER_REPLICA"] == 0 and tc["PARITY"] == 0
    assert tc["RUNNING_CKPT"] + tc["DISK"] == int(lost.sum()) > 0


def test_healing_readmits_and_reseeds():
    params = _params()
    part = partition_pytree(params, 16)
    fab = _fabric(part)
    ckpt = init_running_checkpoint(params, part)
    live = _noisy(params)
    fab.maintain(1, live)
    lost, failed = fab.domain_failure("host", 0)
    fab.on_failure(live, ckpt.values, lost, failed, step=1)
    info = fab.heal_domain("host", 0, live, step=1)
    assert info["healed_devices"] == 2
    assert info["rebalanced_blocks"] > 0
    assert fab.view.n_alive_hosts == 4
    _assert_elastic_invariants(fab)
    # healed capacity is a real failure domain again: losing another host
    # still recovers everything from the re-seeded tiers
    fab.maintain(2, live, force=True)
    lost2, failed2 = fab.domain_failure("host", 1)
    _, info2 = fab.on_failure(live, ckpt.values, lost2, failed2, step=2)
    tc = info2["tier_counts"]
    assert tc["RUNNING_CKPT"] == 0 and tc["DISK"] == 0


# ---------------------------------------------------------------------------
# Maintenance programs: cached by the parity striping, not the view version
# ---------------------------------------------------------------------------

def _striping_params(seed):
    # the striping is a function of the shapes alone (block homes by
    # bytes); the values only give the sweeps something to encode
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(256, 6)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}


def _program_counts(fab):
    return (fab.stats["maintain_program_builds"],
            fab.stats["maintain_program_reuses"])


def _sweep_outputs(fab, live, z):
    """One call of the fabric's cached program, and of one built afresh
    from the codec's current striping, on the same values."""
    from repro.kernels.fused_maintain.ops import ArenaMaintainProgram
    codec = fab.parity
    fresh = ArenaMaintainProgram(fab.partition, fab.arena_layout,
                                 codec.layout, codec.group_of,
                                 codec.n_groups, use_pallas=False)
    return fab._arena_maintain_fn()(live, z), fresh(live, z)


# the XOR codec, and RS(k, 2), whose rows the fabric re-encodes from the
# sweep's snapshot arena beside the same cached program
CODECS = [pytest.param(0, id="arena"), pytest.param(2, id="arena_rs2")]


@pytest.mark.parametrize("rs_parity", CODECS)
def test_inplace_loss_and_heal_reuse_the_maintain_program(rs_parity):
    """Without ``elastic`` a loss and a heal move the view but never the
    striping: the sweep program is built once, every maintain after a
    view move reuses it, and each maintain's parity is bit-identical to a
    plain encode of the same values by the fabric's codec."""
    from repro.fabric.rs import RSCodec
    params = _striping_params(0)
    part = partition_pytree(params, 16)
    fab = _fabric(part, elastic=False, rs_parity=rs_parity)
    assert fab.arena_layout is not None
    view = ClusterView(fab.domains, fab.view.homes.copy())
    if rs_parity:
        ref = RSCodec(part, view, group_size=fab.cfg.parity_group,
                      n_parity=rs_parity, use_pallas=False)
    else:
        ref = ParityCodec(part, view, group_size=fab.cfg.parity_group,
                          use_pallas=False)
    ckpt = init_running_checkpoint(params, part)

    def maintain_and_check(step):
        live = _noisy(params, seed=step)
        fab.maintain(step, live, ckpt.values)
        ref.encode(step, live)
        np.testing.assert_array_equal(np.asarray(fab.parity.parity),
                                      np.asarray(ref.parity))
        return live

    for step in (1, 2, 3):
        live = maintain_and_check(step)
    assert _program_counts(fab) == (1, 0)   # no view move: nothing to reuse
    striping = fab.parity.striping
    lost, failed = fab.domain_failure("host", 0)
    fab.on_failure(live, ckpt.values, lost, failed, step=3,
                   persist_failure=True)
    assert not fab.view.alive[failed].any()
    live = maintain_and_check(4)
    fab.heal_domain("host", 0, live, step=4)
    live = maintain_and_check(5)
    lost, failed = fab.domain_failure("host", 2)
    fab.on_failure(live, ckpt.values, lost, failed, step=5,
                   persist_failure=True)
    maintain_and_check(6)
    maintain_and_check(7)
    assert fab.parity.striping == striping
    assert _program_counts(fab) == (1, 3)
    assert fab.stats["arena_maintains"] == 7
    assert fab.stats["rs_arena_encodes"] == (7 if rs_parity else 0)


@pytest.mark.parametrize("rs_parity", CODECS)
def test_elastic_restripe_builds_once_per_striping(rs_parity):
    """An elastic loss re-stripes and builds a program for the new
    striping. The heal rebalances onto the restored host, which is a
    striping of its own; from then on a loss and a heal of that host
    alternate between two stripings already built, so they build nothing,
    and the cached program's outputs are bit-identical to a program built
    afresh for the striping in force."""
    params = _striping_params(1)
    part = partition_pytree(params, 16)
    fab = _fabric(part, rs_parity=rs_parity)
    ckpt = init_running_checkpoint(params, part)
    live = _noisy(params, seed=1)
    z = fab._as_arena(ckpt.values)
    fab.maintain(1, live, ckpt.values)
    seen = [fab.parity.striping]
    counts = [_program_counts(fab)]

    def record():
        seen.append(fab.parity.striping)
        counts.append(_program_counts(fab))
        cached, fresh = _sweep_outputs(fab, live, z)
        for a, b in zip(jax.tree_util.tree_leaves(cached),
                        jax.tree_util.tree_leaves(fresh)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def cycle(step):
        lost, failed = fab.domain_failure("host", 0)
        fab.on_failure(live, ckpt.values, lost, failed, step=step)
        record()                     # the re-plan sweeps at once
        fab.heal_domain("host", 0, live, step=step + 1)
        fab.maintain(step + 1, live, ckpt.values, force=True)
        record()

    cycle(2)
    cycle(4)
    cycle(6)
    assert len(set(seen[:3])) == 3
    assert seen[3:] == seen[1:3] * 2
    builds = [b for b, _ in counts]
    assert builds == [1, 2, 3, 3, 3, 3, 3]
    assert counts[-1][1] >= 4
    _assert_elastic_invariants(fab)


# ---------------------------------------------------------------------------
# Trace-driven soak: classic runner + controller accounting
# ---------------------------------------------------------------------------

def test_run_with_trace_elastic_vs_inplace():
    from repro.models.classic import make_model
    from repro.training import run_clean, run_with_trace
    model = make_model("mlr", n=400, dim=48, n_classes=4, batch=150)
    clean = run_clean(model, 70)["losses"]
    pol = CheckpointPolicy(fraction=0.25, full_interval=8,
                           strategy=SelectionStrategy.ROUND_ROBIN,
                           recovery=RecoveryMode.PARTIAL,
                           block_rows=model.block_rows)
    trace = [FailureEvent(step=12, kind="host", index=0),
             FailureEvent(step=28, kind="host", index=1),
             FailureEvent(step=44, kind="host", index=2)]
    kw = dict(max_iters=70, seed=0, clean_losses=clean, trace=trace)
    elastic = run_with_trace(model, pol, fabric=FabricConfig(
        n_devices=8, devices_per_host=2, elastic=True, use_pallas=False),
        **kw)
    inplace = run_with_trace(model, pol, fabric=FabricConfig(
        n_devices=8, devices_per_host=2, elastic=False, parity=False,
        use_pallas=False), **kw)
    assert len(elastic["events"]) == 3 == len(inplace["events"])
    for ev in elastic["events"]:
        assert ev["tier_counts"]["RUNNING_CKPT"] == 0
        assert ev["tier_counts"]["DISK"] == 0
    last = inplace["events"][-1]["tier_counts"]
    assert last["RUNNING_CKPT"] + last["DISK"] > 0
    assert all(np.isfinite(elastic["losses"]))
    # per-event accounting is surfaced through the controller too
    assert len(elastic["controller_stats"]["events"]) == 3
    assert elastic["events"][-1]["applied_sq"] <= inplace["events"][-1][
        "applied_sq"] + 1e-9


def test_run_with_trace_healing_restores_capacity():
    from repro.models.classic import make_model
    from repro.training import run_with_trace
    model = make_model("mlr", n=400, dim=48, n_classes=4, batch=150)
    pol = CheckpointPolicy(fraction=0.25, full_interval=8,
                           strategy=SelectionStrategy.ROUND_ROBIN,
                           recovery=RecoveryMode.PARTIAL,
                           block_rows=model.block_rows)
    trace = [FailureEvent(step=10, kind="host", index=0),
             FailureEvent(step=30, kind="host", index=1)]
    r = run_with_trace(model, pol, fabric=FabricConfig(
        n_devices=8, devices_per_host=2, elastic=True, use_pallas=False),
        max_iters=45, seed=0, trace=trace, heal_after=10)
    assert len(r["events"]) == 2
    assert r["controller_stats"]["recoveries"] == 2
    assert all(np.isfinite(r["losses"]))


def test_train_loop_mtbf_soak_mode():
    """SPMD trainer path: mtbf-driven multi-event soak with healing."""
    from repro.configs import get_config
    from repro.data.pipeline import ShardedLMDataset
    from repro.sharding import single_device_ctx
    from repro.training import TrainLoop, TrainLoopConfig
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    pol = CheckpointPolicy.scar(fraction=0.25, interval=3)
    loop_cfg = TrainLoopConfig(
        policy=pol, mtbf={"host": 2.0}, heal_after=2, seed=3,
        fabric=FabricConfig(n_devices=8, devices_per_host=2, elastic=True,
                            use_pallas=False))
    loop = TrainLoop(cfg, ctx, loop_cfg=loop_cfg)
    state = loop.init_state()
    ds = ShardedLMDataset(cfg, batch=2, seq=64, ctx=ctx)
    state = loop.run(state, iter(ds), 8)
    events = loop.controller.stats["events"]
    assert events, "mtbf of 2 steps should fire within 8 steps"
    for ev in events:
        assert ev["tier_counts"]["RUNNING_CKPT"] == 0
        assert ev["tier_counts"]["DISK"] == 0
    assert all(np.isfinite(m["loss"]) for m in loop.metrics)


def test_train_loop_config_validates_mtbf():
    from repro.training import TrainLoopConfig
    with pytest.raises(ValueError):
        TrainLoopConfig(mtbf={"host": 100.0})   # fabric missing


# ---------------------------------------------------------------------------
# Fabric-aware persistent store
# ---------------------------------------------------------------------------

def test_store_domain_keyed_layout_and_partial_read(tmp_path):
    params = _params()
    part = partition_pytree(params, 16)
    dm = _dm()
    homes = block_device_homes(part, dm.n_devices)
    store = ShardedCheckpointStore(str(tmp_path))
    store.init(params, part, homes=homes, domains=dm)
    hosts = np.asarray(dm.host_of(homes))
    # packed layout: one append-mode shard per home host, and every block
    # indexed into its own host's shard
    for h in np.unique(hosts):
        host_dir = os.path.join(str(tmp_path), f"host_{h:04d}")
        shards = [f for f in os.listdir(host_dir)
                  if f.startswith("blocks.") and f.endswith(".shard")]
        assert shards, f"host {h} has no packed shard"
    for gid in range(part.total_blocks):
        assert os.path.dirname(store._shard_path(gid)).endswith(
            f"host_{hosts[gid]:04d}"), f"block {gid} not keyed by its domain"
    assert store.saved_iters().shape == (part.total_blocks,)
    # partial read: only the masked blocks come back
    mask = np.zeros((part.total_blocks,), bool)
    mask[hosts == 2] = True
    got = store.read_blocks(mask)
    full = store.read_all()
    wleaf = next(l for l in part.leaves if l.name.endswith("'w']"))
    masked_w = [b for b in range(wleaf.n_blocks) if mask[wleaf.offset + b]]
    assert masked_w, "expected some of w's blocks homed on host 2"
    for b in masked_w:
        np.testing.assert_array_equal(
            np.asarray(got["w"][b * 16:(b + 1) * 16]),
            np.asarray(full["w"][b * 16:(b + 1) * 16]))
    # read_surviving: blocks of a failed host are absent from the mask
    vals, present = store.read_surviving([1])
    np.testing.assert_array_equal(present, hosts != 1)


def test_store_parity_mirror_offline_reconstruction(tmp_path):
    """Host-local shard dies; its blocks reconstruct offline from the
    surviving shards + the disk parity mirror, bit-exactly."""
    params = _params()
    part = partition_pytree(params, 16)
    dm = _dm()
    homes = block_device_homes(part, dm.n_devices)
    view = ClusterView(dm, homes)
    codec = ParityCodec(part, view, group_size=3, use_pallas=False)
    codec.encode(0, params)
    store = ShardedCheckpointStore(str(tmp_path))
    store.init(params, part, homes=homes, domains=dm)
    nbytes = store.write_parity(0, np.asarray(codec.parity),
                                codec.parity_homes, domains=dm,
                                members=codec.members)
    assert nbytes > 0
    parity, meta = store.read_parity()
    assert meta["step"] == 0 and parity.shape[0] == codec.n_groups
    # the whole of host 1's local shard is gone; reconstruction below uses
    # ONLY what is on disk (parity buffers + PARITY.json membership) — a
    # restarted process has no live codec to ask
    shutil.rmtree(os.path.join(str(tmp_path), "host_0001"))
    vals, present = store.read_surviving([1])
    frames = np.asarray(pack_frames(vals, part, codec.layout))
    want = np.asarray(pack_frames(params, part, codec.layout))
    checked = 0
    for j, ids in enumerate(meta["members"]):
        ids = np.asarray(ids, np.int32)
        missing = ids[~present[ids]]
        if missing.size != 1:
            continue
        acc = parity[j].copy()
        for b in ids[present[ids]]:
            acc ^= frames[b]
        np.testing.assert_array_equal(acc, want[int(missing[0])])
        checked += 1
    assert checked > 0, "no singly-erased group to reconstruct"
