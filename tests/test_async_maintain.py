"""Async maintenance pipeline: double-buffered epoch/publish protocol.

The tentpole invariants of ``FabricConfig(async_maintain=True)``:

- **bit-identity** — every-step async maintenance produces losses,
  running checkpoints, and recovered params bit-identical to the
  synchronous path (the snapshot holds exactly the live values; only
  *when* the sweep's device work completes changes);
- **published-epoch recovery** — a failure injected while a sweep is in
  flight settles the pending epoch first and recovers from the last
  *published* slot, never a torn one; a failure a step past the
  published epoch recovers the stale-but-bounded replica values and the
  staleness is accounted explicitly (recovered_epoch/staleness in the
  recovery stats and the perturbation ledger);
- **deferred fence ordering** — the fence moves off the per-step hot
  path and is taken only at consume points (``maybe_checkpoint``,
  failure/elastic replan, ``block_until_maintained``, end of run);
- **overlap** — the Chrome trace's ``scar/async_sweep`` records cover
  [dispatch, fence] and genuinely overlap the next ``scar/step/train``
  span.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint_io.store import ShardedCheckpointStore
from repro.configs import get_config
from repro.core.blocks import partition_pytree
from repro.core.controller import FTController
from repro.core.policy import (CheckpointPolicy, RecoveryMode,
                               SelectionStrategy)
from repro.data.pipeline import ShardedLMDataset
from repro.fabric import CheckpointFabric, FabricConfig
from repro.models.classic import make_model
from repro.sharding import single_device_ctx
from repro.telemetry.recorder import Recorder
from repro.training import TrainLoop, TrainLoopConfig, run_with_failure


def _keys(seed: int):
    base = jax.random.PRNGKey(seed)

    def key(i: int):
        return jax.random.fold_in(base, i)
    return key


def _tree_equal(a, b) -> bool:
    return all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _controller(model, async_maintain: bool, elastic: bool = False,
                recorder=None, seed: int = 0):
    p = model.init(jax.random.PRNGKey(1))
    pol = CheckpointPolicy(fraction=0.25, full_interval=8,
                           strategy=SelectionStrategy.PRIORITY,
                           recovery=RecoveryMode.PARTIAL,
                           block_rows=model.block_rows)
    ctl = FTController(p, pol, norm_aux=model.norm_aux,
                       rng=jax.random.PRNGKey(seed + 13),
                       colocate=model.colocate,
                       fabric=FabricConfig(n_devices=8, use_pallas=False,
                                           async_maintain=async_maintain,
                                           elastic=elastic),
                       recorder=recorder)
    assert ctl.arena_ready
    return p, ctl


# ---------------------------------------------------------------------------
# config gate + traffic model
# ---------------------------------------------------------------------------

def _async_part(dtype=np.float32):
    # np arrays: jnp would silently downcast f64 -> f32 without x64 mode
    return partition_pytree({"w": np.zeros((32, 4), dtype)}, 8)


def test_async_config_requires_fused_arena():
    """The pipelined sweep exists only for the arena layout: a fabric
    with a tier off builds none and refuses ``async_maintain`` at
    construction; the default configuration takes it."""
    for kw in (dict(replicate=False), dict(parity=False)):
        with pytest.raises(ValueError, match="async_maintain"):
            CheckpointFabric(_async_part(),
                             FabricConfig(async_maintain=True, **kw))
    fab = CheckpointFabric(_async_part(), FabricConfig(async_maintain=True))
    assert fab.arena_layout is not None


def test_async_config_requires_word_packable_dtypes():
    """Both tiers on, but a leaf dtype the arena cannot pack: no layout,
    so ``async_maintain`` is refused too."""
    with pytest.raises(ValueError, match="async_maintain"):
        CheckpointFabric(_async_part(np.float64),
                         FabricConfig(async_maintain=True))
    assert CheckpointFabric(_async_part(np.float64),
                            FabricConfig()).arena_layout is None


def test_async_traffic_is_resident_plus_snapshot():
    """arena_async = resident sweep + one extra arena read/write pair net
    of the adopted copy: symmetric around the resident cost with
    arena_owned (async - resident == resident - owned == arena bytes)."""
    model = make_model("mf", m=60, n=80, rank=3)
    _, ctl = _controller(model, True)
    t = ctl.fabric._traffic_model()
    assert t["arena_async"] - t["arena_resident"] \
        == t["arena_resident"] - t["arena_owned"] > 0


# ---------------------------------------------------------------------------
# bit-identity (classic path, every-step saves — the consume-heavy case)
# ---------------------------------------------------------------------------

def test_async_classic_every_step_bit_identical():
    """scar policy (partial save every iteration): even with a consume
    point every step, async losses and recovery match sync exactly."""
    model = make_model("mf", m=80, n=120, rank=4)
    pol = CheckpointPolicy.scar(fraction=0.25, interval=4)
    pol = CheckpointPolicy(fraction=pol.fraction,
                           full_interval=pol.full_interval,
                           strategy=pol.strategy, recovery=pol.recovery,
                           block_rows=model.block_rows)
    kw = dict(fail_iter=10, fail_fraction=0.4, max_iters=20, seed=0,
              fail_domain="host")
    sync = run_with_failure(model, pol, fabric=FabricConfig(
        n_devices=8, use_pallas=False), **kw)
    asy = run_with_failure(model, pol, fabric=FabricConfig(
        n_devices=8, use_pallas=False, async_maintain=True), **kw)
    assert sync["losses"] == asy["losses"]
    assert asy["fabric_stats"]["async_maintains"] == 20
    assert asy["fabric_stats"]["fence_count"] >= 1
    # same tiers served the recovery, priced against a fresh epoch
    assert asy["recovery"]["tier_counts"] == sync["recovery"]["tier_counts"]
    assert asy["recovery"]["recovered_epoch"] == 10
    assert asy["recovery"]["staleness"] == 0


# ---------------------------------------------------------------------------
# published-epoch recovery
# ---------------------------------------------------------------------------

def test_mid_sweep_failure_recovers_from_published_epoch():
    """Failure injected while the sweep is still in flight: the pending
    epoch settles (never a torn slot) and every lost block restores
    bit-exactly from the published replica."""
    model = make_model("mf", m=60, n=80, rank=3)
    key = _keys(0)
    p, ctl = _controller(model, True)
    fab = ctl.fabric
    for i in range(1, 4):
        p = model.step(p, key(i), i)
        live = ctl.pack_live(p, account=True)
        ctl.maintain(i, live, own_live=True)
    # epoch 3 is dispatched but not settled — mid-sweep by construction
    assert fab.has_pending_maintenance
    assert fab.published_epoch == 3
    lost = ctl.sample_failure(0.5)
    p2, info = ctl.on_failure(p, lost, step=3)
    assert not fab.has_pending_maintenance   # settled at the consume point
    assert info["recovered_epoch"] == 3 and info["staleness"] == 0
    assert info["tier_counts"]["PEER_REPLICA"] == int(np.asarray(lost).sum())
    assert float(info["applied_sq"]) == 0.0
    assert _tree_equal(p2, p)                # bit-exact, zero perturbation


def test_stale_published_epoch_priced_explicitly():
    """Failure one step past the published epoch: the replica tier still
    serves (bounded staleness), and recovered_epoch/staleness land in the
    recovery stats AND the perturbation ledger entry."""
    model = make_model("mf", m=60, n=80, rank=3)
    key = _keys(0)
    rec = Recorder()
    p, ctl = _controller(model, True, recorder=rec)
    fab = ctl.fabric
    for i in range(1, 4):
        p = model.step(p, key(i), i)
        live = ctl.pack_live(p, account=True)
        ctl.maintain(i, live, own_live=True)
    # one more update WITHOUT a maintain: live is at step 4, published at 3
    p = model.step(p, key(4), 4)
    lost = ctl.sample_failure(0.5)
    p2, info = ctl.on_failure(p, lost, step=4)
    assert info["recovered_epoch"] == 3 and info["staleness"] == 1
    # the stale replica served — the sync planner would have fallen back
    # to the running checkpoint here (replicas not fresh at step 4)
    assert info["tier_counts"]["PEER_REPLICA"] == int(np.asarray(lost).sum())
    # stale-by-one values are a real (bounded) perturbation, not zero
    assert float(info["applied_sq"]) > 0.0
    entry = rec.ledger.entries[-1]
    assert entry.extra["recovered_epoch"] == 3
    assert entry.extra["staleness"] == 1


# ---------------------------------------------------------------------------
# deferred fence ordering
# ---------------------------------------------------------------------------

def test_deferred_fence_ordering_under_checkpoint_and_replan():
    model = make_model("mf", m=60, n=80, rank=3)
    key = _keys(0)
    p, ctl = _controller(model, True, elastic=True)
    fab = ctl.fabric
    p = model.step(p, key(1), 1)
    live = ctl.pack_live(p, account=True)
    ctl.maintain(1, live, own_live=True)
    assert fab.has_pending_maintenance       # dispatch left the fence open
    # consume point 1: a checkpoint settles before sourcing the save
    ctl.checkpoint_now(1, live)
    assert not fab.has_pending_maintenance
    p = model.step(p, key(2), 2)
    live = ctl.pack_live(p, account=True)
    ctl.maintain(2, live, own_live=True)
    assert fab.has_pending_maintenance
    # consume point 2: elastic replan fences, recovers, re-publishes
    lost, failed = ctl.sample_domain_failure("host")
    p2, info = ctl.on_failure(p, lost, failed_devices=failed, step=2)
    assert not fab.has_pending_maintenance
    assert info["placement"]["rehomed_blocks"] >= 0
    assert fab.published_epoch == 2          # the replan's sweep published
    p2 = model.step(p2, key(3), 3)
    live = ctl.pack_live(p2, account=True)
    ctl.maintain(3, live, own_live=True)
    assert fab.has_pending_maintenance
    # consume point 3: the explicit deferred fence
    fab.block_until_maintained()
    assert not fab.has_pending_maintenance
    assert fab.stats["fence_count"] == 3


# ---------------------------------------------------------------------------
# LM loop: bit-identity + span overlap (the acceptance-criterion test)
# ---------------------------------------------------------------------------

def _lm_loop(async_maintain: bool):
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    # every-step maintenance, partial save every 4 steps (fraction ×
    # full_interval) — maintain-only steps are where the overlap lives
    pol = CheckpointPolicy(fraction=0.25, full_interval=16,
                           strategy=SelectionStrategy.PRIORITY,
                           recovery=RecoveryMode.PARTIAL)
    rec = Recorder()
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=pol, fabric=FabricConfig(async_maintain=async_maintain),
        arena_state=True, recorder=rec))
    state = loop.init_state()
    ds = ShardedLMDataset(cfg, batch=2, seq=32, ctx=ctx)
    return loop, state, ds, rec


def test_async_lm_bit_identical_and_spans_overlap():
    ls, ss, dss, _ = _lm_loop(False)
    la, sa, dsa, rec = _lm_loop(True)
    ss = ls.run(ss, iter(dss), 10)
    sa = la.run(sa, iter(dsa), 10)
    # bit-identical losses, checkpoint arena, saved_iter, final params
    assert [m["loss"] for m in ls.metrics] == [m["loss"] for m in la.metrics]
    assert (np.asarray(ls.controller._ckpt_arena)
            == np.asarray(la.controller._ckpt_arena)).all()
    assert (np.asarray(ls.controller.ckpt.saved_iter)
            == np.asarray(la.controller.ckpt.saved_iter)).all()
    assert (np.asarray(ss.arena) == np.asarray(sa.arena)).all()
    fab = la.controller.fabric
    assert fab.stats["async_maintains"] == 10
    assert not fab.has_pending_maintenance   # end-of-run fence ran
    # the Chrome trace shows async sweeps genuinely overlapping
    # train-step spans — the deferred [dispatch, fence] intervals
    trains = rec.tracer.intervals("scar/step/train")
    maints = rec.tracer.intervals("scar/async_sweep")
    assert len(maints) == 10
    overlapping = sum(
        any(m0 < t1 and t0 < m1 for (t0, t1) in trains)
        for (m0, m1) in maints)
    assert overlapping >= 1
    deferred = [s for s in rec.tracer.spans
                if s.name == "scar/async_sweep"]
    assert len(deferred) == 10
    assert all(s.args["mode"] == "arena_async" for s in deferred)
    # the host side of each maintain call is a span of its own
    assert len(rec.tracer.intervals("scar/maintain")) == 10
    # phase split + overlap gauge are wired through overhead_summary
    out = la.overhead_summary()
    assert set(out["phases"]) == {"sweep", "save", "fence"}
    assert out["phases"]["fence"]["count"] >= 1
    assert 0.0 < out["overlap_efficiency"] <= 1.0
    assert rec.gauges["fabric/overlap_efficiency"].value \
        == out["overlap_efficiency"]
    # sync mode reports zero overlap (nothing is hidden)
    assert ls.overhead_summary()["overlap_efficiency"] == 0.0


# ---------------------------------------------------------------------------
# store flush error context (satellite)
# ---------------------------------------------------------------------------

def test_store_flush_chains_failed_job_context(tmp_path):
    params = {"w": jnp.arange(24.0, dtype=jnp.float32).reshape(8, 3)}
    part = partition_pytree(params, block_rows=4)
    rec = Recorder()
    store = ShardedCheckpointStore(str(tmp_path))
    store.attach_recorder(rec)
    store.init(params, part)

    def boom(jobs, step):
        raise OSError("disk full")

    store._do_write = boom
    mask = np.ones((part.total_blocks,), bool)
    store.write_blocks(mask, params, step=7, background=True)
    with pytest.raises(RuntimeError) as ei:
        store.flush()
    msg = str(ei.value)
    assert "step 7" in msg and "segment" in msg and "shard" in msg
    # chain: flush context -> retry-budget RuntimeError -> original OSError
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert "attempts" in str(ei.value.__cause__)
    assert isinstance(ei.value.__cause__.__cause__, OSError)
    ev = [e for e in rec.events if e["kind"] == "store_write_failed"]
    assert len(ev) == 1
    retried = [e for e in rec.events if e["kind"] == "store_write_retried"]
    assert len(retried) == store._retry_limit
    assert ev[0]["step"] == 7 and "disk full" in ev[0]["error"]
    assert ev[0]["segment"] is not None and ev[0]["path"] is not None
    # the error is one-shot: a second flush succeeds
    store.flush()
