"""Arena-resident training state: equivalence + recovery + unit tests.

The tentpole invariant of the arena-native refactor: with the flat arena
as the canonical live representation (``ArenaTrainState``), training is
**bit-identical** to the PyTree path — same losses, same running
checkpoint, same final params — while the per-step maintenance runs
pack-free (the sweep reads the live arena directly) and the partial save
sources straight from the training state.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.arena import (build_arena_layout, pack_arena, unpack_arena,
                              as_live_arena)
from repro.core.blocks import partition_pytree
from repro.core.controller import FTController
from repro.core.policy import CheckpointPolicy
from repro.data.pipeline import ShardedLMDataset
from repro.fabric import FabricConfig
from repro.optim.optimizers import adamw, arena_apply, sgd
from repro.sharding import single_device_ctx
from repro.training import (ArenaTrainState, TrainLoop, TrainLoopConfig,
                            TrainState, run_with_failure)


def _tree_equal(a, b) -> bool:
    return all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _lm_loop(arena_state: bool, **loop_kw):
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    pol = loop_kw.pop("policy", CheckpointPolicy.scar(fraction=0.25,
                                                      interval=2))
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(
        policy=pol, fabric=FabricConfig(), arena_state=arena_state,
        **loop_kw))
    state = loop.init_state()
    ds = ShardedLMDataset(cfg, batch=2, seq=32, ctx=ctx)
    return loop, state, ds


# ---------------------------------------------------------------------------
# end-to-end equivalence (the acceptance-criterion test)
# ---------------------------------------------------------------------------

def test_arena_and_pytree_paths_bit_identical():
    """Quick config, both paths: bit-identical losses AND bit-identical
    saved running checkpoints (values + saved_iter) AND final params."""
    la, sa, dsa = _lm_loop(True)
    lt, st, dst = _lm_loop(False)
    assert isinstance(sa, ArenaTrainState)
    assert isinstance(st, TrainState)
    sa = la.run(sa, iter(dsa), 6)
    st = lt.run(st, iter(dst), 6)
    assert [m["loss"] for m in la.metrics] == [m["loss"] for m in lt.metrics]
    # the saved checkpoint is canonical in arena form in both modes
    assert (np.asarray(la.controller._ckpt_arena)
            == np.asarray(lt.controller._ckpt_arena)).all()
    assert (np.asarray(la.controller.ckpt.saved_iter)
            == np.asarray(lt.controller.ckpt.saved_iter)).all()
    assert _tree_equal(sa.params, st.params)
    # the arena loop never packed on the hot path
    fab = la.controller.fabric
    assert fab.stats["arena_resident_maintains"] \
        == fab.stats["arena_maintains"]
    assert lt.controller.fabric.stats["arena_resident_maintains"] == 0


def test_arena_failure_recovers_via_peer_replica():
    """Failure injection on the arena path: every lost block recovers from
    the PEER_REPLICA tier (live values — zero perturbation) and training
    continues finite, still arena-resident."""
    loop, state, ds = _lm_loop(True)
    it = iter(ds)
    state = loop.run(state, it, 3)
    state, info = loop.inject_failure(state, 0.5)
    assert isinstance(state, ArenaTrainState)
    tiers = info["tier_counts"]
    assert tiers["PEER_REPLICA"] == info["lost_blocks"] > 0
    assert tiers["RUNNING_CKPT"] == tiers["DISK"] == tiers["PARITY"] == 0
    assert info["applied_sq"] <= 1e-9   # replica holds this step's values
    state = loop.run(state, it, 3)
    assert all(np.isfinite(m["loss"]) for m in loop.metrics)


def test_classic_runner_arena_matches_tree():
    from repro.models.classic import make_model
    model = make_model("mlr", n=200, dim=32, n_classes=4, batch=100)
    pol = CheckpointPolicy.scar(fraction=0.25, interval=8)
    kw = dict(fail_iter=15, fail_fraction=0.5, max_iters=40, seed=3)
    ra = run_with_failure(model, pol, fabric=FabricConfig(),
                          arena_state=True, **kw)
    rt = run_with_failure(model, pol, fabric=FabricConfig(),
                          arena_state=False, **kw)
    assert ra["arena_state"] and not rt["arena_state"]
    assert ra["losses"] == rt["losses"]
    # runner mode: every maintain is an arena sweep fed by the runner's
    # own pack (own_live — adopted as the replica, not copied), and the
    # accounted bytes match the tree interface's internal-pack total
    assert ra["fabric_stats"]["arena_maintains"] == 40
    assert ra["fabric_stats"]["live_packs"] == 40
    assert ra["fabric_stats"]["arena_resident_maintains"] == 0
    assert (ra["fabric_stats"]["maintain_bytes_moved"]
            == rt["fabric_stats"]["maintain_bytes_moved"])
    # sparse tiers + shorter save interval: the post-save forced maintain
    # must also adopt the runner's pack (own_live threads through
    # maybe_checkpoint), never re-copy it or book it as resident. Byte
    # totals aren't identical here — an off-interval arena-input step
    # runs the full fused sweep where the tree interface runs only the
    # due per-component pass (documented, strictly fresher) — but the
    # arena path may never book MORE than the tree path.
    sparse = dict(replicate_interval=4, parity_interval=4)
    sa = run_with_failure(model, pol, fabric=FabricConfig(**sparse),
                          arena_state=True, **kw)
    st = run_with_failure(model, pol, fabric=FabricConfig(**sparse),
                          arena_state=False, **kw)
    assert sa["losses"] == st["losses"]
    assert sa["fabric_stats"]["arena_resident_maintains"] == 0
    assert (sa["fabric_stats"]["maintain_bytes_moved"]
            <= st["fabric_stats"]["maintain_bytes_moved"])


# ---------------------------------------------------------------------------
# unit: flat optimizer apply
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt_name", ["sgd", "adamw"])
def test_arena_apply_matches_tree_update(opt_name):
    """Flat elementwise apply over the word arena == per-leaf tree apply,
    bit-exactly, including the quantized-dtype round trip (grads and
    moments live in the f32 value domain); pads stay zero (I4)."""
    from repro.core.arena import pack_values
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(70, 9)), jnp.float32),
              "h": jnp.asarray(rng.normal(size=(33, 5)), jnp.bfloat16),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float16)}
    part = partition_pytree(params, 16)
    layout = build_arena_layout(part)
    assert not layout.uniform_f32 and layout.total_values > layout.total_words
    opt = sgd(0.1) if opt_name == "sgd" else adamw(1e-2)
    arena = pack_arena(params, layout)
    st_tree = opt.init(params)
    st_flat = opt.init(jnp.zeros((layout.total_values,), jnp.float32))
    tree = params
    for i in range(3):
        grads = jax.tree_util.tree_map(
            lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), tree)
        g_values = pack_values(grads, layout)
        tree, st_tree = opt.update(grads, st_tree, tree)
        arena, st_flat = arena_apply(opt, g_values, st_flat, arena, layout)
        assert (np.asarray(pack_arena(tree, layout))
                == np.asarray(arena)).all(), f"step {i} diverged"
    # word-domain pads still zero after three updates
    pad_mask = np.ones((layout.total_words,), bool)
    for li, leaf in enumerate(part.leaves):
        off, seg, pay = (layout.leaf_offset[li], layout.seg_words[li],
                         layout.payload_words[li])
        for b in range(leaf.n_blocks):
            pad_mask[off + b * seg: off + b * seg + pay] = False
    # value-domain payload positions: where a tree of ones decodes to 1
    ones = jax.tree_util.tree_map(jnp.ones_like, params)
    vpad_mask = np.asarray(pack_values(ones, layout)) == 0.0
    assert (np.asarray(arena)[pad_mask] == 0.0).all()
    if opt_name == "adamw":
        # moments are value-domain mirrors; their pads stay zero too
        assert (np.asarray(st_flat.mu)[vpad_mask] == 0.0).all()


def test_arena_train_state_lazy_params_view():
    params = {"w": jnp.arange(48, dtype=jnp.float32).reshape(12, 4)}
    layout = build_arena_layout(partition_pytree(params, 8))
    state = ArenaTrainState.create(pack_arena(params, layout), sgd(0.1),
                                   layout)
    view = state.params
    assert _tree_equal(view, params)
    assert state.params is view          # cached, not re-decoded
    assert (np.asarray(state.opt_state.step) == 0).all()


def test_as_live_arena_detection():
    params = {"w": jnp.zeros((12, 4), jnp.float32)}
    layout = build_arena_layout(partition_pytree(params, 8))
    arena = pack_arena(params, layout)
    assert as_live_arena(arena, layout) is arena
    assert as_live_arena(params, layout) is None
    assert as_live_arena(arena, None) is None
    # wrong length / dtype are not arenas
    assert as_live_arena(arena[:-1], layout) is None
    assert as_live_arena(arena.astype(jnp.bfloat16), layout) is None


# ---------------------------------------------------------------------------
# unit: controller + fabric accept the live arena
# ---------------------------------------------------------------------------

def _small_controller(**kw):
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(size=(96, 6)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    pol = kw.pop("policy", CheckpointPolicy.scar(fraction=0.25, interval=2))
    ctl = FTController(params, pol, fabric=FabricConfig(), **kw)
    assert ctl.arena_ready
    return params, ctl


def test_controller_maintain_and_save_accept_live_arena():
    params, ctl_a = _small_controller()
    _, ctl_t = _small_controller()
    drift = jax.tree_util.tree_map(lambda x: x + 0.25, params)
    live = ctl_a.pack_live(drift)
    ctl_a.maintain(2, live)
    ctl_t.maintain(2, drift)
    assert (np.asarray(ctl_a.fabric.last_scores)
            == np.asarray(ctl_t.fabric.last_scores)).all()
    assert (np.asarray(ctl_a.fabric.parity.parity)
            == np.asarray(ctl_t.fabric.parity.parity)).all()
    ma = ctl_a.maybe_checkpoint(2, live)
    mt = ctl_t.maybe_checkpoint(2, drift)
    assert ma and mt
    assert (np.asarray(ctl_a._ckpt_arena)
            == np.asarray(ctl_t._ckpt_arena)).all()
    assert ctl_a.fabric.stats["arena_resident_maintains"] == 1
    assert ctl_t.fabric.stats["arena_resident_maintains"] == 0


def test_controller_full_save_from_live_arena():
    from repro.core.policy import RecoveryMode, SelectionStrategy
    pol = CheckpointPolicy(fraction=1.0, full_interval=2,
                           strategy=SelectionStrategy.ROUND_ROBIN,
                           recovery=RecoveryMode.PARTIAL, block_rows=16)
    params, ctl = _small_controller(policy=pol)
    drift = jax.tree_util.tree_map(lambda x: x + 1.0, params)
    live = ctl.pack_live(drift)
    ctl.maintain(2, live)
    assert ctl.maybe_checkpoint(2, live)
    assert _tree_equal(ctl.ckpt.values, drift)
    assert (np.asarray(ctl.ckpt.saved_iter) == 2).all()


def test_controller_on_failure_round_trips_arena():
    params, ctl = _small_controller()
    drift = jax.tree_util.tree_map(lambda x: x + 0.5, params)
    live = ctl.pack_live(drift)
    ctl.maintain(1, live)
    lost = ctl.sample_failure(0.5)
    recovered, info = ctl.on_failure(live, lost, step=1)
    assert as_live_arena(recovered, ctl.arena_layout) is not None
    # replica tier recovery restores the live values exactly
    assert (np.asarray(recovered) == np.asarray(live)).all()
    assert info["tier_counts"]["PEER_REPLICA"] == info["lost_blocks"]


def test_fabric_resident_maintain_bytes_drop():
    """The no-pack accounting: a live-arena maintain moves exactly the
    live tree's bytes fewer than the pack-path maintain, and the staging
    footprint stays the sweep's compact outputs."""
    params, ctl = _small_controller()
    fab = ctl.fabric
    t = fab._traffic_model()
    assert t["arena_resident"] == t["arena"] - t["model"]
    live = ctl.pack_live(params)
    fab.maintain(1, live)
    assert fab.stats["maintain_bytes_moved"] == t["arena_resident"]
    assert fab.live_arena_mode
    assert fab.redundancy_nbytes()["parity_staging"] == t["staging_arena"]


def test_microbatched_arena_step_matches_single():
    """cfg.microbatch > 1 gives the same loss/update on the arena path."""
    from repro.data import lm_batch
    from repro.models import get_model
    from repro.training.step import make_arena_train_step
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    cfg_mb = dataclasses.replace(cfg, microbatch=2)
    ops = get_model(cfg)
    params = ops.init_params(jax.random.PRNGKey(0), cfg)
    layout = build_arena_layout(partition_pytree(params, 128))
    batch = lm_batch(jax.random.PRNGKey(1), cfg, 4, 32)
    opt = sgd(0.1)
    s0 = ArenaTrainState.create(pack_arena(params, layout), opt, layout)
    s1, l1 = make_arena_train_step(ops, cfg, ctx, opt, layout)(s0, batch)
    s2, l2 = make_arena_train_step(ops, cfg_mb, ctx, opt, layout)(s0, batch)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    np.testing.assert_allclose(np.asarray(s1.arena), np.asarray(s2.arena),
                               rtol=1e-4, atol=1e-5)
