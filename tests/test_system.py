"""End-to-end behaviour tests for the SCAR system.

The headline behaviours of the paper, verified end-to-end on CPU:
1. partial recovery strictly shrinks the recovery perturbation,
2. the SCAR-configured trainer survives failures and keeps converging,
3. the full controller lifecycle (checkpoint → failure → recovery →
   persistent store) is consistent.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint_io import ShardedCheckpointStore
from repro.configs import get_config
from repro.core.controller import FTController
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.data import lm_batch
from repro.data.pipeline import ShardedLMDataset
from repro.models.classic import make_model
from repro.sharding import single_device_ctx
from repro.training import TrainLoop, TrainLoopConfig, run_with_failure
from repro.training.serve import Server


def test_partial_beats_full_recovery_on_mlr():
    """Paper §5.3: partial recovery incurs lower iteration cost."""
    model = make_model("mlr", n=600, dim=64, n_classes=5, batch=200)
    kw = dict(fail_iter=25, fail_fraction=0.5, max_iters=150, seed=3)
    partial = run_with_failure(
        model, CheckpointPolicy(fraction=1.0, full_interval=8,
                                strategy=SelectionStrategy.ROUND_ROBIN,
                                recovery=RecoveryMode.PARTIAL,
                                block_rows=model.block_rows), **kw)
    full = run_with_failure(model, CheckpointPolicy.traditional(8), **kw)
    assert partial["recovery"]["applied_sq"] <= full["recovery"]["applied_sq"]
    assert partial["iteration_cost"] <= full["iteration_cost"]


def test_trainer_survives_failures_and_converges():
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    pol = CheckpointPolicy.scar(fraction=0.25, interval=4)
    loop = TrainLoop(cfg, ctx, loop_cfg=TrainLoopConfig(policy=pol))
    state = loop.init_state()
    ds = ShardedLMDataset(cfg, batch=2, seq=64, ctx=ctx)
    state = loop.run(state, iter(ds), 8)
    state, info = loop.inject_failure(state, 0.5)
    assert info["partial_sq"] <= info["full_sq"] + 1e-6
    state = loop.run(state, iter(ds), 8)
    losses = [m["loss"] for m in loop.metrics]
    assert all(np.isfinite(l) for l in losses)
    assert np.mean(losses[-3:]) < losses[0]   # still making progress


def test_controller_with_persistent_store_lifecycle():
    params = {"w": jnp.arange(2000, dtype=jnp.float32).reshape(500, 4)}
    with tempfile.TemporaryDirectory() as d:
        store = ShardedCheckpointStore(d)
        ctl = FTController(params, CheckpointPolicy.scar(0.25, 8),
                           store=store)
        p = params
        for step in range(1, 9):
            p = jax.tree_util.tree_map(lambda x: x + 1.0, p)
            ctl.maybe_checkpoint(step, p)
        lost = ctl.sample_failure(0.5)
        rec, info = ctl.on_failure(p, lost)
        assert info["partial_sq"] <= info["full_sq"]
        store.flush()
        disk = store.read_all()
        np.testing.assert_allclose(np.asarray(disk["w"]),
                                   np.asarray(ctl.ckpt.values["w"]))
        # scar(0.25, 8): partial checkpoints every rC = 2 iters -> 4 saves
        assert ctl.stats["saves"] == 4
        assert ctl.stats["bytes_mirrored"] > 0


def test_server_generates(key):
    ctx = single_device_ctx()
    cfg = get_config("granite-8b", reduced=True)
    from repro.models import get_model
    ops = get_model(cfg)
    params = ops.init_params(key, cfg)
    srv = Server(cfg, ctx, params)
    batch = lm_batch(jax.random.PRNGKey(5), cfg, 2, 16)
    toks = srv.generate(batch, 4)
    assert toks.shape == (2, 4)
    assert int(toks.min()) >= 0 and int(toks.max()) < cfg.vocab


def test_microbatched_train_step_matches_single():
    """cfg.microbatch > 1 must give the same loss/update (grad averaging)."""
    import dataclasses
    ctx = single_device_ctx()
    cfg = get_config("qwen2-1.5b", reduced=True)
    cfg_mb = dataclasses.replace(cfg, microbatch=2)
    from repro.models import get_model
    from repro.optim.optimizers import sgd
    from repro.training.step import make_train_step
    from repro.training.train_state import TrainState
    ops = get_model(cfg)
    params = ops.init_params(jax.random.PRNGKey(0), cfg)
    batch = lm_batch(jax.random.PRNGKey(1), cfg, 4, 32)
    opt = sgd(0.1)
    s0 = TrainState.create(params, opt)
    s1, l1 = make_train_step(ops, cfg, ctx, opt)(s0, batch)
    s2, l2 = make_train_step(ops, cfg_mb, ctx, opt)(s0, batch)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def test_benchmark_driver_exits_nonzero_when_a_section_raises(
        monkeypatch, capsys):
    """A section that raises still prints its ``_ERROR`` row, the other
    sections still run, and the driver's exit code says it failed."""
    import sys
    from benchmarks import run

    def broken(quick):
        raise RuntimeError("boom")

    monkeypatch.setattr(run, "SECTIONS", {
        "good": lambda quick: ["good_row,1.0,x=1"], "bad": broken})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["run"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "good_row,1.0,x=1" in out
    assert "bad_ERROR,0.0,RuntimeError:boom" in out
