"""``correct`` must come out false when the timed path is broken, and the
control (the reference one precision step down) must fail the limits.

Each fault runs the harness's whole run at a reduced size on the CPU
with the program broken underneath: a step that returns its state
unchanged, a step that leaves out half of the batch, a replica, parity
or checkpoint word altered where the fabric keeps it, a recovered value
altered, a recovery that returns the arena it was given. The cells run
on one chip, so there is no exchange between chips to leave out."""
import time

import benchcase
import pytest

import harness
import reflib
from probe import CompileCounter
from tokens import first_batches


def _run(cell, tmp_path, fault):
    return harness.run_cell(cell, 2**31 + 3, 0.1, False, time.perf_counter(),
                            work_dir=str(tmp_path),
                            counter=CompileCounter(), fault=fault)


def _rebuilt_step(job):
    """The program's arena step, jitted without donation."""
    import jax
    from repro.training.step import make_arena_train_step
    loop = job.loop
    return jax.jit(make_arena_train_step(loop.ops, loop.cfg, loop.ctx,
                                         loop.optimizer, loop.arena_layout))


def unchanged_state(job, where):
    if where == "built":
        step = _rebuilt_step(job)
        job.loop._arena_step = lambda s, b: (s, step(s, b)[1])


def half_batch(job, where):
    if where == "built":
        step = job.loop._arena_step
        job.loop._arena_step = lambda s, b: step(
            s, {k: v[: v.shape[0] // 2] for k, v in b.items()})


def _flip(x, at=0):
    import jax.numpy as jnp
    import numpy as np
    w = np.asarray(x).copy()
    flat = w.reshape(-1).view(np.int32)
    flat[at] ^= 1 << 20
    return jnp.asarray(w)


def altered_tiers(job, where):
    if where == "window_closed":
        fab = job.loop.controller.fabric
        fab.replicas._arena = _flip(fab.replicas.arena)
        fab.parity.parity = _flip(fab.parity.parity, 5)


def altered_save(job, where):
    if where == "window_closed":
        import numpy as np
        ctl = job.loop.controller
        saved = np.asarray(job.store.saved_iters()) == job.step
        ab = next(ab for ab in ctl.arena_layout.blocks if saved[ab.gid])
        ctl._ckpt_arena = _flip(ctl._ckpt_arena, ab.offset)


def altered_recovery(job, where):
    if where == "built":
        import jax
        fab = job.loop.controller.fabric
        recover = fab.on_failure

        def broken(params, *a, **kw):
            out, info = recover(params, *a, **kw)
            leaves, tree = jax.tree_util.tree_flatten(out)
            leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1)
            return jax.tree_util.tree_unflatten(tree, leaves), info

        fab.on_failure = broken


def skipped_recovery(job, where):
    """The fabric plans and books the recovery but hands back the tree
    it was given: the lost blocks keep whatever the lost host left."""
    if where == "built":
        fab = job.loop.controller.fabric
        recover = fab.on_failure

        def skipped(params, *a, **kw):
            _, info = recover(params, *a, **kw)
            return params, info

        fab.on_failure = skipped


@pytest.mark.parametrize("fault,traffic,fails", [
    (unchanged_state, "clean_4x1024", "change_gap"),
    (half_batch, "clean_4x1024", "grad_gap"),
    (altered_tiers, "clean_4x1024", "replica_words"),
    (altered_save, "clean_4x1024", "save_words"),
    (altered_recovery, "hostloss_4x1024", "recovery_words"),
    (skipped_recovery, "hostloss_4x1024", "recovery_words"),
])
def test_fault_is_not_correct(fault, traffic, fails, tmp_path):
    r = _run(benchcase.small_cell("qwen2-1.5b", traffic), tmp_path, fault)
    assert r["correct"] is False
    c = r["checks"][fails]
    assert c["value"] > c["limit"], r["checks"]
    if fault is altered_tiers:
        assert r["checks"]["parity_words"]["value"] > 0


@pytest.mark.parametrize("config,traffic", [
    ("qwen2-1.5b", "clean_4x1024")])
def test_control_fails_the_limits(config, traffic, tmp_path, monkeypatch):
    """The control, the reference one precision step below the
    configuration (matmul operands rounded to float8_e4m3fn for bf16
    weights), put in the program's place for the readings of steps 1-3,
    comes out not correct through the harness's whole run; the float32
    reference in that place reads 0."""
    cell = benchcase.small_cell(config, traffic)
    first_steps = harness.first_steps
    opt = cell.traffic["optimizer"]
    adam = reflib.AdamW(opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                        opt["wd"])

    def in_place(num):
        def steps(job):
            out = first_steps(job)    # the program trains on for the window
            tr = cell.traffic
            batches = first_batches(job.arch.vocab, tr["batch"], tr["seq"],
                                    job.seed, 3)
            params = reflib.init_from_specs(job.specs, job.seed)
            got = reflib.train_readings(job.ref.loss_fn(job.arch), params,
                                        batches, num, adam)
            return dict(out, **{k: got[k] for k in
                                ("losses", "grad", "change")})
        return steps

    monkeypatch.setattr(harness, "first_steps", in_place(
        reflib.Numerics.control(cell.conf["torch_dtype"])))
    r = _run(cell, tmp_path, None)
    assert r["correct"] is False
    assert any(r["checks"][k]["value"] > r["checks"][k]["limit"]
               for k in cell.conf["limits"]), r["checks"]
    monkeypatch.setattr(harness, "first_steps", in_place(reflib.Numerics()))
    r = _run(cell, tmp_path, None)
    assert all(r["checks"][k]["value"] == 0 for k in cell.conf["limits"])
