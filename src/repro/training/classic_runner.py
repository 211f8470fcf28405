"""Experiment runner for the classic iterative-convergent models.

Drives the paper's §5 experiments:

- ``run_clean``              — unperturbed trajectory (the κ(x, ε) baseline).
- ``run_with_perturbation``  — inject one synthetic perturbation at iteration
                               T (random / adversarial / reset): Figures 3/5/6.
- ``run_with_failure``       — full SCAR lifecycle: periodic (partial)
                               checkpoints via FTController, a failure of a
                               fraction p of parameter blocks at a sampled
                               iteration, recovery (full or partial), then
                               continue to convergence: Figures 7/8.
- ``run_with_trace``         — beyond-paper degraded-mode soak: an
                               MTBF-sampled (or explicit) multi-event
                               failure trace where failed domains stay dead
                               in the fabric's cluster view; elastic fabrics
                               re-home/re-seed between events, and domains
                               optionally heal ``heal_after`` iters later.

All return loss trajectories + the empirical iteration cost
ι = κ(y, ε) − κ(x, ε) measured exactly as the paper does.
"""
from __future__ import annotations

import copy
import dataclasses
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.core.controller import FTController
from repro.core.iteration_cost import empirical_iteration_cost, iterations_to_eps
from repro.core.perturb import (adversarial_perturbation, random_perturbation,
                                reset_perturbation)
from repro.core.policy import CheckpointPolicy
from repro.core.blocks import partition_pytree, tree_sq_norm
from repro.models.classic import IterativeModel

PyTree = Any


def _keys(seed: int):
    base = jax.random.PRNGKey(seed)

    def key(i: int):
        return jax.random.fold_in(base, i)
    return key


def iterations_to_converge(model: IterativeModel, max_iters: int = 400,
                           seed: int = 0) -> int:
    traj = run_clean(model, max_iters, seed)["losses"]
    return iterations_to_eps(traj, model.eps)


def run_clean(model: IterativeModel, max_iters: int, seed: int = 0,
              stop_at_eps: bool = False) -> dict:
    key = _keys(seed)
    p = model.init(jax.random.PRNGKey(1))
    losses = []
    for i in range(1, max_iters + 1):
        p = model.step(p, key(i), i)
        losses.append(float(model.loss(p)))
        if stop_at_eps and losses[-1] < model.eps:
            break
    return {"losses": losses, "params": p}


def run_with_perturbation(model: IterativeModel, *, kind: str,
                          at_iter: int, size: Optional[float] = None,
                          fraction: Optional[float] = None,
                          max_iters: int = 400, seed: int = 0,
                          clean_losses: Optional[list] = None) -> dict:
    """One perturbation at ``at_iter`` (types of §5.2), run to max_iters.

    kind: "random" (needs size), "adversarial" (needs size),
    "reset" (needs fraction — reset random blocks to x^(0)).
    """
    key = _keys(seed)
    p0 = model.init(jax.random.PRNGKey(1))
    partition = partition_pytree(p0, model.block_rows,
                                 colocate=model.colocate)
    p = p0
    losses = []
    delta_norm = 0.0
    for i in range(1, max_iters + 1):
        if i == at_iter:
            prng = jax.random.fold_in(jax.random.PRNGKey(seed + 77), i)
            if kind == "random":
                p, dn = random_perturbation(prng, p, size)
            elif kind == "adversarial":
                p, dn = adversarial_perturbation(p, model.x_star(), size)
            elif kind == "reset":
                p, dn = reset_perturbation(prng, p, p0, fraction, partition)
            else:
                raise ValueError(kind)
            delta_norm = float(dn)
        p = model.step(p, key(i), i)
        losses.append(float(model.loss(p)))
    if clean_losses is None:
        clean_losses = run_clean(model, max_iters, seed)["losses"]
    cost = empirical_iteration_cost(losses, clean_losses, model.eps)
    return {"losses": losses, "delta_norm": delta_norm,
            "iteration_cost": cost,
            "kappa_perturbed": iterations_to_eps(losses, model.eps),
            "kappa_clean": iterations_to_eps(clean_losses, model.eps)}


def run_with_failure(model: IterativeModel, policy: CheckpointPolicy, *,
                     fail_iter: int, fail_fraction: float,
                     max_iters: int = 400, seed: int = 0,
                     clean_losses: Optional[list] = None,
                     store=None, fabric=None,
                     fail_domain: str = "uniform",
                     arena_state: bool = True,
                     recorder=None) -> dict:
    """Full SCAR lifecycle on one classic model (Figures 7/8).

    The failure destroys ``fail_fraction`` of parameter blocks (uniformly at
    random, the paper's model) or — with ``fabric`` and
    ``fail_domain="host"``/``"rack"``/``"device"`` — one whole correlated
    failure domain. Recovery follows ``policy.recovery`` from the running
    checkpoint, or the fabric's tier planner when a fabric is given.

    ``arena_state`` (default): when the controller is arena-capable, the
    live params are packed ONCE per consuming iteration and every
    controller call (maintain + save) uses that arena — with
    ``own_live`` the fabric adopts the pack as the replica directly, so
    the total cost matches the tree interface exactly (whose sweep made
    the same one pack internally) while exercising the same arena-native
    controller surface the LM trainer uses. ``False`` keeps the pure
    PyTree interface (bit-identical results either way).
    """
    if fail_domain != "uniform" and fabric is None:
        raise ValueError("correlated fail_domain needs a fabric")
    key = _keys(seed)
    p = model.init(jax.random.PRNGKey(1))
    ctl = FTController(p, policy, norm_aux=model.norm_aux, store=store,
                       rng=jax.random.PRNGKey(seed + 13),
                       colocate=model.colocate, fabric=fabric,
                       recorder=recorder)
    use_arena = arena_state and ctl.arena_ready
    losses = []
    recovery_info = {}
    maint_seconds = 0.0
    for i in range(1, max_iters + 1):
        p = model.step(p, key(i), i)
        # maintain before the checkpoint: the arena sweep's PRIORITY
        # scores are measured against the pre-save running checkpoint
        t0 = time.perf_counter()
        # pack only on iterations whose maintain/save reads the live
        # value (always, under the default every-step tier intervals)
        packed = use_arena and ctl.live_value_needed(i)
        live = ctl.pack_live(p, account=True) if packed else p
        # own_live: the throwaway pack becomes the replica directly (no
        # copy inside the sweep) — same total cost as the tree interface
        ctl.maintain(i, live, own_live=packed)
        ctl.maybe_checkpoint(i, live, own_live=packed)
        # block on the sweep's outputs so maint_seconds books the
        # maintenance device work, not just its dispatch (same
        # attribution TrainLoop.run uses for overhead_seconds). Under
        # async maintenance the per-iteration fence is deliberately
        # skipped — the sweep settles under the next iteration's model
        # step and maint_seconds books the dispatch cost; the final
        # pending epoch is settled once after the loop.
        if ctl.fabric is not None \
                and not getattr(ctl.fabric.cfg, "async_maintain", False):
            ctl.fabric.block_until_maintained()
        maint_seconds += time.perf_counter() - t0
        if i == fail_iter:
            if fail_domain == "uniform":
                lost = ctl.sample_failure(fail_fraction)
                p, recovery_info = ctl.on_failure(p, lost, step=i)
            else:
                lost, failed = ctl.sample_domain_failure(fail_domain)
                p, recovery_info = ctl.on_failure(p, lost,
                                                  failed_devices=failed,
                                                  step=i)
        losses.append(float(model.loss(p)))
    if ctl.fabric is not None:
        # settle the last async epoch (no-op in sync mode) — its fence
        # wait belongs to the run, not to whoever touches the fabric next
        t0 = time.perf_counter()
        ctl.fabric.block_until_maintained()
        maint_seconds += time.perf_counter() - t0
    if clean_losses is None:
        clean_losses = run_clean(model, max_iters, seed)["losses"]
    cost = empirical_iteration_cost(losses, clean_losses, model.eps)
    # snapshot (not alias) the live stats: the controller/fabric keep
    # mutating their dicts if reused after return — results must not
    # change retroactively
    return {"losses": losses, "iteration_cost": cost,
            "recovery": copy.deepcopy(recovery_info),
            "controller_stats": copy.deepcopy(ctl.stats),
            "fabric_stats": (copy.deepcopy(ctl.fabric.stats)
                             if ctl.fabric is not None else None),
            "arena_state": use_arena,
            "maint_seconds_per_iter": maint_seconds / max_iters,
            "kappa_perturbed": iterations_to_eps(losses, model.eps),
            "kappa_clean": iterations_to_eps(clean_losses, model.eps)}


def run_with_trace(model: IterativeModel, policy: CheckpointPolicy, *,
                   fabric, max_iters: int = 400, seed: int = 0,
                   mtbf: Optional[dict] = None, trace=None,
                   heal_after: Optional[int] = None,
                   clean_losses: Optional[list] = None,
                   store=None, arena_state: bool = True,
                   recorder=None) -> dict:
    """Degraded-mode soak on one classic model: a multi-event failure trace
    (explicit ``trace`` list of :class:`FailureEvent`, or MTBF-sampled from
    ``mtbf``), recovered through the fabric's tier planner.

    Unlike ``run_with_failure``, failed domains stay *dead* in the fabric's
    cluster view between events — the second hit lands on a degraded
    topology. With ``FabricConfig(elastic=True)`` the placement engine
    re-homes/re-seeds/re-stripes after every event so the next failure still
    finds live redundancy tiers; with ``elastic=False`` ("recover in place
    and pray the host returns") later events fall through to the expensive
    RUNNING_CKPT/DISK tiers. ``heal_after`` re-admits a failed domain that
    many iterations after its event.

    Returns the loss trajectory, the per-event recovery diagnostics, and
    the paper's §5 empirical iteration cost.
    """
    if fabric is None:
        raise ValueError("run_with_trace needs a fabric")
    key = _keys(seed)
    p = model.init(jax.random.PRNGKey(1))
    ctl = FTController(p, policy, norm_aux=model.norm_aux, store=store,
                       rng=jax.random.PRNGKey(seed + 13),
                       colocate=model.colocate, fabric=fabric,
                       recorder=recorder)
    if trace is None:
        if mtbf is None:
            raise ValueError("pass an explicit trace or mtbf means")
        trace = ctl.fabric.domains.sample_failure_trace(
            np.random.default_rng(seed + 5), max_iters, mtbf)
    events_at: dict[int, list] = {}
    for ev in trace:
        events_at.setdefault(max(1, min(ev.step, max_iters)), []).append(ev)
    use_arena = arena_state and ctl.arena_ready
    heal_at: dict[int, list] = {}
    events_out: list[dict] = []
    losses = []
    redundancy_full: list[bool] = []
    for i in range(1, max_iters + 1):
        p = model.step(p, key(i), i)
        # arena-native controller interface: one shared pack feeds both
        # maintain and the save (own_live: the pack IS the replica),
        # skipped on iterations where neither reads the live value
        # (see run_with_failure)
        packed = use_arena and ctl.live_value_needed(i)
        live = ctl.pack_live(p, account=True) if packed else p
        ctl.maintain(i, live, own_live=packed)
        ctl.maybe_checkpoint(i, live, own_live=packed)
        evs = events_at.pop(i, [])
        if len(evs) > 1:
            # same-step events are one correlated multi-domain loss:
            # recover the union in one tier-planned pass (multi-erasure)
            p, info = ctl.on_domain_events(
                p, [(e.kind, e.index) for e in evs], step=i)
            info["step"] = i
            events_out.append(info)
            if heal_after is not None:
                applied = {(a["kind"], a["index"])
                           for a in info.get("events", [])}
                for ev in evs:
                    if (ev.kind, ev.index) in applied:
                        heal_at.setdefault(i + heal_after, []).append(ev)
        elif evs:
            ev = evs[0]
            p, info = ctl.on_domain_event(p, ev.kind, ev.index, step=i)
            info["step"] = i
            events_out.append(info)
            if heal_after is not None and not info.get("skipped"):
                heal_at.setdefault(i + heal_after, []).append(ev)
        for ev in heal_at.pop(i, []):
            ctl.heal_domain(ev.kind, ev.index, p, step=i)
        # placement-health flag AFTER this step's events/heals — the
        # availability report turns these into time-to-full-redundancy
        redundancy_full.append(ctl.fabric.redundancy_state()["full"])
        losses.append(float(model.loss(p)))
    # settle the last async epoch before the stats snapshot (no-op sync)
    ctl.fabric.block_until_maintained()
    if clean_losses is None:
        clean_losses = run_clean(model, max_iters, seed)["losses"]
    cost = empirical_iteration_cost(losses, clean_losses, model.eps)
    from repro.fabric.availability import summarize_availability
    # snapshot the live stats/events (see run_with_failure): the
    # controller keeps appending to ctl.stats["events"] if reused
    return {"losses": losses, "iteration_cost": cost,
            "events": copy.deepcopy(events_out),
            "controller_stats": copy.deepcopy(ctl.stats),
            "fabric_stats": copy.deepcopy(ctl.fabric.stats),
            "availability": summarize_availability(events_out,
                                                   redundancy_full),
            "kappa_perturbed": iterations_to_eps(losses, model.eps),
            "kappa_clean": iterations_to_eps(clean_losses, model.eps)}
