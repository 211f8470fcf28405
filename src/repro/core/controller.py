"""Fault-tolerance controller (paper §4.3, Figure 4).

Host-side orchestrator that owns the running checkpoint and drives:

1. *Checkpoint coordination* — every ``policy.partial_interval`` iterations,
   score blocks (priority), update the in-memory running checkpoint
   (jitted, device-resident), and mirror the saved blocks to persistent
   storage. Training resumes as soon as the in-memory cache is updated;
   under ``policy.async_persist`` the disk writes (the saved blocks, then
   the parity mirror) go to the store's background writer, one save in
   flight (paper §4.3 step 4).
2. *Recovery coordination* — on a detected failure (a lost block mask),
   partially (or fully) restore from the running checkpoint. If the
   in-memory replica itself was lost (total failure), reload from the
   persistent store.
3. *Fabric coordination* (optional ``fabric=``) — maintain the tiered
   redundancy fabric (anti-affine peer replicas + XOR parity,
   :mod:`repro.fabric`) alongside the running checkpoint, and route
   ``on_failure`` through the tier planner so each lost block recovers
   from the cheapest surviving tier, with per-tier perturbation stats.
   Trace-driven soaks use ``on_domain_event``/``heal_domain`` — failed
   domains stay dead in the fabric's cluster view (elastic fabrics
   re-home/re-seed across the survivors) and every event's tier counts
   land in ``stats["events"]``.

The controller is deliberately thin: all numerics are pure functions from
:mod:`repro.core.checkpoint` / :mod:`repro.core.recovery`, so it composes
with any training loop (including the big-model SPMD trainer).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import (BlockPartition, block_scores,
                               partition_pytree, tree_sq_norm)
from repro.core.checkpoint import (RunningCheckpoint, full_save,
                                   init_running_checkpoint, save_step,
                                   select_save_mask)
from repro.core.norms import get_norm
from repro.core.policy import CheckpointPolicy, RecoveryMode, SelectionStrategy
from repro.core.recovery import (apply_failure_and_recover,
                                 perturbation_norms, sample_failure_mask)
from repro.telemetry.recorder import NULL_RECORDER

PyTree = Any


class FTController:
    """Checkpoint + recovery coordinator for one training job."""

    def __init__(self, params: PyTree, policy: CheckpointPolicy, *,
                 norm_aux: Optional[dict] = None,
                 store: Optional[Any] = None,
                 rng: Optional[jax.Array] = None,
                 colocate: tuple = (),
                 fabric: Optional[Any] = None,
                 inplace_save: bool = True,
                 recorder: Optional[Any] = None,
                 mesh: Optional[Any] = None):
        self.policy = policy
        # unified telemetry (repro.telemetry): the NULL_RECORDER default
        # keeps every emit point a no-op; a real Recorder receives this
        # controller's stats as a registered scope, structured save /
        # failure / recovery events, and the per-recovery ledger entries
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        # donation-based partial save: scatter only the selected blocks
        # into the running checkpoint (O(k·block_bytes)) instead of
        # rewriting every leaf through a full-size jnp.where
        self.inplace_save = inplace_save
        self.partition = partition_pytree(params, policy.block_rows,
                                          colocate=colocate)
        self.norm_fn = get_norm(policy.norm, aux=norm_aux,
                                block_rows=policy.block_rows)
        # flat-arena checkpoint state (set up after the fabric below):
        # when active, _ckpt_arena is the canonical running-checkpoint
        # value store and _ckpt.values is stale (_ckpt_dirty): the ckpt
        # property decodes the tree on demand
        self._arena_layout = None
        self._ckpt_arena = None
        self._ckpt_dirty = False
        self._pack_jit = None
        self._unpack_jit = None
        self._arena_score_jit = None
        self._arena_score_live_jit = None
        self._ckpt = init_running_checkpoint(params, self.partition)
        self.store = store
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        # np generator for topology sampling, derived from the jax key
        # (key_data handles both legacy uint32 and typed key arrays)
        np_seed = int(np.asarray(
            jax.random.key_data(self._rng)).ravel()[-1])
        self._np_rng = np.random.default_rng(np_seed)
        # fabric: a CheckpointFabric, or a FabricConfig to build one over
        # this controller's partition (import deferred so fabric-less
        # controllers never pay the fabric/kernel import chain)
        if fabric is not None:
            from repro.fabric import CheckpointFabric, FabricConfig
            if isinstance(fabric, FabricConfig):
                fabric = CheckpointFabric(self.partition, fabric,
                                          recorder=self.recorder,
                                          mesh=mesh)
            elif self.recorder.enabled:
                fabric.attach_recorder(self.recorder)
            if policy.recovery == RecoveryMode.FULL:
                # the tier planner is inherently partial (survivors keep
                # live values); a FULL-recovery baseline must not silently
                # degrade into it
                raise ValueError("fabric recovery is tiered/partial; use "
                                 "recovery=RecoveryMode.PARTIAL or drop "
                                 "the fabric for a FULL-recovery baseline")
        self.fabric = fabric
        self.stats = self.recorder.scope("controller", {
            "saves": 0, "recoveries": 0, "save_seconds": 0.0,
            "blocks_saved": 0, "bytes_mirrored": 0,
            "save_bytes_moved": 0, "events": []})
        self._jit_save = jax.jit(partial(
            save_step, policy=self.policy, partition=self.partition,
            norm_fn=self.norm_fn))
        self._jit_select = jax.jit(partial(
            select_save_mask, policy=self.policy, partition=self.partition,
            norm_fn=self.norm_fn))
        # arena checkpoint mode: the running checkpoint's values live as
        # the fabric's flat parameter arena — every partial save is ONE
        # donated tile scatter sourced from the maintenance sweep's
        # replica arena. Requires an arena-capable fabric, the in-place
        # save, and (for PRIORITY) squared-L2 scoring — other norms keep
        # the tree-path save.
        if (inplace_save and self.fabric is not None
                and getattr(self.fabric, "arena_layout", None) is not None
                and (policy.strategy != SelectionStrategy.PRIORITY
                     or policy.norm == "l2")):
            from repro.core.arena import (arena_pack_program,
                                          arena_unpack_program)
            layout = self.fabric.arena_layout
            sh = getattr(self.fabric, "_arena_sharding", None)
            self._arena_layout = layout
            self._pack_jit = arena_pack_program(layout, sh)
            self._unpack_jit = arena_unpack_program(layout)
            self._ckpt_arena = self._pack_jit(params)
            # the arena is canonical from here on: drop the tree copy
            # (a whole model's bytes of device memory) and let the
            # ``ckpt`` property decode it on demand
            self._ckpt = RunningCheckpoint(None, self._ckpt.saved_iter,
                                           self._ckpt.rr_cursor)
            self._ckpt_dirty = True
        if store is not None:
            if self.recorder.enabled and hasattr(store, "attach_recorder"):
                store.attach_recorder(self.recorder)
            kw = {}
            if self.fabric is not None:
                # domain-keyed disk layout: DISK-tier reads after a domain
                # loss touch only the needed blocks' files
                kw = dict(homes=self.fabric.view.homes,
                          domains=self.fabric.domains)
            if self._arena_layout is not None:
                # arena-segment store layout: one append write per host
                # per save, sourced straight from the checkpoint arena
                kw["arena_layout"] = self._arena_layout
                kw["arena_values"] = np.asarray(self._ckpt_arena)
            store.init(params, self.partition, **kw)

    # -- arena-native live state --------------------------------------------

    @property
    def arena_layout(self):
        """The flat-arena layout of the hot path (None = tree-only)."""
        return self._arena_layout

    @property
    def arena_ready(self) -> bool:
        """True when the hot path is arena-native — the training loops
        may then feed :meth:`maintain`/:meth:`maybe_checkpoint` (and the
        recovery entry points) the live flat arena instead of the tree,
        eliminating the per-step ``pack_arena``."""
        return self._arena_layout is not None

    def pack_live(self, params: PyTree, account: bool = False) -> jnp.ndarray:
        """Pack a live tree into arena form (jitted; used once at
        training-state init and by tree-stepping runners that keep the
        controller interface arena-native).

        ``account=True`` books the pack's traffic (read the live tree,
        write the arena) onto the fabric's maintenance byte counter —
        tree-stepping runners pass it so their per-iteration pack is not
        silently dropped from the accounting when the downstream sweep
        runs at the pack-free resident rate. Truly resident callers
        (``ArenaTrainState`` init) leave it False: that pack happens once,
        not per step."""
        assert self.arena_ready, "controller has no arena layout"
        if account and self.fabric is not None:
            t = self.fabric._traffic_model()
            self.fabric.stats["maintain_bytes_moved"] += \
                t["model"] + t["arena_bytes"]
            self.fabric.stats["live_packs"] += 1
        return self._pack_jit(params)

    def unpack_live(self, arena: jnp.ndarray) -> PyTree:
        """Decode an arena back to tree form (recovery/analysis paths)."""
        assert self.arena_ready, "controller has no arena layout"
        return self._unpack_jit(arena)

    def rebind_arena(self) -> None:
        """Adopt the fabric's *current* arena layout after an elastic mesh
        resize (:meth:`CheckpointFabric.resize_mesh`): rebuilds the
        pack/unpack/score programs for the new shard count and relayouts
        the running-checkpoint arena onto the new mesh — the data region
        is layout-invariant, so the checkpoint values are bit-preserved
        through any number of shrink/re-grow cycles."""
        assert self.arena_ready and self.fabric is not None, \
            "rebind_arena needs an arena-native controller with a fabric"
        from repro.core.arena import (arena_pack_program,
                                      arena_unpack_program, relayout_arena)
        old = self._arena_layout
        layout = self.fabric.arena_layout
        sh = getattr(self.fabric, "_arena_sharding", None)
        self._arena_layout = layout
        self._pack_jit = arena_pack_program(layout, sh)
        self._unpack_jit = arena_unpack_program(layout)
        self._arena_score_jit = None
        self._arena_score_live_jit = None
        if self._ckpt_arena is not None and layout is not old:
            self._ckpt_arena = relayout_arena(self._ckpt_arena, old, layout,
                                              out_sharding=sh)
            self._ckpt_dirty = True

    def live_value_needed(self, step: int) -> bool:
        """True when this step's :meth:`maintain` or
        :meth:`maybe_checkpoint` will actually read the live value —
        tree-stepping runners skip their shared per-iteration pack (a
        full model+arena memcpy) on steps where nothing consumes it."""
        if self.should_checkpoint(int(step)):
            return True
        return (self.fabric is not None
                and any(self.fabric.maintenance_due(int(step))))

    def _live_arena(self, params):
        from repro.core.arena import as_live_arena
        return as_live_arena(params, self._arena_layout)

    # -- running checkpoint (arena-backed when the fabric has an arena) ------

    @property
    def ckpt(self) -> RunningCheckpoint:
        """The running checkpoint. In arena mode the canonical values are
        ``_ckpt_arena``; the tree form is decoded here on every call
        (recovery/analysis paths — never the per-save hot path) and not
        kept: a kept copy would hold a second model's bytes of device
        memory until the next save."""
        if self._ckpt_dirty:
            return RunningCheckpoint(self._unpack_jit(self._ckpt_arena),
                                     self._ckpt.saved_iter,
                                     self._ckpt.rr_cursor)
        return self._ckpt

    @ckpt.setter
    def ckpt(self, new: RunningCheckpoint) -> None:
        self._ckpt = new
        self._ckpt_dirty = False
        if self._arena_layout is not None:
            self._ckpt_arena = self._pack_jit(new.values)

    # -- checkpoint path ----------------------------------------------------

    def should_checkpoint(self, step: int) -> bool:
        interval = (self.policy.full_interval
                    if self.policy.fraction >= 1.0
                    else self.policy.partial_interval)
        return step > 0 and step % interval == 0

    def maybe_checkpoint(self, step: int, params: PyTree,
                         own_live: bool = False) -> bool:
        if not self.should_checkpoint(step):
            return False
        self.checkpoint_now(step, params, own_live=own_live)
        return True

    def checkpoint_now(self, step: int, params: PyTree,
                      own_live: bool = False) -> jnp.ndarray:
        """Update the running checkpoint; returns the saved block mask.

        ``params`` may be the live flat arena (arena-resident training
        state, requires :attr:`arena_ready`): the partial save then
        sources straight from the training state — no pack, no replica
        freshness gating — and a full save is one contiguous copy.
        ``own_live`` rides along to the post-save freshness maintain (see
        :meth:`maintain`) so a tree-stepping runner's throwaway pack is
        adopted, not re-copied, when that forced sweep runs."""
        with self.recorder.span("scar/save", step=int(step)):
            return self._checkpoint_now(int(step), params, own_live)

    def _checkpoint_now(self, step: int, params: PyTree,
                        own_live: bool) -> jnp.ndarray:
        if self.fabric is not None \
                and getattr(self.fabric, "has_pending_maintenance", False):
            # consume point: the save may source from the published slot
            # and mirrors parity afterwards — take the deferred fence
            # first, outside the save timer, so the in-flight sweep's
            # remainder books as fence time, not save time
            self.fabric.block_until_maintained()
        t0 = time.perf_counter()
        moved0 = self.stats["save_bytes_moved"]
        live = self._live_arena(params)
        full_plain = (self.policy.fraction >= 1.0 and
                      self.policy.strategy != SelectionStrategy.PRIORITY)
        arena_hot = self._arena_layout is not None and not full_plain
        picked = None
        if not full_plain:
            with self.recorder.span("scar/save/select"):
                picked = (self._arena_select(step, params) if arena_hot
                          else self._tree_select(step, params, live))
        with self.recorder.span("scar/save/scatter"):
            if live is not None and full_plain:
                # full save from the live arena: ONE contiguous device copy
                ck = self._ckpt
                self._ckpt_arena = jnp.array(live)
                self._ckpt = RunningCheckpoint(
                    ck.values, jnp.full_like(ck.saved_iter, jnp.int32(step)),
                    ck.rr_cursor)
                self._ckpt_dirty = True
                mask = jnp.ones((self.partition.total_blocks,), bool)
            elif arena_hot:
                mask = self._arena_scatter(step, params, *picked)
            elif full_plain:
                self.ckpt = full_save(self.ckpt, params, jnp.int32(step))
                mask = jnp.ones((self.partition.total_blocks,), bool)
            else:
                mask = self._tree_scatter(step, params, *picked)
            if self.fabric is not None:
                # the save invalidated the drift the cached scores measured
                self.fabric.invalidate_scores()
            # block until the in-memory cache is consistent (paper:
            # training may resume now), then mirror to disk. In arena mode
            # the arena IS the cache — the tree form stays lazily dirty
            # (never materialized on the hot path).
            jax.block_until_ready(self._ckpt_arena if self._arena_layout
                                  is not None else self.ckpt.values)
            n_blocks = int(jnp.sum(mask))
        save_seconds = time.perf_counter() - t0
        self.stats["saves"] += 1
        self.stats["blocks_saved"] += n_blocks
        self.stats["save_seconds"] += save_seconds
        if self.recorder.enabled:
            self.recorder.event(
                "save", step=int(step), blocks=n_blocks,
                bytes_moved=self.stats["save_bytes_moved"] - moved0,
                seconds=save_seconds,
                mode="arena" if self._arena_layout is not None else "tree")
        if self.store is not None:
            if self._arena_layout is not None:
                with self.recorder.span("scar/save/tiles_to_host") as sp:
                    mask_np = np.asarray(mask)
                    tiles = self._arena_layout.tiles_for_blocks(
                        np.nonzero(mask_np)[0])
                    from repro.core.arena import ARENA_TILE
                    data = np.asarray(
                        self._ckpt_arena.reshape(-1, ARENA_TILE)[tiles])
                    sp.add_bytes(data.nbytes)
            if self.policy.async_persist:
                # one save in flight: the previous save's writes land
                # before this one queues its own, so the host holds at
                # most two saves' payloads however slow the disk is
                with self.recorder.span("scar/save/store_wait"):
                    self.store.wait_writes()
            with self.recorder.span("scar/save/store_enqueue"):
                if self._arena_layout is not None:
                    self.stats["bytes_mirrored"] += self.store.write_arena(
                        mask_np, tiles, data, step,
                        background=self.policy.async_persist)
                else:
                    self.stats["bytes_mirrored"] += self.store.write_blocks(
                        mask, self.ckpt.values, step,
                        background=self.policy.async_persist)
        if self.fabric is not None:
            if not self.fabric.is_fresh(int(step)):
                # keep the redundancy tiers at least as fresh as the
                # checkpoint (a same-step maintain() may have skipped an
                # off-interval tier — force refreshes every tier)
                with self.recorder.span("scar/save/refresh"):
                    self.fabric.maintain(int(step), params, force=True,
                                         own_live=own_live)
            if (self.store is not None
                    and getattr(self.fabric, "parity", None) is not None
                    and self.fabric.parity.parity is not None
                    and hasattr(self.store, "write_parity")):
                # mirror parity to disk: blocks whose domain shard died stay
                # reconstructable offline from survivors + parity. Under
                # async_persist the writer takes it after this save's
                # shard write, from a host snapshot taken here
                with self.recorder.span("scar/save/parity_to_host") as sp:
                    parity = np.asarray(self.fabric.parity.parity)
                    sp.add_bytes(parity.nbytes)
                self.stats["bytes_mirrored"] += self.store.write_parity(
                    int(step), parity, self.fabric.parity.parity_homes,
                    domains=self.fabric.domains,
                    members=self.fabric.parity.members,
                    background=self.policy.async_persist)
        return mask

    def _tree_select(self, step: int, params: PyTree, live) -> tuple:
        """Tree-path selection: ``(rng, scores, mask, cursor, idx)``; the
        last three are None where the jitted save selects for itself."""
        assert live is None, ("live-arena saves need the arena "
                              "checkpoint path (arena-capable fabric)")
        self._rng, sub = jax.random.split(self._rng)
        scores = None
        if (self.policy.strategy == SelectionStrategy.PRIORITY
                and self.fabric is not None
                and self.fabric.last_scores_step == int(step)
                and self.policy.norm == "l2"):
            # this step's maintenance sweep already measured the drift
            # vs the running checkpoint — reuse it instead of a third
            # full read of params + ckpt
            scores = self.fabric.last_scores
        if not self.inplace_save:
            return sub, scores, None, None, None
        mask, cursor = self._jit_select(self.ckpt, params, rng=sub,
                                        scores=scores)
        return sub, scores, mask, cursor, np.nonzero(np.asarray(mask))[0]

    def _tree_scatter(self, step: int, params: PyTree, sub, scores, mask,
                      cursor, idx) -> jnp.ndarray:
        if mask is None:
            self.ckpt, mask = self._jit_save(self.ckpt, params,
                                             jnp.int32(step), rng=sub,
                                             scores=scores)
            return mask
        from repro.kernels.fused_maintain.ops import tree_scatter_save
        new_values, moved = tree_scatter_save(
            self.ckpt.values, params, idx, self.partition)
        new_saved = jnp.where(mask, jnp.int32(step), self.ckpt.saved_iter)
        self.ckpt = RunningCheckpoint(new_values, new_saved, cursor)
        self.stats["save_bytes_moved"] += moved
        return mask

    def _arena_select(self, step: int, params: PyTree) -> tuple:
        """Arena-mode selection: ``(idx, mask, cursor)`` — the ``k`` blocks
        this partial save writes, as host indices and a host mask."""
        pol = self.policy
        total = self.partition.total_blocks
        k = self.partition.blocks_for_k(pol.fraction)
        cursor = self._ckpt.rr_cursor
        self._rng, sub = jax.random.split(self._rng)
        if pol.strategy == SelectionStrategy.PRIORITY:
            if (self.fabric.last_scores_step == int(step)
                    and self.fabric.last_scores is not None):
                scores = self.fabric.last_scores
            else:
                scores = self._arena_scores(params)
            _, idx = jax.lax.top_k(scores, k)
            idx = np.asarray(idx)
        elif pol.strategy == SelectionStrategy.ROUND_ROBIN:
            c = int(cursor)
            idx = (c + np.arange(k)) % total
            cursor = jnp.int32((c + k) % total)
        elif pol.strategy == SelectionStrategy.RANDOM:
            idx = np.asarray(jax.random.choice(sub, total, (k,),
                                               replace=False))
        else:
            raise ValueError(f"unknown strategy {pol.strategy}")
        mask = np.zeros((total,), bool)
        mask[idx] = True
        return idx, mask, cursor

    def _arena_scatter(self, step: int, params: PyTree, idx, mask,
                       cursor) -> jnp.ndarray:
        """Partial save in arena mode: ONE donated tile scatter into the
        checkpoint arena, sourced from the live arena itself when the
        training state is arena-resident (it *is* this step's values — no
        pack and no replica freshness gating), else from the maintenance
        sweep's replica arena (this step's snapshot — zero extra reads of
        the live tree) or, off-schedule, a fresh pack. O(k·seg_bytes)
        moved, a single dispatch each way."""
        from repro.kernels.fused_maintain.ops import arena_scatter_save
        ck = self._ckpt
        live = self._live_arena(params)
        rep = self.fabric.replicas
        published = (rep is not None and rep.arena is not None
                     and rep.is_fresh(int(step)))
        if self.fabric.cfg.async_maintain and published:
            # async mode: save off the published slot even when the live
            # arena is at hand — the snapshot holds this step's values
            # bit-exactly, and sourcing from it keeps the save's reads
            # off the buffer the next train step is about to donate
            # (arena_local: on a mesh the replica lives on the rotated
            # anti-affine device order and must be re-placed before it
            # can enter a jit with the flat-sharded checkpoint arena)
            src = rep.arena_local()
        elif live is not None:
            src = live
        elif published:
            src = rep.arena_local()
        else:
            src = self._pack_jit(params)
        self._ckpt_arena, moved = arena_scatter_save(
            self._ckpt_arena, src, self._arena_layout, idx,
            use_pallas=self.fabric.cfg.use_pallas)
        new_saved = jnp.where(jnp.asarray(mask), jnp.int32(step),
                              ck.saved_iter)
        self._ckpt = RunningCheckpoint(ck.values, new_saved, cursor)
        self._ckpt_dirty = True
        self.stats["save_bytes_moved"] += moved
        return jnp.asarray(mask)

    def _arena_scores(self, params: PyTree) -> jnp.ndarray:
        """Squared-L2 drift per block, computed arena-native (tile diff +
        segment-sum; a pack first when the live state arrives as a tree)
        — the PRIORITY fallback when this step's maintenance sweep didn't
        already cache the scores."""
        if self._arena_score_jit is None:
            from repro.core.arena import arena_drift_scores, pack_arena
            layout = self._arena_layout

            def _tile_scores(rep, z):
                # dtype-aware word scorer: decodes each word by its
                # stored dtype and handles word-packed tail blocks —
                # bit-identical to the historical f32 tile diff +
                # segment-sum on an all-f32 tail-free layout
                return arena_drift_scores(rep, z, layout)

            def _tree_scores(p, z):
                return _tile_scores(pack_arena(p, layout), z)

            self._arena_score_jit = jax.jit(_tree_scores)
            self._arena_score_live_jit = jax.jit(_tile_scores)
        live = self._live_arena(params)
        if live is not None:
            return self._arena_score_live_jit(live, self._ckpt_arena)
        return self._arena_score_jit(params, self._ckpt_arena)

    def maintain(self, step: int, params: PyTree,
                 own_live: bool = False) -> None:
        """Per-iteration fabric upkeep (replica refresh / parity re-encode
        on their configured intervals). No-op without a fabric.

        When the policy's PRIORITY selection can consume the sweep's
        scores (squared-L2 drift), the running-checkpoint values ride
        along so the arena sweep scores blocks in the same read — the
        loops call maintain() *before* maybe_checkpoint() so a same-step
        save reuses them.

        ``params`` may be the live flat arena (arena-resident training
        state): the sweep then runs pack-free against it directly.
        ``own_live=True`` additionally hands the buffer over as the
        replica itself (no copy) — only for throwaway packs the caller
        will never donate or mutate (see
        :meth:`CheckpointFabric.maintain`)."""
        if self.fabric is None:
            return
        want_scores = (self.policy.strategy == SelectionStrategy.PRIORITY
                       and self.policy.norm == "l2"
                       and self.should_checkpoint(int(step)))
        if not want_scores:
            ckpt_values = None
        elif self._arena_layout is not None:
            # arena mode: the checkpoint arena feeds the sweep directly —
            # no tree materialization on the hot path
            ckpt_values = self._ckpt_arena
        else:
            ckpt_values = self.ckpt.values
        self.fabric.maintain(int(step), params, ckpt_values=ckpt_values,
                             own_live=own_live)

    # -- recovery path ------------------------------------------------------

    def sample_failure(self, fraction: float) -> jnp.ndarray:
        self._rng, sub = jax.random.split(self._rng)
        return sample_failure_mask(sub, self.partition, fraction)

    def sample_domain_failure(self, kind: str = "host",
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Correlated whole-domain failure → (lost mask, failed devices).
        Requires a fabric (it owns the failure-domain topology)."""
        assert self.fabric is not None, "domain failures need a fabric"
        return self.fabric.sample_domain_failure(self._np_rng, kind)

    def on_domain_event(self, params: PyTree, kind: str, index: int,
                        step: Optional[int] = None) -> tuple[PyTree, dict]:
        """Apply one trace event: fail a *specific* domain, recover, and —
        under the fabric's elastic mode — re-home/re-seed/re-stripe. The
        cluster view keeps the domain dead afterwards (trace semantics: the
        view tracks real cluster state) until :meth:`heal_domain`.
        Events on fully-dead domains are skipped."""
        assert self.fabric is not None, "domain events need a fabric"
        lost, failed = self.fabric.domain_failure(kind, index)
        if failed.size == 0:
            return params, {"skipped": True, "kind": kind, "index": index}
        recovered, info = self.on_failure(params, lost,
                                          failed_devices=failed, step=step,
                                          persist_failure=True)
        info["kind"], info["index"] = kind, index
        return recovered, info

    def on_domain_events(self, params: PyTree, events,
                         step: Optional[int] = None) -> tuple[PyTree, dict]:
        """Apply several trace events landing in the SAME step (correlated
        multi-domain loss — the multi-erasure case the RS tier exists
        for). Every event's loss is resolved against the pre-failure view
        *before* any device is marked dead, then the union recovers in ONE
        tier-planned pass: a block that lost both its primary and its
        replica domain sees the combined failure, exactly what a
        simultaneous loss means. A single event routes through
        :meth:`on_domain_event`, bit-identical to the one-event path."""
        assert self.fabric is not None, "domain events need a fabric"
        events = [(str(k), int(i)) for k, i in events]
        if len(events) == 1:
            return self.on_domain_event(params, *events[0], step=step)
        lost = np.zeros((self.partition.total_blocks,), bool)
        failed_parts, applied = [], []
        for kind, index in events:
            ev_lost, ev_failed = self.fabric.domain_failure(kind, index)
            if ev_failed.size == 0:
                continue
            lost |= ev_lost
            failed_parts.append(ev_failed)
            applied.append({"kind": kind, "index": index,
                            "failed_devices": int(ev_failed.size)})
        if not failed_parts:
            return params, {"skipped": True, "events": applied}
        failed = np.unique(np.concatenate(failed_parts))
        recovered, info = self.on_failure(params, lost,
                                          failed_devices=failed, step=step,
                                          persist_failure=True)
        info["events"] = applied
        return recovered, info

    def scrub(self, step: Optional[int] = None) -> dict:
        """Run the fabric's silent-error integrity pass and price it in
        the ledger: detected-and-corrected corruption applies ‖δ′‖² ≈ 0
        (the scrub restored the exact bits), so its ledger entry records
        the detection honestly at zero perturbation — the *undetected*
        window between scrubs is what a soak prices by comparing scrub
        cadence against the flip schedule. No-op (``checked=False``)
        without an integrity-capable fabric."""
        if self.fabric is None or not getattr(
                self.fabric.parity, "supports_integrity", False):
            return {"checked": False, "detected": 0, "corrected": 0,
                    "reports": []}
        out = self.fabric.scrub(step=step)
        if self.recorder.enabled and out["detected"]:
            self.recorder.record_recovery(
                step=None if step is None else int(step),
                lost_blocks=0,
                tier_counts={"SILENT_ERROR": out["detected"]},
                applied_sq=0.0,
                silent_detected=out["detected"],
                silent_corrected=out["corrected"])
        return out

    def heal_domain(self, kind: str, index: int,
                    params: Optional[PyTree] = None,
                    step: Optional[int] = None) -> dict:
        """Re-admit a healed domain to the fabric's cluster view (elastic
        fabrics also rebalance placement onto the restored capacity)."""
        assert self.fabric is not None, "domain healing needs a fabric"
        return self.fabric.heal_domain(kind, index, params=params, step=step)

    def on_failure(self, params: PyTree, lost_mask: jnp.ndarray,
                   failed_devices=None, step: Optional[int] = None,
                   persist_failure: Optional[bool] = None,
                   ) -> tuple[PyTree, dict]:
        """Recover from a partial failure. Returns (params', diagnostics).

        With a fabric, recovery routes through the tier planner: each lost
        block resolves to the cheapest surviving redundancy tier, and the
        diagnostics gain per-tier block counts and perturbation norms.
        ``failed_devices`` names the dead devices of a correlated failure
        (None = the paper's uniform block-loss model). ``persist_failure``
        (see :meth:`CheckpointFabric.on_failure`) keeps the devices dead in
        the cluster view — the trace-driven path sets it; one-shot
        experiments default to the fabric's ``elastic`` flag.

        ``params`` may be the live flat arena (arena-resident training
        state): recovery then decodes it once, runs the tier-planned tree
        recovery, and returns the recovered state re-packed as an arena —
        ONE contiguous write the caller drops straight back into its
        ``ArenaTrainState`` (the cold path pays the two conversions; the
        hot path never does).
        """
        with self.recorder.span("scar/recovery",
                                step=None if step is None else int(step)):
            live = self._live_arena(params)
            if live is None:
                return self._recover(params, lost_mask, failed_devices,
                                     step, persist_failure)
            with self.recorder.span("scar/recovery/decode"):
                tree = self.unpack_live(live)
            recovered, info = self._recover(tree, lost_mask, failed_devices,
                                            step, persist_failure)
            with self.recorder.span("scar/recovery/repack"):
                return self.pack_live(recovered), info

    def _recover(self, params: PyTree, lost_mask, failed_devices,
                 step: Optional[int], persist_failure: Optional[bool],
                 ) -> tuple[PyTree, dict]:
        """Tree-form recovery behind :meth:`on_failure`."""
        if self.recorder.enabled:
            self.recorder.event(
                "failure", step=None if step is None else int(step),
                lost_blocks=int(np.asarray(lost_mask, bool).sum()),
                failed_devices=(0 if failed_devices is None
                                else int(np.asarray(failed_devices).size)))
        ckpt = self.ckpt
        if self.store is not None and getattr(self.store, "must_reload", False):
            values = self.store.read_all()
            ckpt = RunningCheckpoint(values, ckpt.saved_iter, ckpt.rr_cursor)
        if self.fabric is not None:
            lost = np.asarray(lost_mask, bool)
            info = perturbation_norms(params, ckpt, jnp.asarray(lost),
                                      self.partition)
            disk_reader = None
            if self.store is not None:
                disk_reader = getattr(self.store, "read_blocks",
                                      self.store.read_all)
            recovered, tier_info = self.fabric.on_failure(
                params, ckpt.values, lost,
                failed_devices=failed_devices, step=step,
                disk_reader=disk_reader, persist_failure=persist_failure)
            info["applied_sq"] = tree_sq_norm(recovered, params)
            info["lost_blocks"] = int(lost.sum())
            info.update(tier_info)
            # per-event accounting: the trace-driven soak loops read this
            # off the controller to chart tier usage over a failure schedule
            self.stats["events"].append({
                "step": None if step is None else int(step),
                "lost_blocks": info["lost_blocks"],
                "failed_devices": info.get("failed_devices", 0),
                "tier_counts": info.get("tier_counts"),
                "applied_sq": float(info["applied_sq"]),
                "placement": info.get("placement"),
            })
        else:
            recovered, info = apply_failure_and_recover(
                params, ckpt, lost_mask, self.policy.recovery, self.partition)
        self.stats["recoveries"] += 1
        out = {k: (float(v) if hasattr(v, "item") else v)
               for k, v in info.items()}
        if self.recorder.enabled:
            # ledger entry + structured recovery event: the measured
            # ||δ'||² prices this failure in Thm-3.2/4.1 iterations.
            # Async recoveries also carry which epoch was actually
            # restored — a stale published slot is priced explicitly.
            extra = {}
            if "recovered_epoch" in out:
                extra["recovered_epoch"] = int(out["recovered_epoch"])
                extra["staleness"] = int(out.get("staleness", 0))
            self.recorder.record_recovery(
                step=None if step is None else int(step),
                lost_blocks=int(out.get("lost_blocks", 0)),
                tier_counts=out.get("tier_counts"),
                applied_sq=float(out.get("applied_sq", 0.0)),
                tier_sq=out.get("tier_sq"),
                failed_devices=out.get("failed_devices", 0),
                **extra)
        return recovered, out

    # -- analysis helpers ---------------------------------------------------

    def block_drift(self, params: PyTree) -> jnp.ndarray:
        """Per-block distance between live params and the running ckpt."""
        return block_scores(params, self.ckpt.values, self.partition,
                            self.norm_fn)
