"""The checkpoint fabric facade: cluster view + replicas + parity + planner.

``CheckpointFabric`` is the single object the FTController (and the
training loops) talk to:

- ``maintain(step, params)``      — refresh replicas / re-encode parity on
                                    their configured intervals (idempotent
                                    per step).
- ``sample_domain_failure(...)``  — correlated whole-domain failure: the
                                    lost-block mask plus the failed devices.
- ``domain_failure(kind, index)`` — the lost mask for one *specific* domain
                                    (trace-driven injection).
- ``on_failure(...)``             — tier-plan the lost blocks, recover each
                                    from the cheapest surviving tier, and
                                    report per-tier perturbation norms. With
                                    ``elastic=True`` the failed devices stay
                                    dead in the :class:`ClusterView` and the
                                    placement engine re-homes the recovered
                                    blocks, re-seeds replicas, and
                                    re-stripes parity over the survivors.
- ``heal_domain(kind, index)``    — re-admit a healed domain to the view
                                    (and, elastic, rebalance onto it).

All components share one mutable :class:`~repro.fabric.placement.ClusterView`
— `block_device_homes` is only the *initial* placement; the view owns the
current one.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import BlockPartition
from repro.fabric.domains import FailureDomainMap
from repro.fabric.parity import ParityCodec
from repro.fabric.placement import ClusterView, rebalance_homes, rehome_blocks
from repro.fabric.replica import ReplicaSet
from repro.fabric.tiers import TieredRecovery
from repro.sharding.partition import block_device_homes
from repro.telemetry.recorder import NULL_RECORDER, Histogram

PyTree = Any


@dataclasses.dataclass(frozen=True)
class FabricConfig:
    n_devices: int = 8
    devices_per_host: int = 2
    hosts_per_rack: int = 2
    replicate: bool = True
    replicate_interval: int = 1    # steps between replica refreshes
    parity: bool = True
    parity_group: int = 4          # members per XOR parity group
    parity_interval: int = 1       # steps between parity re-encodes
    rs_parity: int = 0             # 0 = XOR codec; m >= 1 = RS(k, m) codec
                                   # with m GF(256) parity rows per group
    elastic: bool = False          # post-failure re-homing/re-seeding
    async_maintain: bool = False   # double-buffered pipelined arena sweep
    use_pallas: Optional[bool] = None   # None = auto: Pallas on TPU only

    def __post_init__(self):
        if self.replicate_interval < 1 or self.parity_interval < 1:
            raise ValueError("maintenance intervals must be >= 1")
        if self.parity_group < 2:
            raise ValueError("parity_group must be >= 2: a 1-member group "
                             "degenerates the XOR code to a bare copy")
        if self.rs_parity < 0:
            raise ValueError("rs_parity must be >= 0 (0 selects the XOR "
                             "codec, m >= 1 the RS(k, m) codec)")


class CheckpointFabric:
    def __init__(self, partition: BlockPartition,
                 cfg: Optional[FabricConfig] = None,
                 homes: Optional[np.ndarray] = None,
                 recorder: Optional[Any] = None,
                 mesh: Optional[Any] = None):
        self.cfg = cfg or FabricConfig()
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.partition = partition
        self.domains = FailureDomainMap(self.cfg.n_devices,
                                        self.cfg.devices_per_host,
                                        self.cfg.hosts_per_rack)
        # flat parameter arena: the canonical hot-path representation —
        # requires both tiers (the sweep's pack is the replica write, its
        # XOR routing needs the parity striping) and word-packable leaf
        # dtypes (f32/bf16/f16/fp8/int8… stored as raw bit patterns; only
        # f64/int64/complex/bool gate — they fall back to the component
        # passes with a warn+event upstream). With a
        # ``mesh`` the layout is built with one tile-aligned shard per
        # device (``shards=mesh size``) so every device owns a contiguous
        # span and the sweep runs shard-local (see arena.py "Sharded
        # form"); the meshed fabric additionally requires an all-f32
        # model for now — a quantized layout's value domain is not
        # tile-divisible, so the flat optimizer sharding would not line
        # up with the word shards.
        self.arena_layout = None
        if self.cfg.replicate and self.cfg.parity:
            from repro.core.arena import arena_compatible, build_arena_layout
            uniform_f32 = all(np.dtype(l.dtype) == np.dtype(np.float32)
                              for l in partition.leaves)
            if arena_compatible(partition) \
                    and (mesh is None or uniform_f32):
                shards = 1
                if mesh is not None:
                    shards = int(np.asarray(mesh.devices).size)
                self.arena_layout = build_arena_layout(partition,
                                                       shards=shards)
        if self.cfg.async_maintain and self.arena_layout is None:
            raise ValueError(
                "async_maintain needs an arena layout (both tiers on and "
                "word-packable leaf dtypes): the double-buffer snapshot and "
                "deferred fence only exist for the arena sweep")
        # SPMD binding: mesh position i (row-major) IS fabric logical
        # device i, so the sharded arena's span owners line up with the
        # failure-domain map. Requires the mesh to cover the configured
        # topology exactly at construction (shrunk meshes only ever come
        # from resize_mesh, which carries the surviving logical ids).
        self.mesh = None
        self._mesh_logical = None
        self._arena_sharding = None
        self._replica_sharding = None
        self._xfer_split = (0, 0, 0)    # (local, ici, dcn) bytes/transfer
        if mesh is not None:
            n = int(np.asarray(mesh.devices).size)
            if n != self.cfg.n_devices:
                raise ValueError(
                    f"mesh has {n} devices but the fabric topology is "
                    f"configured for {self.cfg.n_devices} "
                    "(FabricConfig.n_devices must match the mesh so "
                    "failure domains map onto real devices)")
            if self.arena_layout is None:
                raise ValueError(
                    "a meshed fabric needs the sharded arena layout: both "
                    "tiers on and an all-f32 model (quantized dtypes are "
                    "single-host-arena only for now) — the component "
                    "passes have no sharded form")
            self._bind_mesh(mesh, np.arange(n, dtype=np.int32))
        if homes is not None:
            initial = np.asarray(homes, np.int32)
        elif self.mesh is not None:
            # span-derived homes: a block lives where the sharded arena
            # places its first tile, so "primary home" and "owning shard"
            # agree and the sweep's writes are home-local by construction
            from repro.core.arena import arena_block_homes
            initial = arena_block_homes(self.arena_layout).astype(np.int32)
        else:
            initial = block_device_homes(partition, self.cfg.n_devices)
        self.view = ClusterView(self.domains, initial)
        self.replicas = (ReplicaSet(partition, self.view)
                         if self.cfg.replicate else None)
        self.parity = None
        # GSPMD cannot partition a Mosaic kernel, and the codecs read
        # frames of the sharded arena: on a mesh they run their jnp paths
        codec_pallas = False if mesh is not None else self.cfg.use_pallas
        if self.cfg.parity:
            if self.cfg.rs_parity > 0:
                from repro.fabric.rs import RSCodec
                self.parity = RSCodec(partition, self.view,
                                      group_size=self.cfg.parity_group,
                                      n_parity=self.cfg.rs_parity,
                                      use_pallas=codec_pallas)
            else:
                self.parity = ParityCodec(partition, self.view,
                                          group_size=self.cfg.parity_group,
                                          use_pallas=codec_pallas)
        self.planner = TieredRecovery(partition, self.view,
                                      replicas=self.replicas,
                                      parity=self.parity)
        if self.replicas is not None and self._arena_sharding is not None:
            self.replicas.main_sharding = self._arena_sharding
        self.last_maintained_step = -1
        # the arena routing, the sweep program and the traffic model are
        # functions of the parity striping, not of the alive set: one
        # entry per striping seen, each part built lazily (see
        # _program_entry). A loss or heal that leaves the striping as it
        # was rebuilds nothing; an elastic re-stripe back to a striping
        # seen before reuses its entry; resize_mesh, which replaces the
        # arena layout, clears them
        self._programs: dict[tuple, dict] = {}
        # (key, view version) the sweep program was last served under
        self._program_served: Optional[tuple] = None
        self._pack_fn = None
        self.last_scores = None
        self.last_scores_step = -1
        # True once a maintain has been fed the live arena itself
        # (arena-resident training state): every sweep from then on is
        # pack-free and the accounting switches to the resident model
        self.live_arena_mode = False
        # async maintenance (cfg.async_maintain): two-slot snapshot arena
        # with an epoch/publish protocol. ``_async_maintain`` copies the
        # live arena into the inactive slot (one async device copy behind
        # optimization_barrier), flips ``_active_slot``, dispatches the
        # sweep against the published slot, and returns without fencing —
        # the sweep overlaps the trainer's next step. ``published_epoch``
        # is the step whose snapshot the live tiers currently hold (at
        # Python level the flip is atomic: replica + parity + scores are
        # always ingested for the same step, never torn). ``_pending``
        # holds the one in-flight sweep; it is settled (fenced) at the
        # next maintain, at any consume point (failure, checkpoint,
        # shutdown), or via ``block_until_maintained``.
        self._slots: list[Any] = [None, None]
        self._active_slot = 0
        self.published_epoch = -1
        self._pending: Optional[dict] = None
        self._snap_donate = None
        self._snap_fresh = None
        # donation lets the snapshot reuse the slot retired two epochs
        # ago; the CPU backend ignores donation (with a warning per call),
        # so fall back to fresh copies there — the protocol is identical
        self._donate_slots = jax.default_backend() not in ("cpu",)
        self.async_hidden_seconds = 0.0
        self.async_total_seconds = 0.0
        self.fence_hist = Histogram()
        self.stats = self.recorder.scope("fabric", {
            "replica_refreshes": 0, "parity_encodes": 0,
            "recoveries": 0, "rehomes": 0, "heals": 0,
            "arena_maintains": 0,
            "arena_resident_maintains": 0, "live_packs": 0,
            "async_maintains": 0, "fence_count": 0,
            "maintain_bytes_moved": 0,
            "ici_bytes_moved": 0, "dcn_bytes_moved": 0,
            "mesh_resizes": 0, "tier_fallbacks": 0,
            "rs_arena_encodes": 0, "scrubs": 0,
            "silent_errors_detected": 0, "silent_errors_corrected": 0,
            # maintenance programs built, and served again from the cache
            # after the view moved (a loss, heal or re-stripe)
            "maintain_program_builds": 0, "maintain_program_reuses": 0,
            "arena_padding_ratio": 0.0,
            # which arena sweep the last built program runs ("pallas" or
            # "jnp") and, for "jnp", why the Pallas kernel is not eligible
            "arena_sweep": "", "arena_sweep_reason": ""})
        if self.arena_layout is not None:
            # gauge, not a counter: pad words / payload words of the live
            # layout — the number tail packing shrinks (run-report +
            # maint_arena_padding bench read it from here)
            self.stats["arena_padding_ratio"] = float(
                self.arena_layout.padding_ratio)
        if self.recorder.enabled:
            self.recorder.adopt_histogram("fabric/fence_seconds",
                                          self.fence_hist)

    def attach_recorder(self, recorder: Any) -> None:
        """Late-bind a recorder (controller attach path for prebuilt
        fabrics). No-op if ``recorder`` is null or one is already live —
        the stats dict is re-registered by reference, so existing readers
        keep working."""
        if recorder is None or not getattr(recorder, "enabled", False) \
                or self.recorder.enabled:
            return
        self.recorder = recorder
        self.stats = recorder.scope("fabric", self.stats)
        recorder.adopt_histogram("fabric/fence_seconds", self.fence_hist)

    @property
    def homes(self) -> np.ndarray:
        """Current primary placement (the view's, not the initial one)."""
        return self.view.homes

    # -- SPMD mesh binding ---------------------------------------------------

    def _bind_mesh(self, mesh, logical_ids: np.ndarray) -> None:
        """Bind the fabric to a device mesh: mesh position ``i`` ↔ fabric
        logical device ``logical_ids[i]``. Computes the flat arena
        sharding, the anti-affine replica sharding (shard ``j``'s copy
        lands a whole failure domain away — the rotation maximizing
        cross-host, then cross-rack, pairs in the *bound* topology), and
        the per-transfer local/ICI/DCN byte split the maintain events
        report (by physical slice, not by failure domain)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.sharding.partition import arena_sharding
        self.mesh = mesh
        self._mesh_logical = np.asarray(logical_ids, np.int32)
        self._arena_sharding = arena_sharding(mesh)
        devs = np.asarray(mesh.devices).reshape(-1)
        n = devs.size
        hosts = np.asarray(self.domains.host_of(self._mesh_logical))
        racks = np.asarray(self.domains.rack_of(self._mesh_logical))
        best, shift = (-1, -1), 0
        for s in range(1, n):
            dst = (np.arange(n) + s) % n
            key = (int(np.sum(hosts[dst] != hosts)),
                   int(np.sum(racks[dst] != racks)))
            if key > best:
                best, shift = key, s
        if shift == 0:
            self._replica_sharding = None   # single device: copy in place
            self._xfer_split = (0, 0, 0)
            return
        rolled = np.roll(devs, -shift)      # span j -> devs[(j+shift) % n]
        self._replica_sharding = NamedSharding(
            Mesh(rolled, ("arena",)), PartitionSpec("arena"))
        # classify each span's replica hop by the wire it crosses: chips
        # of one slice talk over ICI, slices over DCN (same device = no
        # wire at all). The failure-domain map above is logical — a
        # one-host mesh may declare every chip its own host domain — so
        # the wire comes from where the devices physically are.
        dst = (np.arange(n) + shift) % n
        wire = np.asarray([getattr(d, "slice_index", d.process_index)
                           for d in devs])
        sw = self.arena_layout.shard_words * 4
        moved = dst != np.arange(n)
        local = int(np.sum(~moved)) * sw
        ici = int(np.sum(moved & (wire[dst] == wire))) * sw
        dcn = int(np.sum(moved & (wire[dst] != wire))) * sw
        self._xfer_split = (local, ici, dcn)

    def _replica_xfer(self, rep):
        """Ship the replica arena to its anti-affine homes: one rotated
        ``device_put`` — every device sends its span to a device in a
        different failure domain (a true D2D transfer under SPMD; a no-op
        copy without a mesh). Books the ICI/DCN split."""
        if self._replica_sharding is None:
            return rep
        out = jax.device_put(rep, self._replica_sharding)
        _, ici, dcn = self._xfer_split
        self.stats["ici_bytes_moved"] += ici
        self.stats["dcn_bytes_moved"] += dcn
        return out

    # -- maintenance ---------------------------------------------------------

    def maintain(self, step: int, params: PyTree,
                 ckpt_values: Optional[PyTree] = None,
                 force: bool = False, own_live: bool = False) -> None:
        """Refresh redundancy tiers from live params (idempotent per step).

        With an arena layout and both tiers due, the refresh runs as one
        arena sweep (``kernels/fused_maintain``): the live values are read
        once and yield the replica snapshot, the XOR parity frames, and —
        when ``ckpt_values`` is passed — per-block PRIORITY scores against
        the running checkpoint, cached on ``last_scores`` for the
        controller's next partial save. Off-interval tree input and
        fabrics without an arena layout (one tier off, or a leaf dtype
        the arena cannot pack) run the component passes
        (``replicas.refresh`` / ``parity.encode``).

        ``params`` may be the live flat arena itself (arena-resident
        training state, requires ``arena_layout``): the sweep then runs
        pack-free — pure 2-read/1-write — and an off-interval step with
        only one tier due still takes the full sweep (the live state has
        no tree form for the per-component passes; refreshing the other
        tier early is strictly fresher, never stale).

        ``own_live=True`` (arena input only) transfers ownership of that
        buffer to the fabric: it becomes the replica directly, no copy —
        for tree-stepping callers whose per-iteration pack is throwaway.
        The caller must never donate or mutate the arena afterwards;
        truly resident state (donated through the train step) must leave
        this False so the sweep emits an independent replica copy.
        """
        step = int(step)
        if step == self.last_maintained_step and not force:
            return
        with self.recorder.span("scar/maintain", step=step):
            self._maintain(step, params, ckpt_values, force, own_live)

    def _maintain(self, step: int, params: PyTree, ckpt_values,
                  force: bool, own_live: bool) -> None:
        from repro.core.arena import as_live_arena
        # note: without an arena layout a 1-D input is treated as what it
        # always was — a bare single-leaf param tree on the per-component
        # paths (a genuine live arena can only come from an arena-capable
        # controller, which implies the layout exists here)
        live = as_live_arena(params, self.arena_layout)
        due_replica, due_parity = self.maintenance_due(step, force=force)
        b0 = self.stats["maintain_bytes_moved"]
        i0 = self.stats["ici_bytes_moved"]
        d0 = self.stats["dcn_bytes_moved"]
        if self.cfg.async_maintain and live is not None \
                and (due_replica or due_parity):
            # pipelined path: dispatch only, no fence — the sweep runs
            # under the trainer's next step. The sweep's [dispatch,
            # settle] interval is kept when the pending sweep settles,
            # so the trace shows the true overlap.
            self._async_maintain(step, live, ckpt_values, own_live=own_live)
            self.last_maintained_step = step
            if self.recorder.enabled:
                self.recorder.event(
                    "maintain", step=step, mode="arena_async",
                    bytes_moved=self.stats["maintain_bytes_moved"] - b0,
                    ici_bytes=self.stats["ici_bytes_moved"] - i0,
                    dcn_bytes=self.stats["dcn_bytes_moved"] - d0,
                    replica=due_replica, parity=due_parity)
            return
        mode = "components"
        if self.arena_layout is not None and (
                (due_replica and due_parity)
                or (live is not None and (due_replica or due_parity))):
            self._arena_maintain(step, params, ckpt_values,
                                 own_live=own_live)
            mode = ("arena_resident" if self.live_arena_mode
                    and live is not None and not own_live else "arena")
        else:
            t = self._traffic_model()
            if due_replica:
                self.replicas.refresh(step, params)
                self.stats["replica_refreshes"] += 1
                self.stats["maintain_bytes_moved"] += t["replica_pass"]
            if due_parity:
                self.parity.encode(step, params)
                self.stats["parity_encodes"] += 1
                self.stats["maintain_bytes_moved"] += t["parity_pass"]
            if due_replica or due_parity:
                self.published_epoch = step
        self.last_maintained_step = step
        if self.recorder.enabled:
            self.recorder.event(
                "maintain", step=step, mode=mode,
                bytes_moved=self.stats["maintain_bytes_moved"] - b0,
                ici_bytes=self.stats["ici_bytes_moved"] - i0,
                dcn_bytes=self.stats["dcn_bytes_moved"] - d0,
                replica=due_replica, parity=due_parity)

    def _arena_maintain(self, step: int, params: PyTree,
                        ckpt_values, own_live: bool = False) -> None:
        """One pack + ONE kernel dispatch for the whole model: the pack
        is the replica write (arena form), the sweep emits group-sorted
        XOR parity and PRIORITY score partials. ``ckpt_values`` may be
        the running checkpoint as an arena (the controller's canonical
        form — zero conversion), a PyTree (packed once), or None (no
        scoring this step).

        With arena-resident live state (``params`` already the flat
        arena) there is no pack at all: the sweep reads the live and
        checkpoint arenas once each and emits the replica copy from the
        same read — the accounted bytes drop by the live tree's size."""
        from repro.core.arena import as_live_arena
        fn = self._arena_maintain_fn()
        z = self._as_arena(ckpt_values)
        is_arena = as_live_arena(params, self.arena_layout) is not None
        owned = own_live and is_arena
        resident = is_arena and not owned
        rep, scores, parity = fn(params, z, own_live=owned)
        self.replicas.ingest_arena(step, self._replica_xfer(rep),
                                   self.arena_layout)
        if self.parity.needs_arena_encode:
            # RS rows re-encode from the sweep's snapshot arena (the same
            # buffer the replica tier stores, pre-rotation — so the
            # refreshed_step == encoded_step arena recovery route and the
            # integrity scrub both see one consistent coded snapshot)
            self.parity.encode_from_arena(step, rep, self.arena_layout)
            self.stats["rs_arena_encodes"] += 1
        else:
            self.parity.ingest(step, parity)
        if z is not None:
            self.last_scores = scores
            self.last_scores_step = step
        self.stats["replica_refreshes"] += 1
        self.stats["parity_encodes"] += 1
        self.stats["arena_maintains"] += 1
        if is_arena:
            # either way every maintain from here on is an arena sweep —
            # the seed staging never materializes (redundancy_nbytes)
            self.live_arena_mode = True
        if resident:
            self.stats["arena_resident_maintains"] += 1
        self.stats["maintain_bytes_moved"] += self._traffic_model()[
            "arena_owned" if owned else
            "arena_resident" if resident else "arena"]
        self.published_epoch = int(step)

    def _async_maintain(self, step: int, live, ckpt_values,
                        own_live: bool = False) -> None:
        """Dispatch one pipelined sweep epoch and return immediately.

        Pipeline depth is one: the previous epoch's sweep is settled
        first, so the fence wait here is ``max(0, sweep - step_time)`` —
        exactly the stall the overlap failed to hide (zero when the
        sweep fits under a step). Then the live arena is snapshotted
        into the inactive slot (``optimization_barrier`` forces a real
        copy — the live buffer is donated through the train step and
        must not be aliased), the slot flips, ``published_epoch``
        advances, and the owned sweep (the snapshot IS the replica — no
        second copy) is dispatched against the published slot. Nothing
        blocks: JAX's async dispatch runs the copy + sweep while the
        caller computes step N+1, and any consumer that reaches the
        output arrays first waits on dataflow, never on a torn slot.

        ``own_live=True`` (tree-stepping callers, throwaway pack): the
        pack is adopted as the snapshot directly — no copy at all, same
        as the sync owned path, still dispatched without a fence."""
        self._settle_pending()
        span_t0 = self.recorder.tracer.now() if self.recorder.enabled \
            else 0.0
        t0 = time.perf_counter()
        fn = self._arena_maintain_fn()
        z = self._as_arena(ckpt_values)
        if own_live:
            snap = live
        else:
            inactive = 1 - self._active_slot
            stale = self._slots[inactive]
            if self._snap_fresh is None:
                def snapshot_arena(a):
                    return jax.lax.optimization_barrier(a)

                def snapshot_arena_into(slot, a):
                    return jax.lax.optimization_barrier(a)

                self._snap_fresh = jax.jit(snapshot_arena)
                self._snap_donate = jax.jit(snapshot_arena_into,
                                            donate_argnums=(0,))
            if self._donate_slots and stale is not None \
                    and stale.shape == live.shape \
                    and stale.dtype == live.dtype:
                # reuse the buffer retired two epochs ago (the published
                # slot moved on; nothing references this one any more)
                snap = self._snap_donate(stale, live)
            else:
                snap = self._snap_fresh(live)
            self._slots[inactive] = snap
            self._active_slot = inactive
        _, scores, parity = fn(snap, z, own_live=True)
        self.replicas.ingest_arena(step, self._replica_xfer(snap),
                                   self.arena_layout)
        if self.parity.needs_arena_encode:
            # RS re-encode rides the same async dispatch — no fence here;
            # _settle_pending blocks on the parity rows like the XOR path
            self.parity.encode_from_arena(step, snap, self.arena_layout)
            self.stats["rs_arena_encodes"] += 1
        else:
            self.parity.ingest(step, parity)
        if z is not None:
            self.last_scores = scores
            self.last_scores_step = step
        self.live_arena_mode = True
        self.published_epoch = int(step)
        self._pending = {"step": int(step), "t0": t0, "span_t0": span_t0}
        self.stats["replica_refreshes"] += 1
        self.stats["parity_encodes"] += 1
        self.stats["arena_maintains"] += 1
        self.stats["async_maintains"] += 1
        self.stats["maintain_bytes_moved"] += self._traffic_model()[
            "arena_owned" if own_live else "arena_async"]

    @property
    def has_pending_maintenance(self) -> bool:
        """True while an async sweep epoch is dispatched but not yet
        settled (consumers fence via :meth:`block_until_maintained`)."""
        return self._pending is not None

    def _settle_pending(self) -> float:
        """Fence the in-flight async sweep (no-op without one); returns
        the seconds actually waited. Books the epoch's hidden/total time
        into the overlap-efficiency accounting and keeps (with a recorder)
        the ``scar/async_sweep`` record covering [dispatch, fence] — the
        interval the Chrome trace shows overlapping the next
        ``scar/step/train``."""
        p = self._pending
        if p is None:
            return 0.0
        self._pending = None
        w0 = time.perf_counter()
        if self.parity is not None and self.parity.parity is not None:
            jax.block_until_ready(self.parity.parity)
        if self.replicas is not None and self.replicas.arena is not None:
            jax.block_until_ready(self.replicas.arena)
        now = time.perf_counter()
        wait = now - w0
        total = now - p["t0"]
        self.fence_hist.observe(wait)
        self.stats["fence_count"] += 1
        self.async_total_seconds += total
        self.async_hidden_seconds += max(0.0, total - wait)
        if self.recorder.enabled:
            self.recorder.gauge("fabric/overlap_efficiency").set(
                self.overlap_efficiency())
            self.recorder.tracer.record(
                "scar/async_sweep", p["span_t0"], self.recorder.tracer.now(),
                step=p["step"], mode="arena_async")
        return wait

    def overlap_efficiency(self) -> float:
        """Fraction of async sweep wall-clock hidden under the trainer's
        compute (0.0 until the first settled async epoch)."""
        if self.async_total_seconds <= 0.0:
            return 0.0
        return self.async_hidden_seconds / self.async_total_seconds

    def _as_arena(self, ckpt_values):
        """Coerce checkpoint values to arena form (None passes through)."""
        if ckpt_values is None:
            return None
        if isinstance(ckpt_values, (jnp.ndarray, np.ndarray)) \
                and getattr(ckpt_values, "ndim", None) == 1:
            assert ckpt_values.size == self.arena_layout.total_words, \
                "checkpoint arena does not match this fabric's layout"
            return ckpt_values
        if self._pack_fn is None:
            from repro.core.arena import arena_pack_program
            self._pack_fn = arena_pack_program(self.arena_layout,
                                               self._arena_sharding)
        return self._pack_fn(ckpt_values)

    def _program_entry(self) -> tuple[tuple, dict]:
        """The cache key of what the arena routing, the sweep program and
        the traffic model are built from, and its entry: the parity
        striping (the codec's ``striping``, set once per cut), the arena's
        shard count and the sweep's backend. The alive set is not part of
        it."""
        key = (None if self.parity is None else self.parity.striping,
               None if self.arena_layout is None
               else self.arena_layout.shards,
               self.cfg.use_pallas)
        return key, self._programs.setdefault(key, {})

    def _arena_routing(self):
        """The current striping's arena routing, built once per striping
        and read by both the sweep program and the traffic model."""
        _, entry = self._program_entry()
        if "routing" not in entry:
            from repro.kernels.fused_maintain.ops import arena_routing
            entry["routing"] = arena_routing(
                self.arena_layout, self.parity.layout, self.parity.group_of)
        return entry["routing"]

    def _arena_maintain_fn(self):
        """The arena sweep program for the parity's current striping,
        built the first time that striping is swept (under
        ``scar/maintain/build``). A view change that keeps the striping
        (every loss and heal of a non-elastic fabric) and an elastic
        re-stripe back to a striping seen before build nothing; a call
        after the view moved that finds its striping's program books a
        reuse."""
        key, entry = self._program_entry()
        prog = entry.get("arena")
        served = (key, self.view.version)
        if prog is None:
            with self.recorder.span("scar/maintain/build"):
                prog = entry["arena"] = self._build_arena_program()
            self.stats["maintain_program_builds"] += 1
        elif self._program_served != served:
            self.stats["maintain_program_reuses"] += 1
        self._program_served = served
        return prog

    def _build_arena_program(self):
        # the host side of a build: routing tables for the striping (the
        # program's trace and compile follow at its first call, in the
        # caller's span)
        from repro.kernels.fused_maintain.ops import ArenaMaintainProgram
        prog = ArenaMaintainProgram(
            self.partition, self.arena_layout, self.parity.layout,
            self.parity.group_of, self.parity.n_groups,
            use_pallas=self.cfg.use_pallas,
            out_sharding=self._arena_sharding,
            routing=self._arena_routing())
        self.stats["arena_sweep"] = prog.sweep
        self.stats["arena_sweep_reason"] = prog.sweep_reason
        return prog

    def block_until_maintained(self) -> None:
        """Block until the last maintenance sweep's device work is done
        (dispatch returns early under async execution). Timing-attribution
        helper for loops that report per-step maintenance overhead — owns
        the knowledge of which tensor represents the sweep's completion.
        With a pending async epoch this is the deferred fence: it settles
        the pending sweep (books overlap accounting + the sweep's record)
        rather than bare-blocking."""
        if self._pending is not None:
            self._settle_pending()
            return
        if self.parity is not None and self.parity.parity is not None:
            jax.block_until_ready(self.parity.parity)
        elif self.replicas is not None and self.replicas.arena is not None:
            jax.block_until_ready(self.replicas.arena)

    def maintenance_due(self, step: int,
                        force: bool = False) -> tuple[bool, bool]:
        """(replica due, parity due) at ``step`` under the configured
        intervals — what :meth:`maintain` would actually refresh. Lets
        tree-stepping callers skip preparing a live value (e.g. the
        classic runners' shared pack) on steps where nothing reads it."""
        step = int(step)
        due_replica = self.replicas is not None and (
            force or step % self.cfg.replicate_interval == 0)
        due_parity = self.parity is not None and (
            force or step % self.cfg.parity_interval == 0
            or self.parity.parity is None)
        return due_replica, due_parity

    def is_fresh(self, step: int) -> bool:
        """True when every configured tier holds this step's live values —
        an off-interval :meth:`maintain` can run without refreshing a tier,
        so ``last_maintained_step`` alone does not imply freshness."""
        step = int(step)
        if self.replicas is not None and not self.replicas.is_fresh(step):
            return False
        if self.parity is not None and not self.parity.is_fresh(step):
            return False
        return True

    def invalidate_scores(self) -> None:
        """Drop the cached PRIORITY scores (the controller calls this
        after a partial save mutates the running checkpoint — the drift
        they measured no longer exists)."""
        self.last_scores = None
        self.last_scores_step = -1

    def _traffic_model(self) -> dict[str, int]:
        """Analytic bytes per maintenance step under the current striping
        (cached per striping, beside its routing and program)."""
        _, entry = self._program_entry()
        if "traffic" not in entry:
            model = sum(
                int(np.prod(l.shape) if l.shape else 1)
                * np.dtype(l.dtype).itemsize for l in self.partition.leaves)
            if self.parity is not None:
                from repro.kernels.fused_maintain.ops import maintain_traffic
                arena = self.arena_layout is not None
                t = dict(maintain_traffic(
                    self.partition, self.parity.layout, self.parity.n_groups,
                    self.parity.members.shape[1],
                    arena_layout=self.arena_layout,
                    routing=self._arena_routing() if arena else None))
                # per-component splits for off-interval steps: the scoring
                # pass (2·model) only happens at PRIORITY checkpoint time
                # on the seed path, so it is excluded from both
                t["parity_pass"] = t["seed"] - 4 * t["model"]
            else:
                t = {"seed": 2 * model, "model": model, "parity": 0,
                     "staging_seed": 0, "parity_pass": 0}
            t["replica_pass"] = 2 * t["model"]
            entry["traffic"] = t
        return entry["traffic"]

    def redundancy_state(self) -> dict:
        """Cheap per-step health snapshot of the redundancy tiers under
        the view's *current* placement (pure metadata — no tensor data is
        touched, safe to call every step of a soak):

        - ``replica_alive_frac`` — fraction of replicas homed on alive
          devices;
        - ``parity_groups_ok_frac`` — fraction of parity groups whose
          parity home and every member's primary home are alive (the
          precondition for a free single-erasure reconstruction of the
          next failure);
        - ``full`` — every configured tier fully placed on live hardware,
          i.e. the next domain loss is guaranteed to recover from the
          live-value tiers.
        """
        rep_frac = par_frac = 1.0
        if self.replicas is not None:
            rep_frac = float(np.mean(
                self.view.alive[self.replicas.replica_homes]))
        if self.parity is not None:
            members = self.parity.members
            valid = members >= 0
            homes_ok = np.where(
                valid, self.view.alive[self.view.homes[
                    np.where(valid, members, 0)]], True).all(axis=1)
            # XOR homes are (n_groups,), RS homes (n_groups, m): a group
            # is fully placed only when every parity row's home is alive
            ph = np.asarray(self.parity.parity_homes).reshape(
                members.shape[0], -1)
            ok = self.view.alive[ph].all(axis=1) & homes_ok
            par_frac = float(np.mean(ok)) if ok.size else 1.0
        return {"replica_alive_frac": rep_frac,
                "parity_groups_ok_frac": par_frac,
                "full": bool(rep_frac >= 1.0 and par_frac >= 1.0)}

    def redundancy_nbytes(self, store: Optional[Any] = None) -> dict[str, int]:
        """Real memory/disk footprint of the redundancy machinery: replica
        and parity payloads, the parity staging buffers (the arena sweep's
        compact outputs, or the seed encode's packed frames + member
        gather), and, when a persistent ``store`` is given, its on-disk
        shard bytes."""
        staging = 0
        if self.parity is not None:
            # the arena sweep's compact staging applies only when every
            # maintain takes the sweep: in live-arena mode every maintain
            # (on- or off-interval) is the resident sweep; with tree input
            # mismatched tier intervals route off-interval steps through
            # the seed encode, whose frames+gather footprint is the peak
            all_arena = self.live_arena_mode or (
                self.arena_layout is not None
                and self.cfg.replicate_interval == self.cfg.parity_interval)
            if all_arena:
                staging = self._traffic_model()["staging_arena"]
            else:
                staging = self.parity.staging_nbytes()
        out = {
            "replica": self.replicas.nbytes() if self.replicas else 0,
            "parity": self.parity.nbytes() if self.parity else 0,
            "parity_staging": staging,
        }
        if store is not None and hasattr(store, "disk_nbytes"):
            disk = store.disk_nbytes()
            # "live" is the indexed subset of "shard" — not additive
            out["store_disk"] = int(disk["shard"] + disk["parity"])
            out["store_disk_live"] = int(disk["live"] + disk["parity"])
        return out

    # -- failure injection ---------------------------------------------------

    def sample_domain_failure(self, rng: np.random.Generator,
                              kind: str = "host",
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Correlated whole-domain loss → (lost block mask, failed devices)."""
        failed = self.domains.sample_domain_failure(rng, kind)
        failed = failed[self.view.alive[failed]]
        lost = np.isin(self.view.homes, failed)
        return lost, failed

    def domain_failure(self, kind: str, index: int,
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Loss of one *specific* domain under the current placement
        (trace-driven injection). Devices already dead in the view are not
        failed again — an event on a fully-dead domain is a no-op."""
        failed = self.domains.devices_in(kind, index)
        failed = failed[self.view.alive[failed]]
        lost = np.isin(self.view.homes, failed)
        return lost, failed

    # -- recovery ------------------------------------------------------------

    def on_failure(self, params: PyTree, ckpt_values: PyTree,
                   lost_mask, failed_devices=None,
                   step: Optional[int] = None,
                   disk_values: Optional[PyTree] = None,
                   disk_reader=None,
                   persist_failure: Optional[bool] = None,
                   ) -> tuple[PyTree, dict]:
        """Tier-planned recovery. ``failed_devices=None`` models the paper's
        uniform block loss (no device actually died — every redundancy tier
        survives). ``step=None`` assumes the failure hit at the last
        maintained step, i.e. replicas/parity are fresh.

        ``persist_failure`` controls whether the failed devices stay dead in
        the cluster view after recovery (they do in a trace-driven soak,
        where the view tracks real cluster state; one-shot paper-style
        experiments leave it False so each event is independent). Defaults
        to ``cfg.elastic``. With ``elastic=True`` the placement engine then
        re-homes the lost blocks across the survivors, re-seeds replicas
        anti-affinely in the degraded topology, and re-stripes parity — the
        *next* failure still finds live redundancy tiers.
        """
        # consume point: a half-swept async epoch must never serve a
        # recovery — settle the in-flight sweep first, then every tier
        # holds exactly the last *published* epoch
        self._settle_pending()
        if failed_devices is None:
            failed_devices = np.empty((0,), np.int32)
        failed = np.asarray(failed_devices, np.int32).ravel()
        if step is None:
            step = self.last_maintained_step
        step = int(step)
        recovered_epoch, staleness = step, 0
        if self.cfg.async_maintain and 0 <= self.published_epoch < step:
            # async mode decouples the sweep from the step that produced
            # the params: the live tiers hold the published epoch, one or
            # more steps behind the failure. Plan against that epoch —
            # a slightly stale replica is a bounded perturbation (Thm
            # 4.1 regime, priced explicitly by the ledger via the
            # staleness fields below), far cheaper than falling all the
            # way back to the checkpoint tier.
            recovered_epoch = int(self.published_epoch)
            staleness = step - recovered_epoch
        persist = self.cfg.elastic if persist_failure is None else \
            bool(persist_failure)
        if persist and failed.size:
            self.view.mark_failed(failed)
        with self.recorder.span("scar/recovery/plan"):
            plan = self.planner.plan(lost_mask, failed, recovered_epoch)
        with self.recorder.span("scar/recovery/restore"):
            recovered, stats = self.planner.recover(
                params, ckpt_values, plan, disk_values=disk_values,
                disk_reader=disk_reader)
        self.stats["recoveries"] += 1
        stats["failed_devices"] = int(failed.size)
        stats["recovered_epoch"] = recovered_epoch
        stats["staleness"] = staleness
        # never-silent: every parity group whose losses exceeded the
        # code's surviving strength says why the cheap tier declined
        stats["tier_fallbacks"] = plan.fallbacks
        for fb in plan.fallbacks:
            self.stats["tier_fallbacks"] += 1
            if self.recorder.enabled:
                self.recorder.event("tier_fallback", step=step, **fb)
        if self.cfg.elastic and failed.size:
            stats["placement"] = self._replan(step, recovered)
        return recovered, stats

    def _replan(self, step: int, params: PyTree) -> dict:
        """Post-failure elastic re-plan: re-home displaced blocks, re-seed
        replicas, re-stripe parity — all against the recovered params, so
        every tier is fresh on the new placement."""
        with self.recorder.span("scar/replan", step=step):
            return self._replan_tiers(step, params)

    def _replan_tiers(self, step: int, params: PyTree) -> dict:
        displaced = rehome_blocks(self.view)
        if self.arena_layout is not None:
            # arena mode: re-seed + re-stripe, then one arena sweep
            # refreshes both tiers against the new striping (its program
            # is looked up by the striping)
            self.replicas.reseed()
            self.parity.restripe()
            self._arena_maintain(step, params, None)
        else:
            if self.replicas is not None:
                self.replicas.reseed()
                self.replicas.refresh(step, params)
                self.stats["replica_refreshes"] += 1
            if self.parity is not None:
                self.parity.restripe()
                self.parity.encode(step, params)
                self.stats["parity_encodes"] += 1
            self.published_epoch = step
        self.planner.rehome()
        self.last_maintained_step = step
        self.stats["rehomes"] += 1
        out = {"rehomed_blocks": int(displaced.size),
               "alive_devices": self.view.n_alive_devices,
               "alive_hosts": self.view.n_alive_hosts,
               "parity_groups": (self.parity.n_groups
                                 if self.parity is not None else 0)}
        if self.recorder.enabled:
            self.recorder.event("rehome", step=step, **out)
        return out

    # -- integrity (silent errors) -------------------------------------------

    def scrub(self, step: Optional[int] = None) -> dict:
        """CodeNet-style integrity pass over the coded redundancy state.

        Recomputes the RS parity rows from the replica arena and XORs
        them against the stored rows: nonzero syndromes mean the coded
        snapshot was silently corrupted since encode — a soft error the
        liveness machinery cannot see. Localizable corruptions (single
        corrupted member or parity row, needs m ≥ 2) are corrected in
        place by XOR-ing the error pattern back out; the rest are
        detected and reported. Requires the RS codec (``rs_parity ≥ 1``
        for detection, ≥ 2 for localization) and an arena-mode replica
        whose snapshot matches the encode step — otherwise the pass
        reports ``checked=False`` and touches nothing.
        """
        with self.recorder.span("scar/scrub", step=step):
            return self._scrub(step)

    def _scrub(self, step: Optional[int]) -> dict:
        out = {"checked": False, "detected": 0, "corrected": 0,
               "reports": []}
        codec = self.parity
        if codec is None or not getattr(codec, "supports_integrity",
                                        False):
            return out
        self._settle_pending()
        if codec.parity is None or self.replicas is None \
                or self.replicas.arena is None \
                or self.replicas.refreshed_step != codec.encoded_step:
            return out
        self.stats["scrubs"] += 1
        out["checked"] = True
        synd = codec.syndromes_from_arena(self.replicas.arena,
                                          self.replicas.arena_layout)
        for rep in codec.localize_corruption(synd):
            out["detected"] += 1
            self.stats["silent_errors_detected"] += 1
            corrected = False
            if rep["localized"]:
                new_arena = codec.correct_in_arena(self.replicas.arena,
                                                   rep)
                if rep["kind"] == "member":
                    self.replicas.ingest_arena(codec.encoded_step,
                                               new_arena,
                                               self.replicas.arena_layout)
                corrected = True
                out["corrected"] += 1
                self.stats["silent_errors_corrected"] += 1
            ev = dict(step=step, group=rep["group"], kind=rep["kind"],
                      member=rep["member"], block=rep["block"],
                      row=rep["row"], localized=rep["localized"],
                      corrected=corrected)
            out["reports"].append(ev)
            if self.recorder.enabled:
                # ``kind`` is the event bus's own discriminator — the
                # corruption's member/parity classification rides as
                # ``error_kind``
                fields = {("error_kind" if k == "kind" else k): v
                          for k, v in ev.items()}
                self.recorder.event("silent_error_detected", **fields)
        return out

    def inject_arena_bit_flip(self, block: Optional[int] = None,
                              word: Optional[int] = None,
                              bit: Optional[int] = None,
                              rng: Optional[np.random.Generator] = None,
                              ) -> dict:
        """Fault injection for soaks/tests: flip one bit of one block's
        payload in the *replica arena* — a silent corruption no liveness
        check sees, caught (and with RS m ≥ 2, localized and corrected)
        only by :meth:`scrub`. Returns where the flip landed."""
        assert self.replicas is not None \
            and self.replicas.arena is not None, \
            "bit-flip injection needs an arena-mode replica"
        assert self.parity is not None
        self._settle_pending()
        gather = np.asarray(self.parity._ensure_arena_gather(
            self.replicas.arena_layout))
        if rng is None:
            rng = np.random.default_rng(0)
        if block is None:
            block = int(rng.integers(self.partition.total_blocks))
        cols = np.nonzero(gather[block] >= 0)[0]
        col = int(cols[int(word) % cols.size]) if word is not None \
            else int(cols[rng.integers(cols.size)])
        b = int(bit) if bit is not None else int(rng.integers(32))
        idx = int(gather[block, col])
        arena = self.replicas.arena
        old = np.asarray(arena[idx], np.float32).view(np.int32).item()
        new = np.array([(old & 0xFFFFFFFF) ^ (1 << b)], np.uint32)
        arena = arena.at[idx].set(jnp.asarray(new.view(np.float32)[0]))
        self.replicas.ingest_arena(self.replicas.refreshed_step, arena,
                                   self.replicas.arena_layout)
        return {"block": int(block), "word": col, "bit": b,
                "arena_index": idx}

    # -- healing -------------------------------------------------------------

    def heal_domain(self, kind: str, index: int,
                    params: Optional[PyTree] = None,
                    step: Optional[int] = None) -> dict:
        """Re-admit a healed domain's devices to the view. With
        ``elastic=True`` the placement engine rebalances primary load onto
        the restored capacity and re-seeds/re-stripes the redundancy tiers
        (against ``params`` when given, so they are immediately fresh;
        otherwise the next ``maintain`` refreshes them)."""
        with self.recorder.span("scar/heal", step=step):
            return self._heal(kind, index, params, step)

    def _heal(self, kind: str, index: int, params: Optional[PyTree],
              step: Optional[int]) -> dict:
        # consume point: an elastic heal re-stripes the tiers — never
        # against a half-swept async epoch
        self._settle_pending()
        healed = self.view.heal(self.domains.devices_in(kind, index))
        info = {"healed_devices": int(healed.size)}
        if healed.size == 0:
            return info
        self.stats["heals"] += 1
        if not self.cfg.elastic:
            if self.recorder.enabled:
                self.recorder.event("heal", domain_kind=kind,
                                    domain_index=int(index), step=step,
                                    **info)
            return info
        at = int(step) if step is not None else self.last_maintained_step
        moved = rebalance_homes(self.view)
        if self.arena_layout is not None and params is not None:
            self.replicas.reseed()
            self.parity.restripe()
            self._arena_maintain(at, params, None)
        else:
            if self.replicas is not None:
                self.replicas.reseed()
                if params is not None:
                    self.replicas.refresh(at, params)
            if self.parity is not None:
                self.parity.restripe()
                if params is not None:
                    self.parity.encode(at, params)
            if params is not None:
                self.published_epoch = at
        self.planner.rehome()
        info["rebalanced_blocks"] = int(moved.size)
        info["alive_hosts"] = self.view.n_alive_hosts
        if self.recorder.enabled:
            self.recorder.event("heal", domain_kind=kind,
                                domain_index=int(index), step=step, **info)
        return info

    # -- elastic mesh resize -------------------------------------------------

    def resize_mesh(self, mesh, logical_ids, step: Optional[int] = None,
                    params: Optional[Any] = None):
        """Re-bind a meshed fabric to a shrunk (or re-grown) device mesh.

        ``logical_ids[i]`` is the fabric logical device at mesh position
        ``i`` — on a shrink these are the survivors, on a re-grow the full
        original id range. Rebuilds the arena layout at the new shard
        count (the data region is identical, only the zero shard-pad tail
        changes — see :func:`~repro.core.arena.relayout_arena`), re-homes
        every block to its new owning shard, re-seeds replicas and
        re-stripes parity in the surviving topology, and invalidates every
        cached program/slot laid out for the old shard count.

        ``params`` — the live arena *already relayouted to the new layout
        and placed on the new mesh* — triggers an immediate maintain so
        every tier is fresh on the new placement; without it the tiers go
        stale until the caller's next ``maintain`` (the old-layout replica
        stays decodable meanwhile: the data region is layout-invariant).

        Returns the new :class:`~repro.core.arena.ArenaLayout`; the caller
        (the training loop) relayouts its own state against it and re-jits
        the step.
        """
        assert self.arena_layout is not None, \
            "resize_mesh is a sharded-arena operation (meshed fabric only)"
        self._settle_pending()
        from repro.core.arena import arena_block_homes, build_arena_layout
        logical_ids = np.asarray(logical_ids, np.int32)
        new_layout = build_arena_layout(
            self.partition, shards=int(np.asarray(mesh.devices).size))
        self.arena_layout = new_layout
        self._bind_mesh(mesh, logical_ids)
        # span-derived homes over the surviving shards
        self.view.homes[:] = logical_ids[arena_block_homes(new_layout)]
        self.view.version += 1
        # every cached artifact below is laid out for the old shard count
        self._programs.clear()
        self._pack_fn = None
        self._slots = [None, None]
        if self.replicas is not None:
            self.replicas.reseed()
            self.replicas.main_sharding = self._arena_sharding
        if self.parity is not None:
            self.parity.restripe()
        self.planner.rehome()
        at = int(step) if step is not None else self.last_maintained_step
        if params is not None:
            self._arena_maintain(at, params, None)
            self.last_maintained_step = at
        self.stats["mesh_resizes"] += 1
        self.stats["arena_padding_ratio"] = float(new_layout.padding_ratio)
        if self.recorder.enabled:
            self.recorder.event(
                "mesh_resize", step=at, shards=new_layout.shards,
                alive_devices=self.view.n_alive_devices,
                alive_hosts=self.view.n_alive_hosts)
        return new_layout
