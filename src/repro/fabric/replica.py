"""Anti-affine peer replication of running-state blocks (tier 1).

Each block's replica is placed in a different failure domain (the farthest
one the *current* topology offers: another rack when racks survive, else
another host), so a whole-domain failure never takes a block *and* its
replica together. Placement is read from the fabric's mutable
:class:`~repro.fabric.placement.ClusterView` — after a domain loss the set
is :meth:`reseed`-ed so replicas stay anti-affine in the degraded topology
instead of pointing at dead devices. Replicas hold live parameter values as
of the last refresh — refreshing is a device-to-device copy (no host trip,
no disk), cheap enough to run every iteration, so a replica-recovered block
is restored to its *live* value: zero perturbation in the Thm 4.1
accounting (see DESIGN.md).

The snapshot lives in one of two forms: a PyTree (the seed component
pass) or a flat **parameter arena** (:mod:`repro.core.arena`) ingested by
the arena maintenance sweep — the canonical hot-path form. Recovery reads
whichever is present; ``values`` materializes a tree from the arena on
demand (recovery-path only, never the hot loop).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import BlockPartition
from repro.fabric.placement import ClusterView, anti_affine_replica_homes

PyTree = Any


class ReplicaSet:
    """One replica per block, anti-affine to the block's primary home."""

    def __init__(self, partition: BlockPartition, view: ClusterView):
        self.partition = partition
        self.view = view
        self.domains = view.domains
        self.replica_homes = anti_affine_replica_homes(view)
        self._tree: Optional[PyTree] = None
        self._arena: Optional[jnp.ndarray] = None
        self.arena_layout = None
        # SPMD meshes: the fabric sets this to the flat arena sharding.
        # The ingested replica then lives on the *rotated* (anti-affine)
        # device order, and consumers that feed it into a jit alongside
        # flat-sharded state re-place it here first — XLA requires one
        # consistent device assignment per computation.
        self.main_sharding = None
        self.refreshed_step = -1

    # -- maintenance ---------------------------------------------------------

    def refresh(self, step: int, params: PyTree) -> None:
        """Snapshot live params into the replicas (device copy)."""
        self._tree = jax.tree_util.tree_map(jnp.array, params)
        self._arena = None
        self.refreshed_step = int(step)

    def ingest_arena(self, step: int, arena: jnp.ndarray,
                     arena_layout) -> None:
        """Adopt an arena-form snapshot (the arena sweep's pack output —
        the pack IS the replica write). The tree form is materialized
        lazily and only on the recovery path.

        Under async maintenance this call IS the publish: the fabric's
        double-buffer snapshot becomes the replica arena here, atomically
        at Python level with the parity ingest for the same step — a
        reader never observes replica and parity from different epochs.
        The adopted arena may still have device work in flight; readers
        either fence through ``fabric.block_until_maintained`` or wait on
        dataflow, so a torn (half-swept) slot is unobservable."""
        self._arena = arena
        self.arena_layout = arena_layout
        self._tree = None
        self.refreshed_step = int(step)

    @property
    def arena(self) -> Optional[jnp.ndarray]:
        """The arena-form snapshot, or None when tree-form (or empty)."""
        return self._arena

    def arena_local(self) -> Optional[jnp.ndarray]:
        """The arena snapshot re-placed on the primary (flat) sharding —
        for consumers that mix it with flat-sharded state in one jit.
        Identity without a mesh (or when no snapshot exists)."""
        if self._arena is None or self.main_sharding is None:
            return self._arena
        return jax.device_put(self._arena, self.main_sharding)

    @property
    def values(self) -> Optional[PyTree]:
        """Tree-form snapshot; decodes the arena on first access."""
        if self._tree is None and self._arena is not None:
            from repro.core.arena import unpack_arena
            self._tree = unpack_arena(self.arena_local(), self.arena_layout)
        return self._tree

    def is_fresh(self, step: int) -> bool:
        """True when replicas hold the *current* live values (no parameter
        update has happened since the refresh)."""
        return (self._tree is not None or self._arena is not None) \
            and self.refreshed_step == int(step)

    def staleness(self, step: int) -> int:
        """Steps between ``step`` and the snapshot the replicas hold
        (0 = fresh; -1 = no snapshot at all). The async pipeline's
        bounded-staleness accounting reads this to price a recovery
        against the epoch actually restored."""
        if self._tree is None and self._arena is None:
            return -1
        return max(0, int(step) - self.refreshed_step)

    def reseed(self) -> None:
        """Recompute replica placement in the view's current (possibly
        degraded) topology. Values are untouched — re-seeding is a
        placement change; the next :meth:`refresh` lands on the new homes."""
        self.replica_homes = anti_affine_replica_homes(self.view)

    # -- survivorship --------------------------------------------------------

    def surviving(self, failed_devices) -> np.ndarray:
        """(total_blocks,) bool — replicas whose home device is alive in the
        view and not among this event's failed devices."""
        if self._tree is None and self._arena is None:
            return np.zeros((self.partition.total_blocks,), bool)
        failed = np.asarray(failed_devices, np.int32)
        return (self.view.alive[self.replica_homes]
                & ~np.isin(self.replica_homes, failed))

    def nbytes(self) -> int:
        if self._arena is not None:
            return int(self._arena.nbytes)
        if self._tree is None:
            return 0
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(self._tree))
