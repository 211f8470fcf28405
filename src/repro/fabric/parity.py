"""XOR parity groups over blocks with single-erasure reconstruction (tier 2).

Storage-cheap redundancy: blocks are striped into groups of ``g`` members
whose homes sit on *distinct hosts*, and one parity block (the XOR of the
members' bit patterns) is kept per group — 1/g of the replica tier's
memory. A whole-host failure then loses at most one member per group, and
the lost member is reconstructed bit-exactly as
``parity ^ XOR(surviving members)`` by the fused Pallas ``parity_xor``
kernel.

Reconstruction needs the survivors' frames *as of encode time*; re-encoding
runs at memory bandwidth (one XOR pass), so the codec is refreshed every
maintenance call and reconstruction recovers the *live* value — zero
perturbation, same accounting as the replica tier. A stale parity (any
parameter update since encode) is unusable — the XOR would mix bit patterns
from different iterations into garbage — so the tier planner gates on
freshness.

Block frames: each block's payload is bit-packed into 32-bit words
(``dtype_word_ratio`` elements per word — raw bf16/fp8/int8 bits, not f32
images, so frame bytes scale with the stored precision), one fixed-width
int32 row per global block id (zero-padded — zeros are XOR-neutral).
Colocated leaves (shared block ids) concatenate side by side within the
frame. Non-word-packable dtypes (f64/int64/…) keep the historical
one-f32-image-per-element convention.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import (BlockPartition, LeafMeta,  # noqa: F401
                               decode_block_words, expand_block_mask,
                               leaf_block_words, leaf_word_width)
from repro.fabric.placement import (ClusterView, effective_parity_group,
                                    parity_group_homes, stripe_parity_groups)
from repro.kernels.parity_xor.ops import parity_encode, parity_reconstruct

PyTree = Any


# ---------------------------------------------------------------------------
# Block frames: fixed-width bit-pattern rows, one per global block id
# ---------------------------------------------------------------------------

# canonical definition lives with the block partition (the arena shares
# it); kept under the old name for in-package callers. Since the
# word-level arena this is the payload *word* count per block (elements
# bit-packed ``dtype_word_ratio`` per word), not the element count.
_leaf_frame_width = leaf_word_width


@dataclasses.dataclass(frozen=True)
class FrameLayout:
    """Column placement of each leaf's payload inside its blocks' frames.

    Column starts (and the total frame width) are aligned to the arena
    tile (``repro.core.arena.ARENA_TILE`` words) so a group-sorted XOR
    over ``(8, 128)`` arena tiles lands on whole-tile frame columns —
    the padding columns are zero on every path (XOR-neutral)."""
    cols: tuple[int, ...]      # per-leaf start column (tile-aligned)
    widths: tuple[int, ...]    # per-leaf payload width
    frame_elems: int           # int32 words per frame (tile-aligned)


def frame_layout(partition: BlockPartition) -> FrameLayout:
    from repro.core.arena import _align
    cols, widths = [], []
    used: dict[int, int] = {}  # block-id offset -> columns consumed so far
    for leaf in partition.leaves:
        w = _leaf_frame_width(leaf, partition.block_rows)
        start = used.get(leaf.offset, 0)   # colocated leaves share offsets
        cols.append(start)
        widths.append(w)
        used[leaf.offset] = start + _align(w)
    return FrameLayout(tuple(cols), tuple(widths),
                       _align(max(used.values())))


def pack_frames(values: PyTree, partition: BlockPartition,
                layout: FrameLayout) -> jnp.ndarray:
    """(total_blocks, frame_elems) int32 — raw bit-packed words, 0-padded."""
    out = jnp.zeros((partition.total_blocks, layout.frame_elems), jnp.int32)
    flat = jax.tree_util.tree_leaves(values)
    for x, leaf, col, w in zip(flat, partition.leaves, layout.cols,
                               layout.widths):
        bits = leaf_block_words(x, partition.block_rows)
        out = out.at[leaf.offset:leaf.offset + leaf.n_blocks,
                     col:col + w].set(bits)
    return out


def unpack_frames_into(dst: PyTree, frames_by_block: jnp.ndarray,
                       block_mask: np.ndarray, partition: BlockPartition,
                       layout: FrameLayout) -> PyTree:
    """Overwrite the masked blocks of ``dst`` with values decoded from
    ``frames_by_block``; all other blocks pass through untouched."""
    mask = np.asarray(block_mask, bool)
    flat = jax.tree_util.tree_leaves(dst)
    out = []
    for x, leaf, col, w in zip(flat, partition.leaves, layout.cols,
                               layout.widths):
        seg = mask[leaf.offset:leaf.offset + leaf.n_blocks]
        if not seg.any():
            out.append(x)
            continue
        bits = frames_by_block[leaf.offset:leaf.offset + leaf.n_blocks,
                               col:col + w]
        decoded = decode_block_words(bits, leaf,
                                     partition.block_rows).astype(x.dtype)
        em = expand_block_mask(jnp.asarray(seg), leaf, partition.block_rows)
        out.append(jnp.where(em, decoded, x))
    return jax.tree_util.tree_unflatten(partition.treedef, out)


# ---------------------------------------------------------------------------
# Codec
# ---------------------------------------------------------------------------

class ParityCodec:
    """XOR parity over anti-affine block groups, Pallas-kernel backed.

    Group striping and parity homing are read from the fabric's mutable
    :class:`~repro.fabric.placement.ClusterView` — after a domain loss,
    :meth:`restripe` re-cuts the groups over the surviving hosts (the RAID
    width clamp follows the *alive* host count) and invalidates the parity
    until the next :meth:`encode`.
    """

    # single parity row per group; the RS subclass raises both. The
    # arena sweep emits XOR parity directly (needs_arena_encode
    # False); codecs that must re-encode from the snapshot arena set it.
    n_parity = 1
    needs_arena_encode = False
    supports_integrity = False

    def __init__(self, partition: BlockPartition, view: ClusterView,
                 group_size: int = 4, use_pallas: bool | None = None):
        if group_size < 2:
            raise ValueError("parity group_size must be >= 2")
        self.partition = partition
        self.view = view
        self.domains = view.domains
        self.requested_group_size = group_size
        self.use_pallas = use_pallas
        self.layout = frame_layout(partition)
        self.parity: Optional[jnp.ndarray] = None
        self.encoded_step = -1
        # arena → frame gather index, built lazily per arena layout (the
        # arena-path reconstruction sources member frames straight from
        # the maintenance sweep's snapshot arena)
        self._arena_gather: Optional[np.ndarray] = None
        self._arena_gather_layout = None
        self._build()

    def _build(self) -> None:
        """(Re)derive groups, parity homes, and the fused encode program
        from the view's current placement."""
        self._stripe()
        self.parity_homes = parity_group_homes(self.members, self.view)
        self._build_encode()

    def _stripe(self) -> None:
        """Cut member groups over the view's current placement (shared by
        the XOR and RS codecs — only homes and the fold differ)."""
        self.group_size = effective_parity_group(self.view,
                                                 self.requested_group_size,
                                                 reserve=self.n_parity)
        self.members = stripe_parity_groups(self.view, self.group_size,
                                            fold_tail=self.n_parity < 2)
        self.n_groups = self.members.shape[0]
        self.group_of = np.full((self.partition.total_blocks,), -1, np.int32)
        for j, row in enumerate(self.members):
            for b in row[row >= 0]:
                self.group_of[b] = j
        self.valid = (self.members >= 0)
        # -1 members gather row 0 but are masked out by ``valid``
        self._gather_ids = np.where(self.valid, self.members, 0)
        # what the fabric keys its maintenance programs on: it changes
        # exactly when the groups do, and is computed here, once per cut,
        # so a maintain only compares it
        self.striping = (self.n_groups, self.members.shape[1],
                         hashlib.blake2b(self.group_of.tobytes(),
                                         digest_size=16).digest())

    def _build_encode(self) -> None:
        # encode runs every maintenance interval (the hot loop): fuse
        # pack + gather + XOR fold into one cached jitted program so the
        # per-step cost is one dispatch, not a per-leaf eager op chain
        gather = jnp.asarray(self._gather_ids)
        valid = jnp.asarray(self.valid)

        def _encode(values):
            frames = pack_frames(values, self.partition, self.layout)
            return parity_encode(frames[gather], valid,
                                 use_pallas=self.use_pallas)
        self._encode_fn = jax.jit(_encode)

    # -- maintenance ---------------------------------------------------------

    def encode(self, step: int, values: PyTree) -> None:
        """Re-encode all parity blocks from live values (one XOR pass)."""
        self.parity = self._encode_fn(values)
        self.encoded_step = int(step)

    def ingest(self, step: int, parity: jnp.ndarray) -> None:
        """Adopt a parity buffer encoded elsewhere (the arena maintenance
        sweep XOR-folds arena tiles straight into group frames —
        bit-identical to :meth:`encode` under the same striping)."""
        self.parity = parity
        self.encoded_step = int(step)

    def restripe(self) -> None:
        """Re-cut the parity groups over the view's current topology.

        The old parity buffers XOR frames of the old groups — meaningless
        under the new striping — so the codec is invalidated until the next
        :meth:`encode` (the fabric re-encodes immediately after a
        post-failure restripe)."""
        self._build()
        self.parity = None
        self.encoded_step = -1

    def is_fresh(self, step: int) -> bool:
        return self.parity is not None and self.encoded_step == int(step)

    def nbytes(self) -> int:
        return 0 if self.parity is None else int(self.parity.nbytes)

    def staging_nbytes(self) -> int:
        """Peak staging footprint of one seed-path :meth:`encode`: the
        packed ``(total_blocks, frame_elems)`` bit-pattern buffer plus the
        ``(n_groups, width, frame_elems)`` member gather the XOR fold
        consumes. The arena maintenance sweep replaces both with compact
        parity tiles (see ``kernels/fused_maintain``); callers accounting
        real memory overhead must include whichever applies."""
        frames = self.partition.total_blocks * self.layout.frame_elems * 4
        gathered = int(self.members.size) * self.layout.frame_elems * 4
        return frames + gathered

    # -- recovery ------------------------------------------------------------

    def code_strength(self, failed_devices) -> np.ndarray:
        """(n_groups,) erasures each group can absorb right now: its
        parity rows homed on devices alive and outside the failing set.
        0 or 1 for the XOR codec, up to m for RS."""
        failed = np.asarray(failed_devices, np.int32)
        homes = np.asarray(self.parity_homes).reshape(self.n_groups, -1)
        ok = self.view.alive[homes] & ~np.isin(homes, failed)
        return ok.sum(axis=1).astype(np.int64)

    def reconstructable(self, lost_mask: np.ndarray,
                        available_mask: np.ndarray,
                        failed_devices, step: int) -> np.ndarray:
        """(total_blocks,) bool — lost blocks recoverable from parity.

        A lost block is parity-recoverable iff the parity is fresh and
        its group's erasure count (members without an available live
        frame) is within the group's surviving code strength — exactly
        one erasure against one live parity home for the XOR codec, up
        to m erasures against m surviving parity rows for RS.
        """
        total = self.partition.total_blocks
        if not self.is_fresh(step):
            return np.zeros((total,), bool)
        lost = np.asarray(lost_mask, bool)
        available = np.asarray(available_mask, bool)
        failed = np.asarray(failed_devices, np.int32)
        member_unavail = self.valid & ~available[self._gather_ids]
        erased = member_unavail.sum(axis=1)
        strength = self.code_strength(failed)
        ok_group = (erased >= 1) & (erased <= strength)
        out = np.zeros((total,), bool)
        grouped_ok = ok_group[:, None] & member_unavail
        out[self._gather_ids[grouped_ok]] = True
        return out & lost

    def exceeded_groups(self, lost_mask: np.ndarray,
                        available_mask: np.ndarray,
                        failed_devices, step: int) -> list[dict]:
        """Never-silent fallback accounting: groups that hold lost blocks
        the code cannot recover (erasures exceed surviving strength, or
        the parity is stale). One dict per exceeded group — the fabric
        turns each into a ``tier_fallback`` event so a RUNNING_CKPT
        fallback always says *why* the cheaper tier declined."""
        lost = np.asarray(lost_mask, bool)
        available = np.asarray(available_mask, bool)
        failed = np.asarray(failed_devices, np.int32)
        member_lost = self.valid & lost[self._gather_ids]
        erased = (self.valid & ~available[self._gather_ids]).sum(axis=1)
        fresh = self.is_fresh(step)
        strength = self.code_strength(failed) if fresh \
            else np.zeros((self.n_groups,), np.int64)
        bad = member_lost.any(axis=1) & (erased > strength)
        return [dict(group=int(j), lost_members=int(member_lost[j].sum()),
                     unavailable=int(erased[j]), strength=int(strength[j]),
                     fresh=bool(fresh))
                for j in np.nonzero(bad)[0]]

    def reconstruct(self, values: PyTree, recover_mask: np.ndarray,
                    available_mask: np.ndarray) -> jnp.ndarray:
        """Reconstruct the masked blocks' frames; returns a
        (total_blocks, frame_elems) int32 buffer (zeros off-mask).

        ``values`` must hold live frames for every available member
        (survivors and fresh-replica-restored blocks).
        """
        frames = pack_frames(values, self.partition, self.layout)
        return self._reconstruct_frames(frames, recover_mask,
                                        available_mask)

    def _ensure_arena_gather(self, arena_layout) -> np.ndarray:
        """Cache the arena-word → frame-column gather for this layout."""
        from repro.core.arena import frames_gather_index
        if self._arena_gather is None \
                or self._arena_gather_layout is not arena_layout:
            self._arena_gather = frames_gather_index(arena_layout,
                                                     self.layout)
            self._arena_gather_layout = arena_layout
        return self._arena_gather

    def reconstruct_from_arena(self, arena: jnp.ndarray, arena_layout,
                               recover_mask: np.ndarray,
                               available_mask: np.ndarray) -> jnp.ndarray:
        """Arena-path reconstruction: member frames come from the flat
        snapshot arena via one gather (``frames_from_arena``) instead of
        a full-tree ``pack_frames`` pass. Valid when the arena is the
        encode-time snapshot — in arena maintenance mode the replica
        arena and the parity are emitted by the same sweep, so the tier
        planner checks ``refreshed_step == encoded_step`` and routes
        here."""
        from repro.core.arena import frames_from_arena
        frames = frames_from_arena(arena,
                                   self._ensure_arena_gather(arena_layout))
        return self._reconstruct_frames(frames, recover_mask,
                                        available_mask)

    def _reconstruct_frames(self, frames: jnp.ndarray,
                            recover_mask: np.ndarray,
                            available_mask: np.ndarray) -> jnp.ndarray:
        assert self.parity is not None
        grouped = frames[jnp.asarray(self._gather_ids)]
        survivors = self.valid & np.asarray(available_mask, bool)[
            self._gather_ids]
        rec = parity_reconstruct(grouped, self.parity,
                                 jnp.asarray(survivors),
                                 use_pallas=self.use_pallas)
        ids = np.nonzero(np.asarray(recover_mask, bool))[0]
        out = jnp.zeros_like(frames)
        if ids.size:
            out = out.at[jnp.asarray(ids)].set(rec[jnp.asarray(
                self.group_of[ids])])
        return out
