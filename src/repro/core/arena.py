"""Flat parameter arena: one contiguous per-host buffer for all leaves.

The fabric's hot loop (replica refresh + parity encode + PRIORITY scoring
+ in-place partial save) previously operated on a *forest* of leaves: one
kernel dispatch per touched leaf, `(1, BE)` row tiles that waste TPU
sublanes, and per-leaf eager dispatch overhead that dominates wall-clock
at small scale (see ``BENCH_maintain.json``).

The arena collapses the forest to a single contiguous buffer of 32-bit
**words** (carried as ``float32`` at the JAX level so every existing
consumer keeps its dtype expectations; the words of non-f32 leaves are
raw bit patterns, not values):

  - every leaf's payload is bit-packed ``dtype_word_ratio`` elements per
    word (f32/i32 → 1, bf16/f16/i16 → 2, fp8/i8 → 4; f32 leaves are
    therefore stored *bitwise as their values*, the historical layout),
    and the block table tags each segment with the leaf dtype — replica,
    parity, RS MAC, scatter saves and the integrity scrub all move raw
    words, so redundancy bytes scale with the stored precision;
  - **main region**: multi-block and >= tile leaves laid out block-major
    in flatten order, each block's payload zero-padded to a multiple of
    ``ARENA_TILE`` = 8·128 words so every block covers whole ``(8, 128)``
    sublane-aligned tiles of the 2D ``(rows, 128)`` retiling;
  - **tail region** (tail packing): single-block leaves narrower than a
    tile are packed back-to-back at *word* granularity after the main
    region — they share tiles, which removes the ~1.6× alignment cost
    small leaves used to pay on the reduced config. The region end is
    re-aligned so ``data_words`` stays a tile multiple; build with
    ``tail_pack=False`` to recover the fully aligned layout;
  - the **block table** maps ``(leaf, block) → (offset, words, payload)``
    — ``payload`` is the live words, the tail up to ``words`` is zero
    padding (XOR-neutral for parity, diff-neutral for scores);
  - colocated leaves (shared global block ids) get *separate* segments —
    the table is keyed by arena-block id, so a partial save or disk
    mirror of one gid moves every colocated payload for that gid;
  - per-leaf arena column starts equal the parity ``FrameLayout``
    (word-) columns, so an XOR over arena words lands bit-exactly in the
    codec's ``(n_groups, frame_elems)`` parity frames.

Alongside the word domain the layout describes a **value domain** for
the optimizer seam: per leaf, ``seg_elems = seg_words · ratio`` f32
values per block — ``decode_values`` / ``encode_values`` move between
the two with one slice per *run* of consecutive same-dtype leaves
(coalesced; an all-bf16 model is a single run). A run of packed
16/8-bit words decodes planar (field 0 of every word, then field 1, …).
For an all-f32 model ``total_values == total_words`` and both
transforms are the identity, so gradients, moments and the optimizer
update are bit-identical to the historical f32 arena.

Invariants (relied on by kernels, the store, and the property tests):

  I1  main-region ``offset``/``words`` are multiples of ``ARENA_TILE``;
      tail-region blocks are word-contiguous (``words == payload``,
      offsets unaligned) and ``tail_start``/``data_words``/
      ``total_words`` are tile multiples.
  I2  segments are disjoint and cover ``[0, data_words)`` exactly except
      the tail-alignment gap ``[tail_end, data_words)``, which is zero;
      ``[data_words, total_words)`` is the arena-level shard pad (zero
      tiles appended so ``n_tiles`` divides ``shards`` evenly — empty
      when ``shards == 1``).
  I3  ``unpack(pack(tree)) == tree`` bit-exactly for every word-packable
      dtype (f32/bf16/f16/fp8/int8/…), any shape (including scalars and
      ragged tail blocks).
  I4  pad words are 0x00000000 after ``pack`` and are *kept* zero by
      every arena mutation (scatter saves copy whole segments, so pads
      are overwritten with source pads — also zero; the tail-alignment
      gap and the shard-pad tail are never scatter targets). Sub-word
      element pads are zero *bits*, which decode to value 0 for every
      packable dtype.

Sharded form: when the trainer runs on a mesh, the same 1-D buffer
carries a flat ``NamedSharding`` over every mesh axis — device ``d`` of
``n`` owns words ``[d·total/n, (d+1)·total/n)``, a whole number of
``(8, 128)`` tiles by I1/I2. ``arena_block_homes`` derives the
block→device map *from* that span ownership, so "each device owns the
tile-aligned segments of its home blocks" holds by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import (BlockPartition, decode_block_words,
                               dtype_word_ratio, expand_block_mask,
                               fields_to_words, leaf_block_view,
                               leaf_block_words, leaf_frame_width,
                               leaf_word_width, word_fields, word_packable)

PyTree = Any

ARENA_LANES = 128          # lane width of the 2D retiling
ARENA_SUBLANES = 8         # f32 sublane tile height
ARENA_TILE = ARENA_LANES * ARENA_SUBLANES   # words per (8, 128) tile

# kept for reference/back-compat: the dtypes the pre-word-level arena
# admitted (f32 round-trippable). The live gate is ``arena_compatible``,
# which now admits every word-packable dtype.
ARENA_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def _align(n: int, a: int = ARENA_TILE) -> int:
    return -(-max(int(n), 1) // a) * a


def leaf_payload_words(leaf, block_rows: int) -> int:
    """Live words per block of this leaf — the parity frame payload
    width (:func:`repro.core.blocks.leaf_word_width`)."""
    return leaf_word_width(leaf, block_rows)


def arena_compatible(partition: BlockPartition) -> bool:
    """True when every leaf dtype is word-packable (1/2/4-byte int or
    float: f32, bf16, f16, fp8, int8/16/32, uint8/16/32 — stored as raw
    bit patterns, so the round trip is bit-exact by construction).
    Truly unsupported dtypes (f64, int64, complex, bool) gate the model
    to the PyTree path with a loud ``fabric/arena_gated`` warn+event."""
    return all(word_packable(l.dtype) for l in partition.leaves)


@dataclasses.dataclass(frozen=True)
class ArenaBlock:
    """One block-table row: where block ``b`` of leaf ``li`` lives."""
    leaf: int          # leaf index in flatten order
    gid: int           # global block id (colocated leaves share gids)
    offset: int        # word offset of the segment (tile-aligned unless tail)
    words: int         # segment length (== payload for tail blocks)
    payload: int       # live words; [payload, words) is zero padding


@dataclasses.dataclass(frozen=True, eq=False)
class ArenaLayout:
    """Static block table + tile routing for one partition.

    ``ab_t0``/``ab_nt`` (first tile / touched-tile count per arena block)
    and the gid→arena-block CSR (``gid_ab``/``gid_ptr``) make the
    per-save lookups O(selected) — the save hot path never scans the
    full table.

    ``eq=False``: identity comparison/hash, so a layout can ride as a
    static (meta) field of a registered pytree (``ArenaTrainState``) —
    the numpy tables would make the generated ``__eq__`` ill-defined, and
    every consumer shares the one instance its fabric built anyway."""
    partition: BlockPartition
    blocks: tuple[ArenaBlock, ...]      # offset-ascending
    leaf_offset: tuple[int, ...]        # word offset of each leaf's segment
    seg_words: tuple[int, ...]          # segment words per block, per leaf
    payload_words: tuple[int, ...]      # live words per block, per leaf
    total_words: int                    # ARENA_TILE multiple (incl. shard pad)
    ab_t0: np.ndarray                   # (n_ab,) first tile per arena block
    ab_nt: np.ndarray                   # (n_ab,) touched tiles per arena block
    gid_ab: np.ndarray                  # arena blocks sorted by gid (CSR)
    gid_ptr: np.ndarray                 # (total_blocks + 1,) CSR pointers
    shards: int = 1                     # even flat-sharding divisor of n_tiles
    data_words: int = -1                # words before the shard-pad tail
    tail_start: int = -1                # word offset of the tail-packed region
    leaf_order: tuple[int, ...] = ()    # leaf indices in offset order
    payload_elems: tuple[int, ...] = () # live elements per block, per leaf
    seg_elems: tuple[int, ...] = ()     # value-domain elems per block, per leaf
    total_values: int = -1              # f32 value-domain length

    @property
    def n_tiles(self) -> int:
        return self.total_words // ARENA_TILE

    @property
    def pad_words(self) -> int:
        """Zero words of the shard-pad tail (0 when ``shards == 1``)."""
        return self.total_words - (self.total_words if self.data_words < 0
                                   else self.data_words)

    @property
    def shard_words(self) -> int:
        """Words each of the ``shards`` flat shards owns (tile multiple)."""
        return self.total_words // self.shards

    @property
    def rows_2d(self) -> int:
        return self.total_words // ARENA_LANES

    @property
    def nbytes(self) -> int:
        return self.total_words * 4

    @property
    def uniform_f32(self) -> bool:
        """True when every leaf is f32 — words *are* values and the value
        domain is the identity (``total_values == total_words``)."""
        return all(np.dtype(l.dtype) == np.dtype(np.float32)
                   for l in self.partition.leaves)

    @property
    def has_tail(self) -> bool:
        return 0 <= self.tail_start < self.data_words

    @property
    def tail_end(self) -> int:
        """End of the last tail payload (``data_words`` minus the
        tail-alignment gap; == ``tail_start`` when no tail region)."""
        end = self.tail_start
        for ab in self.blocks:
            if ab.offset >= self.tail_start:
                end = max(end, ab.offset + ab.payload)
        return end

    @property
    def padding_ratio(self) -> float:
        """Pad words / live payload words over the whole buffer — the
        number tail packing shrinks (reported in ``maintain_traffic`` and
        the ``maint_arena_padding`` bench row)."""
        data = sum(ab.payload for ab in self.blocks)
        return (self.total_words - data) / max(data, 1)

    # -- host-side routing (O(selected), not O(table)) -----------------------

    def tile_gids(self) -> np.ndarray:
        """(n_tiles,) global block id owning each (8, 128) tile.

        Tail-region tiles report -1: they may be shared by several
        blocks, so per-gid reductions must use :meth:`word_tables` there.
        Shard-pad tail tiles report gid 0: their words are zero in every
        arena (I4), so any per-gid reduction over tiles (scores, diffs)
        sees an exact ``+0.0`` contribution — bit-neutral."""
        gids = np.zeros((self.n_tiles,), np.int32)
        for ab in self.blocks:
            if ab.offset >= self.tail_start >= 0:
                continue
            t0 = ab.offset // ARENA_TILE
            gids[t0:t0 + ab.words // ARENA_TILE] = ab.gid
        if self.has_tail:
            gids[self.tail_start // ARENA_TILE:
                 self.data_words // ARENA_TILE] = -1
        return gids

    def word_tables(self) -> tuple[np.ndarray, np.ndarray, tuple]:
        """Cached ``(word_gid, word_code, code_dtypes)``.

        ``word_gid[w]`` is the gid owning word ``w`` (pads → 0, whose
        zero words contribute an exact +0.0 to any reduction);
        ``word_code[w]`` tags the stored dtype: 0 = f32 (including every
        pad), ``k >= 1`` = ``code_dtypes[k - 1]``. The per-word drift
        scorer and the tail parity epilogue are driven by these."""
        cached = getattr(self, "_word_tables", None)
        if cached is None:
            gid = np.zeros((self.total_words,), np.int32)
            code = np.zeros((self.total_words,), np.int8)
            codes: dict[str, int] = {}
            dts: list[np.dtype] = []
            for ab in self.blocks:
                dt = np.dtype(self.partition.leaves[ab.leaf].dtype)
                if dt == np.dtype(np.float32) or not word_packable(dt):
                    c = 0
                else:
                    if dt.name not in codes:
                        dts.append(dt)
                        codes[dt.name] = len(dts)
                    c = codes[dt.name]
                gid[ab.offset:ab.offset + ab.words] = ab.gid
                code[ab.offset:ab.offset + ab.words] = c
            cached = (gid, code, tuple(dts))
            object.__setattr__(self, "_word_tables", cached)
        return cached

    def value_runs(self) -> tuple[tuple[int, int, int, int, Any], ...]:
        """Cached coalesced decode/encode plan: ``(word_start, words,
        value_start, values, dtype)`` per run of consecutive same-dtype
        leaves in offset order (pads ride inside their leaf's run; the
        tail-alignment gap and shard pad close an f32 run). An all-f32
        model is one run; an all-bf16 model is one run."""
        cached = getattr(self, "_value_runs", None)
        if cached is None:
            runs: list[list] = []   # [w0, nw, v0, nv, dtype]
            w = v = 0

            def push(nw: int, nv: int, dt) -> None:
                nonlocal w, v
                if nw == 0:
                    return
                if runs and np.dtype(runs[-1][4]) == np.dtype(dt):
                    runs[-1][1] += nw
                    runs[-1][3] += nv
                else:
                    runs.append([w, nw, v, nv, np.dtype(dt)])
                w += nw
                v += nv

            for li in self.leaf_order:
                leaf = self.partition.leaves[li]
                dt = np.dtype(leaf.dtype) if word_packable(leaf.dtype) \
                    else np.dtype(np.float32)
                push(self.seg_words[li] * leaf.n_blocks,
                     self.seg_elems[li] * leaf.n_blocks, dt)
            push(self.total_words - w, self.total_values - v, np.float32)
            assert w == self.total_words and v == self.total_values
            cached = tuple(tuple(r) for r in runs)
            object.__setattr__(self, "_value_runs", cached)
        return cached

    def blocks_for_gids(self, global_ids) -> np.ndarray:
        """Ascending arena-block indices covering the given gids — every
        colocated leaf's segment rides along (they share gids)."""
        gids = np.unique(np.asarray(global_ids, np.int64).ravel())
        if gids.size == 0:
            return np.empty((0,), np.int64)
        parts = [self.gid_ab[self.gid_ptr[g]:self.gid_ptr[g + 1]]
                 for g in gids]
        return np.sort(np.concatenate(parts))

    def tiles_for_blocks(self, global_ids) -> np.ndarray:
        """Ascending unique (8-row-) tile indices touched by the given
        gids (tail blocks may share tiles, hence the dedup)."""
        abs_ = self.blocks_for_gids(global_ids)
        if abs_.size == 0:
            return np.empty((0,), np.int32)
        t0, nt = self.ab_t0[abs_], self.ab_nt[abs_]
        total = int(nt.sum())
        starts = np.cumsum(nt) - nt
        tiles = (np.repeat(t0, nt)
                 + (np.arange(total) - np.repeat(starts, nt)))
        return np.unique(tiles).astype(np.int32)

    def split_tail_blocks(self, global_ids) -> tuple[np.ndarray, np.ndarray]:
        """Arena-block indices of the given gids, split into
        (main-region, tail-region) — the two scatter granularities."""
        abs_ = self.blocks_for_gids(global_ids)
        if abs_.size == 0 or not self.has_tail:
            return abs_, np.empty((0,), np.int64)
        off = np.asarray([self.blocks[i].offset for i in abs_])
        tail = off >= self.tail_start
        return abs_[~tail], abs_[tail]

    def seg_bytes_for_blocks(self, global_ids) -> int:
        """Bytes a scatter of these gids actually moves: whole touched
        tiles for main-region blocks, payload words for tail blocks."""
        main, tail = self.split_tail_blocks(global_ids)
        tiles = 0
        if main.size:
            t0, nt = self.ab_t0[main], self.ab_nt[main]
            total = int(nt.sum())
            starts = np.cumsum(nt) - nt
            tiles = np.unique(np.repeat(t0, nt) + (np.arange(total)
                              - np.repeat(starts, nt))).size
        words = sum(self.blocks[i].payload for i in tail)
        return 4 * (ARENA_TILE * tiles + int(words))


def as_live_arena(x: Any, layout: Optional[ArenaLayout]):
    """Return ``x`` when it is a live flat arena for ``layout``, else None.

    The training stack's arena-native hot path passes the flat ``(N,)``
    f32 buffer where tree-form params used to flow; consumers
    (FTController, CheckpointFabric, ArenaMaintainProgram) use this one
    predicate so the two forms share every entry point. A 1-D leaf tree
    can only be mistaken for an arena if it is a single bare f32 array of
    exactly ``total_words`` (a tile-aligned size no real model hits) —
    and the arena path is only reachable with a fabric-built layout."""
    if layout is None:
        return None
    if getattr(x, "ndim", None) == 1 and getattr(x, "size", 0) \
            == layout.total_words and x.dtype == jnp.float32:
        return x
    return None


def build_arena_layout(partition: BlockPartition, shards: int = 1,
                       tail_pack: bool = True) -> ArenaLayout:
    """Lay out ``partition`` in the flat word arena.

    Main-region leaves go first in flatten order (tile-aligned
    segments); tail leaves (single-block, payload < ``ARENA_TILE``
    words) follow back-to-back at word granularity, then the region is
    re-aligned to a tile. ``tail_pack=False`` keeps every segment
    tile-aligned (the pre-tail-packing layout — the ``maint_arena_padding``
    bench compares the two).

    ``shards > 1`` appends zero tiles so ``n_tiles % shards == 0`` —
    every flat shard of the 1-D buffer then owns a whole number of
    ``(8, 128)`` tiles and the data region ``[0, data_words)`` is
    *identical* to the ``shards=1`` layout (relayout across shard counts
    is a slice + re-pad, bit-exact)."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    br = partition.block_rows
    n = len(partition.leaves)
    pw_leaf = [leaf_word_width(leaf, br) for leaf in partition.leaves]
    is_tail = [tail_pack and leaf.n_blocks == 1 and pw_leaf[li] < ARENA_TILE
               for li, leaf in enumerate(partition.leaves)]
    order = ([li for li in range(n) if not is_tail[li]]
             + [li for li in range(n) if is_tail[li]])
    blocks: list[ArenaBlock] = []
    leaf_offset = [0] * n
    seg_words = [0] * n
    payload_words = [0] * n
    payload_elems = [0] * n
    seg_elems = [0] * n
    off = voff = 0
    tail_start = None
    for li in order:
        leaf = partition.leaves[li]
        pw = pw_leaf[li]
        seg = pw if is_tail[li] else _align(pw)
        if is_tail[li] and tail_start is None:
            tail_start = off
        r = dtype_word_ratio(leaf.dtype)
        leaf_offset[li] = off
        seg_words[li] = seg
        payload_words[li] = pw
        payload_elems[li] = leaf_frame_width(leaf, br)
        seg_elems[li] = seg * r
        for b in range(leaf.n_blocks):
            blocks.append(ArenaBlock(leaf=li, gid=leaf.offset + b,
                                     offset=off, words=seg, payload=pw))
            off += seg
            voff += seg * r
    if tail_start is None:
        tail_start = off
    data_words = _align(off)
    voff += data_words - off          # tail-alignment gap, f32 values
    ab_gid = np.asarray([ab.gid for ab in blocks], np.int64)
    gid_order = np.argsort(ab_gid, kind="stable")
    gid_ptr = np.searchsorted(ab_gid[gid_order],
                              np.arange(partition.total_blocks + 1))
    pad_tiles = (-(data_words // ARENA_TILE)) % shards
    total_words = data_words + pad_tiles * ARENA_TILE
    total_values = voff + pad_tiles * ARENA_TILE
    ab_t0 = np.asarray([ab.offset // ARENA_TILE for ab in blocks], np.int64)
    ab_last = np.asarray([(ab.offset + max(ab.words, 1) - 1) // ARENA_TILE
                          for ab in blocks], np.int64)
    return ArenaLayout(partition=partition, blocks=tuple(blocks),
                       leaf_offset=tuple(leaf_offset),
                       seg_words=tuple(seg_words),
                       payload_words=tuple(payload_words),
                       total_words=total_words,
                       ab_t0=ab_t0, ab_nt=ab_last - ab_t0 + 1,
                       gid_ab=gid_order, gid_ptr=gid_ptr,
                       shards=shards, data_words=data_words,
                       tail_start=tail_start, leaf_order=tuple(order),
                       payload_elems=tuple(payload_elems),
                       seg_elems=tuple(seg_elems),
                       total_values=total_values)


# ---------------------------------------------------------------------------
# pack / unpack / restore (pure, jittable; layout is static)
# ---------------------------------------------------------------------------

def _is_f32(leaf) -> bool:
    return np.dtype(leaf.dtype) == np.dtype(np.float32)


def pack_arena(values: PyTree, layout: ArenaLayout,
               out_sharding=None) -> jnp.ndarray:
    """Pack a tree into the flat (total_words,) word arena.

    One read of every leaf, one write of the arena — this *is* the replica
    refresh cost when the fabric snapshots into arena form. f32 leaves are
    value-stored (bitwise the historical layout); other word-packable
    dtypes are raw bit patterns via :func:`leaf_block_words`.

    ``out_sharding`` (a flat 1-D ``NamedSharding``) pins the result to
    the sharded-arena layout. Only the result is constrained: per-part
    constraints change the gradient program's reduction order, and the
    arena step then drifts from the PyTree step at ULP level."""
    part = layout.partition
    leaves = jax.tree_util.tree_leaves(values)
    parts = []
    covered = 0
    for li in layout.leaf_order:
        x, leaf = leaves[li], part.leaves[li]
        seg = layout.seg_words[li]
        if _is_f32(leaf):
            view = leaf_block_view(x.astype(jnp.float32), part.block_rows)
        else:
            view = jax.lax.bitcast_convert_type(
                leaf_block_words(x, part.block_rows), jnp.float32)
        if view.shape[1] < seg:
            view = jnp.pad(view, ((0, 0), (0, seg - view.shape[1])))
        parts.append(view.reshape(-1))
        covered += seg * leaf.n_blocks
    if layout.total_words > covered:
        parts.append(jnp.zeros((layout.total_words - covered,), jnp.float32))
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if out_sharding is not None:
        out = jax.lax.with_sharding_constraint(out, out_sharding)
    return out


def _decode_leaf(arena: jnp.ndarray, layout: ArenaLayout, li: int):
    """Contiguous slice of leaf ``li``'s segment, decoded to leaf shape."""
    leaf = layout.partition.leaves[li]
    seg, payload = layout.seg_words[li], layout.payload_words[li]
    off = layout.leaf_offset[li]
    flat = jax.lax.dynamic_slice(arena, (off,), (leaf.n_blocks * seg,))
    view = flat.reshape(leaf.n_blocks, seg)
    if _is_f32(leaf):
        vals = view[:, :payload]
        rows = max(leaf.rows, 1)
        vals = vals.reshape(-1, max(leaf.row_width, 1))[:rows]
        return vals.reshape(leaf.shape).astype(leaf.dtype)
    bits = jax.lax.bitcast_convert_type(view[:, :payload], jnp.int32)
    return decode_block_words(bits, leaf, layout.partition.block_rows)


def unpack_arena(arena: jnp.ndarray, layout: ArenaLayout) -> PyTree:
    """Inverse of :func:`pack_arena`, bit-exact (invariant I3)."""
    out = [_decode_leaf(arena, layout, li)
           for li in range(len(layout.partition.leaves))]
    return jax.tree_util.tree_unflatten(layout.partition.treedef, out)


def arena_pack_program(layout: ArenaLayout, out_sharding=None):
    """:func:`pack_arena` over ``layout``, jitted under the stable program
    name ``jit_arena_pack`` (a device trace names it apart from other
    programs)."""
    def arena_pack(tree):
        return pack_arena(tree, layout, out_sharding=out_sharding)
    return jax.jit(arena_pack)


def arena_unpack_program(layout: ArenaLayout):
    """:func:`unpack_arena` over ``layout``, jitted under the stable
    program name ``jit_arena_unpack``."""
    def arena_unpack(arena):
        return unpack_arena(arena, layout)
    return jax.jit(arena_unpack)


# ---------------------------------------------------------------------------
# value domain (the optimizer seam)
# ---------------------------------------------------------------------------

def pack_values(values: PyTree, layout: ArenaLayout,
                out_sharding=None) -> jnp.ndarray:
    """Pack a tree into the flat ``(total_values,)`` f32 value buffer —
    the gradient/moment counterpart of :func:`pack_arena`, and exactly
    ``decode_values(pack_arena(values))``: the tree is packed to words in
    its own (stored) dtypes and the words decode to f32, which is exact
    for every packable float. For an all-f32 layout this is the *same
    program* as ``pack_arena`` (words are values)."""
    return decode_values(pack_arena(values, layout, out_sharding), layout)


def decode_values(arena: jnp.ndarray, layout: ArenaLayout) -> jnp.ndarray:
    """Word arena → ``(total_values,)`` f32 values, one slice per
    coalesced same-dtype run (identity for all-f32 layouts).

    A run of ``nw`` words holding ``r`` elements each decodes *planar*:
    its values are field 0 of every word, then field 1, … (see
    :func:`~repro.core.blocks.word_fields`), not element order. The
    optimizer is elementwise, so any fixed order serves; this one needs
    no lane interleave, which the TPU can only do through padded
    ``(…, r)`` intermediates."""
    if layout.uniform_f32:
        return arena
    parts = []
    for w0, nw, _v0, _nv, dt in layout.value_runs():
        w = jax.lax.slice(arena, (w0,), (w0 + nw,))
        if dt == np.dtype(np.float32):
            parts.append(w)
            continue
        bits = jax.lax.bitcast_convert_type(w, jnp.int32)
        parts.extend(f.astype(jnp.float32) for f in word_fields(bits, dt))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def encode_values(values: jnp.ndarray, layout: ArenaLayout) -> jnp.ndarray:
    """Inverse of :func:`decode_values`: re-encode the (planar) f32 value
    buffer into raw arena words (``astype`` to the stored dtype — the same
    rounding the PyTree optimizer path applies — then bitcast)."""
    if layout.uniform_f32:
        return values
    parts = []
    for _w0, nw, v0, nv, dt in layout.value_runs():
        v = jax.lax.slice(values, (v0,), (v0 + nv,))
        if dt == np.dtype(np.float32):
            parts.append(v)
            continue
        r = dtype_word_ratio(dt)
        bits = fields_to_words([v[k * nw:(k + 1) * nw].astype(dt)
                                for k in range(r)])
        parts.append(jax.lax.bitcast_convert_type(bits, jnp.float32))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def relayout_values(buf, old: ArenaLayout, new: ArenaLayout,
                    out_sharding=None):
    """Value-domain counterpart of :func:`relayout_arena` (optimizer
    moments across a shard-count change): the region before the shard
    pad is partition-determined, so this is a host slice + re-pad."""
    d_old = old.total_values - old.pad_words
    d_new = new.total_values - new.pad_words
    if d_old != d_new:
        raise ValueError("relayout_values: layouts disagree on the data "
                         f"region ({d_old} vs {d_new} values) — not the "
                         "same partition")
    host = np.asarray(buf)
    out = np.concatenate(
        [host[:d_new], np.zeros((new.pad_words,), np.float32)])
    return jax.device_put(out, out_sharding) if out_sharding is not None \
        else jnp.asarray(out)


def arena_drift_scores(live: jnp.ndarray, ref: jnp.ndarray,
                       layout: ArenaLayout) -> jnp.ndarray:
    """Per-gid squared drift ``||live_b − ref_b||²`` → (total_blocks,) f32,
    decoding each word by its stored dtype.

    Main-region tiles reduce per tile first (for an all-f32 layout this
    is bit-identical to the historical tile scorer); tail-region words
    reduce by ``word_gid`` directly, since tail tiles are shared. Pad
    words diff two zero words → exact +0.0 (I4)."""
    word_gid, word_code, dts = layout.word_tables()
    wc = (live - ref) ** 2
    lbits = jax.lax.bitcast_convert_type(live, jnp.int32)
    rbits = jax.lax.bitcast_convert_type(ref, jnp.int32)
    for k, dt in enumerate(dts, start=1):
        dk = 0.0
        for fl, fr in zip(word_fields(lbits, dt), word_fields(rbits, dt)):
            d = fl.astype(jnp.float32) - fr.astype(jnp.float32)
            dk = dk + d * d
        wc = jnp.where(jnp.asarray(word_code == k), dk, wc)
    total = layout.partition.total_blocks
    tile_gid = np.where(word_gid[::ARENA_TILE] >= 0,
                        word_gid[::ARENA_TILE], 0)
    partials = jnp.sum(wc.reshape(-1, ARENA_TILE), axis=1)
    if layout.has_tail:
        tt0 = layout.tail_start // ARENA_TILE
        tt1 = layout.data_words // ARENA_TILE
        mask = np.ones((layout.n_tiles,), bool)
        mask[tt0:tt1] = False
        partials = jnp.where(jnp.asarray(mask), partials, 0.0)
    scores = jax.ops.segment_sum(partials, jnp.asarray(tile_gid),
                                 num_segments=total)
    if layout.has_tail:
        lo, hi = layout.tail_start, layout.data_words
        scores = scores + jax.ops.segment_sum(
            wc[lo:hi], jnp.asarray(word_gid[lo:hi]), num_segments=total)
    return scores


def relayout_arena(arena, old: ArenaLayout, new: ArenaLayout,
                   out_sharding=None):
    """Re-pad an arena across a shard-count change, bit-exactly.

    The data region ``[0, data_words)`` is identical for every shard
    count of the same partition (``build_arena_layout`` only moves the
    zero tail), so relayout is a host-side slice + re-pad. Used on the
    elastic resize path (mesh shrink / re-grow), which is failure-rate —
    not per-step — so the device round trip is acceptable; the result is
    ``device_put`` onto ``out_sharding`` when given."""
    if old.data_words != new.data_words:
        raise ValueError("relayout_arena: layouts disagree on the data "
                         f"region ({old.data_words} vs {new.data_words} "
                         "words) — not the same partition")
    host = np.asarray(arena)
    data = host[:new.data_words]
    out = np.concatenate(
        [data, np.zeros((new.total_words - new.data_words,), np.float32)])
    return jax.device_put(out, out_sharding) if out_sharding is not None \
        else jnp.asarray(out)


def arena_block_homes(layout: ArenaLayout,
                      n_devices: Optional[int] = None) -> np.ndarray:
    """(total_blocks,) home device of each gid, derived from flat-shard
    span ownership: the device whose contiguous word span holds the
    first tile of the gid's first arena block. With ``shards ==
    n_devices`` every device's span is tile-aligned (I1/I2), so a
    device's home blocks are exactly the tile-aligned segments it
    already owns — the sharded maintain sweep and the partial save read
    only local (plus boundary-straddling) tiles."""
    n = layout.shards if n_devices is None else int(n_devices)
    if layout.n_tiles % n:
        raise ValueError(f"n_tiles {layout.n_tiles} not divisible by "
                         f"{n} devices — build the layout with shards={n}")
    tiles_per = layout.n_tiles // n
    first_ab = layout.gid_ab[layout.gid_ptr[:-1]]
    return (layout.ab_t0[first_ab] // tiles_per).astype(np.int64)


def arena_restore(dst: PyTree, arena: jnp.ndarray, global_mask,
                  layout: ArenaLayout) -> PyTree:
    """Overwrite the masked blocks of ``dst`` from the arena.

    The arena-source counterpart of ``select_blocks`` /
    ``tree_masked_restore``: each touched leaf decodes one contiguous
    arena slice; untouched leaves pass through as the same buffer."""
    part = layout.partition
    mask = np.asarray(global_mask, bool)
    out = []
    for li, (x, leaf) in enumerate(zip(jax.tree_util.tree_leaves(dst),
                                       part.leaves)):
        seg = mask[leaf.offset:leaf.offset + leaf.n_blocks]
        out.append(_restore_leaf(x, arena, jnp.asarray(seg), layout, li)
                   if seg.any() else x)
    return jax.tree_util.tree_unflatten(part.treedef, out)


# One program per leaf: run op by op, the decode of a packed leaf holds
# several leaf-sized intermediates at once — 4 GB over the steady state
# for a 151,936-row bf16 embedding at d_model 1536, on the recovery path
# where the device is fullest.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _restore_leaf(x, arena, seg, layout: ArenaLayout, li: int):
    leaf = layout.partition.leaves[li]
    decoded = _decode_leaf(arena, layout, li).astype(x.dtype)
    em = expand_block_mask(seg, leaf, layout.partition.block_rows)
    return jnp.where(em, decoded, x)


# ---------------------------------------------------------------------------
# parity frame bridge
# ---------------------------------------------------------------------------

def frames_gather_index(layout: ArenaLayout, frame_layout) -> np.ndarray:
    """(total_blocks, frame_elems) arena word index per frame position
    (-1 where the frame is zero padding) — ``frames_from_arena``'s map.

    Valid because the arena's per-leaf columns match the ``FrameLayout``
    word columns: frame row ``gid`` is the side-by-side concat of every
    colocated leaf's segment for that gid. Word-granular, so tail-packed
    (unaligned) blocks index straight in."""
    part = layout.partition
    idx = np.full((part.total_blocks, frame_layout.frame_elems), -1,
                  np.int64)
    for ab in layout.blocks:
        col = frame_layout.cols[ab.leaf]
        idx[ab.gid, col:col + ab.payload] = np.arange(
            ab.offset, ab.offset + ab.payload)
    return idx


def frames_from_arena(arena: jnp.ndarray, gather_idx: np.ndarray,
                      ) -> jnp.ndarray:
    """(total_blocks, frame_elems) int32 bit-pattern frames — bit-exact
    vs ``pack_frames`` of the unpacked tree (one gather, no per-leaf
    pass)."""
    idx = jnp.asarray(np.where(gather_idx >= 0, gather_idx, 0))
    vals = jnp.where(jnp.asarray(gather_idx >= 0), arena[idx],
                     jnp.float32(0.0))
    return jax.lax.bitcast_convert_type(vals, jnp.int32)
