"""Drivers for the fused_maintain kernel family.

``ArenaMaintainProgram`` is the fabric's maintenance program: one jitted
sweep over the flat parameter arena that reads the live values once and
yields the replica snapshot, the XOR parity and the PRIORITY scores. Its
host-side routing (``arena_routing``: every arena tile's parity
destination) is a function of the parity striping alone; the fabric
builds it once per striping and hands it to the program and to the
analytic traffic model (``maintain_traffic``).

``arena_scatter_save`` and ``tree_scatter_save`` are the checkpoint-side
counterparts: donation-based in-place partial saves that move only the
selected blocks' bytes into the running checkpoint (one dispatch over the
arena, one per touched leaf over a tree) instead of rewriting every leaf
through ``jnp.where``.

Backend contract matches the other kernel packages: compiled Pallas on
TPU, the jnp path elsewhere (interpret-mode Pallas is for validation
only).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import BlockPartition, leaf_view_shape
from repro.fabric.parity import FrameLayout
from repro.kernels.fused_maintain.kernel import scatter_save_pallas

PyTree = Any


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Arena maintenance: ONE dispatch over the flat parameter arena
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArenaRouting:
    """Host-side tile routing for the arena sweep (static per striping)."""
    perm: np.ndarray          # (T,) arena tile visited at sorted step s
    dest: np.ndarray          # (T,) compact parity tile per sorted step
    first: np.ndarray         # (T,) 1 at the first step of its dest
    touched: np.ndarray       # (n_dest,) full parity tile index, ascending
    members: np.ndarray       # (n_dest, m_hat) arena tile ids, -1 padded
    tile_gid: np.ndarray      # (T,) global block id per arena tile
    frame_tiles: int          # parity frame width in arena tiles


def arena_routing(arena_layout, frame_layout: FrameLayout,
                  group_of: np.ndarray) -> ArenaRouting:
    """Map every (8, 128) arena tile to its parity destination tile.

    Tile ``k`` of block ``gid`` (leaf ``l``) lands in parity frame row
    ``group_of[gid]`` at columns ``cols[l] + k·ARENA_TILE`` — whole tiles
    because the frame layout is arena-tile aligned. Sorting tiles by
    destination makes every parity output tile's contributors consecutive
    grid steps (seed on ``first``, XOR-fold after) across the entire
    model at once.

    Tail-packed blocks (word-granular, tile-sharing) are *not* routed
    here — :class:`ArenaMaintainProgram` XOR-folds their payload words
    into the parity with a word-granular epilogue."""
    from repro.core.arena import ARENA_TILE
    group_of = np.asarray(group_of, np.int32)
    n_tiles = arena_layout.n_tiles
    ftiles = frame_layout.frame_elems // ARENA_TILE
    tail_start = getattr(arena_layout, "tail_start", -1)
    if tail_start < 0:
        tail_start = arena_layout.total_words
    # shard-pad tail tiles (sharded layouts only) carry no payload: they
    # route to no parity destination (dest -1, dropped from the perm) and
    # report gid 0 — zero words diffed against zero words add an exact
    # +0.0 to gid 0's score, so the score path can stay full-length.
    # Tail-region tiles are likewise unrouted (word epilogue).
    dest_full = np.full((n_tiles,), -1, np.int64)
    tile_gid = np.zeros((n_tiles,), np.int32)
    for ab in arena_layout.blocks:
        g = group_of[ab.gid]
        assert g >= 0, f"arena block gid={ab.gid} outside any parity group"
        if ab.offset >= tail_start:
            continue
        t0 = ab.offset // ARENA_TILE
        nt = ab.words // ARENA_TILE
        col_t = frame_layout.cols[ab.leaf] // ARENA_TILE
        dest_full[t0:t0 + nt] = g * ftiles + col_t + np.arange(nt)
        tile_gid[t0:t0 + nt] = ab.gid
    data_tiles = np.nonzero(dest_full >= 0)[0]
    perm = data_tiles[np.argsort(dest_full[data_tiles],
                                 kind="stable")].astype(np.int32)
    dest_sorted = dest_full[perm]
    touched, inverse = np.unique(dest_sorted, return_inverse=True)
    dest = inverse.astype(np.int32)
    first = np.ones_like(dest)
    first[1:] = (dest[1:] != dest[:-1]).astype(np.int32)
    m_hat = int(np.bincount(dest).max()) if dest.size else 0
    members = np.full((touched.size, m_hat), -1, np.int32)
    fill = np.zeros((touched.size,), np.int64)
    for pos, row in zip(perm, dest):
        members[row, fill[row]] = pos
        fill[row] += 1
    return ArenaRouting(perm=perm, dest=dest, first=first,
                       touched=touched.astype(np.int32), members=members,
                       tile_gid=tile_gid, frame_tiles=int(ftiles))


class ArenaMaintainProgram:
    """The jitted single-sweep maintenance program over the flat arena.

    ``program(params, ckpt_arena)`` packs the live tree into arena form
    (the pack IS the replica refresh — one read of every leaf, one write
    of the snapshot) and runs ONE kernel dispatch over the 2D-retiled
    arena emitting the group-sorted XOR parity and per-tile PRIORITY
    score partials; tiny O(output) epilogues fold partials into
    per-block scores and scatter the compact parity tiles into the
    codec's ``(n_groups, frame_elems)`` layout.

    Returns ``(replica_arena, scores, parity)`` — parity bit-identical
    to :meth:`ParityCodec.encode` under the same striping, scores
    allclose to :func:`repro.core.blocks.block_scores` (different
    association order; per-dtype word decode for quantized leaves).
    With ``ckpt_arena=None`` the sweep still refreshes replica +
    parity; scores are zeros (nothing to diff).

    Tail-packed blocks are swept by a word-granular epilogue: their
    payload words gather by flat parity position and XOR *into* the
    tile-scattered parity (a position can receive both a main tile and
    tail words — different gids of one group own different leaves'
    overlapping columns). The compiled Pallas arena kernel is an
    aligned-tile f32 program, so it only engages on uniform-f32 layouts
    without a tail region; everything else runs the (identical-output)
    jnp sweep.

    ``params`` may also be the live flat arena itself (arena-resident
    training state): the pack disappears entirely and the sweep is the
    pure 2-read/1-write pass — read live + checkpoint arenas, write the
    replica copy + compact outputs. Outputs are bit-identical to the
    pack path on the same values (``pack ∘ unpack`` is the identity).

    ``routing`` is the striping's :func:`arena_routing`, when the caller
    already holds it (the fabric builds it once per striping); without
    it the program builds its own."""

    def __init__(self, partition: BlockPartition, arena_layout,
                 frame_layout: FrameLayout, group_of: np.ndarray,
                 n_groups: int, use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None, out_sharding=None,
                 routing: Optional[ArenaRouting] = None):
        from repro.core.arena import (ARENA_TILE, arena_drift_scores,
                                      pack_arena)
        if use_pallas is None:
            use_pallas = _is_tpu()
        if interpret is None:
            interpret = not _is_tpu()
        # the compiled arena kernel assumes words == f32 values on
        # exclusively owned aligned tiles; quantized or tail-packed
        # layouts run the jnp word sweep (same outputs) instead, and so
        # does a mesh-sharded arena (GSPMD cannot partition a Mosaic
        # kernel). ``sweep``/``sweep_reason`` say which one this runs
        reasons = [why for why, bad in (
            ("pallas off", not use_pallas),
            ("not uniform_f32", not arena_layout.uniform_f32),
            ("has_tail", arena_layout.has_tail),
            ("sharded", out_sharding is not None)) if bad]
        use_pallas = not reasons
        self.sweep = "pallas" if use_pallas else "jnp"
        self.sweep_reason = ", ".join(reasons)
        self.layout = arena_layout
        if routing is None:
            routing = arena_routing(arena_layout, frame_layout, group_of)
        self.routing = r = routing
        total = partition.total_blocks
        n_dest = int(r.touched.size)
        full_tiles = n_groups * r.frame_tiles
        frame_elems = frame_layout.frame_elems
        touched = jnp.asarray(r.touched)
        members = jnp.asarray(np.where(r.members >= 0, r.members, 0))
        valid = jnp.asarray(r.members >= 0)
        gid_sorted = jnp.asarray(r.tile_gid[r.perm])

        # tail-packed blocks: word-granular parity routing. Every tail
        # payload word has one flat parity position group·frame_elems +
        # col + j; positions shared across gids (overlapping columns of
        # different leaves in one group) gather all their contributor
        # words and XOR-fold.
        tail_pos = tail_members = tail_valid = None
        if arena_layout.has_tail:
            gof = np.asarray(group_of, np.int64)
            pos_l, wid_l = [], []
            for ab in arena_layout.blocks:
                if ab.offset < arena_layout.tail_start:
                    continue
                base = (gof[ab.gid] * frame_elems
                        + frame_layout.cols[ab.leaf])
                pos_l.append(base + np.arange(ab.payload))
                wid_l.append(np.arange(ab.offset, ab.offset + ab.payload))
            pos = np.concatenate(pos_l)
            wid = np.concatenate(wid_l)
            upos, inv = np.unique(pos, return_inverse=True)
            m_hat = int(np.bincount(inv).max())
            tmem = np.zeros((upos.size, m_hat), np.int64)
            tval = np.zeros((upos.size, m_hat), bool)
            fill = np.zeros((upos.size,), np.int64)
            for w, row in zip(wid, inv):
                tmem[row, fill[row]] = w
                tval[row, fill[row]] = True
                fill[row] += 1
            tail_pos = jnp.asarray(upos)
            tail_members = jnp.asarray(tmem)
            tail_valid = jnp.asarray(tval)

        def _sweep(rep, z_arena):
            if use_pallas:
                from repro.kernels.fused_maintain.kernel import \
                    arena_maintain_pallas
                sc, par = arena_maintain_pallas(
                    rep.reshape(-1, 128), z_arena.reshape(-1, 128),
                    r.perm, r.dest, r.first, n_dest, interpret=interpret)
                scores = jax.ops.segment_sum(sc, gid_sorted,
                                             num_segments=total)
                par_c = par.reshape(n_dest, ARENA_TILE)
            else:
                # per-dtype word scorer: bit-identical to the historical
                # tile scorer on all-f32 main regions, word-gid reduction
                # over the (shared-tile) tail region
                scores = arena_drift_scores(rep, z_arena, arena_layout)
                bits = jax.lax.bitcast_convert_type(
                    rep.reshape(-1, ARENA_TILE), jnp.int32)
                gathered = bits[members]          # (n_dest, m_hat, TILE)
                par_c = jax.lax.reduce(
                    jnp.where(valid[..., None], gathered, 0),
                    jnp.int32(0), jax.lax.bitwise_xor, (1,))
            full = jnp.zeros((full_tiles, ARENA_TILE), jnp.int32)
            parity = full.at[touched].set(par_c).reshape(n_groups * r.frame_tiles * ARENA_TILE)
            if tail_pos is not None:
                wbits = jax.lax.bitcast_convert_type(rep, jnp.int32)
                fold = jax.lax.reduce(
                    jnp.where(tail_valid, wbits[tail_members], 0),
                    jnp.int32(0), jax.lax.bitwise_xor, (1,))
                # XOR into (not over) the tile parity: a flat position
                # can hold a main tile's words AND tail contributions
                parity = parity.at[tail_pos].set(parity[tail_pos] ^ fold)
            return scores, parity.reshape(n_groups, frame_elems)

        # ``out_sharding`` (SPMD meshes) pins the internal pack to the
        # flat arena sharding the sweep reads
        def _scored(params, z_arena):
            rep = pack_arena(params, arena_layout, out_sharding=out_sharding)
            scores, parity = _sweep(rep, z_arena)
            return rep, scores, parity

        def _unscored(params):
            rep = pack_arena(params, arena_layout, out_sharding=out_sharding)
            _, parity = _sweep(rep, rep)
            return rep, jnp.zeros((total,), jnp.float32), parity

        # arena-resident live state: the live params ARE already an
        # arena, so there is nothing to pack — the sweep reads the live
        # buffer and the replica snapshot is a plain copy of it, emitted
        # from the same read (2 reads + 1 write + compact outputs). The
        # optimization_barrier keeps the copy an op (not an identity the
        # runtime could forward as an alias of the input): the replica
        # must own its buffer because the live arena is donated into the
        # very next train step.
        def _scored_live(live, z_arena):
            scores, parity = _sweep(live, z_arena)
            return jax.lax.optimization_barrier(live), scores, parity

        def _unscored_live(live):
            _, parity = _sweep(live, live)
            return (jax.lax.optimization_barrier(live),
                    jnp.zeros((total,), jnp.float32), parity)

        # owned live arena (``own_live=True``): a tree-stepping caller
        # hands over the pack it just made — the buffer itself becomes
        # the replica, so the sweep emits no copy at all (the caller
        # guarantees the arena is never donated or mutated afterwards);
        # total cost matches the internal-pack path exactly
        def _scored_owned(live, z_arena):
            return _sweep(live, z_arena)

        def _unscored_owned(live):
            _, parity = _sweep(live, live)
            return jnp.zeros((total,), jnp.float32), parity

        self._scored = jax.jit(_scored)
        self._unscored = jax.jit(_unscored)
        self._scored_live = jax.jit(_scored_live)
        self._unscored_live = jax.jit(_unscored_live)
        self._scored_owned = jax.jit(_scored_owned)
        self._unscored_owned = jax.jit(_unscored_owned)

    def __call__(self, params: PyTree,
                 ckpt_arena: Optional[jnp.ndarray] = None,
                 own_live: bool = False):
        from repro.core.arena import as_live_arena
        live = as_live_arena(params, self.layout)
        if live is not None and own_live:
            if ckpt_arena is None:
                scores, parity = self._unscored_owned(live)
            else:
                scores, parity = self._scored_owned(live, ckpt_arena)
            return live, scores, parity
        if live is not None:
            return (self._unscored_live(live) if ckpt_arena is None
                    else self._scored_live(live, ckpt_arena))
        if ckpt_arena is None:
            return self._unscored(params)
        return self._scored(params, ckpt_arena)


# ---------------------------------------------------------------------------
# Arena in-place partial save: ONE donated scatter for the whole model
# ---------------------------------------------------------------------------

_ARENA_SCATTER_CACHE: dict = {}


def _arena_scatter_fn(total_words: int, k_hat: int, w_hat: int,
                      use_pallas: bool, interpret: bool):
    from repro.core.arena import ARENA_TILE
    key = (total_words, k_hat, w_hat, use_pallas, interpret)
    fn = _ARENA_SCATTER_CACHE.get(key)
    if fn is not None:
        return fn

    def _scatter(dst, src, tiles, widx):
        out = dst
        if k_hat:
            if use_pallas:
                from repro.kernels.fused_maintain.kernel import \
                    arena_scatter_pallas
                out = arena_scatter_pallas(out.reshape(-1, 128),
                                           src.reshape(-1, 128), tiles,
                                           interpret=interpret)
            else:
                d = out.reshape(-1, ARENA_TILE)
                out = d.at[tiles].set(src.reshape(-1, ARENA_TILE)[tiles])
            out = out.reshape(total_words)
        if w_hat:
            # tail-packed blocks share tiles, so their save granularity
            # is the payload word (duplicate pad indices are idempotent)
            out = out.at[widx].set(src[widx])
        return out

    fn = jax.jit(_scatter, donate_argnums=(0,))
    _ARENA_SCATTER_CACHE[key] = fn
    return fn


def arena_scatter_save(dst_arena: jnp.ndarray, src_arena: jnp.ndarray,
                       arena_layout, global_idx: np.ndarray,
                       use_pallas: Optional[bool] = None,
                       interpret: Optional[bool] = None,
                       ) -> tuple[jnp.ndarray, int]:
    """Overwrite the selected blocks' arena segments of ``dst_arena``
    from ``src_arena`` in place — one donated dispatch total, O(k·seg)
    bytes, vs ``tree_scatter_save``'s one dispatch per touched leaf.

    Main-region blocks move as whole tiles (the Pallas/jnp tile
    scatter); tail-packed blocks move their payload words only — a tile
    copy would clobber unselected tile-mates. Bytes moved therefore
    match :meth:`ArenaLayout.seg_bytes_for_blocks` exactly.

    ``global_idx``: host-resident selected global block ids (colocated
    leaves' segments ride along — they share gids). Returns
    ``(updated_arena, bytes_moved)``; ``dst_arena`` is donated. An arena
    sharded over several devices takes the jnp scatter: GSPMD cannot
    partition a Mosaic kernel."""
    if use_pallas is None:
        use_pallas = _is_tpu()
    sharding = getattr(dst_arena, "sharding", None)
    if sharding is not None and len(sharding.device_set) > 1:
        use_pallas = False
    if interpret is None:
        interpret = not _is_tpu()
    main, tail = arena_layout.split_tail_blocks(global_idx)
    tiles = np.empty((0,), np.int32)
    if main.size:
        t0, nt = arena_layout.ab_t0[main], arena_layout.ab_nt[main]
        starts = np.cumsum(nt) - nt
        tiles = np.unique(np.repeat(t0, nt) + (np.arange(int(nt.sum()))
                          - np.repeat(starts, nt))).astype(np.int32)
    widx = (np.concatenate(
        [np.arange(arena_layout.blocks[i].offset,
                   arena_layout.blocks[i].offset
                   + arena_layout.blocks[i].payload) for i in tail])
        if tail.size else np.empty((0,), np.int64))
    if tiles.size == 0 and widx.size == 0:
        return dst_arena, 0
    k_hat = _bucket(tiles.size, arena_layout.n_tiles) if tiles.size else 0
    tiles_p = np.full((max(k_hat, 1),), tiles[0] if tiles.size else 0,
                      np.int32)
    tiles_p[:tiles.size] = tiles
    # w_hat is a *layout constant* — the whole tail region, bucketed —
    # not the selection's tail word count: a per-save w_hat crosses with
    # k_hat into a fresh jit key almost every save (ROUND_ROBIN windows
    # shift across rotations) and recompiles in the save hot loop. Pad
    # slots repeat a word this save writes anyway (first selected
    # block's first payload word), so the duplicates are idempotent;
    # the tail region is sub-tile-scale by construction, so the extra
    # scatter lanes are noise.
    tail_words = (arena_layout.tail_end - arena_layout.tail_start
                  if arena_layout.has_tail else 0)
    w_hat = (_bucket(tail_words, arena_layout.total_words)
             if tail_words else 0)
    pad_src = tail if tail.size else main
    pad_word = int(arena_layout.blocks[int(pad_src[0])].offset)
    widx_p = np.full((max(w_hat, 1),), pad_word, np.int64)
    widx_p[:widx.size] = widx
    fn = _arena_scatter_fn(int(arena_layout.total_words), k_hat, w_hat,
                           use_pallas, interpret)
    out = fn(dst_arena, src_arena, jnp.asarray(tiles_p),
             jnp.asarray(widx_p))
    from repro.core.arena import ARENA_TILE
    return out, int(tiles.size) * ARENA_TILE * 4 + int(widx.size) * 4


# ---------------------------------------------------------------------------
# In-place partial save
# ---------------------------------------------------------------------------

_SCATTER_CACHE: dict = {}


def _bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n, clipped to cap — bounds jit recompiles to
    O(log cap) distinct selection sizes per leaf signature."""
    return min(1 << max(0, math.ceil(math.log2(max(n, 1)))), cap)


def _scatter_leaf_fn(shape: tuple, dtype, k_hat: int, block_rows: int,
                     use_pallas: bool, interpret: bool):
    key = (shape, str(dtype), k_hat, block_rows, use_pallas, interpret)
    fn = _SCATTER_CACHE.get(key)
    if fn is not None:
        return fn
    rows_total, width = leaf_view_shape(shape, block_rows)

    def _scatter(dst, src, sel):
        d2 = dst.reshape(max(rows_total, 1), max(width, 1))
        s2 = src.astype(dst.dtype).reshape(max(rows_total, 1), max(width, 1))
        if use_pallas:
            out = scatter_save_pallas(d2, s2, sel, block_rows,
                                      interpret=interpret)
        else:
            # row-expanded gather/scatter: duplicates from the clip and the
            # bucket padding rewrite identical values (idempotent)
            row_idx = (sel[:, None] * block_rows
                       + jnp.arange(block_rows)[None, :]).reshape(-1)
            row_idx = jnp.minimum(row_idx, max(rows_total, 1) - 1)
            out = d2.at[row_idx].set(s2[row_idx])
        return out.reshape(shape)

    fn = jax.jit(_scatter, donate_argnums=(0,))
    _SCATTER_CACHE[key] = fn
    return fn


def tree_scatter_save(dst: PyTree, src: PyTree, global_idx: np.ndarray,
                      partition: BlockPartition,
                      use_pallas: Optional[bool] = None,
                      interpret: Optional[bool] = None,
                      ) -> tuple[PyTree, int]:
    """Overwrite the selected blocks of ``dst`` from ``src`` in place.

    ``global_idx`` — host-resident selected global block ids. Leaves with
    no selected block pass through untouched (zero traffic); each touched
    leaf moves only its selected blocks' rows. Returns
    ``(updated_tree, bytes_moved)``. ``dst`` leaves are donated — callers
    must not reuse the input buffers of touched leaves.
    """
    if use_pallas is None:
        use_pallas = _is_tpu()
    if interpret is None:
        interpret = not _is_tpu()
    idx = np.unique(np.asarray(global_idx, np.int64))
    dst_flat = jax.tree_util.tree_leaves(dst)
    src_flat = jax.tree_util.tree_leaves(src)
    br = partition.block_rows
    out = []
    moved = 0
    # colocated leaves share block-id ranges; each leaf still scatters its
    # own payload for the shared ids
    for d, s, leaf in zip(dst_flat, src_flat, partition.leaves):
        lo = np.searchsorted(idx, leaf.offset)
        hi = np.searchsorted(idx, leaf.offset + leaf.n_blocks)
        sel = (idx[lo:hi] - leaf.offset).astype(np.int32)
        if sel.size == 0:
            out.append(d)
            continue
        k_hat = _bucket(sel.size, leaf.n_blocks)
        padded = np.full((k_hat,), sel[0], np.int32)
        padded[:sel.size] = sel
        fn = _scatter_leaf_fn(tuple(leaf.shape), leaf.dtype, k_hat, br,
                              use_pallas, interpret)
        out.append(fn(d, s, jnp.asarray(padded)))
        rows_per = np.minimum((sel + 1) * br, max(leaf.rows, 1)) - sel * br
        moved += int(rows_per.clip(min=0).sum()) * leaf.row_width \
            * np.dtype(leaf.dtype).itemsize
    return jax.tree_util.tree_unflatten(partition.treedef, out), moved


# ---------------------------------------------------------------------------
# Analytic traffic model (bytes per maintain step / per partial save)
# ---------------------------------------------------------------------------

def _tree_nbytes(partition: BlockPartition) -> int:
    return sum(int(np.prod(l.shape) or 1) * np.dtype(l.dtype).itemsize
               for l in partition.leaves)


def maintain_traffic(partition: BlockPartition, layout: FrameLayout,
                     n_groups: int, group_width: int, arena_layout=None,
                     routing: Optional[ArenaRouting] = None,
                     ) -> dict[str, int]:
    """Analytic HBM bytes moved by one full maintenance step (replica
    refresh + parity encode + priority scoring): the seed's component
    passes and, given the arena layout and its striping's ``routing``,
    the arena sweep.

    The seed path reads the live tree once per pass (replica copy, frame
    pack, score) plus writes/reads two full-model staging buffers (the
    packed ``(total_blocks, frame_elems)`` frames and the
    ``(n_groups, g, E)`` gather); the arena sweep reads the live and
    checkpoint arenas once each and touches only compact parity tiles and
    per-tile score partials besides the replica write.
    """
    model = _tree_nbytes(partition)
    frames = partition.total_blocks * layout.frame_elems * 4
    gathered = n_groups * group_width * layout.frame_elems * 4
    parity = n_groups * layout.frame_elems * 4
    seed = (
        model + model            # replica: read live + write replica
        + model + frames         # pack_frames: read live + write frames
        + frames + gathered      # gather: read frames + write grouped
        + gathered + parity      # encode: read grouped + write parity
        + model + model          # block_scores: read live + read ckpt
    )
    out = {"seed": int(seed), "model": int(model), "parity": int(parity),
           "staging_seed": int(frames + gathered)}
    if arena_layout is not None:
        # arena path: the pack (read live + write the arena snapshot) IS
        # the replica refresh; the single-dispatch sweep then reads the
        # snapshot and the checkpoint arena once and writes compact
        # parity tiles + per-tile score partials; a tiny epilogue
        # scatters the compact tiles into the codec parity layout
        from repro.core.arena import ARENA_TILE
        a = arena_layout.nbytes
        tail_words = sum(ab.payload for ab in arena_layout.blocks
                         if ab.offset >= arena_layout.tail_start) \
            if arena_layout.has_tail else 0
        compact = int(routing.touched.size) * ARENA_TILE * 4 + tail_words * 4
        partials = arena_layout.n_tiles * 4
        out["arena_bytes"] = int(a)
        # pad words / live payload words: the alignment overhead tail
        # packing removes — a gauge, not a byte count
        out["padding_ratio"] = float(arena_layout.padding_ratio)
        out["staging_arena"] = int(compact + partials)
        out["arena"] = int(
            model + a                # pack: read live, write snapshot
            + a + a                  # sweep: read snapshot + ckpt arena
            + compact + partials     # sweep outputs
            + compact + parity)      # epilogue: compact -> codec layout
        # arena-resident live state: no pack — the sweep reads the live
        # arena and the checkpoint arena once each and writes the replica
        # copy from the same read (pure 2-read/1-write plus the compact
        # outputs); the per-step saving vs the pack path is exactly the
        # live tree's `model` bytes
        out["arena_resident"] = int(
            a + a                    # sweep: read live + ckpt arena
            + a                      # write the replica copy
            + compact + partials     # sweep outputs
            + compact + parity)      # epilogue: compact -> codec layout
        # owned live arena (tree-stepping callers hand their pack over
        # as the replica): no copy — the caller's pack (model + a,
        # booked by pack_live(account=True)) plus this equals the
        # internal-pack "arena" total exactly
        out["arena_owned"] = int(out["arena_resident"] - a)
        # async double-buffer: one extra snapshot copy (read live + write
        # the inactive slot, 2a) in front of the *owned* sweep over the
        # published slot (the snapshot IS the replica — no second copy),
        # so the total is the resident sweep plus one arena read. That +a
        # is the price of decoupling the sweep from the donated live
        # buffer; the wall-clock it buys back is the whole sweep.
        out["arena_async"] = int(out["arena_resident"] + a)
        # SPMD sharded arena: the sweep byte count is unchanged in total
        # (same 2-read/1-write pass, now executed shard-locally — each of
        # the `shards` devices touches 1/shards of every term), but the
        # replica copy crosses the interconnect: an anti-affine placement
        # moves the whole arena device-to-device once per sweep. Per-
        # device HBM traffic is arena_sharded / shards.
        out["arena_sharded"] = int(out["arena_resident"])
        out["arena_sharded_xfer"] = int(a)
        out["arena_shards"] = int(getattr(arena_layout, "shards", 1))
    return out
